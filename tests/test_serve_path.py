"""The launcher's main path (``repro.launch.serve``) at a tiny scale:
``build_index`` keeps every row, ``serve`` resolves every request and
fails loudly, and the compile cache sits at its fixed place.

The tiny deployment has more lists than the pool sizing of earlier
versions (``cap // T + 0.5 * n_clusters + 16`` blocks) could hold once
every list owns a block, so a build there used to drop rows.
"""

import dataclasses
import os

import jax
import numpy as np
import pytest

from repro.core.faults import FaultPlan
from repro.core.ivf import IVFIndexConfig
from repro.core.runtime import RuntimeConfig
from repro.data.synthetic import sift_like
from repro.launch import serve as srv

N = 1024
TINY = IVFIndexConfig(n_clusters=256, dim=128, block_size=64,
                      capacity_vectors=2 * N, nprobe=8, k=10)


@pytest.fixture
def tiny(monkeypatch):
    monkeypatch.setitem(
        srv.INDEXES, "tiny",
        (lambda scale: dataclasses.replace(TINY), N, sift_like),
    )
    return "tiny"


def _cfg(mode="parallel"):
    return RuntimeConfig(mode=mode, nprobe=TINY.nprobe, k=TINY.k,
                         flush_min=16, flush_interval=0.05)


@pytest.mark.parametrize("mode", ["parallel", "fused"])
def test_build_and_serve_keep_every_row(tiny, mode):
    old_blocks = int(2 * N // TINY.block_size + TINY.n_clusters * 0.5 + 16)
    assert old_blocks < TINY.n_clusters
    index, corpus = srv.build_index(tiny, 1.0, seed=0)
    assert int(index.state.num_dropped) == 0
    assert index.ntotal == N
    assert index.stats()["blocks_in_use"] > old_blocks

    rep = srv.serve(index, corpus, _cfg(mode), qps_search=40,
                    qps_insert=160, duration=1.0, seed=1)
    assert rep.searches > 0 and rep.inserts > 0
    # every insert future resolved with its own ids, warm-up included
    ids = rep.inserted_ids
    assert len(ids) == srv.INSERT_BATCH * (rep.inserts + 1)
    assert len(np.unique(ids)) == len(ids)
    assert len(rep.inserted_vectors) == len(ids)
    assert int(index.state.num_dropped) == 0
    assert index.ntotal == N + len(ids)
    s = rep.stats
    assert s["search"].n == rep.searches
    assert all(s[c] == 0 for c in srv.RUNTIME_FAULT_COUNTERS)


@pytest.mark.parametrize("nth", [None, range(1, 10**5)],
                         ids=["every_call", "after_warm_up"])
def test_serve_raises_when_searches_fail(tiny, nth):
    index, corpus = srv.build_index(tiny, 1.0, seed=0)
    faults = FaultPlan().fail("search_step", nth=nth)
    with pytest.raises(srv.ServeError, match="failed"):
        srv.serve(index, corpus, _cfg(), qps_search=40, qps_insert=160,
                  duration=0.5, seed=1, faults=faults)


def test_compile_cache_dir(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert srv.use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        default = os.path.join(srv.CHECKOUT, ".jax_cache")
        assert srv.use_compile_cache() == default
        assert jax.config.jax_compilation_cache_dir == default
        assert (srv.CHECKOUT / "pyproject.toml").is_file()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("fail", [False, True], ids=["ok", "failing"])
def test_cli_exit_code(tiny, monkeypatch, tmp_path, fail):
    monkeypatch.setattr(srv, "use_compile_cache", lambda: str(tmp_path))
    if fail:
        class FailingRuntime(srv.ServingRuntime):
            def __init__(self, index, cfg, faults=None):
                super().__init__(
                    index, cfg, FaultPlan().fail("search_step", nth=None)
                )

        monkeypatch.setattr(srv, "ServingRuntime", FailingRuntime)
    rc = srv.main(["--index", tiny, "--qps-search", "20",
                   "--qps-insert", "80", "--duration", "0.3"])
    assert rc == (1 if fail else 0)
