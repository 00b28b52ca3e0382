"""A search dispatch brings its answers to the host in one transfer.

The search program packs its f32 distances and int32 ids into one
``int32[Q, 2k]`` buffer (``pack_answers``), the dispatch starts the copy
at launch, and the lane fetches it once (``ServingRuntime._fetch_answers``).
What reaches the caller is the two-array answer, bit for bit: on every
scan path and payload that runs here, padded rows and ``k`` past the live
rows included, in every mode.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import IVFIndex, IVFIndexConfig
from repro.core.faults import FaultPlan
from repro.core.runtime import (
    RuntimeConfig,
    ServingRuntime,
    pack_answers,
    unpack_answers,
)

D = 16
NPROBE = 2
K = 32  # more than the live rows of two probed lists: inf / -1 answers
BUCKET = 8


def _data(n, d=D, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(8, d)).astype(np.float32) * 3
    return (
        centers[rng.integers(0, 8, n)]
        + rng.normal(size=(n, d)).astype(np.float32)
    ).astype(np.float32)


X = _data(600)
QUERIES = X[:3]  # padded to BUCKET: rows 3.. are padding

# (search path, payload, flat dtype, rerank): every combination the
# runtime accepts that runs on the CPU
CASES = [
    ("block_table", "flat", "float32", False),
    ("chain_walk", "flat", "float32", False),
    ("union", "flat", "float32", False),
    ("union_pallas", "flat", "float32", False),
    ("union_fused", "flat", "float32", False),
    ("union_fused_scan", "flat", "float32", False),
    ("union_fused_scan", "flat", "float32", True),
    ("block_table", "flat", "bfloat16", False),
    ("union_fused_scan", "flat", "bfloat16", True),
    ("union_fused_scan", "flat", "int8", False),
    ("union_fused_scan", "flat", "int8", True),
    ("block_table", "pq", "float32", False),
    ("chain_walk", "pq", "float32", False),
    ("union_fused_scan", "pq", "float32", False),
]


def _index(payload="flat", dtype="float32", live=40):
    """Trained on 600 rows, holding ``live`` of them: two probed lists
    hold fewer than ``K`` rows."""
    idx = IVFIndex(IVFIndexConfig(
        n_clusters=4, dim=D, block_size=16, max_chain=16,
        capacity_vectors=2000, payload=payload,
        pq_m=4 if payload == "pq" else 0, dtype=dtype, nprobe=NPROBE, k=K,
    ))
    idx.train(X)
    idx.add(X[:live])
    return idx


def _stopped_runtime(idx, path="block_table", rerank=False):
    """A runtime whose workers are gone, so the test drives its steps."""
    rt = ServingRuntime(idx, RuntimeConfig(
        nprobe=NPROBE, k=K, search_path=path, rerank=rerank))
    rt.stop()
    return rt


def _two_arrays(rt, base, rerank, state, pb, valid):
    """The answers as the runtime fetched them before: ``(d, i)`` out of
    the unpacked search program, one ``np.asarray`` each."""
    d, i = jax.jit(rt._make_search(base, NPROBE, rerank))(state, pb, valid)
    return np.asarray(d), np.asarray(i)


def _assert_bit_identical(got, want):
    (d1, i1), (d0, i0) = got, want
    assert d1.dtype == np.float32 and i1.dtype == np.int32
    assert d1.shape == d0.shape == i1.shape == i0.shape == (BUCKET, K)
    np.testing.assert_array_equal(d1.view(np.int32), d0.view(np.int32))
    np.testing.assert_array_equal(i1, i0)


def test_pack_round_trip_keeps_every_bit():
    bits = np.array(
        [[0x7F800000, 0xFF800000, 0x7FC00000, 0x7FC12345],  # +-inf, NaNs
         [0x7F800001, 0xFFFFFFFF, 0x80000000, 0x00000001],  # sNaN, -0, denorm
         [0x3F800000, 0xC2C80000, 0x00800000, 0x7F7FFFFF]],
        np.uint32).view(np.int32)
    d = bits.view(np.float32)
    i = np.array([[-1, 0, 7, 2**31 - 1], [-1, -1, 3, -2**31],
                  [5, 6, -1, 1]], np.int32)
    packed = jax.jit(pack_answers)(jnp.asarray(d), jnp.asarray(i))
    assert packed.dtype == jnp.int32 and packed.shape == (3, 8)
    d1, i1 = unpack_answers(np.asarray(packed))
    assert d1.dtype == np.float32 and i1.dtype == np.int32
    np.testing.assert_array_equal(d1.view(np.int32), bits)
    np.testing.assert_array_equal(i1, i)


@pytest.mark.parametrize("path,payload,dtype,rerank", CASES,
                         ids=["-".join(map(str, c)) for c in CASES])
def test_packed_search_step_matches_two_array_answers(path, payload, dtype,
                                                      rerank):
    rt = _stopped_runtime(_index(payload, dtype), path, rerank)
    with rt._state_lock:
        base = rt._current_budget()
        step = rt._search_step_for(base)
    state = rt.index.state
    pb, valid = rt._padded(QUERIES, BUCKET)
    want = _two_arrays(rt, base, rerank, state, pb, valid)
    packed = step(state, pb, valid)
    packed.copy_to_host_async()
    got = rt._fetch_answers(packed)
    _assert_bit_identical(got, want)
    d, i = got
    assert (i[len(QUERIES):] == -1).all()  # padded rows answer nothing
    # k past the live rows: the tail of every real row is (inf, -1)
    assert np.isinf(d[:len(QUERIES), -1]).all()
    assert (i[:len(QUERIES), -1] == -1).all()
    assert (i[:len(QUERIES), 0] >= 0).all()
    assert rt.stats()["search_fetches"] == 1


def test_packed_fused_step_matches_two_array_answers():
    """The fused program's search output is the same packed buffer; an
    insert with no valid row rides along and changes nothing it reads."""
    rt = _stopped_runtime(_index())
    with rt._state_lock:
        base = rt._current_budget()
        fused = rt._fused_step_for(base, "insert")
    state = rt.index.state
    pb, valid = rt._padded(QUERIES, BUCKET)
    want = _two_arrays(rt, base, False, state, pb, valid)
    no_rows = (np.zeros((BUCKET, D), np.float32),
               np.full((BUCKET,), -1, np.int32), np.zeros((BUCKET,), bool))
    # the fused step donates its state: hand it a copy
    _, packed = fused(jax.tree.map(jnp.copy, state), pb, valid, *no_rows)
    _assert_bit_identical(rt._fetch_answers(packed), want)


@pytest.mark.parametrize("mode", ["serial", "parallel", "fused"])
def test_one_fetch_per_search_dispatch(mode):
    """Each dispatch fetches once, in every mode.  The workers sleep on
    their first turn so the three searches form one batch and, in fused
    mode, pair with the insert in one fused program."""
    x = _data(1200, seed=1)
    idx = IVFIndex(IVFIndexConfig(n_clusters=4, dim=D, block_size=16,
                                  max_chain=64, capacity_vectors=8000))
    idx.train(x)
    idx.add(x)
    plan = (FaultPlan()
            .delay("insert_loop", 0.25, nth=0)
            .delay("search_loop", 0.35, nth=0))
    rt = ServingRuntime(
        idx,
        RuntimeConfig(mode=mode, nprobe=4, k=5, flush_min=8,
                      flush_interval=0.02),
        faults=plan,
    )
    try:
        futs = [rt.submit_search(x[j : j + 1]) for j in range(3)]
        ins = rt.submit_insert(_data(4, seed=20) + 40.0)
        for j, f in enumerate(futs):
            d, i = f.result(timeout=60)
            assert d.dtype == np.float32 and i.dtype == np.int32
            assert i.shape == (1, 5) and i[0, 0] == j
        assert len(ins.result(timeout=60)) == 4
        for j in range(4):
            rt.submit_search(x[j : j + 2]).result(timeout=60)
        s = rt.stats()
    finally:
        rt.stop()
    assert s["search_dispatches"] >= 5
    assert s["search_fetches"] == s["search_dispatches"]
    assert s["fused_fallbacks"] == 0
    if mode == "fused":
        assert rt._fused_steps  # the first batch went out fused
