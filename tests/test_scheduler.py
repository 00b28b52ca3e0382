"""Serving-runtime behaviour: batching, rejection, modes, consistency."""

import time

import numpy as np
import pytest

from repro.core import IVFIndex, IVFIndexConfig, build_ivf
from repro.core.faults import FaultPlan
from repro.core.scheduler import RequestRejected, RuntimeConfig, ServingRuntime


def _data(n, d, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(8, d)).astype(np.float32) * 3
    return (
        centers[rng.integers(0, 8, n)]
        + rng.normal(size=(n, d)).astype(np.float32)
    ).astype(np.float32)


@pytest.fixture(scope="module")
def base_index():
    x = _data(1500, 16)
    return x, lambda: build_ivf(
        x, n_clusters=4, block_size=16, max_chain=64, add_batch=256,
        capacity_vectors=8000,
    )


@pytest.mark.parametrize("mode", ["serial", "parallel", "fused"])
def test_modes_serve_and_insert(base_index, mode):
    x, make = base_index
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode=mode, nprobe=4, k=5, flush_interval=0.05,
                      flush_min=4),
    )
    try:
        # searches return correct neighbours
        futs = [rt.submit_search(x[i : i + 1]) for i in range(6)]
        for i, f in enumerate(futs):
            d, ids = f.result(timeout=10)
            assert ids.shape == (1, 5)
            assert ids[0, 0] == i  # self-match
        # online inserts become visible
        new = _data(12, 16, seed=9) + 40.0
        ins = rt.submit_insert(new)
        new_ids = ins.result(timeout=10)
        assert len(new_ids) == 12
        time.sleep(0.1)
        f = rt.submit_search(new[:1])
        d, ids = f.result(timeout=10)
        assert ids[0, 0] == new_ids[0]
    finally:
        rt.stop()


def test_rejection_when_slots_exhausted(base_index):
    x, make = base_index
    rt = ServingRuntime(
        make(), RuntimeConfig(mode="parallel", n_slots=2, nprobe=4, k=5)
    )
    try:
        # grab both slots without letting the worker drain (burst)
        got_reject = False
        futs = []
        for _ in range(50):
            try:
                futs.append(rt.submit_search(x[:1]))
            except RequestRejected:
                got_reject = True
                break
        assert got_reject
        for f in futs:
            f.result(timeout=10)
    finally:
        rt.stop()


def test_insert_batching_respects_cap(base_index):
    x, make = base_index
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", flush_min=8, flush_max=16,
                      flush_interval=0.05, nprobe=4, k=5),
    )
    try:
        futs = [rt.submit_insert(_data(4, 16, seed=100 + i)) for i in range(4)]
        for f in futs:
            f.result(timeout=10)
        assert rt.index.ntotal >= 1500  # all applied eventually
    finally:
        rt.stop()


def test_flush_max_overflow_requeued_not_dropped(base_index):
    """Batches past flush_max are requeued; every future gets exactly the
    ids of its own vectors (no silent drop, no shared full-batch ids)."""
    x, make = base_index
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", flush_min=4, flush_max=8,
                      flush_interval=0.05, nprobe=4, k=5),
    )
    try:
        before = rt.index.ntotal
        sizes = [6, 6, 6, 5]  # 23 rows: forces several flush_max splits
        futs = [
            rt.submit_insert(_data(s, 16, seed=200 + i))
            for i, s in enumerate(sizes)
        ]
        got = [f.result(timeout=20) for f in futs]
        for s, ids in zip(sizes, got):
            assert len(ids) == s  # per-item ids, not the whole batch's
        all_ids = np.concatenate(got)
        assert len(np.unique(all_ids)) == sum(sizes)  # disjoint, none lost
        deadline = time.perf_counter() + 10
        while rt.index.ntotal < before + sum(sizes):
            assert time.perf_counter() < deadline, "vectors vanished"
            time.sleep(0.02)
    finally:
        rt.stop()


def test_search_path_union_fused_serves(base_index):
    """The fused streaming path plugs into the runtime end to end."""
    x, make = base_index
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", nprobe=4, k=5,
                      search_path="union_fused"),
    )
    try:
        futs = [rt.submit_search(x[i : i + 1]) for i in range(4)]
        for i, f in enumerate(futs):
            d, ids = f.result(timeout=60)
            assert ids.shape == (1, 5)
            assert ids[0, 0] == i  # self-match
    finally:
        rt.stop()


def test_unknown_search_path_raises(base_index):
    """A typo'd path must fail at construction, not silently benchmark
    block_table (regression: the impl map used .get with a default)."""
    x, make = base_index
    with pytest.raises(ValueError, match="union_fusde"):
        ServingRuntime(make(), RuntimeConfig(search_path="union_fusde"))


@pytest.mark.parametrize("path", ["union_pallas", "union_fused_scan"])
def test_runtime_accepts_full_path_set(base_index, path):
    """Every path make_search_fn supports must be dispatchable."""
    x, make = base_index
    rt = ServingRuntime(
        make(), RuntimeConfig(mode="parallel", nprobe=4, k=5, search_path=path)
    )
    try:
        d, ids = rt.submit_search(x[:1]).result(timeout=60)
        assert ids[0, 0] == 0
    finally:
        rt.stop()


def test_chain_budget_recomputed_after_growth():
    """Regression (silent recall loss): the chain budget was frozen at
    construction, so chains grown past 2x the initial depth were truncated
    and their candidates dropped.  A runtime that inserted far past the
    initial depth must return the same ids as a freshly-built index over the
    same corpus."""
    rng = np.random.default_rng(17)
    d = 16
    x0 = _data(120, d, seed=31)  # ~4 blocks/cluster at block_size 8
    x1 = _data(2000, d, seed=32)  # grows chains ~16x
    cfg = IVFIndexConfig(
        n_clusters=4, dim=d, block_size=8, max_chain=128, nprobe=4, k=5,
        capacity_vectors=6000,
    )
    idx = IVFIndex(cfg)
    idx.train(x0)
    idx.add(x0)
    init_depth = idx._chain_budget()
    rt = ServingRuntime(
        idx,
        RuntimeConfig(mode="parallel", nprobe=4, k=5, flush_min=4,
                      flush_interval=0.02),
    )
    try:
        chunks = [x1[i : i + 250] for i in range(0, len(x1), 250)]
        for ch in chunks:  # sequential: deterministic insertion order
            rt.submit_insert(ch).result(timeout=30)
        assert idx._chain_budget() > 2 * init_depth, "test must outgrow 2x"
        q = x1[-20:]
        d_rt, i_rt = rt.submit_search(q).result(timeout=60)
    finally:
        rt.stop()
    # oracle: same centroids (trained on x0), same insertion order
    fresh = IVFIndex(cfg)
    fresh.train(x0)
    fresh.add(x0)
    for ch in chunks:
        fresh.add(ch)
    d_f, i_f = fresh.search(q, nprobe=4, k=5)
    np.testing.assert_allclose(d_rt, d_f, rtol=1e-5, atol=1e-4)
    assert (i_rt == i_f).all()


def test_budget_buckets_pow2_and_evicts_stale_steps():
    """Dispatch-time chain budgets are power-of-two buckets (O(log
    max_chain) recompiles under steady growth, never one per increment)
    and advancing the bucket evicts the jit-cache entries keyed by the
    superseded smaller budgets (chains never shrink, so those executables
    can never be dispatched again)."""
    d = 16
    x0 = _data(60, d, seed=41)
    cfg = IVFIndexConfig(
        n_clusters=4, dim=d, block_size=8, max_chain=128, nprobe=4, k=5,
        capacity_vectors=8000,
    )
    idx = IVFIndex(cfg)
    idx.train(x0)
    idx.add(x0)
    rt = ServingRuntime(
        idx, RuntimeConfig(mode="parallel", nprobe=4, k=5)
    )
    try:
        rt.stop()  # drive budgets/caches directly, no worker races
        seen = set()
        for n in (200, 400, 800, 1600, 3200):
            idx.add(_data(n, d, seed=n))
            rt._budget = None  # what _apply_insert does after an insert
            b = rt._current_budget()
            assert b & (b - 1) == 0 or b == cfg.max_chain, b
            seen.add(b)
            rt._search_step_for(b)
            rt._fused_step_for(b)
            # only the current bucket's entries survive growth; keys carry
            # (base, effective_budget, nprobe, rerank[, kind]) so ladder
            # rungs can share the caches without thrashing eviction
            assert set(rt._search_steps) == {(b, b, 4, False)}
            assert set(rt._fused_steps) == {(b, b, 4, False, "insert")}
        assert len(seen) > 2, "test must cross several buckets"
        assert len(seen) < 8, "pow2 bucketing keeps the bucket count small"
    finally:
        rt.stop()


def test_search_failure_resolves_futures_and_releases_slots(base_index):
    """Regression (slot/future leak): an exception mid-dispatch used to
    leave every batched future unresolved and the semaphore slots acquired
    forever — after a few failures the runtime rejected all traffic.
    Malformed payloads now fail fast at submit, so the mid-step failure is
    injected deterministically instead."""
    x, make = base_index
    n_slots = 4
    plan = FaultPlan().fail("search_step", nth=range(n_slots))
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", n_slots=n_slots, nprobe=4, k=5),
        faults=plan,
    )
    try:
        # every dispatch in the first wave fails (single-item batches fail
        # outright; multi-item batches burn several call indices retrying)
        bad = [rt.submit_search(x[:1]) for _ in range(2)]
        for f in bad:
            with pytest.raises(Exception):
                f.result(timeout=30)
        # every slot must be back: a full burst of valid searches succeeds
        deadline = time.perf_counter() + 30
        while plan.calls("search_step") < n_slots:  # drain the fault window
            assert time.perf_counter() < deadline
            try:
                rt.submit_search(x[:1]).result(timeout=30)
            except Exception:
                pass
        good = [rt.submit_search(x[i : i + 1]) for i in range(n_slots)]
        for i, f in enumerate(good):
            d, ids = f.result(timeout=30)
            assert ids[0, 0] == i
    finally:
        rt.stop()


def test_insert_failure_resolves_futures(base_index):
    """A failing insert batch must fail its futures, not hang them, and the
    insert lane must keep serving afterwards (failure injected: malformed
    payloads no longer reach the worker)."""
    x, make = base_index
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", nprobe=4, k=5, flush_min=1,
                      flush_interval=0.05),
        faults=FaultPlan().fail("mutation_step", nth=0),
    )
    try:
        bad = rt.submit_insert(_data(2, 16, seed=299))
        with pytest.raises(Exception):
            bad.result(timeout=30)
        ok = rt.submit_insert(_data(4, 16, seed=300))
        assert len(ok.result(timeout=30)) == 4
    finally:
        rt.stop()


def test_malformed_payload_fails_fast_at_submit(base_index):
    """Wrong-dim / non-finite / empty payloads raise in the caller's thread
    at submit time and consume no slot — they can never fail a co-batched
    request deep in a worker."""
    x, make = base_index
    n_slots = 3
    rt = ServingRuntime(
        make(), RuntimeConfig(mode="parallel", n_slots=n_slots, nprobe=4, k=5)
    )
    try:
        for _ in range(2 * n_slots):  # more tries than slots: none consumed
            with pytest.raises(ValueError, match="dim"):
                rt.submit_search(np.zeros((1, 3), np.float32))
        with pytest.raises(ValueError, match="non-finite"):
            rt.submit_insert(np.full((2, 16), np.nan, np.float32))
        with pytest.raises(ValueError, match="empty"):
            rt.submit_insert(np.zeros((0, 16), np.float32))
        with pytest.raises(ValueError, match="not integral"):
            rt.submit_delete(np.array([1.5, 2.5]))
        with pytest.raises(ValueError, match="ids for"):
            rt.submit_update(_data(3, 16), np.array([1, 2], np.int32))
        # all slots still free; the lanes were never involved
        good = [rt.submit_search(x[i : i + 1]) for i in range(n_slots)]
        for i, f in enumerate(good):
            assert f.result(timeout=30)[1][0, 0] == i
    finally:
        rt.stop()


def test_latency_samples_bounded(base_index):
    """Regression: the latency samples grew forever under sustained
    traffic.  stats() now reads fixed log-bucket histograms: memory stays
    constant while every sample still counts."""
    x, make = base_index
    rt = ServingRuntime(make(), RuntimeConfig(mode="parallel", nprobe=4, k=5))
    hist = rt._lat["search"]
    try:
        for _ in range(3):
            futs = [rt.submit_search(x[:1]) for _ in range(7)]
            for f in futs:
                f.result(timeout=30)
        assert len(hist._counts) == hist.N_BUCKETS  # fixed, not per sample
        assert rt.stats()["search"].n == 21  # no cap: every sample counts
    finally:
        rt.stop()


def test_rerank_requires_fused_path(base_index):
    """rerank on a non-fused path must fail at construction."""
    x, make = base_index
    with pytest.raises(NotImplementedError, match="rerank"):
        ServingRuntime(
            make(), RuntimeConfig(search_path="block_table", rerank=True)
        )


def test_search_path_union_fused_rerank_serves(base_index):
    """The exact re-rank epilogue plugs into the runtime end to end (fp32
    payload: identical results to the plain fused path)."""
    x, make = base_index
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", nprobe=4, k=5,
                      search_path="union_fused_scan", rerank=True),
    )
    try:
        futs = [rt.submit_search(x[i : i + 1]) for i in range(4)]
        for i, f in enumerate(futs):
            d, ids = f.result(timeout=60)
            assert ids.shape == (1, 5)
            assert ids[0, 0] == i  # self-match
    finally:
        rt.stop()


def test_stats_collected(base_index):
    x, make = base_index
    rt = ServingRuntime(make(), RuntimeConfig(mode="parallel", nprobe=4, k=5))
    try:
        futs = [rt.submit_search(x[:1]) for _ in range(5)]
        for f in futs:
            f.result(timeout=10)
        s = rt.stats()
        assert s["search"].n == 5
        assert s["search"].mean_ms > 0
    finally:
        rt.stop()
