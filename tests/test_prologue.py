"""Fused candidate-routing prologue: streaming coarse probe parity,
``block_owner`` maintenance, and old-vs-new prologue equivalence.

Three contracts guard the prologue refactor:

* ``coarse_topk`` (kernel / ``lax.scan`` fallback / jnp oracle) and
  ``coarse_probe`` meet the top-k contract against the f32-HIGHEST
  reference (tests/topk_contract.py), also for N_clusters that is not a
  multiple of the centroid tile; among equal returned distances the lower
  centroid id comes first (``top_k`` order, which the streaming kernels
  reproduce with a (distance, id) sort key).
* ``IVFState.block_owner`` stays consistent with the block table through
  insert -> rearrange -> insert round trips (allocation, recycling via the
  free stack, and compaction all move ownership).
* The fused search paths return results identical to the old prologue
  (``jnp.unique`` union + dense ``[Q, CB]`` membership/probe-slot
  operands) across every payload dtype x rerank, on randomized grown
  workloads — verified by re-running the same dispatch with the old
  prologue swapped back in.

Runs in tier-1 (no marker): grids are kept tiny per the interpret-mode
grid-step budget.
"""

import dataclasses
import types

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import build_ivf
from repro.core.block_pool import check_invariants
from repro.core.reference import exact_topk
from repro.core.search import coarse_probe, search_union_fused
from repro.kernels.ivf_scan import coarse_topk, coarse_topk_scan
from repro.kernels.ref import coarse_topk_ref
from topk_contract import assert_topk_contract


# ---------------------------------------------------------------------------
# coarse_topk parity (kernel <-> scan <-> oracle <-> coarse_probe)
# ---------------------------------------------------------------------------


def _probe(centroids, queries, nprobe):
    """``coarse_probe`` itself, jitted the way every search path runs it
    (eager XLA can round the fused epilogue differently than jit)."""
    fn = jax.jit(lambda c, q: coarse_probe(
        types.SimpleNamespace(centroids=c), q, nprobe
    ))
    return fn(centroids, queries)


@pytest.mark.parametrize(
    "q,d,n,nprobe",
    [
        (13, 32, 100, 7),  # N not a multiple of the 128 tile (pad + mask)
        (64, 128, 384, 32),  # acceptance geometry: 3 centroid tiles
        (1, 16, 8, 8),  # nprobe == N (full probe)
        (130, 64, 300, 16),  # Q > q_tile -> two query tiles
        (5, 16, 30, 9),  # everything tiny and misaligned
    ],
)
def test_coarse_topk_bitexact_with_coarse_probe(q, d, n, nprobe):
    rng = np.random.default_rng(q * 100 + n)
    queries = jnp.asarray(rng.normal(size=(q, d)), jnp.float32)
    cents = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    ref = exact_topk(cents, queries, nprobe)
    for name, (got_i, got_d) in {
        "coarse_probe": _probe(cents, queries, nprobe),
        "kernel": coarse_topk(queries, cents, nprobe=nprobe, interpret=True),
        "scan": coarse_topk_scan(queries, cents, nprobe=nprobe),
        "ref": jax.jit(
            lambda c, qs: coarse_topk_ref(qs, c, nprobe=nprobe)
        )(cents, queries),
    }.items():
        assert_topk_contract(queries, cents, (got_d, got_i), ref,
                             err_msg=name)


def test_coarse_topk_breaks_ties_by_centroid_id():
    """Duplicated centroids produce exact distance ties; every impl must
    return them in ``top_k`` order (lower centroid id first)."""
    rng = np.random.default_rng(3)
    base = rng.normal(size=(10, 16)).astype(np.float32)
    cents = jnp.asarray(np.repeat(base, 3, axis=0))  # ids 3k,3k+1,3k+2 tie
    queries = jnp.asarray(rng.normal(size=(6, 16)), jnp.float32)
    ref = exact_topk(cents, queries, 9)
    # the construction really does produce in-row ties
    assert (np.diff(np.asarray(ref[0]), axis=1) == 0).any()
    for name, (got_i, got_d) in {
        "coarse_probe": _probe(cents, queries, 9),
        "kernel": coarse_topk(queries, cents, nprobe=9, interpret=True),
        "scan": coarse_topk_scan(queries, cents, nprobe=9),
        "ref": jax.jit(
            lambda c, qs: coarse_topk_ref(qs, c, nprobe=9)
        )(cents, queries),
    }.items():
        assert_topk_contract(queries, cents, (got_d, got_i), ref,
                             err_msg=name)
        # within each run of equal returned distances, lower id first
        got_d, got_i = np.asarray(got_d), np.asarray(got_i)
        tie = got_d[:, 1:] == got_d[:, :-1]
        assert (got_i[:, 1:] > got_i[:, :-1])[tie].all(), name


def test_coarse_topk_small_c_tile_covers_multi_tile_merge():
    """A tiny centroid tile forces many accumulator merges (the streaming
    path proper); still bit-exact."""
    rng = np.random.default_rng(5)
    queries = jnp.asarray(rng.normal(size=(9, 24)), jnp.float32)
    cents = jnp.asarray(rng.normal(size=(70, 24)), jnp.float32)
    want_i, want_d = _probe(cents, queries, 11)
    got_i, got_d = coarse_topk(
        queries, cents, nprobe=11, c_tile=16, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_d), np.asarray(want_d))


# ---------------------------------------------------------------------------
# block_owner maintenance
# ---------------------------------------------------------------------------


def _clustered(n, d, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(8, d)).astype(np.float32) * 3
    return (
        centers[rng.integers(0, 8, n)]
        + rng.normal(size=(n, d)).astype(np.float32)
    ).astype(np.float32)


def _owner_oracle(state):
    """[P] owner map derived from the block table (host side)."""
    cb = np.asarray(state.cluster_blocks)
    owner = np.full(state.pool_ids.shape[0], -1, np.int32)
    for cl in range(cb.shape[0]):
        for b in cb[cl]:
            if b >= 0:
                owner[b] = cl
    return owner


def test_block_owner_tracks_insert_rearrange_insert():
    """Ownership follows every allocation path: fresh bump blocks, chains
    compacted by rearrangement (old blocks freed -> owner NULL), and
    recycled free-stack blocks claimed by later inserts."""
    x = _clustered(700, 16, seed=1)
    idx = build_ivf(
        x, n_clusters=8, block_size=16, max_chain=32, add_batch=128,
        nprobe=4, k=5, rearrange_threshold=50, capacity_vectors=3000,
    )
    np.testing.assert_array_equal(
        np.asarray(idx.state.block_owner), _owner_oracle(idx.state)
    )
    idx.add(_clustered(200, 16, seed=2))
    passes = idx.maybe_rearrange(max_passes=8)
    assert passes > 0, "workload must actually trigger compaction"
    np.testing.assert_array_equal(
        np.asarray(idx.state.block_owner), _owner_oracle(idx.state)
    )
    # freed chain blocks sit on the free stack owning nothing
    s = jax.device_get(idx.state)
    freed = s.free_stack[: int(s.free_top)]
    assert len(freed) > 0
    assert (np.asarray(s.block_owner)[freed] == -1).all()
    # the next insert recycles them and re-claims ownership
    idx.add(_clustered(300, 16, seed=3))
    np.testing.assert_array_equal(
        np.asarray(idx.state.block_owner), _owner_oracle(idx.state)
    )
    check_invariants(idx.state, idx.pool_cfg)  # includes the owner checks


# ---------------------------------------------------------------------------
# e2e: new prologue == old prologue, all fused dtypes x rerank
# ---------------------------------------------------------------------------


def _old_union_candidates(cfg, state, queries, nprobe, chain_budget,
                          scan_impl="jnp"):
    """The PR-3 prologue re-expressed in the new UnionCandidates format:
    ``jnp.unique`` union, cluster-major candidate order, stable-argsort
    compaction, owners taken from the union clusters (not block_owner).
    Feeding this through the unchanged fused dispatch reproduces the old
    pipeline end to end."""
    from repro.core.search import UnionCandidates

    q = queries.shape[0]
    mc = min(chain_budget or cfg.max_chain, cfg.max_chain)
    probe_idx, _ = coarse_probe(state, queries, nprobe)
    union = jnp.unique(
        probe_idx.reshape(-1), size=q * nprobe, fill_value=-1
    )
    blocks = state.cluster_blocks[jnp.maximum(union, 0), :mc]
    blocks = jnp.where((union != -1)[:, None], blocks, -1)
    flat = blocks.reshape(-1)
    owners = jnp.where(flat != -1, jnp.repeat(union, mc), -1)
    cap = min(flat.shape[0], state.pool_payload.shape[0])
    if cap < flat.shape[0]:
        perm = jnp.argsort(flat == -1, stable=True)[:cap]
        flat, owners = flat[perm], owners[perm]
    return UnionCandidates(flat, owners, probe_idx)


def _grown_index(dtype, payload="flat", pq_m=0):
    x = _clustered(700, 32, seed=4)
    kw = dict(payload=payload, pq_m=pq_m) if payload == "pq" else dict(
        dtype=dtype
    )
    idx = build_ivf(
        x, n_clusters=8, block_size=16, max_chain=32, add_batch=256,
        nprobe=4, k=10, rearrange_threshold=60, capacity_vectors=3000, **kw,
    )
    extra = _clustered(150, 32, seed=5)
    idx.add(extra)
    idx.maybe_rearrange(max_passes=6)
    tail = _clustered(80, 32, seed=6)
    idx.add(tail)
    return np.concatenate([x, extra, tail]), idx


@pytest.mark.parametrize(
    "dtype,rerank",
    [
        ("float32", False),
        ("float32", True),
        ("bfloat16", False),
        ("bfloat16", True),
        ("int8", False),
        ("int8", True),
        ("pq", False),
        ("pq", True),
    ],
)
def test_fused_matches_old_prologue(dtype, rerank, monkeypatch):
    """The complete fused dispatch (scan impl; the kernel impl shares the
    routing derivation, tested per-kernel) returns identical (distance,
    id) results with the old and new prologues on a randomized grown
    workload — the refactor changes HBM traffic, not results."""
    if dtype == "pq":
        corpus, idx = _grown_index(None, payload="pq", pq_m=8)
    else:
        corpus, idx = _grown_index(dtype)
    rng = np.random.default_rng(7)
    q = jnp.asarray(corpus[rng.integers(0, len(corpus), 6)] + 0.001)
    budget = idx._chain_budget()

    def run():
        return search_union_fused(
            idx.pool_cfg, idx.state, q, nprobe=4, k=10, scan_impl="scan",
            chain_budget=budget, pq=idx.pq, rerank=rerank,
        )

    d_new, i_new = run()
    import repro.core.search as search_mod

    monkeypatch.setattr(
        search_mod, "_union_candidates", _old_union_candidates
    )
    d_old, i_old = run()
    np.testing.assert_array_equal(np.asarray(i_new), np.asarray(i_old))
    np.testing.assert_allclose(
        np.asarray(d_new), np.asarray(d_old), rtol=0, atol=0
    )


def test_union_path_skips_dead_slots_same_results():
    """search_union (and its pallas twin's candidate list) now scores only
    the deduped live blocks; results match the per-query block_table path
    on ties-free data."""
    from repro.core.search import make_search_fn

    corpus, idx = _grown_index("float32")
    rng = np.random.default_rng(8)
    q = jnp.asarray(corpus[rng.integers(0, len(corpus), 5)] + 0.001)
    budget = idx._chain_budget()
    d0, i0 = make_search_fn(
        idx.pool_cfg, nprobe=4, k=10, path="block_table", chain_budget=budget
    )(idx.state, q)
    d1, i1 = make_search_fn(
        idx.pool_cfg, nprobe=4, k=10, path="union", chain_budget=budget
    )(idx.state, q)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i0))
    np.testing.assert_allclose(
        np.asarray(d1), np.asarray(d0), rtol=1e-5, atol=1e-4
    )
    # and the compacted candidate list really is smaller than the padded
    # union the old prologue shipped
    from repro.core.search import _union_candidates

    uc = _union_candidates(idx.pool_cfg, idx.state, q, 4, budget)
    old = _old_union_candidates(idx.pool_cfg, idx.state, q, 4, budget)
    n_live_new = int((np.asarray(uc.flat_blocks) >= 0).sum())
    n_live_old = int((np.asarray(old.flat_blocks) >= 0).sum())
    assert uc.flat_blocks.shape[0] <= old.flat_blocks.shape[0]
    assert n_live_new <= n_live_old  # dedup can only shrink
    # identical live block sets
    assert set(np.asarray(uc.flat_blocks)[np.asarray(uc.flat_blocks) >= 0]
               .tolist()) == \
        set(np.asarray(old.flat_blocks)[np.asarray(old.flat_blocks) >= 0]
            .tolist())


def test_prologue_owner_matches_union_cluster():
    """block_owner-derived owners agree with the union-cluster-derived
    owners of the old prologue for every live candidate."""
    from repro.core.search import _union_candidates

    corpus, idx = _grown_index("float32")
    rng = np.random.default_rng(9)
    q = jnp.asarray(corpus[rng.integers(0, len(corpus), 4)])
    uc = _union_candidates(idx.pool_cfg, idx.state, q, 4, idx._chain_budget())
    flat = np.asarray(uc.flat_blocks)
    owners = np.asarray(uc.owners)
    oracle = _owner_oracle(idx.state)
    live = flat >= 0
    np.testing.assert_array_equal(owners[live], oracle[flat[live]])
    assert (owners[~live] == -1).all()
