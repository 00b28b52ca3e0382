"""Online mutation subsystem: tombstone deletes, in-place updates, and
dead-space-reclaiming compaction.

Everything here is marked ``mutation`` so CI runs it as its own job slice
(mirroring ``pq``/``quant``); tier-1 excludes it.  The acceptance contract:

* after interleaved insert/delete/update + at least one compaction, search
  results across every fused dtype x rerank contain no deleted id, agree
  with the pure-JAX ref oracle, and recall@10 at 30% deletions is within
  0.5% of an index rebuilt from only the live vectors;
* ``check_invariants`` validates live-mask <-> id-map <-> chain consistency
  in both directions after every mutation kind;
* the serving runtime's mutation stream (submit_delete / submit_update)
  applies batched, ordered, and counted.
"""

import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import build_ivf
from repro.core.block_pool import (
    PoolConfig,
    check_invariants,
    dead_fraction,
    init_state,
    pool_stats,
    snapshot_ids,
    utilisation,
)
from repro.core.insert import make_insert_fn
from repro.core.metrics import recall_at_k
from repro.core.mutate import make_delete_fn, make_update_fn
from repro.core.rearrange import make_rearrange_fn
from repro.core.reference import ivf_exact_topk, live_rows
from repro.core.search import exact_search, make_search_fn, search_union_fused
from topk_contract import assert_topk_contract, id_table

pytestmark = pytest.mark.mutation


def _clustered(n, d, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(8, d)).astype(np.float32) * 3
    return (
        centers[rng.integers(0, 8, n)]
        + rng.normal(size=(n, d)).astype(np.float32)
    ).astype(np.float32)


# ---------------------------------------------------------------------------
# delete / update primitives
# ---------------------------------------------------------------------------


def _small_state(dtype="float32", seed=1, n=60):
    d, tm = 8, 4
    cfg = PoolConfig(n_clusters=3, dim=d, block_size=tm, n_blocks=64,
                     max_chain=16, dtype=dtype)
    rng = np.random.default_rng(seed)
    cents = rng.normal(size=(3, d)).astype(np.float32) * 3
    state = init_state(cfg, jnp.asarray(cents))
    ins = make_insert_fn(cfg)
    x = (cents[rng.integers(0, 3, n)]
         + rng.normal(size=(n, d)).astype(np.float32))
    state = ins(state, jnp.asarray(x), jnp.arange(n, dtype=jnp.int32))
    return cfg, state, x


def test_delete_tombstones_and_counts():
    cfg, state, x = _small_state()
    delete = make_delete_fn(cfg)
    targets = np.asarray([3, 17, 44, 9], np.int32)
    state = delete(state, jnp.asarray(targets))
    check_invariants(state, cfg)
    assert int(state.num_deleted) == 4
    assert int(state.num_vectors) == 60 - 4
    assert int(state.dead_count.sum()) == 4
    live = sorted(i for ids in snapshot_ids(state, cfg).values() for i in ids)
    assert live == sorted(set(range(60)) - set(targets.tolist()))
    # chain slots are untouched — only the live bit flipped
    assert int(state.cluster_len.sum()) == 60


def test_delete_misses_and_duplicates_counted():
    cfg, state, x = _small_state()
    delete = make_delete_fn(cfg)
    # 7 twice in one batch (one hit + one miss), 999 never inserted (miss),
    # and a second batch re-deleting 7 (miss)
    state = delete(state, jnp.asarray([7, 999, 7], jnp.int32))
    check_invariants(state, cfg)
    assert int(state.num_deleted) == 1
    assert int(state.num_missed) == 2
    state = delete(state, jnp.asarray([7], jnp.int32))
    check_invariants(state, cfg)
    assert int(state.num_deleted) == 1
    assert int(state.num_missed) == 3
    assert int(state.num_vectors) == 59


def test_delete_respects_validity_mask():
    cfg, state, x = _small_state()
    delete = make_delete_fn(cfg)
    ids = jnp.asarray([5, 6, 7, 8], jnp.int32)
    valid = jnp.asarray([True, False, True, False])
    state = delete(state, ids, valid)
    check_invariants(state, cfg)
    live = {i for ids_ in snapshot_ids(state, cfg).values() for i in ids_}
    assert 5 not in live and 7 not in live
    assert 6 in live and 8 in live
    assert int(state.num_missed) == 0  # masked rows are not misses


@pytest.mark.parametrize("dtype", ["float32", "int8"])
def test_update_moves_vector_between_clusters(dtype):
    cfg, state, x = _small_state(dtype=dtype)
    update = make_update_fn(cfg)
    search = make_search_fn(cfg, nprobe=3, k=1, path="union_fused_scan")
    # replace id 11 with a vector near a *different* centroid
    cents = np.asarray(state.centroids)
    old_cluster = int(np.argmin(np.sum((cents - x[11]) ** 2, axis=1)))
    new_cluster = (old_cluster + 1) % 3
    new_v = (cents[new_cluster] + 0.01).astype(np.float32)[None]
    state = update(state, jnp.asarray(new_v), jnp.asarray([11], jnp.int32))
    check_invariants(state, cfg)
    assert int(state.num_vectors) == 60  # net zero: tombstone + insert
    assert int(state.dead_count.sum()) == 1  # the stale copy
    d, i = search(state, jnp.asarray(new_v))
    assert int(np.asarray(i)[0, 0]) == 11  # same id, fresh vector
    # searching near the old vector no longer returns 11
    d, i = search(state, jnp.asarray(x[11][None]))
    assert int(np.asarray(i)[0, 0]) != 11 or np.allclose(x[11], new_v[0])


def test_update_unknown_id_is_upsert():
    cfg, state, x = _small_state()
    update = make_update_fn(cfg)
    v = np.full((1, 8), 9.0, np.float32)
    state = update(state, jnp.asarray(v), jnp.asarray([500], jnp.int32))
    check_invariants(state, cfg)
    assert int(state.num_vectors) == 61
    assert int(state.num_missed) == 1  # the tombstone pass found nothing
    live = {i for ids_ in snapshot_ids(state, cfg).values() for i in ids_}
    assert 500 in live


def test_unmappable_id_insert_then_delete_misses():
    """Ids past max_ids stay resident and searchable but cannot be mutated
    (documented map-capacity contract)."""
    d, tm = 8, 4
    cfg = PoolConfig(n_clusters=2, dim=d, block_size=tm, n_blocks=8,
                     max_chain=4, max_ids=16)
    rng = np.random.default_rng(3)
    cents = rng.normal(size=(2, d)).astype(np.float32)
    state = init_state(cfg, jnp.asarray(cents))
    ins = make_insert_fn(cfg)
    state = ins(state, jnp.asarray(rng.normal(size=(2, d)), jnp.float32),
                jnp.asarray([3, 99], jnp.int32))  # 99 >= max_ids
    check_invariants(state, cfg)
    delete = make_delete_fn(cfg)
    state = delete(state, jnp.asarray([99], jnp.int32))
    check_invariants(state, cfg)
    assert int(state.num_deleted) == 0
    assert int(state.num_missed) == 1
    live = {i for ids_ in snapshot_ids(state, cfg).values() for i in ids_}
    assert 99 in live  # still resident


def test_update_duplicate_ids_last_write_wins():
    """Regression: update([7, 7]) used to re-insert two live rows under one
    id — the unmapped copy was undeletable forever.  Duplicates within a
    batch now collapse to the last write."""
    cfg, state, x = _small_state()
    update = make_update_fn(cfg)
    v_first = np.full((1, 8), 2.0, np.float32)
    v_last = np.full((1, 8), -2.0, np.float32)
    batch = np.concatenate([v_first, v_last])
    state = update(state, jnp.asarray(batch),
                   jnp.asarray([7, 7], jnp.int32))
    check_invariants(state, cfg)
    assert int(state.num_vectors) == 60  # exactly one live copy of id 7
    s = jax.device_get(state)
    loc = int(s.id_map[7])
    b, t = loc // cfg.block_size, loc % cfg.block_size
    np.testing.assert_allclose(s.pool_payload[b, t], v_last[0], atol=1e-5)
    # and the single copy is still deletable
    delete = make_delete_fn(cfg)
    state = delete(state, jnp.asarray([7], jnp.int32))
    check_invariants(state, cfg)
    live = {i for ids_ in snapshot_ids(state, cfg).values() for i in ids_}
    assert 7 not in live


def test_unmapped_inserts_counted():
    """Ids past max_ids can never be mutated; the gauge makes the overflow
    loud instead of letting deletes silently start missing."""
    d, tm = 8, 4
    cfg = PoolConfig(n_clusters=2, dim=d, block_size=tm, n_blocks=16,
                     max_chain=8, max_ids=8)
    rng = np.random.default_rng(9)
    state = init_state(cfg, jnp.asarray(
        rng.normal(size=(2, d)).astype(np.float32)))
    ins = make_insert_fn(cfg)
    state = ins(state, jnp.asarray(rng.normal(size=(4, d)), jnp.float32),
                jnp.asarray([1, 2, 20, 21], jnp.int32))
    check_invariants(state, cfg)
    assert int(state.num_unmapped) == 2
    assert pool_stats(state, cfg)["num_unmapped"] == 2


# ---------------------------------------------------------------------------
# compaction = reclamation
# ---------------------------------------------------------------------------


def test_compaction_drops_tombstones_and_reclaims_blocks():
    cfg, state, x = _small_state(n=60)
    delete = make_delete_fn(cfg)
    rearr = make_rearrange_fn(cfg, threshold=10**9, dead_frac=0.2)
    rng = np.random.default_rng(4)
    targets = rng.choice(60, 30, replace=False).astype(np.int32)
    state = delete(state, jnp.asarray(targets))
    check_invariants(state, cfg)
    used_before = int(state.cur_p) - int(state.free_top)
    # loop the maintenance step until quiescent (dead-fraction trigger only:
    # the insert-statistic threshold is set unreachable)
    passes = 0
    for _ in range(8):
        state, triggered = rearr(state)
        if not bool(triggered):
            break
        passes += 1
        check_invariants(state, cfg)
    assert passes >= 1
    assert int(state.dead_count.sum()) == 0
    assert int(state.cluster_len.sum()) == 30  # live rows only
    used_after = int(state.cur_p) - int(state.free_top)
    assert used_after < used_before  # dead space returned to the free stack
    live = sorted(i for ids_ in snapshot_ids(state, cfg).values()
                  for i in ids_)
    assert live == sorted(set(range(60)) - set(targets.tolist()))


def test_fully_dead_cluster_frees_every_block():
    cfg, state, x = _small_state(n=60)
    delete = make_delete_fn(cfg)
    rearr = make_rearrange_fn(cfg, threshold=10**9, dead_frac=0.1)
    sn = snapshot_ids(state, cfg)
    k = max(sn, key=lambda c: len(sn[c]))
    state = delete(state, jnp.asarray(sn[k], jnp.int32))
    for _ in range(8):
        state, triggered = rearr(state)
        if not bool(triggered):
            break
        check_invariants(state, cfg)
    s = jax.device_get(state)
    assert int(s.cluster_len[k]) == 0
    assert int(s.cluster_nblocks[k]) == 0
    assert int(s.cluster_head[k]) == -1 and int(s.cluster_tail[k]) == -1
    # its blocks all landed on the free stack and are reusable
    ins = make_insert_fn(cfg)
    cents = np.asarray(state.centroids)
    refill = (cents[k] + 0.01 * np.arange(8)[:, None]).astype(np.float32)
    state = ins(state, jnp.asarray(refill),
                jnp.arange(200, 208, dtype=jnp.int32))
    check_invariants(state, cfg)


def test_compaction_survives_bump_exhaustion():
    """Regression: the bump pointer is monotone, so bump-only compaction
    shut reclamation off permanently once cur_p neared the pool end.  The
    free-stack fallback keeps reclaiming (non-contiguous run) forever."""
    d, tm = 8, 4
    cfg = PoolConfig(n_clusters=2, dim=d, block_size=tm, n_blocks=24,
                     max_chain=8)
    rng = np.random.default_rng(11)
    cents = np.stack([np.zeros(d), np.full(d, 10.0)]).astype(np.float32)
    state = init_state(cfg, jnp.asarray(cents))
    ins = make_insert_fn(cfg)
    delete = make_delete_fn(cfg)
    rearr = make_rearrange_fn(cfg, threshold=10**9, dead_frac=0.2)
    # churn until the bump region is exhausted, then keep churning: every
    # round deletes half a cluster and must still get its space back
    nid = 0
    for round_ in range(12):
        x = (cents[rng.integers(0, 2, 8)]
             + 0.1 * rng.normal(size=(8, d))).astype(np.float32)
        ids = np.arange(nid, nid + 8, dtype=np.int32)
        nid += 8
        state = ins(state, jnp.asarray(x), jnp.asarray(ids))
        assert int(state.num_dropped) == 0, round_  # space WAS reclaimed
        live = [i for ids_ in snapshot_ids(state, cfg).values()
                for i in ids_]
        victims = rng.choice(live, len(live) // 2, replace=False)
        state = delete(state, jnp.asarray(victims.astype(np.int32)))
        for _ in range(6):
            state, triggered = rearr(state)
            if not bool(triggered):
                break
        check_invariants(state, cfg)
        assert int(state.dead_count.sum()) == 0, round_  # reclaimed
    # the bump region really was exhausted along the way (the fallback
    # engages once cur_p + chain length would overflow, so cur_p parks
    # within one chain of the pool end)
    assert int(state.cur_p) >= cfg.n_blocks - 2, int(state.cur_p)


def test_utilisation_and_dead_fraction_track_live_population():
    cfg, state, x = _small_state(n=60)
    cap = cfg.n_blocks * cfg.block_size
    assert float(utilisation(state, cfg)) == pytest.approx(60 / cap)
    assert float(dead_fraction(state)) == 0.0
    delete = make_delete_fn(cfg)
    state = delete(state, jnp.arange(15, dtype=jnp.int32))
    # live occupancy drops immediately; before the fix every allocated slot
    # still counted as occupied
    assert float(utilisation(state, cfg)) == pytest.approx(45 / cap)
    assert float(dead_fraction(state)) == pytest.approx(15 / 60)
    stats = pool_stats(state, cfg)
    assert stats["live_vectors"] == 45
    assert stats["dead_slots"] == 15
    assert stats["utilisation"] == pytest.approx(45 / cap)
    assert stats["dead_fraction"] == pytest.approx(0.25)


def test_scales_travel_with_compacted_int8_rows():
    """int8 reconstruction survives tombstone-dropping compaction (scales
    and codes move together; the id map re-points at the new slots)."""
    cfg, state, x = _small_state(dtype="int8", n=60)
    delete = make_delete_fn(cfg)
    rearr = make_rearrange_fn(cfg, threshold=10**9, dead_frac=0.1)
    rng = np.random.default_rng(5)
    targets = rng.choice(60, 20, replace=False).astype(np.int32)
    state = delete(state, jnp.asarray(targets))
    for _ in range(8):
        state, triggered = rearr(state)
        if not bool(triggered):
            break
        check_invariants(state, cfg)
    s = jax.device_get(state)
    live_ids = np.setdiff1d(np.arange(60), targets)
    for vid in live_ids:
        loc = int(s.id_map[vid])
        b, t = loc // cfg.block_size, loc % cfg.block_size
        owner = int(s.block_owner[b])
        recon = (np.asarray(s.centroids)[owner]
                 + s.pool_payload[b, t].astype(np.float32)
                 * s.pool_scales[b, t])
        err = np.abs(recon - x[vid])
        assert (err <= s.pool_scales[b, t] * 0.5 + 1e-5).all(), (vid, err.max())


# ---------------------------------------------------------------------------
# e2e acceptance: churn workload across all fused dtypes x rerank
# ---------------------------------------------------------------------------


def _churned(dtype, payload="flat", pq_m=0, seed=7):
    """Interleaved insert/delete/update + >= 1 compaction; returns
    (live corpus dict id->vector, deleted id set, index)."""
    d = 32
    x = _clustered(900, d, seed=seed)
    kw = dict(payload=payload, pq_m=pq_m) if payload == "pq" else dict(
        dtype=dtype
    )
    idx = build_ivf(
        x, n_clusters=8, block_size=16, max_chain=32, add_batch=256,
        nprobe=4, k=10, rearrange_threshold=10**9, dead_frac_threshold=0.15,
        capacity_vectors=4000, **kw,
    )
    rng = np.random.default_rng(seed + 1)
    oracle = {i: x[i] for i in range(900)}
    # grow online
    extra = _clustered(150, d, seed=seed + 2)
    ids = idx.add(extra)
    oracle.update({int(i): v for i, v in zip(ids, extra)})
    # delete 30% of everything resident
    all_ids = np.asarray(sorted(oracle), np.int32)
    dead = rng.choice(all_ids, int(0.3 * len(all_ids)), replace=False)
    n = idx.delete(dead)
    assert n == len(dead)
    for i in dead:
        del oracle[int(i)]
    # update 60 survivors in place
    upd = rng.choice(np.asarray(sorted(oracle), np.int32), 60, replace=False)
    newv = _clustered(60, d, seed=seed + 3)
    idx.update(newv, upd)
    for i, v in zip(upd, newv):
        oracle[int(i)] = v
    # reclaim (dead-fraction trigger)
    passes = idx.maybe_rearrange(max_passes=16)
    assert passes >= 1, "churn must trigger at least one compaction"
    check_invariants(idx.state, idx.pool_cfg)
    # a little more growth after compaction (recycled blocks)
    tail = _clustered(80, d, seed=seed + 4)
    ids = idx.add(tail)
    oracle.update({int(i): v for i, v in zip(ids, tail)})
    return oracle, set(int(i) for i in dead), idx


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
def test_churned_search_all_dtypes(dtype, rerank):
    """Acceptance: post-churn search returns never a deleted id, every
    returned id is live, and the scan impl agrees with the pure-JAX jnp
    oracle — for raw f32/bf16 payloads by both meeting the top-k contract
    against the IVF-exact f32-HIGHEST reference (tests/topk_contract.py),
    for codes and re-ranked survivors by identical ids."""
    if dtype == "pq":
        oracle, dead, idx = _churned(None, payload="pq", pq_m=8)
    else:
        oracle, dead, idx = _churned(dtype)
    rng = np.random.default_rng(11)
    live_ids = np.asarray(sorted(oracle), np.int32)
    q = jnp.asarray(
        np.stack([oracle[int(i)] for i in live_ids[
            rng.integers(0, len(live_ids), 8)]]) + 0.001
    )
    budget = idx._chain_budget()

    def run(scan_impl):
        return search_union_fused(
            idx.pool_cfg, idx.state, q, nprobe=4, k=10,
            scan_impl=scan_impl, chain_budget=budget, pq=idx.pq,
            rerank=rerank,
        )

    d_s, i_s = run("scan")
    d_j, i_j = run("jnp")
    if dtype in ("float32", "bfloat16") and not rerank:
        live = live_rows(idx.state)
        ref = ivf_exact_topk(idx.state, q, nprobe=4, k=10, live=live)
        for name, got in (("scan", (d_s, i_s)), ("jnp", (d_j, i_j))):
            assert_topk_contract(q, id_table(live), got, ref, err_msg=name)
    else:
        np.testing.assert_array_equal(np.asarray(i_s), np.asarray(i_j))
        np.testing.assert_allclose(
            np.asarray(d_s), np.asarray(d_j), rtol=1e-5, atol=1e-5
        )
    out = np.asarray(i_s)
    found = out[out >= 0]
    assert not np.isin(found, np.asarray(sorted(dead))).any()
    assert np.isin(found, live_ids).all()


def test_churn_recall_within_half_percent_of_rebuild():
    """Acceptance: recall@10 at 30% deletions (after compaction) within
    0.5% of an index rebuilt from only the live vectors."""
    oracle, dead, idx = _churned("float32")
    live_ids = np.asarray(sorted(oracle), np.int32)
    corpus = np.stack([oracle[int(i)] for i in live_ids])
    rng = np.random.default_rng(13)
    q = corpus[rng.integers(0, len(corpus), 32)] + 0.01
    # exact oracle over the live corpus, in live-id space
    _, ie = exact_search(jnp.asarray(corpus), jnp.asarray(q), 10)
    true_ids = live_ids[np.asarray(ie)]
    d, i = idx.search(q, nprobe=8, k=10)
    r_churn = recall_at_k(i, true_ids, 10)
    rebuilt = build_ivf(
        corpus, n_clusters=8, block_size=16, max_chain=32, add_batch=256,
        nprobe=4, k=10, capacity_vectors=4000,
    )
    d2, i2 = rebuilt.search(q, nprobe=8, k=10)
    # rebuilt row j carries original id live_ids[j]
    remapped = np.where(i2 >= 0, live_ids[np.maximum(i2, 0)], -1)
    r_rebuilt = recall_at_k(remapped, true_ids, 10)
    assert abs(r_churn - r_rebuilt) <= 0.005, (r_churn, r_rebuilt)


def test_rerank_epilogue_never_resurrects_dead_rows():
    """Defense-in-depth contract of _live_locs: even if survivor locations
    pointed at tombstones, the epilogue masks them (here exercised through
    the normal pipeline: post-delete pre-compaction state, rerank on)."""
    oracle, dead, idx = _churned("int8")
    rng = np.random.default_rng(17)
    # query directly at deleted vectors — the strongest bait
    dead_l = sorted(dead)
    probe_targets = [dead_l[i] for i in
                     rng.integers(0, len(dead_l), 8)]
    # reconstruct bait queries from the original corpus positions
    x = _clustered(900, 32, seed=7)
    q = jnp.asarray(np.stack([
        x[t] if t < 900 else np.zeros(32, np.float32)
        for t in probe_targets
    ]))
    fn = make_search_fn(
        idx.pool_cfg, nprobe=8, k=10, path="union_fused_scan",
        chain_budget=idx._chain_budget(), rerank=True,
    )
    d, i = fn(idx.state, q)
    out = np.asarray(i)
    assert not np.isin(out[out >= 0], np.asarray(dead_l)).any()


# ---------------------------------------------------------------------------
# serving runtime mutation stream
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["parallel", "fused"])
def test_runtime_delete_update_stream(mode):
    from repro.core.scheduler import RuntimeConfig, ServingRuntime

    x = _clustered(600, 16, seed=21)
    idx = build_ivf(x, n_clusters=4, block_size=16, max_chain=32,
                    add_batch=256, capacity_vectors=3000,
                    rearrange_threshold=10**9, dead_frac_threshold=0.1)
    rt = ServingRuntime(
        idx,
        RuntimeConfig(mode=mode, nprobe=4, k=5, flush_min=4,
                      flush_interval=0.05, auto_compact=True),
    )
    try:
        # warm the search path
        d, ids = rt.submit_search(x[:2]).result(timeout=120)
        assert (ids[:, 0] == np.arange(2)).all()
        # delete a batch; the victim must vanish from results
        victims = np.arange(10, 20, dtype=np.int32)
        got = rt.submit_delete(victims).result(timeout=60)
        np.testing.assert_array_equal(got, victims)
        deadline = time.perf_counter() + 30
        while True:  # the lane applies asynchronously in fused mode
            d, ids = rt.submit_search(x[10:12]).result(timeout=60)
            if not np.isin(ids, victims).any():
                break
            assert time.perf_counter() < deadline
            time.sleep(0.05)
        # update: same id, new vector, retrievable at the new location
        newv = _clustered(3, 16, seed=22) + 70.0
        upd_ids = np.asarray([100, 101, 102], np.int32)
        got = rt.submit_update(newv, upd_ids).result(timeout=60)
        np.testing.assert_array_equal(got, upd_ids)
        deadline = time.perf_counter() + 30
        while True:
            d, ids = rt.submit_search(newv).result(timeout=60)
            if (ids[:, 0] == upd_ids).all():
                break
            assert time.perf_counter() < deadline
            time.sleep(0.05)
        s = rt.stats()
        assert s["deletes"] == 10
        assert s["updates"] == 3
        assert s["mutation"].n >= 2  # delete + update latency samples
        assert 0.0 <= s["dead_fraction"] <= 1.0
        assert s["live_vectors"] == 600 - 10
        check_invariants(idx.state, idx.pool_cfg)
    finally:
        rt.stop()


def test_runtime_mixed_kind_order_preserved():
    """delete(id) then insert-of-new-rows then update(id2) submitted
    back-to-back must apply in order (runs split on kind change)."""
    from repro.core.scheduler import RuntimeConfig, ServingRuntime

    x = _clustered(300, 16, seed=31)
    idx = build_ivf(x, n_clusters=4, block_size=16, max_chain=32,
                    add_batch=128, capacity_vectors=2000)
    rt = ServingRuntime(
        idx,
        RuntimeConfig(mode="parallel", nprobe=4, k=5, flush_min=64,
                      flush_interval=0.2),
    )
    try:
        f1 = rt.submit_delete(np.asarray([5], np.int32))
        f2 = rt.submit_insert(_clustered(4, 16, seed=32) + 50.0)
        newv = _clustered(1, 16, seed=33) + 90.0
        f3 = rt.submit_update(newv, np.asarray([7], np.int32))
        for f in (f1, f2, f3):
            f.result(timeout=60)
        s = rt.stats()
        assert s["deletes"] == 1 and s["updates"] == 1 and s["inserts"] >= 4
        check_invariants(idx.state, idx.pool_cfg)
        live = {i for ids_ in snapshot_ids(idx.state, idx.pool_cfg).values()
                for i in ids_}
        assert 5 not in live and 7 in live
    finally:
        rt.stop()


def test_runtime_auto_compact_reclaims():
    from repro.core.scheduler import RuntimeConfig, ServingRuntime

    x = _clustered(600, 16, seed=41)
    idx = build_ivf(x, n_clusters=4, block_size=16, max_chain=32,
                    add_batch=256, capacity_vectors=3000,
                    rearrange_threshold=10**9, dead_frac_threshold=0.1)
    rt = ServingRuntime(
        idx,
        RuntimeConfig(mode="parallel", nprobe=4, k=5, flush_min=4,
                      flush_interval=0.05, auto_compact=True),
    )
    try:
        rng = np.random.default_rng(42)
        victims = rng.choice(600, 200, replace=False).astype(np.int32)
        rt.submit_delete(victims).result(timeout=60)
        deadline = time.perf_counter() + 30
        while rt.stats()["compactions"] == 0:
            assert time.perf_counter() < deadline, "auto-compact never ran"
            time.sleep(0.05)
        s = rt.stats()
        assert s["dead_fraction"] < 0.1
        check_invariants(idx.state, idx.pool_cfg)
    finally:
        rt.stop()
