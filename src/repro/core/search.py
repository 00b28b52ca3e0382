"""IVF search over the block pool: coarse probe -> block scan -> top-k.

Two scan paths are provided and benchmarked against each other in §Perf:

* ``chain_walk``  — paper-faithful: follow ``next_block`` header pointers one
  hop at a time (a ``lax.scan`` whose carry is the frontier block of every
  probed chain).  This is the direct port of the GPU linked-list traversal
  and is intentionally kept as the *baseline*: each hop is a dependent
  gather, so the TPU pays a serialised round trip per hop.
* ``block_table`` — TPU adaptation: gather the whole chain for every probed
  cluster in one vectorised HLO gather via ``cluster_blocks`` and scan all
  candidate blocks as one batched matmul (MXU-shaped).  Same results,
  no pointer chasing.

The distance scan itself can additionally be routed through the Pallas
kernel (``repro.kernels.ivf_scan``) via ``scan_impl="pallas"``.

* ``union_fused`` — streaming selection on top of the union scan: scoring
  and top-k are fused in one Pallas kernel keeping a per-query top-``K'``
  accumulator in VMEM, so the ``[C, Q, T]`` score tensor is never
  materialized to HBM (``union_fused_scan`` is the chunked ``lax.scan``
  fallback with the same semantics).  See ``docs/search_paths.md``.

The fused paths dispatch on the payload dtype (``PoolConfig.dtype``):
float32 and bfloat16 blocks route through ``ivf_block_topk``, int8
*residual* codes through the integer-MXU ``ivf_block_topk_int8``
(per-vector scales from ``IVFState.pool_scales``), PQ codes through
``ivf_pq_block_topk``.  The fused kernels identify candidates by *packed
pool location* (``block*T + offset``, derived in-kernel from the prefetched
block id at zero HBM cost); the final top-k resolves locations to global
ids with one ``[Q, k]`` gather.  With ``rerank=True`` the K' survivor rows
are gathered by location and an exact-fp32 re-rank epilogue
(``rerank_topk``; jnp fallback for the scan impl) re-sorts them before the
final top-k — recovering the recall a low-precision first pass gives up.

The *routing prologue* is fused too (§Perf): the coarse probe streams
through ``coarse_topk`` (per-query top-``nprobe`` accumulator on-chip —
the ``[Q, N_clusters]`` distance matrix never exists in HBM, bit-exact
with ``coarse_probe``), the union candidate list is deduped + compacted
by one sort/cumsum pass over the ``[CB]`` block list (no per-query work,
no ``[Q, NP, CU]`` match tensor), and per-(query, candidate) membership /
probe slots are derived *inside* the fused kernels by comparing each
candidate's prefetched owner (``IVFState.block_owner``, maintained
incrementally by insert/rearrange) against the VMEM-resident ``[Q, NP]``
probe list — per-query routing traffic is O(NP), not O(CB).

Every path accounts for tombstones (``core.mutate``): the fused kernels
stream ``IVFState.pool_live`` alongside the payload and force dead rows to
``inf`` before the top-K' accumulator; the gather paths fold the live mask
into their validity masks; the re-rank epilogue re-checks survivor
locations against the mask (defense in depth).  A deleted id can therefore
never surface from any impl, and k > live returns the usual (inf, -1) tail.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.block_pool import NULL, IVFState, PoolConfig
from repro.core.pq import PQParams

INF = jnp.float32(jnp.inf)
# f32 contractions at full precision: at TPU default precision an f32 dot
# is one bf16 pass, whose rounding at SIFT-like norms exceeds the gaps
# between neighbours and reorders the probe and the top-k
HIGHEST = jax.lax.Precision.HIGHEST

# score_fn hooks have signature (state, queries, payload, probe_idx) ->
# [Q, C, T] scores; centroids and any other index-dependent data must come
# from the traced ``state`` (see core.pq.pq_score_fn).


def l2_sq(queries: jax.Array, points: jax.Array) -> jax.Array:
    """[Q, D] x [N, D] -> [Q, N] squared L2 distances."""
    qn = jnp.sum(queries * queries, axis=-1, keepdims=True)
    pn = jnp.sum(points * points, axis=-1)
    return qn + pn[None, :] - 2.0 * jnp.matmul(
        queries, points.T, precision=HIGHEST
    )


def coarse_probe(state: IVFState, queries: jax.Array, nprobe: int):
    """Top-``nprobe`` nearest centroids per query (ivf coarse quantizer)."""
    d = l2_sq(queries, state.centroids)
    neg_d, idx = jax.lax.top_k(-d, nprobe)
    return idx.astype(jnp.int32), -neg_d


def exact_search(corpus: jax.Array, queries: jax.Array, k: int):
    """Brute-force oracle used for recall metrics."""
    d = l2_sq(queries, corpus)
    neg_d, idx = jax.lax.top_k(-d, k)
    return -neg_d, idx


# ---------------------------------------------------------------------------
# Block-table path (TPU-native)
# ---------------------------------------------------------------------------


def gather_candidate_blocks(
    state: IVFState, probe_idx: jax.Array, chain_budget: Optional[int] = None
):
    """probe_idx [Q, nprobe] -> (payload [Q, C, T, ...], ids [Q, C, T], valid).

    ``chain_budget`` statically bounds how many chain slots are gathered per
    cluster.  ``max_chain`` is a *capacity* knob (worst-case hot list); the
    live maximum chain length is usually far smaller, and gathering the full
    table pays for NULL padding.  The runtime picks the budget from
    ``cluster_nblocks.max()`` bucketed to a power of two (see IVFIndex),
    so results are exact and the jit cache stays tiny.
    """
    table = state.cluster_blocks
    if chain_budget is not None and chain_budget < table.shape[1]:
        table = table[:, :chain_budget]
    blocks = table[probe_idx]  # [Q, nprobe, budget]
    q = blocks.shape[0]
    flat = blocks.reshape(q, -1)  # [Q, C]
    safe = jnp.where(flat == NULL, 0, flat)
    payload = state.pool_payload[safe]
    ids = state.pool_ids[safe]
    # tombstoned rows keep a stale id until compaction — the live mask, not
    # id validity, decides whether a slot may score
    live = state.pool_live[safe] != 0
    valid = (flat != NULL)[..., None] & (ids != NULL) & live
    return payload, ids, valid


def flat_block_scores(queries: jax.Array, payload: jax.Array) -> jax.Array:
    """queries [Q, D], payload [Q, C, T, D] -> squared L2 [Q, C, T].

    bf16 payloads accumulate norms and dots in f32 (matching the fused
    kernels) — a bf16-accumulated norm would silently skew distances."""
    pf = payload.astype(jnp.float32)
    vn = jnp.sum(pf * pf, axis=-1)
    qn = jnp.sum(queries * queries, axis=-1)[:, None, None]
    dots = jnp.einsum(
        "qd,qctd->qct", queries.astype(payload.dtype), payload,
        preferred_element_type=jnp.float32, precision=HIGHEST,
    )
    return qn + vn - 2.0 * dots


def search_block_table(
    cfg: PoolConfig,
    state: IVFState,
    queries: jax.Array,
    *,
    nprobe: int,
    k: int,
    score_fn: Optional[Callable] = None,
    chain_budget: Optional[int] = None,
    pq: Optional[PQParams] = None,  # unused (PQ rides on score_fn here)
    rerank: bool = False,
):
    """Vectorised search. Returns (dists [Q, k], ids [Q, k])."""
    if rerank:
        raise NotImplementedError(
            "rerank is a fused-path epilogue; use union_fused[_scan]"
        )
    probe_idx, _ = coarse_probe(state, queries, nprobe)
    payload, ids, valid = gather_candidate_blocks(state, probe_idx, chain_budget)
    if score_fn is None:
        scores = flat_block_scores(queries, payload)
    else:
        scores = score_fn(state, queries, payload, probe_idx)
    scores = jnp.where(valid, scores, INF)
    q = queries.shape[0]
    flat_scores = scores.reshape(q, -1)
    flat_ids = ids.reshape(q, -1)
    neg_d, sel = jax.lax.top_k(-flat_scores, k)
    out_ids = jnp.take_along_axis(flat_ids, sel, axis=1)
    out_ids = jnp.where(jnp.isinf(-neg_d), NULL, out_ids)
    return -neg_d, out_ids


# ---------------------------------------------------------------------------
# Chain-walk path (paper-faithful linked list traversal)
# ---------------------------------------------------------------------------


def search_chain_walk(
    cfg: PoolConfig,
    state: IVFState,
    queries: jax.Array,
    *,
    nprobe: int,
    k: int,
    score_fn: Optional[Callable] = None,
    chain_budget: Optional[int] = None,
    pq: Optional[PQParams] = None,  # unused (PQ rides on score_fn here)
    rerank: bool = False,
):
    """Follow ``next_block`` headers hop by hop (GPU traversal port)."""
    if rerank:
        raise NotImplementedError(
            "rerank is a fused-path epilogue; use union_fused[_scan]"
        )
    q = queries.shape[0]
    probe_idx, _ = coarse_probe(state, queries, nprobe)
    cur0 = state.cluster_head[probe_idx]  # [Q, nprobe]
    best_d0 = jnp.full((q, k), INF)
    best_i0 = jnp.full((q, k), NULL, jnp.int32)

    def hop(carry, _):
        cur, best_d, best_i = carry
        safe = jnp.where(cur == NULL, 0, cur)
        payload = state.pool_payload[safe]  # [Q, nprobe, T, ...]
        ids = state.pool_ids[safe]  # [Q, nprobe, T]
        if score_fn is None:
            scores = flat_block_scores(
                queries, payload.reshape(q, -1, *payload.shape[2:])
            ).reshape(ids.shape)
        else:
            scores = score_fn(state, queries, payload, probe_idx)
        live = state.pool_live[safe] != 0
        alive = (cur != NULL)[..., None] & (ids != NULL) & live
        scores = jnp.where(alive, scores, INF)
        cat_d = jnp.concatenate([best_d, scores.reshape(q, -1)], axis=1)
        cat_i = jnp.concatenate([best_i, ids.reshape(q, -1)], axis=1)
        neg_d, sel = jax.lax.top_k(-cat_d, k)
        best_i = jnp.take_along_axis(cat_i, sel, axis=1)
        nxt = jnp.where(cur == NULL, NULL, state.next_block[safe])
        return (nxt, -neg_d, best_i), None

    (cur, best_d, best_i), _ = jax.lax.scan(
        hop, (cur0, best_d0, best_i0), None,
        length=chain_budget or cfg.max_chain,
    )
    best_i = jnp.where(jnp.isinf(best_d), NULL, best_i)
    return best_d, best_i


# ---------------------------------------------------------------------------
# Union-dedup scan (beyond-paper TPU optimisation, §Perf):
# the union of probed clusters across the query batch is scanned once, so
# every candidate block is read from HBM exactly once per *batch* instead of
# once per *query*.  ``scan_impl="pallas"`` routes the distance computation
# through the scalar-prefetch Pallas kernel (repro.kernels.ivf_scan).
# ---------------------------------------------------------------------------


class UnionCandidates(NamedTuple):
    flat_blocks: jax.Array  # [CB] deduped live block ids, NULL-padded tail
    owners: jax.Array  # [CB] owning cluster per candidate (NULL padding)
    probe_idx: jax.Array  # [Q, NP] probed cluster ids (distinct per row)


def _coarse_dispatch(
    state: IVFState, queries: jax.Array, nprobe: int, scan_impl: str
):
    """Coarse probe matching the path's execution style: the Pallas paths
    stream it through ``coarse_topk`` (no [Q, N] matrix in HBM), the scan
    fallback through its chunked ``lax.scan`` twin, and the jnp oracle
    through plain ``coarse_probe`` — all three are bit-exact, ties
    included, so the choice never changes results."""
    if scan_impl == "pallas":
        from repro.kernels.ops import coarse_topk

        return coarse_topk(queries, state.centroids, nprobe=nprobe)
    if scan_impl == "scan":
        from repro.kernels.ivf_scan import coarse_topk_scan

        return coarse_topk_scan(queries, state.centroids, nprobe=nprobe)
    return coarse_probe(state, queries, nprobe)


def _union_candidates(
    cfg: PoolConfig,
    state: IVFState,
    queries: jax.Array,
    nprobe: int,
    chain_budget: Optional[int],
    scan_impl: str = "jnp",
) -> UnionCandidates:
    """Fused routing prologue of the union paths: streaming coarse probe,
    then dedup + compaction of the candidate block list in a single
    sort/cumsum pass over the [CB] block ids — computed once per dispatch,
    not per query.  No ``jnp.unique``, no [Q, NP, CU] match tensor, no
    [Q, CB] membership operand: the per-(query, candidate) routing is
    derived in-kernel from ``owners`` and ``probe_idx``.

    The compacted list is statically capped at min(CB, P): every live
    block appears at most once (chains are disjoint), so dead slots (chain
    padding, cross-query duplicates) cost neither a grid step nor a DMA in
    the streaming kernels."""
    q = queries.shape[0]
    mc = min(chain_budget or cfg.max_chain, cfg.max_chain)
    probe_idx, _ = _coarse_dispatch(state, queries, nprobe, scan_impl)
    blocks = state.cluster_blocks[:, :mc][probe_idx].reshape(-1)  # [Q*NP*mc]
    # NULLs sort to the back via a +inf-like key; the first occurrence of
    # each block id is scattered to its rank among the uniques
    sentinel = jnp.int32(2**31 - 1)
    srt = jnp.sort(jnp.where(blocks == NULL, sentinel, blocks))
    keep = (srt != sentinel) & jnp.concatenate(
        [jnp.ones((1,), bool), srt[1:] != srt[:-1]]
    )
    cap = min(blocks.shape[0], cfg.n_blocks)
    pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
    flat = (
        jnp.full((cap,), NULL, jnp.int32)
        .at[jnp.where(keep, pos, cap)]
        .set(jnp.where(keep, srt, NULL), mode="drop")
    )
    owners = jnp.where(
        flat == NULL, NULL, state.block_owner[jnp.maximum(flat, 0)]
    )
    return UnionCandidates(flat, owners, probe_idx)


def search_union(
    cfg: PoolConfig,
    state: IVFState,
    queries: jax.Array,
    *,
    nprobe: int,
    k: int,
    score_fn: Optional[Callable] = None,  # unused (flat payload only)
    scan_impl: str = "jnp",
    chain_budget: Optional[int] = None,
    pq: Optional[PQParams] = None,
    rerank: bool = False,
):
    if cfg.payload != "flat" or cfg.has_scales:
        raise NotImplementedError(
            "union/union_pallas score raw f32/bf16 vectors; PQ and int8 "
            "payloads use the fused union paths (or block_table/chain_walk "
            "for PQ)"
        )
    if rerank:
        raise NotImplementedError(
            "rerank is a fused-path epilogue; use union_fused[_scan]"
        )
    q = queries.shape[0]
    # compacted prologue: dead (NULL / duplicate) slots are gone, so the
    # scan below only ever scores live blocks (they used to be scored
    # against clamped block 0 and masked)
    uc = _union_candidates(
        cfg, state, queries, nprobe, chain_budget,
        "pallas" if scan_impl == "pallas" else "jnp",
    )
    flat_blocks = uc.flat_blocks

    if scan_impl == "pallas":
        from repro.kernels.ops import ivf_block_scan

        scores = ivf_block_scan(queries, state.pool_payload, flat_blocks)
    else:
        from repro.kernels.ref import ivf_block_scan_ref

        scores = ivf_block_scan_ref(queries, state.pool_payload, flat_blocks)
    # scores [CB, Q, T] -> mask holes, non-membership, empty slots, and
    # tombstones (dead rows keep a stale id until compaction)
    ids = state.pool_ids[jnp.maximum(flat_blocks, 0)]  # [CB, T]
    live = state.pool_live[jnp.maximum(flat_blocks, 0)] != 0  # [CB, T]
    slot_ok = (flat_blocks != NULL)[:, None] & (ids != NULL) & live
    member_b = (
        uc.probe_idx[:, :, None] == uc.owners[None, None, :]
    ).any(axis=1)  # [Q, CB] (an XLA compare — fine outside the kernels)
    ok = slot_ok[None, :, :] & member_b[:, :, None]  # [Q, CB, T]
    sq = jnp.where(ok, jnp.transpose(scores, (1, 0, 2)), INF)
    flat_scores = sq.reshape(q, -1)
    flat_ids = jnp.broadcast_to(ids[None], (q, *ids.shape)).reshape(q, -1)
    neg_d, sel = jax.lax.top_k(-flat_scores, k)
    out_ids = jnp.take_along_axis(flat_ids, sel, axis=1)
    out_ids = jnp.where(jnp.isinf(-neg_d), NULL, out_ids)
    return -neg_d, out_ids


# ---------------------------------------------------------------------------
# Fused streaming-selection union scan (§Perf headline): identical candidate
# set to ``search_union``, but scoring and selection are fused — a running
# per-query top-K' accumulator is kept on-chip across the candidate-block
# scan, so only [Q, K'] (score, id) pairs are written back instead of the
# full [CB, Q, T] score tensor.  The final ``top_k(k)`` runs over K'
# candidates, not CB*T.  See docs/search_paths.md for when to pick it.
# ---------------------------------------------------------------------------


def default_kprime(k: int) -> int:
    """Accumulator width: smallest lane-aligned (128) multiple >= k."""
    return max(128, -(-k // 128) * 128)


def _rerank_dispatch(queries, rows, scales, loc, scan_impl):
    if scan_impl == "pallas":
        from repro.kernels.ops import rerank_topk

        return rerank_topk(queries, rows, scales, loc)
    from repro.kernels.ref import rerank_topk_ref

    return rerank_topk_ref(queries, rows, scales, loc)


def _live_locs(state, loc):
    """Invalidate survivor locations whose slot is no longer live.  The
    first pass already masks tombstones in-kernel, so this is pure defense
    in depth — it makes 'a deleted id can never leave the epilogue' a local
    property of the re-rank instead of a cross-kernel invariant."""
    live = state.pool_live.reshape(-1)[jnp.clip(loc, 0)] != 0
    return jnp.where((loc != NULL) & live, loc, NULL)


def _rerank_flat(cfg, state, queries, loc, scan_impl):
    """Exact-fp32 re-rank of flat-payload survivors: gather the K' rows by
    packed location (one XLA gather), then fused dequant + distance +
    (distance, location) sort.  int8 rows are residual codes, so the owning
    cluster's centroid is added back before scoring.  Returns
    ([Q, K'] dists asc, [Q, K'] locs)."""
    p, t = state.pool_ids.shape
    loc = _live_locs(state, loc)
    safe = jnp.clip(loc, 0)
    rows = state.pool_payload.reshape(p * t, -1)[safe]  # [Q, K', D]
    scales = jnp.ones(loc.shape, jnp.float32)
    if cfg.has_scales:
        svs = state.pool_scales.reshape(-1)[safe]
        # block_owner is maintained incrementally (free blocks own NULL —
        # clamp for the gather; invalid locations are masked by loc == -1)
        owner = jnp.maximum(state.block_owner[safe // t], 0)
        rows = state.centroids[owner] + rows.astype(jnp.float32) * svs[..., None]
    return _rerank_dispatch(queries, rows, scales, loc, scan_impl)


def _rerank_pq(cfg, state, pq, queries, loc, scan_impl):
    """Re-rank PQ survivors at full precision: decode codes, add the
    owning cluster's centroid back (residual semantics), exact fp32
    distance."""
    from repro.core import pq as pqmod

    p, t = state.pool_ids.shape
    loc = _live_locs(state, loc)
    safe = jnp.clip(loc, 0)
    codes = state.pool_payload.reshape(p * t, -1)[safe]  # [Q, K', M]
    cent = state.centroids[jnp.maximum(state.block_owner[safe // t], 0)]
    recon = cent + pqmod.decode(pq, codes)
    ones = jnp.ones(loc.shape, jnp.float32)
    return _rerank_dispatch(queries, recon, ones, loc, scan_impl)


def search_union_fused(
    cfg: PoolConfig,
    state: IVFState,
    queries: jax.Array,
    *,
    nprobe: int,
    k: int,
    score_fn: Optional[Callable] = None,  # unused (fused paths score inline)
    scan_impl: str = "pallas",
    chain_budget: Optional[int] = None,
    kprime: Optional[int] = None,
    pq: Optional[PQParams] = None,  # required for payload == "pq"
    rerank: bool = False,
):
    if cfg.payload == "pq" and pq is None:
        raise ValueError(
            "union_fused on a PQ payload needs the trained PQParams "
            "(pass pq=index.pq / via make_search_fn)"
        )
    # Fused routing prologue: the candidate list arrives deduped +
    # compacted (cap = min(Q*nprobe*budget, P) — every live block at most
    # once, dead slots truncated), and the only per-query routing operands
    # the kernels receive are the [Q, NP] probe list (VMEM-resident) and
    # the [CB] candidate owners (scalar-prefetched): membership and the
    # residual probe slot are derived on-chip.  No [Q, CB] cand_ok/pslot,
    # no [Q, N_clusters] coarse matrix.
    uc = _union_candidates(
        cfg, state, queries, nprobe, chain_budget, scan_impl
    )
    flat_blocks, owners, probe_idx = uc.flat_blocks, uc.owners, uc.probe_idx
    kp = kprime or default_kprime(k)
    assert kp >= k, (kp, k)
    if cfg.payload == "pq":
        from repro.core import pq as pqmod

        # per-(query, probe) residual ADC tables
        lut = pqmod.probe_residual_luts(
            pq, state.centroids, queries, probe_idx
        )  # [Q, NP, M, KSUB]
        if scan_impl == "pallas":
            from repro.kernels.ops import ivf_pq_block_topk

            d, i = ivf_pq_block_topk(
                lut, state.pool_payload, flat_blocks, owners,
                state.pool_ids, state.pool_live, probe_idx, kprime=kp,
            )
        elif scan_impl == "scan":
            from repro.kernels.ivf_scan import ivf_pq_block_topk_scan

            d, i = ivf_pq_block_topk_scan(
                lut, state.pool_payload, flat_blocks, owners,
                state.pool_ids, state.pool_live, probe_idx, kprime=kp,
            )
        else:
            from repro.kernels.ref import ivf_pq_block_topk_ref

            d, i = ivf_pq_block_topk_ref(
                lut, state.pool_payload, flat_blocks, owners,
                state.pool_ids, state.pool_live, probe_idx, kprime=kp,
            )
    elif cfg.has_scales:
        # int8 residual payload: quantize the per-probe query residuals
        # once, then the integer-MXU variant scores codes against codes
        from repro.kernels.ivf_scan import quantize_queries

        qres = queries[:, None, :] - state.centroids[probe_idx]
        q_codes, q_meta = quantize_queries(qres)  # [Q, NP, D], [Q, NP, 2]
        if scan_impl == "pallas":
            from repro.kernels.ops import ivf_block_topk_int8

            d, i = ivf_block_topk_int8(
                q_codes, q_meta, state.pool_payload, state.pool_scales,
                flat_blocks, owners, state.pool_ids, state.pool_live,
                probe_idx, kprime=kp,
            )
        elif scan_impl == "scan":
            from repro.kernels.ivf_scan import ivf_block_topk_int8_scan

            d, i = ivf_block_topk_int8_scan(
                q_codes, q_meta, state.pool_payload, state.pool_scales,
                flat_blocks, owners, state.pool_ids, state.pool_live,
                probe_idx, kprime=kp,
            )
        else:
            from repro.kernels.ref import ivf_block_topk_int8_ref

            d, i = ivf_block_topk_int8_ref(
                q_codes, q_meta, state.pool_payload, state.pool_scales,
                flat_blocks, owners, state.pool_ids, state.pool_live,
                probe_idx, kprime=kp,
            )
    elif scan_impl == "pallas":
        from repro.kernels.ops import ivf_block_topk

        d, i = ivf_block_topk(
            queries, state.pool_payload, flat_blocks, owners,
            state.pool_ids, state.pool_live, probe_idx, kprime=kp,
        )
    elif scan_impl == "scan":
        from repro.kernels.ivf_scan import ivf_block_topk_scan

        d, i = ivf_block_topk_scan(
            queries, state.pool_payload, flat_blocks, owners,
            state.pool_ids, state.pool_live, probe_idx, kprime=kp,
        )
    else:
        from repro.kernels.ref import ivf_block_topk_ref

        d, i = ivf_block_topk_ref(
            queries, state.pool_payload, flat_blocks, owners,
            state.pool_ids, state.pool_live, probe_idx, kprime=kp,
        )
    # the fused kernels emit packed pool locations (block*T + offset,
    # derived in-kernel from the prefetched block id at zero HBM cost)
    if rerank:
        # exact re-rank epilogue over the K' survivors; output rows come
        # back sorted ascending by (exact distance, location)
        if cfg.payload == "pq":
            d, loc = _rerank_pq(cfg, state, pq, queries, i, scan_impl)
        else:
            d, loc = _rerank_flat(cfg, state, queries, i, scan_impl)
        d, loc = d[:, :k], loc[:, :k]
        out_ids = state.pool_ids.reshape(-1)[jnp.clip(loc, 0)]
        out_ids = jnp.where((loc == NULL) | jnp.isinf(d), NULL, out_ids)
        return d, out_ids
    # second selection stage: k out of the K' streamed survivors, then one
    # [Q, k] gather resolves locations to caller-visible global ids
    neg_d, sel = jax.lax.top_k(-d, k)
    loc = jnp.take_along_axis(i, sel, axis=1)
    out_ids = state.pool_ids.reshape(-1)[jnp.clip(loc, 0)]
    out_ids = jnp.where((loc == NULL) | jnp.isinf(-neg_d), NULL, out_ids)
    return -neg_d, out_ids


# All selectable scan paths (docs/search_paths.md documents the ladder) and
# the subset that can serve a PQ payload: block_table / chain_walk score
# through the score_fn hook, the fused union paths route through the PQ-ADC
# streaming kernel; plain union / union_pallas score raw vectors only.
SEARCH_IMPLS = {
    "block_table": search_block_table,
    "chain_walk": search_chain_walk,
    "union": search_union,
    "union_pallas": partial(search_union, scan_impl="pallas"),
    "union_fused": search_union_fused,
    "union_fused_scan": partial(search_union_fused, scan_impl="scan"),
}
PQ_SEARCH_PATHS = frozenset(
    {"block_table", "chain_walk", "union_fused", "union_fused_scan"}
)
# the fused union paths are the only ones that understand int8 payloads
# (everything else would score the raw codes as numbers) and the only ones
# with the re-rank epilogue
FUSED_SEARCH_PATHS = frozenset({"union_fused", "union_fused_scan"})
INT8_SEARCH_PATHS = FUSED_SEARCH_PATHS


def resolve_search_impl(
    cfg: PoolConfig, path: str, rerank: bool = False
) -> Callable:
    """Look up a scan path, rejecting typos and payload mismatches loudly
    (a silent fallback would benchmark / serve the wrong path)."""
    if path not in SEARCH_IMPLS:
        raise ValueError(
            f"unknown search_path {path!r}; expected one of "
            f"{sorted(SEARCH_IMPLS)}"
        )
    if cfg.payload == "pq" and path not in PQ_SEARCH_PATHS:
        raise NotImplementedError(
            f"search_path {path!r} scores raw vectors; PQ payloads support "
            f"{sorted(PQ_SEARCH_PATHS)}"
        )
    if cfg.has_scales and path not in INT8_SEARCH_PATHS:
        raise NotImplementedError(
            f"search_path {path!r} scores raw vectors; int8 payloads "
            f"support {sorted(INT8_SEARCH_PATHS)}"
        )
    if rerank and path not in FUSED_SEARCH_PATHS:
        raise NotImplementedError(
            f"rerank is a fused-path epilogue; search_path {path!r} does "
            f"not support it (use one of {sorted(FUSED_SEARCH_PATHS)})"
        )
    return SEARCH_IMPLS[path]


def make_search_fn(
    cfg: PoolConfig,
    *,
    nprobe: int,
    k: int,
    path: str = "block_table",
    score_fn: Optional[Callable] = None,
    chain_budget: Optional[int] = None,
    pq: Optional[PQParams] = None,
    rerank: bool = False,
):
    """Jitted search step closed over static (nprobe, k, traversal path)."""
    impl = resolve_search_impl(cfg, path, rerank)

    @jax.jit
    def step(state: IVFState, queries: jax.Array):
        return impl(
            cfg, state, queries, nprobe=nprobe, k=k, score_fn=score_fn,
            chain_budget=chain_budget, pq=pq, rerank=rerank,
        )

    return step
