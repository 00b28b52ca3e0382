"""Memory-block based dynamic vector insertion (paper Alg. 2).

The paper's GPU kernel is thread-per-vector with two atomics:

* ``did = atomicAdd(nl_k, 1)`` — slot assignment inside the cluster;
* ``P[atomicAdd(cur_P, 1)]`` — lock-free block allocation when a thread
  crosses a block boundary (``moff == 0``).

On TPU the SPMD analogue is a *deterministic* batch transform: a stable sort
by cluster gives every incoming vector its within-batch rank, so
``did = cluster_len[k] + rank`` reproduces the exact post-state of the atomic
protocol (the paper's insertion order inside one batch is arbitrary; ours is
batch order, which is one of the admissible serialisations).  Everything is
a handful of vectorised scatters — no data copies of resident vectors, no
reallocation, and the whole step runs under ``jit`` with the state donated.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.block_pool import (
    NULL,
    IVFState,
    PoolConfig,
    alloc_available,
    alloc_blocks,
    commit_alloc,
    quantize_int8,
)


def assign_clusters(centroids: jax.Array, vectors: jax.Array) -> jax.Array:
    """k <- argmin_c ||y - c||^2  (Alg. 2 line 5)."""
    # ||y-c||^2 = ||y||^2 - 2 y.c + ||c||^2 ; ||y||^2 constant per row.
    # Full f32 precision, as in the search's coarse probe (core.search)
    dots = jnp.matmul(vectors, centroids.T,
                      precision=jax.lax.Precision.HIGHEST)
    cn = jnp.sum(centroids * centroids, axis=-1)
    return jnp.argmin(cn[None, :] - 2.0 * dots, axis=-1).astype(jnp.int32)


def insert_payload(
    cfg: PoolConfig,
    state: IVFState,
    assign: jax.Array,  # [B] i32 cluster of each new vector
    payload: jax.Array,  # [B, D] vectors | [B, M] u8 codes
    new_ids: jax.Array,  # [B] i32 global ids
    valid: jax.Array | None = None,  # [B] bool — ragged batches (padding)
) -> IVFState:
    """Insert a batch into the pool.  Pure function of (state, batch)."""
    b = assign.shape[0]
    tm = cfg.block_size
    if valid is None:
        valid = jnp.ones((b,), bool)
    # Padding rows are parked on cluster 0 but masked out of every scatter.
    assign = jnp.where(valid, assign, 0)

    # Within-batch rank of each valid row inside its cluster: stable sort by
    # (assign, ~valid) so valid rows of a cluster precede padding; padding
    # rows receive ranks past the valid run, which every scatter masks out.
    key = assign * 2 + (~valid).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    idx = jnp.arange(b, dtype=jnp.int32)
    sorted_key = key[order]
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_key[1:] != sorted_key[:-1]]
    )
    run_start = jax.lax.associative_scan(jnp.maximum, jnp.where(is_start, idx, 0))
    rank = jnp.zeros((b,), jnp.int32).at[order].set(idx - run_start)

    # Hard per-cluster capacity: a chain can hold max_chain * T_m vectors.
    # Rows past capacity are *rejected* and counted (the paper's resource-
    # exhaustion rejection, §3.3); because the capacity filter removes the
    # highest ranks of a cluster, surviving dids stay contiguous.
    old_len = state.cluster_len
    cap_vecs = cfg.max_chain * tm
    pre_did = old_len[assign] + rank
    vec_ok = valid & (pre_did < cap_vecs)
    want = vec_ok  # chain-capacity survivors; pool capacity filters below
    counts_want = jax.ops.segment_sum(
        want.astype(jnp.int32), assign, num_segments=cfg.n_clusters
    )
    old_nblk = state.cluster_nblocks
    want_nblk = (old_len + counts_want + tm - 1) // tm
    nblk_needed = want_nblk - old_nblk  # [N] >= 0 demanded new blocks
    # exclusive cumsum -> allocation rank base per cluster
    cum = jnp.cumsum(nblk_needed)
    base = cum - nblk_needed
    total_new = cum[-1]

    # Pool exhaustion: allocation ranks are served free-stack-first then
    # bump, so failure is a *suffix* of [0, total_new).  Clip the demand to
    # what the allocator can actually hand out; rows that would land in a
    # failed block are rejected below (again a per-cluster rank suffix, so
    # surviving dids stay contiguous).
    succ_total = jnp.minimum(total_new, alloc_available(state))
    succ_nblk = jnp.clip(succ_total - base, 0, nblk_needed)  # [N] granted
    usable_cap = jnp.minimum((old_nblk + succ_nblk) * tm, cap_vecs)
    vec_ok = valid & vec_ok & (pre_did < usable_cap[assign])
    n_rejected = (valid & ~vec_ok).sum().astype(jnp.int32)
    valid = vec_ok
    counts = jax.ops.segment_sum(
        valid.astype(jnp.int32), assign, num_segments=cfg.n_clusters
    )
    new_len = old_len + counts
    new_nblk = (new_len + tm - 1) // tm

    # ---- allocate new physical blocks (Alg. 2 lines 10-15) --------------
    # at most B new blocks per batch; enumerate candidate slots j in [0, B)
    j = jnp.arange(b, dtype=jnp.int32)
    j_valid = j < total_new
    # cluster owning allocation rank j: searchsorted over inclusive cumsum
    owner = jnp.searchsorted(cum, j, side="right").astype(jnp.int32)
    owner = jnp.clip(owner, 0, cfg.n_clusters - 1)
    jj = j - base[owner]  # index of this new block within its cluster's run
    phys = alloc_blocks(state, j, j_valid)  # NULL past pool capacity

    # block-table scatter: cluster_blocks[owner, old_nblk[owner] + jj] = phys
    # (failed allocations write NULL into slots past new_nblk — a no-op)
    tbl_rows = jnp.where(j_valid, owner, cfg.n_clusters)
    tbl_cols = jnp.where(j_valid, old_nblk[owner] + jj, cfg.max_chain)
    cluster_blocks = state.cluster_blocks.at[tbl_rows, tbl_cols].set(
        phys, mode="drop"
    )

    # block->owner map, maintained incrementally (the fused search prologue
    # prefetches it per candidate instead of rebuilding a [P] scatter from
    # the block table on every dispatch)
    own_rows = jnp.where(j_valid & (phys != NULL), phys, cfg.n_blocks)
    block_owner = state.block_owner.at[own_rows].set(owner, mode="drop")

    # linked-list scatter (paper header relink, Alg. 2 line 14):
    # predecessor of run element jj>0 is phys of rank j-1 (same cluster by
    # construction of contiguous runs); predecessor of jj==0 is the old tail
    # (if the chain was non-empty).
    prev_same_run = alloc_blocks(state, j - 1, j_valid & (jj > 0))
    old_tail = state.cluster_tail[owner]
    prev_blk = jnp.where(jj > 0, prev_same_run, old_tail)
    link_valid = j_valid & (prev_blk != NULL) & (phys != NULL)
    next_block = state.next_block.at[
        jnp.where(link_valid, prev_blk, cfg.n_blocks)
    ].set(phys, mode="drop")

    # head/tail updates (only for blocks that were actually granted)
    first_valid = j_valid & (jj == 0) & (old_nblk[owner] == 0) & (phys != NULL)
    cluster_head = state.cluster_head.at[
        jnp.where(first_valid, owner, cfg.n_clusters)
    ].set(phys, mode="drop")
    last_valid = j_valid & (jj == succ_nblk[owner] - 1)
    cluster_tail = state.cluster_tail.at[
        jnp.where(last_valid, owner, cfg.n_clusters)
    ].set(phys, mode="drop")

    # ---- scatter the vectors themselves (Alg. 2 lines 6-8, 20) ----------
    did = old_len[assign] + rank
    mid = did // tm
    moff = did % tm
    vec_blk = cluster_blocks[assign, jnp.clip(mid, 0, cfg.max_chain - 1)]
    rows = jnp.where(valid, vec_blk, cfg.n_blocks)
    # quantize-on-insert (int8 flat payloads): the raw f32 rows are encoded
    # once here — as *residuals* against their coarse centroid (Faiss
    # IVF-SQ by_residual semantics: the residual dynamic range is a
    # fraction of the raw vectors', so the 8-bit step shrinks with it) —
    # and only the codes + per-vector scales become resident; resident data
    # is never re-encoded or copied (paper Alg. 2 invariant)
    pool_scales = state.pool_scales
    if cfg.has_scales:
        residuals = payload.astype(jnp.float32) - state.centroids[assign]
        payload, scales = quantize_int8(residuals)
        pool_scales = pool_scales.at[rows, moff].set(scales, mode="drop")
    pool_payload = state.pool_payload.at[rows, moff].set(
        payload.astype(state.pool_payload.dtype), mode="drop"
    )
    pool_ids = state.pool_ids.at[rows, moff].set(
        jnp.where(valid, new_ids, NULL), mode="drop"
    )
    # every accepted row is born live, and its id maps to its packed pool
    # location so delete/update can find it without a host round trip
    # (ids >= max_ids stay resident but unmappable — mutations miss them)
    pool_live = state.pool_live.at[rows, moff].set(
        jnp.uint8(1), mode="drop"
    )
    loc = rows * tm + moff
    max_ids = state.id_map.shape[0]
    map_ok = valid & (new_ids >= 0) & (new_ids < max_ids)
    id_map = state.id_map.at[jnp.where(map_ok, new_ids, max_ids)].set(
        loc.astype(jnp.int32), mode="drop"
    )
    # monotonically-assigned ids WILL outgrow the direct-address map under
    # sustained churn; the gauge makes that loud before deletes start
    # silently missing
    n_unmapped = (valid & ~map_ok).sum().astype(jnp.int32)

    n_inserted = valid.sum().astype(jnp.int32)
    return dataclasses.replace(
        state,
        pool_payload=pool_payload,
        pool_ids=pool_ids,
        pool_scales=pool_scales,
        pool_live=pool_live,
        id_map=id_map,
        block_owner=block_owner,
        next_block=next_block,
        cluster_head=cluster_head,
        cluster_tail=cluster_tail,
        cluster_blocks=cluster_blocks,
        cluster_nblocks=new_nblk,
        cluster_len=new_len,
        new_since_rearrange=state.new_since_rearrange + counts,
        num_vectors=state.num_vectors + n_inserted,
        num_dropped=state.num_dropped + n_rejected,
        num_unmapped=state.num_unmapped + n_unmapped,
        **commit_alloc(state, succ_total),
    )


def make_insert_fn(cfg: PoolConfig, encode=None):
    """Jitted end-to-end insert step: raw vectors -> updated state.

    ``encode(state, assign, vectors) -> payload`` converts raw vectors to the
    pool payload (identity for ivfflat; residual-PQ encode for ivfpq).  The
    state is donated so XLA writes the pool in place (paper property: no
    reallocation, no copying of resident data).
    """

    def step(state: IVFState, vectors, new_ids, valid=None):
        assign = assign_clusters(state.centroids, vectors)
        payload = vectors if encode is None else encode(state, assign, vectors)
        return insert_payload(cfg, state, assign, payload, new_ids, valid)

    return jax.jit(step, donate_argnums=(0,))
