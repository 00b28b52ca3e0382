"""Plain references for search results.

Exact squared-L2 distances in f32 at ``HIGHEST`` matmul precision, and an
IVF-exact search built on them: probe the ``nprobe`` nearest lists, then
brute-force every live row of those lists.  Nothing here goes through the
search paths under test — no block table, chain walk or kernel: the live
rows are read back from the pool by its live mask and scored directly.

Flat payloads only.  A bfloat16 pool is scored the way the search paths
define it: norms in f32, and the dot product of bf16-rounded queries with
the stored rows, accumulated in f32.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.block_pool import IVFState

HIGHEST = jax.lax.Precision.HIGHEST


def l2_sq(queries: jax.Array, points: jax.Array) -> jax.Array:
    """[Q, D] x [N, D] -> [Q, N] squared L2 distances, f32 at HIGHEST."""
    q = jnp.asarray(queries, jnp.float32)
    p = jnp.asarray(points)
    qd = q.astype(p.dtype).astype(jnp.float32)  # identity for f32 rows
    pf = p.astype(jnp.float32)
    qn = jnp.sum(q * q, axis=-1, keepdims=True)
    pn = jnp.sum(pf * pf, axis=-1)
    return qn + pn[None, :] - 2.0 * jnp.matmul(qd, pf.T, precision=HIGHEST)


def exact_topk(points: jax.Array, queries: jax.Array, k: int):
    """Brute force: ([Q, k] dists ascending, [Q, k] row indices).  Ties
    go to the lower row index (``lax.top_k`` order)."""
    neg, idx = jax.lax.top_k(-l2_sq(queries, points), k)
    return -neg, idx


class LiveRows(NamedTuple):
    ids: np.ndarray  # [L] i32 id of every live row
    lists: np.ndarray  # [L] i32 IVF list holding the row
    rows: jax.Array  # [L, D] stored vectors (payload dtype)


def live_rows(state: IVFState) -> LiveRows:
    """Every live row of the pool, read by its live mask: id, owning list
    and stored vector.  The vectors are gathered on the device."""
    if state.pool_payload.dtype not in (jnp.float32, jnp.bfloat16):
        raise NotImplementedError(
            "the exact reference scores raw vectors; int8 and PQ pools "
            "hold codes"
        )
    live, ids, owner = jax.device_get(
        (state.pool_live, state.pool_ids, state.block_owner)
    )
    loc = np.flatnonzero(np.asarray(live).reshape(-1))
    t = live.shape[1]
    d = state.pool_payload.shape[-1]
    rows = state.pool_payload.reshape(-1, d)[jnp.asarray(loc, jnp.int32)]
    return LiveRows(
        ids=np.asarray(ids).reshape(-1)[loc].astype(np.int32),
        lists=np.asarray(owner)[loc // t].astype(np.int32),
        rows=rows,
    )


def _masked_topk(queries, rows, ids, member, k):
    d = jnp.where(member, l2_sq(queries, rows), jnp.inf)
    neg, sel = jax.lax.top_k(-d, k)
    out = jnp.where(jnp.isinf(neg), -1, ids[sel])
    return -neg, out


_masked_topk_jit = jax.jit(_masked_topk, static_argnames=("k",))


def ivf_exact_topk(state: IVFState, queries, *, nprobe: int, k: int,
                   live: LiveRows | None = None, chunk: int = 64):
    """IVF-exact reference: the ``nprobe`` lists nearest each query by
    exact distance, then brute force over every live row of those lists.
    ``nprobe=None`` searches the whole live set.  Returns ([Q, k] dists
    ascending, [Q, k] ids), with (inf, -1) past the live candidates."""
    live = live_rows(state) if live is None else live
    queries = np.asarray(queries, np.float32)
    lists = jnp.asarray(live.lists)
    ids = jnp.asarray(live.ids)
    n_lists = state.centroids.shape[0]
    ds, outs = [], []
    for s in range(0, len(queries), chunk):
        q = queries[s : s + chunk]
        n = len(q)
        q = np.pad(q, ((0, chunk - n), (0, 0)))  # one compile per chunk
        if nprobe is None:
            member = jnp.ones((chunk, len(live.ids)), bool)
        else:
            _, probe = exact_topk(state.centroids, q, nprobe)
            probed = jnp.zeros((chunk, n_lists), bool).at[
                jnp.arange(chunk)[:, None], probe
            ].set(True)
            member = probed[:, lists]  # [chunk, L]
        d, i = _masked_topk_jit(jnp.asarray(q), live.rows, ids, member, k=k)
        ds.append(np.asarray(d)[:n])
        outs.append(np.asarray(i)[:n])
    return np.concatenate(ds), np.concatenate(outs)
