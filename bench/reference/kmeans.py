"""The benchmark's own k-means of the corpus: the plain reference of the
build's coarse quantizer.

``lloyd`` runs Lloyd's algorithm as the configuration states it
(``kmeans_iters`` steps from a random sample of the rows), from the
benchmark's own sample: each row's nearest centroid at ``HIGHEST`` and each
list's mean in f32, on the device.  ``objective`` is the k-means cost of
any centroids, the sum of each row's squared distance to its nearest one,
in f64 on the host.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import HIGHEST

SEED = 20240802  # the reference's own sample of starting rows


@partial(jax.jit, static_argnames=("chunk",))
def _lloyd_step(x, weight, centroids, chunk: int):
    cn = jnp.sum(centroids * centroids, -1)

    def nearest(rows):
        d = cn[None] - 2.0 * jnp.matmul(rows, centroids.T, precision=HIGHEST)
        return jnp.argmin(d, -1)

    near = jax.lax.map(nearest, x.reshape(-1, chunk, x.shape[-1])).reshape(-1)
    lists = centroids.shape[0]
    sums = jax.ops.segment_sum(x * weight[:, None], near, num_segments=lists)
    counts = jax.ops.segment_sum(weight, near, num_segments=lists)
    return jnp.where(counts[:, None] > 0,
                     sums / jnp.maximum(counts, 1.0)[:, None], centroids)


def lloyd(rows: np.ndarray, n_lists: int, iters: int,
          chunk: int = 4096) -> np.ndarray:
    """[n_lists, D] f32 centroids after ``iters`` Lloyd steps; a list that
    empties keeps its centroid."""
    n = len(rows)
    chunk = min(chunk, n)
    pad = -n % chunk
    start = np.random.default_rng(SEED).choice(n, n_lists, replace=False)
    c = jnp.asarray(rows[np.sort(start)], jnp.float32)
    x = jnp.asarray(np.pad(np.asarray(rows, np.float32), ((0, pad), (0, 0))))
    weight = jnp.asarray(np.r_[np.ones(n), np.zeros(pad)], jnp.float32)
    for _ in range(iters):
        c = _lloyd_step(x, weight, c, chunk)
    return np.asarray(c)


def objective(rows: np.ndarray, centroids: np.ndarray, nearest: np.ndarray,
              chunk: int = 1 << 16) -> float:
    """Sum over ``rows`` of the squared distance to ``centroids[nearest]``,
    in f64."""
    c = np.asarray(centroids, np.float64)
    return float(sum(
        ((np.asarray(rows[s : s + chunk], np.float64)
          - c[nearest[s : s + chunk]]) ** 2).sum()
        for s in range(0, len(rows), chunk)))
