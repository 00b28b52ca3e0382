"""The benchmark's own k-means of the corpus: the plain reference of the
build's coarse quantizer.

``lloyd`` runs Lloyd's algorithm as the configuration states it
(``kmeans_iters`` steps from a random sample of the rows), from the
benchmark's own sample: each row's nearest centroid as the check's
assignment finds it (``common.nearest_two``) and each list's mean in f32,
on the device.  The k-means cost of any centroids is the sum of the
distances ``common.assign`` returns, taken in f64.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import chunk_rows, nearest_two, pad_rows

SEED = 20240802  # the reference's own sample of starting rows


@partial(jax.jit, static_argnames=("chunk",))
def _lloyd_step(x, n, centroids, chunk: int):
    """One step over the first ``n`` rows of ``x``: the new centroids, and
    the cost of the assignment it made, summed from the reduction's own
    distances (which keeps them in use: ``nearest_two``)."""
    near, _, _, _, dist, _ = nearest_two(x, centroids, chunk)
    lists = centroids.shape[0]
    real = jnp.arange(x.shape[0]) < n
    near = jnp.where(real, near, lists)  # padding rows fall outside
    sums = jax.ops.segment_sum(x, near, num_segments=lists)
    counts = jax.ops.segment_sum(jnp.ones_like(dist), near,
                                 num_segments=lists)
    return jnp.where(counts[:, None] > 0,
                     sums / jnp.maximum(counts, 1.0)[:, None],
                     centroids), jnp.sum(jnp.where(real, dist, 0.0))


def lloyd(rows: np.ndarray, n_lists: int, iters: int,
          chunk: int | None = None) -> np.ndarray:
    """[n_lists, D] f32 centroids after ``iters`` Lloyd steps; a list that
    empties keeps its centroid."""
    n = len(rows)
    chunk = chunk_rows(n, n_lists, chunk)
    start = np.random.default_rng(SEED).choice(n, n_lists, replace=False)
    c = jnp.asarray(rows[np.sort(start)], jnp.float32)
    x = pad_rows(rows, chunk)
    for _ in range(iters):
        c, _ = _lloyd_step(x, n, c, chunk)
    return np.asarray(c)
