"""Plain IVF references shared by both payloads: nearest-list assignment,
the coarse probe, and the precision steps the controls use.

Nothing here imports the program.  Distances to centroids are squared L2
on the device in f32 at ``HIGHEST`` (assignment of every row) or on the
host in f64 (the probe of the checked queries).  Each distance may carry a
rounding error of up to ``8 * eps_f32 * (|x|^2 + |c|^2)``, the bound of the
expanded form ``|x|^2 + |c|^2 - 2 x.c`` in f32, so a list or a row whose
distances lie within twice that of a boundary may go either way.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ROUNDING = 8 * float(np.finfo(np.float32).eps)


def round_to(x: np.ndarray, dtype) -> np.ndarray:
    """``x`` rounded to ``dtype`` and back, as f64."""
    return np.asarray(x, np.float32).astype(dtype).astype(np.float64)


def dot(a: np.ndarray, b: np.ndarray, precision: str) -> np.ndarray:
    """``a @ b.T`` of f32 operands: exact in f64 (``highest``), in three
    bf16 passes ``hi*hi + hi*lo + lo*hi`` (``high``, what the TPU does for
    f32 at that precision), or with float8 e4m3 operands (``fp8``)."""
    if precision == "highest":
        return np.asarray(a, np.float64) @ np.asarray(b, np.float64).T
    if precision == "high":
        ah = round_to(a, ml_dtypes.bfloat16)
        al = round_to(np.asarray(a, np.float64) - ah, ml_dtypes.bfloat16)
        bh = round_to(b, ml_dtypes.bfloat16)
        bl = round_to(np.asarray(b, np.float64) - bh, ml_dtypes.bfloat16)
        return ah @ bh.T + ah @ bl.T + al @ bh.T
    if precision == "fp8":
        return (round_to(a, ml_dtypes.float8_e4m3fn)
                @ round_to(b, ml_dtypes.float8_e4m3fn).T)
    raise ValueError(f"unknown precision {precision!r}")


def sq_dists(x: np.ndarray, c: np.ndarray, precision: str = "highest"):
    """[N, D] x [M, D] -> ([N, M] squared L2, [N, M] its rounding bound),
    both f64.  Below ``highest`` the result is rounded to f32 as the
    device would leave it."""
    xn = (np.asarray(x, np.float64) ** 2).sum(-1)[:, None]
    cn = (np.asarray(c, np.float64) ** 2).sum(-1)[None, :]
    d = xn + cn - 2.0 * dot(x, c, precision)
    if precision != "highest":
        d = d.astype(np.float32).astype(np.float64)
    return d, ROUNDING * (xn + cn)


@partial(jax.jit, static_argnames=("chunk",))
def _assign_device(rows, centroids, chunk: int):
    cn = jnp.sum(centroids * centroids, -1)

    def one(x):
        xn = jnp.sum(x * x, -1, keepdims=True)
        d = xn + cn[None] - 2.0 * jnp.matmul(x, centroids.T,
                                              precision=HIGHEST)
        neg, idx = jax.lax.top_k(-d, 2)
        tol = ROUNDING * (xn[:, 0] + cn[idx[:, 0]])
        return idx[:, 0], idx[:, 1], (neg[:, 0] - neg[:, 1]) <= 2 * tol

    b, s, t = jax.lax.map(one, rows.reshape(-1, chunk, rows.shape[-1]))
    return b.reshape(-1), s.reshape(-1), t.reshape(-1)


def assign(rows: np.ndarray, centroids: np.ndarray, chunk: int = 4096,
           quantum: int = 1 << 16):
    """Nearest list of every row, on the device in f32 at ``HIGHEST``:
    ([N] best, [N] second, [N] bool: the two within rounding).  Rows are
    padded to a multiple of ``quantum``, so runs of a cell share a shape."""
    n = len(rows)
    chunk = min(chunk, max(8, n))
    pad = -n % max(chunk, quantum if n > quantum else chunk)
    x = jnp.asarray(np.pad(np.asarray(rows, np.float32), ((0, pad), (0, 0))))
    best, second, tie = jax.device_get(
        _assign_device(x, jnp.asarray(centroids, jnp.float32), chunk))
    return best[:n].astype(np.int64), second[:n].astype(np.int64), tie[:n]


def probe(queries: np.ndarray, centroids: np.ndarray, nprobe: int,
          precision: str = "highest"):
    """Coarse probe: ([Q, L] bool surely probed, [Q, L] bool maybe probed).

    With every distance within ``tol`` of its true value, a list below the
    first unprobed distance by more than ``2 tol`` beats every unprobed
    list, and one above the last probed distance by more than ``2 tol``
    loses to every probed list; between the two it may go either way."""
    d, tol = sq_dists(queries, centroids, precision)
    t = 2.0 * tol.max(1, keepdims=True)
    srt = np.sort(d, axis=1)
    d_in, d_out = srt[:, nprobe - 1 : nprobe], srt[:, nprobe : nprobe + 1]
    return d < d_out - t, d <= d_in + t
