"""Plain IVF references shared by every payload: nearest-list assignment,
the coarse probe, and the precision steps the controls use.

Nothing here imports the program.  Distances to centroids are squared L2,
on the host in f64 (the probe of the checked queries, and the check of the
device assignment) or on the device in f32 at ``HIGHEST`` (the assignment
of every row, below).  The program computes them in the expanded form
``|x|^2 + |c|^2 - 2 x.c`` in f32, whose rounding error is at most
``ROUNDING * (|x|^2 + |c|^2)``, so a list or a row whose distances lie
within twice that of a boundary may go either way.

The contract of a payload's reference module (``ivf_flat``, ``ivf_pq``),
which ``bench/check.py`` calls and nothing else:

* ``PAYLOAD``: the program's payload name the module judges.
* ``Scorer(quantizer, precision)``: ``quantizer`` is a dict of host arrays
  read from the index after the window, ``centroids`` always and
  ``codebooks`` where the payload has them; ``precision`` is ``highest``
  (the reference) or a step below it (a control).
* ``Scorer.encode(vecs, lists) -> rows``: the stored form of the rows
  ``vecs`` [N, D] held in ``lists`` [N], as the index should store them.
* ``Scorer.scores(q, rows, lists) -> [C]``: the distance of the query
  ``q`` [D] to rows stored as ``rows`` (``encode``'s form) in ``lists``.
* ``Scorer.encode_numbers(vecs, lists, rows, rng) -> dict``: the
  reference checking its own encoding: of a sample drawn by ``rng`` of
  the rows ``rows`` it encoded on the device, those an f64 encoding on
  the host says are wrong, ties within rounding excepted (none for a
  payload stored as the vector itself).
* ``stored_numbers(want, got) -> dict``: the numbers that compare stored
  payload rows ``got`` with the reference's encoding ``want`` of the same
  rows: ``stored_mismatch`` always, and whatever else the payload needs.
* ``quantizer_numbers(quantizer, rows, lists, config, rng) -> dict``:
  the numbers that judge what the payload adds to the trained quantizer,
  from the corpus ``rows`` held in ``lists`` (their nearest under the
  program's centroids), against the reference's own training of it as
  the configuration states it (none for a flat payload).

A module ignores the arguments it does not use (the flat scan has no use
for lists or codebooks).  The configuration's ``limits`` choose which of
the numbers are judged.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ROUNDING = 8 * float(np.finfo(np.float32).eps)
#: bytes of one chunk's [rows, lists] f32 distances in the device assignment
CHUNK_BYTES = 1 << 30
#: rows of the seeded sample whose device assignment is checked in f64
ASSIGN_SAMPLE = 2048
_NONE = np.int32(np.iinfo(np.int32).max)  # the index of no list


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


# ------------------------------------------------- device assignment --
def _before(v1, i1, v2, i2):
    return (v1 < v2) | ((v1 == v2) & (i1 < i2))


def _first_of_two(a, b):
    """The better of two (value, index) pairs, ordered by (value, index)."""
    first = _before(a[0], a[1], b[0], b[1])
    return jnp.where(first, a[0], b[0]), jnp.where(first, a[1], b[1])


def nearest_one(d):
    """Traceable: (the least value of ``d`` along its last axis, its
    index), lower index first on a tie, as one reduction whose two
    outputs the caller uses: where the value of an argmin-like reduction
    goes unused, the TPU compiler gives it the type bf16 and compares the
    partial minima at that precision (``nearest_two``)."""
    idx = jax.lax.broadcasted_iota(jnp.int32, d.shape, d.ndim - 1)
    return jax.lax.reduce((d, idx), (np.float32(np.inf), _NONE),
                          _first_of_two, (d.ndim - 1,))


def _best_two_of_four(a, b):
    """The two best of two (best, second) pairs, ordered by (value, index)."""
    a1, ai1, a2, ai2 = a
    b1, bi1, b2, bi2 = b
    first_a = _before(a1, ai1, b1, bi1)
    v1 = jnp.where(first_a, a1, b1)
    i1 = jnp.where(first_a, ai1, bi1)
    cv, ci = jnp.where(first_a, a2, a1), jnp.where(first_a, ai2, ai1)
    dv, di = jnp.where(first_a, b1, b2), jnp.where(first_a, bi1, bi2)
    first_c = _before(cv, ci, dv, di)
    return (v1, i1, jnp.where(first_c, cv, dv), jnp.where(first_c, ci, di))


def nearest_two(rows, centroids, chunk: int):
    """Traceable: for each row, its nearest and second-nearest list,
    whether the two lie within the program's rounding of each other, its
    squared distance to the nearest, and the two distances as the
    reduction compared them.

    Per chunk of rows, one contraction at ``HIGHEST`` gives
    ``|c|^2 - 2 x.c`` and one reduction over the lists keeps the best two
    (no sort over the lists).  The two are then re-measured as
    ``|x - c|^2`` from the f32 residual, which does not cancel, and put in
    that order.

    The reduction's distances (the last two outputs) are returned so that
    a caller uses them: where the value of an argmin-like reduction goes
    unused, the TPU compiler gives it the type bf16 and the reduction
    compares its partial minima at that precision (the program's own
    ``argmin`` over its lists compiles so)."""
    cn = jnp.sum(centroids * centroids, -1)

    def one(x):
        s = cn[None] - 2.0 * jnp.matmul(x, centroids.T, precision=HIGHEST)
        idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        v1, i1, v2, i2 = jax.lax.reduce(
            (s, idx, jnp.full_like(s, jnp.inf), jnp.full_like(idx, _NONE)),
            (np.float32(np.inf), _NONE, np.float32(np.inf), _NONE),
            _best_two_of_four, (1,))
        i2 = jnp.where(i2 == _NONE, i1, i2)  # one list only
        d1 = jnp.sum((x - centroids[i1]) ** 2, -1)
        d2 = jnp.sum((x - centroids[i2]) ** 2, -1)
        swap = _before(d2, i2, d1, i1)
        best, second = jnp.where(swap, i2, i1), jnp.where(swap, i1, i2)
        xn = jnp.sum(x * x, -1)
        tie = jnp.abs(d2 - d1) <= 2 * ROUNDING * (xn + cn[best])
        return (best, second, tie, jnp.minimum(d1, d2), xn + v1, xn + v2)

    out = jax.lax.map(one, rows.reshape(-1, chunk, rows.shape[-1]))
    return tuple(o.reshape(-1) for o in out)


_nearest_two = jax.jit(nearest_two, static_argnames=("chunk",))


def chunk_rows(n_rows: int, n_lists: int, chunk: int | None = None) -> int:
    """Rows per chunk: a power of two whose [chunk, lists] f32 scores fit
    ``CHUNK_BYTES``, at most the rows there are (at least 8)."""
    if chunk is None:
        chunk = 1 << max(3, int(np.log2(max(1, CHUNK_BYTES // (4 * n_lists)))))
        chunk = min(chunk, 16384)
    return int(min(chunk, max(8, n_rows)))


def pad_rows(rows, chunk: int, quantum: int = 1 << 16):
    """Rows padded with zeros to a multiple of ``chunk`` (and of
    ``quantum`` past one quantum), so runs of a cell share a shape."""
    n = len(rows)
    pad = -n % max(chunk, quantum if n > quantum else chunk)
    return jnp.asarray(np.pad(np.asarray(rows, np.float32),
                              ((0, pad), (0, 0))))


def assign(rows: np.ndarray, centroids: np.ndarray, chunk: int | None = None):
    """Nearest list of every row, on the device (``nearest_two``):
    ([N] best, [N] second, [N] bool: the two within the program's
    rounding, [N] f32 squared distance to the best)."""
    n = len(rows)
    chunk = chunk_rows(n, len(centroids), chunk)
    best, second, tie, dist, _, _ = jax.device_get(_nearest_two(
        pad_rows(rows, chunk), jnp.asarray(centroids, jnp.float32), chunk))
    return (best[:n].astype(np.int64), second[:n].astype(np.int64), tie[:n],
            dist[:n])


def assign_mismatch(rows: np.ndarray, centroids: np.ndarray,
                    best: np.ndarray, rng,
                    sample: int = ASSIGN_SAMPLE) -> int:
    """Of a sample of ``rows`` drawn by ``rng``, how many the device put in
    a list farther than their f64 nearest by more than the program's
    rounding."""
    pick = rng.choice(len(rows), min(sample, len(rows)), replace=False)
    bad = 0
    for s in range(0, len(pick), 256):
        j = pick[s : s + 256]
        d, tol = sq_dists(rows[j], centroids)
        near = d.argmin(1)
        at = np.arange(len(j))
        bad += int((d[at, best[j]] - d[at, near] > 2 * tol[at, near]).sum())
    return bad


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
