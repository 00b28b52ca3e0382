"""Plain reference of the IVF-PQ scan (Jegou et al., TPAMI 2011, residual
IVFADC): a row of list ``l`` is stored as the codes of its residual
``x - c_l``, one codeword of 256 per subspace; the distance of a query to
it is the sum over subspaces of ``|(q - c_l)_m - codebook[m, code_m]|^2``
(asymmetric distance computation, ADC).  It follows the contract in
``common``; the quantizer carries the index's trained ``codebooks``
[M, 256, dsub] beside its centroids.

``highest`` is the reference: each code is the codeword nearest to the
residual, measured as ``|r_m - b|^2`` from the f32 residual on the device
(every row of the index at once), which does not cancel; the tables are
exact (f64); ``Scorer.encode_numbers`` checks a sample of those codes
against an f64 encoding on the host.  ``fp8`` forms the tables and the
encoding from float8 e4m3 operands: the control, one step below the bf16
pass the program's tables and encoder take at the TPU's default precision.

The codebooks are the program's, trained by its build; every number but
one takes them as given.  ``quantizer_numbers`` judges them: their
quantization error against that of the reference's own codebook k-means
(``train_codebooks``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import (ASSIGN_SAMPLE, ROUNDING, dot,
                                    nearest_one, pad_rows)

PAYLOAD = "pq"
KSUB = 256  # codewords per subspace
ENCODE_CHUNK = 8192  # rows per step of the device encoding
SEED = 20240803  # the reference's own sample of training rows and codewords
ERROR_ROWS = 1 << 18  # residuals two codebooks' quantization error is read on


def _sub_dists(r: np.ndarray, books: np.ndarray, precision: str):
    """residuals [N, D] -> [N, M, K] squared distances to every codeword."""
    m, k, dsub = books.shape
    r = np.asarray(r, np.float64).reshape(len(r), m, dsub)
    if precision == "highest":
        return ((r[:, :, None, :] - books[None]) ** 2).sum(-1)
    out = np.empty((len(r), m, k))
    for j in range(m):
        rn = (r[:, j] ** 2).sum(-1)[:, None]
        bn = (books[j] ** 2).sum(-1)[None]
        out[:, j] = rn + bn - 2.0 * dot(r[:, j], books[j], precision)
    return out


def _nearest_codes(r, books, precision: str):
    """Traceable: residuals [C, M, dsub] -> (codes [C, M] i32, each
    subspace's squared distance to its code [C, M] f32).  At ``highest``
    the distance is ``|r_m - b|^2`` from the f32 residual, which does not
    cancel; at ``fp8`` it is ``|b|^2 - 2 r.b`` with both operands rounded
    to e4m3.  Both outputs of the reduction are returned, so that the
    minimum keeps its type (``common.nearest_one``)."""
    if precision == "highest":
        d = jnp.sum((r[:, :, None, :] - books[None]) ** 2, -1)
    else:
        r8 = r.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        books8 = books.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        d = jnp.sum(books * books, -1)[None] - 2.0 * jnp.einsum(
            "cmd,mkd->cmk", r8, books8, precision=jax.lax.Precision.HIGHEST)
    error, codes = nearest_one(d)
    return codes, error


@partial(jax.jit, static_argnames=("precision", "chunk"))
def _encode(x, lists, centroids, books, precision: str, chunk: int):
    """Codes [N, M] u8 of rows ``x`` [N, D] held in ``lists``, and each
    row's squared quantization error [N] (at ``highest``)."""
    m, _, dsub = books.shape

    def one(args):
        xs, ls = args
        codes, error = _nearest_codes(
            (xs - centroids[ls]).reshape(chunk, m, dsub), books, precision)
        return codes.astype(jnp.uint8), jnp.sum(error, -1)

    codes, error = jax.lax.map(one, (x.reshape(-1, chunk, x.shape[-1]),
                                     lists.reshape(-1, chunk)))
    return codes.reshape(-1, m), error.reshape(-1)


def _encode_rows(vecs, lists, centroids, books, precision: str):
    """``_encode`` of ``vecs`` [N, D] in ``lists`` [N], padded to a shared
    shape and cut back: (codes [N, M] u8, errors [N] f32) on the host."""
    n = len(vecs)
    chunk = min(ENCODE_CHUNK, n)
    x = pad_rows(vecs, chunk)
    ls = np.zeros(len(x), np.int32)
    ls[:n] = lists
    codes, error = jax.device_get(_encode(
        x, jnp.asarray(ls), jnp.asarray(centroids, jnp.float32),
        jnp.asarray(books, jnp.float32), precision, chunk))
    return codes[:n], error[:n]


@partial(jax.jit, static_argnames=("chunk",))
def _codebook_step(r, n, books, chunk: int):
    """One Lloyd step of every subspace's codebook over the first ``n``
    residuals of ``r`` [N, D]: each codeword the mean in f32 of the
    residuals nearest to it (one that none is nearest to is kept), and
    the quantization error of the assignment, summed."""
    m, k, dsub = books.shape
    codes, error = jax.lax.map(
        lambda rs: _nearest_codes(rs.reshape(chunk, m, dsub), books,
                                  "highest"),
        r.reshape(-1, chunk, r.shape[-1]))
    codes, error = codes.reshape(-1, m), error.reshape(-1, m)
    real = (jnp.arange(codes.shape[0]) < n)[:, None]
    seg = jnp.where(real, jnp.arange(m)[None] * k + codes, m * k).reshape(-1)
    sums = jax.ops.segment_sum(r.reshape(-1, dsub), seg, num_segments=m * k)
    counts = jax.ops.segment_sum(jnp.ones(seg.shape, jnp.float32), seg,
                                 num_segments=m * k)
    flat = books.reshape(m * k, dsub)
    new = jnp.where(counts[:, None] > 0,
                    sums / jnp.maximum(counts, 1.0)[:, None], flat)
    return new.reshape(m, k, dsub), jnp.sum(jnp.where(real, error, 0.0))


def train_codebooks(residuals: np.ndarray, m: int, iters: int) -> np.ndarray:
    """The reference's own codebooks [M, 256, dsub] f32: per subspace,
    ``iters`` Lloyd steps over ``residuals`` [N, D] from 256 of them drawn
    by its own seed."""
    n, dim = residuals.shape
    dsub = dim // m
    rng = np.random.default_rng(SEED)
    books = np.stack([residuals[rng.choice(n, KSUB, replace=False),
                                j * dsub : (j + 1) * dsub] for j in range(m)])
    chunk = min(ENCODE_CHUNK, n)
    r = pad_rows(residuals, chunk)
    books = jnp.asarray(books, jnp.float32)
    for _ in range(iters):
        books, _ = _codebook_step(r, n, books, chunk)
    return np.asarray(books)


def quantizer_numbers(quantizer: dict, rows: np.ndarray, lists: np.ndarray,
                      config: dict, rng) -> dict:
    """``codebook_excess``: by how much the quantization error of a sample
    of the corpus' residuals (``ERROR_ROWS`` rows drawn by ``rng``) under
    the program's codebooks exceeds that under the reference's own, as a
    share.  The reference trains its own as the configuration states the
    build does: ``pq_iters`` Lloyd steps per subspace over the residuals
    of ``pq_train_rows`` corpus rows, here its own sample of them."""
    c = np.asarray(quantizer["centroids"], np.float32)
    books = np.asarray(quantizer["codebooks"], np.float32)
    n = len(rows)
    train = np.sort(np.random.default_rng(SEED).choice(
        n, min(n, config["pq_train_rows"]), replace=False))
    own = train_codebooks(rows[train] - c[lists[train]], books.shape[0],
                          config["pq_iters"])
    pick = np.sort(rng.choice(n, min(n, ERROR_ROWS), replace=False))

    def error(b):
        return np.sum(_encode_rows(rows[pick], lists[pick], c, b,
                                   "highest")[1], dtype=np.float64)

    return {"codebook_excess": float(error(books) / error(own) - 1.0)}


class Scorer:
    def __init__(self, quantizer: dict, precision: str):
        if precision not in ("highest", "fp8"):
            raise ValueError(f"unknown precision {precision!r}")
        self.centroids = np.asarray(quantizer["centroids"], np.float64)
        self.books = np.asarray(quantizer["codebooks"], np.float64)
        self.precision = precision

    def scores(self, q: np.ndarray, codes: np.ndarray,
               lists: np.ndarray) -> np.ndarray:
        """[C] ADC distance of ``q`` [D] to rows stored in ``lists`` [C]
        as ``codes`` [C, M]."""
        ul, inv = np.unique(lists, return_inverse=True)
        lut = _sub_dists(q[None] - self.centroids[ul], self.books,
                         self.precision)  # [U, M, K]
        m = self.books.shape[0]
        return lut[inv[:, None], np.arange(m)[None], codes.astype(np.int64)
                   ].sum(-1)

    def encode(self, vecs: np.ndarray, lists: np.ndarray) -> np.ndarray:
        """Codes [N, M] of ``vecs`` [N, D] stored in ``lists`` [N]."""
        if not len(vecs):
            return np.zeros((0, self.books.shape[0]), np.uint8)
        return _encode_rows(vecs, lists, self.centroids, self.books,
                            self.precision)[0]

    def encode_numbers(self, vecs: np.ndarray, lists: np.ndarray,
                       codes: np.ndarray, rng,
                       sample: int = ASSIGN_SAMPLE) -> dict:
        """``ref_code_mismatch``: of ``sample`` rows drawn by ``rng``, those
        with a code [M] in ``codes`` farther from the f64 residual than
        its f64 nearest codeword by more than the rounding of the f32
        residual and distance."""
        pick = rng.choice(len(vecs), min(sample, len(vecs)), replace=False)
        m = self.books.shape[0]
        bn = (self.books ** 2).sum(-1)  # [M, K]
        at = np.arange(m)[None]
        bad = 0
        for s in range(0, len(pick), 256):
            j = pick[s : s + 256]
            r = np.asarray(vecs[j], np.float64) - self.centroids[lists[j]]
            d = _sub_dists(r, self.books, "highest")  # [S, M, K]
            near, got = d.argmin(-1), codes[j].astype(np.int64)
            rn = (r.reshape(len(j), m, -1) ** 2).sum(-1)
            tol = ROUNDING * (rn + bn[at, near] + bn[at, got])
            gap = (np.take_along_axis(d, got[..., None], -1)[..., 0]
                   - d.min(-1))
            bad += int((gap > 2 * tol).any(-1).sum())
        return {"ref_code_mismatch": bad}


def stored_numbers(want: np.ndarray, got: np.ndarray) -> dict:
    """``stored_mismatch``: rows whose codes differ from the reference's in
    more than a quarter of their subspaces (a wrong or garbled row);
    ``code_mismatch_share``: the share of rows that differ in any subspace
    (the encoder's precision)."""
    differ = (want != got).sum(1)
    return {"stored_mismatch": int((differ > want.shape[1] / 4).sum()),
            "code_mismatch_share": float((differ > 0).mean()) if len(differ)
            else 0.0}
