"""Plain reference of the IVF-PQ scan (Jegou et al., TPAMI 2011, residual
IVFADC): a row of list ``l`` is stored as the codes of its residual
``x - c_l``, one codeword of 256 per subspace; the distance of a query to
it is the sum over subspaces of ``|(q - c_l)_m - codebook[m, code_m]|^2``
(asymmetric distance computation, ADC).

``highest`` takes every distance exactly (f64).  ``fp8`` forms the tables
and the encoding from float8 e4m3 operands: the control, one step below
the bf16 pass the program's tables and encoder take at the TPU's default
precision.  The codebooks and centroids are the index's trained quantizer.
"""

from __future__ import annotations

import numpy as np

from bench.reference.common import dot

PAYLOAD = "pq"


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


class Scorer:
    def __init__(self, centroids: np.ndarray, codebooks: np.ndarray,
                 precision: str):
        self.centroids = np.asarray(centroids, np.float64)
        self.books = np.asarray(codebooks, np.float64)  # [M, K, dsub]
        self.precision = precision

    def scores(self, q: np.ndarray, vecs, lists: np.ndarray,
               codes: np.ndarray) -> np.ndarray:
        """[C] ADC distance of ``q`` [D] to rows stored in ``lists`` [C]
        as ``codes`` [C, M]."""
        ul, inv = np.unique(lists, return_inverse=True)
        lut = _sub_dists(q[None] - self.centroids[ul], self.books,
                         self.precision)  # [U, M, K]
        m = self.books.shape[0]
        return lut[inv[:, None], np.arange(m)[None], codes.astype(np.int64)
                   ].sum(-1)

    def encode(self, vecs: np.ndarray, lists: np.ndarray) -> np.ndarray:
        """Codes of ``vecs`` [N, D] stored in ``lists`` [N]."""
        out = []
        for s in range(0, len(vecs), 4096):
            r = (np.asarray(vecs[s : s + 4096], np.float64)
                 - self.centroids[lists[s : s + 4096]])
            out.append(_sub_dists(r, self.books, self.precision).argmin(-1))
        if not out:
            return np.zeros((0, self.books.shape[0]), np.uint8)
        return np.concatenate(out).astype(np.uint8)
