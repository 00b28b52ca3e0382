"""Plain reference of the flat IVF scan: the squared L2 distance of a query
to each candidate row, from the row vectors the benchmark itself sent.

``highest`` is exact (f64).  ``high`` is the same distance as an f32
program at three bf16 passes would leave it: the control.
"""

from __future__ import annotations

import numpy as np

from bench.reference.common import sq_dists

PAYLOAD = "flat"


class Scorer:
    def __init__(self, centroids: np.ndarray, precision: str):
        self.centroids = centroids
        self.precision = precision

    def scores(self, q: np.ndarray, vecs: np.ndarray) -> np.ndarray:
        """[C] squared L2 of ``q`` [D] to ``vecs`` [C, D]."""
        return sq_dists(q[None], vecs, self.precision)[0][0]

    def encode(self, vecs: np.ndarray):
        """The payload a row should be stored as: the vector itself."""
        return np.asarray(vecs, np.float32)
