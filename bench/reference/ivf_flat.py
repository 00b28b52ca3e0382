"""Plain reference of the flat IVF scan: the squared L2 distance of a query
to each candidate row, from the row vectors the benchmark itself sent.
It follows the contract in ``common``; lists play no part.

``highest`` is exact (f64).  ``high`` is the same distance as an f32
program at three bf16 passes would leave it: the control.
"""

from __future__ import annotations

import numpy as np

from bench.reference.common import sq_dists

PAYLOAD = "flat"


class Scorer:
    def __init__(self, quantizer: dict, precision: str):
        self.centroids = quantizer["centroids"]
        self.precision = precision

    def scores(self, q: np.ndarray, rows: np.ndarray, lists=None) -> np.ndarray:
        """[C] squared L2 of ``q`` [D] to ``rows`` [C, D]."""
        return sq_dists(q[None], rows, self.precision)[0][0]

    def encode(self, vecs: np.ndarray, lists=None) -> np.ndarray:
        """The payload a row should be stored as: the vector itself."""
        return np.asarray(vecs, np.float32)

    def encode_numbers(self, vecs, lists, rows, rng) -> dict:
        """None: the encoding is the vector itself, on the host."""
        return {}


def stored_numbers(want: np.ndarray, got: np.ndarray) -> dict:
    """``stored_mismatch``: stored rows that differ from the vector sent."""
    differ = (want != got).any(1)
    return {"stored_mismatch": int(differ.sum())}


def quantizer_numbers(quantizer, rows, lists, config, rng) -> dict:
    """None: a flat payload adds nothing to the centroids."""
    return {}
