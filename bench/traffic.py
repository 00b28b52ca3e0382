"""The one traffic generator: a traffic file's parameters and a seed in, the
window's whole schedule out.

Arrivals are open-loop Poisson: each lane's count is fixed at rate times
seconds and its due times are uniform order statistics over the window,
which is a Poisson process conditioned on its count.  Every seed therefore
sends the same amount of work, in another order.  Mutation kinds are drawn
for each request independently in distribution, but their counts are fixed
from the shares, then shuffled.

Every query and inserted or updated row is a fresh draw from the
configuration's mixture: a corpus row plus the generator's within-mode
noise (``draw`` in the configuration file), never a copy of a corpus row.
Delete and update targets are distinct corpus ids, so every id is touched
at most once and its final state is known whatever the serving order.
"""

from __future__ import annotations

import dataclasses

import numpy as np

KINDS = ("insert", "delete", "update")


def draw_rows(rng, corpus: np.ndarray, n: int, draw: dict) -> np.ndarray:
    """``n`` fresh rows: random corpus rows plus within-mode noise."""
    base = corpus[rng.integers(0, len(corpus), n)]
    x = base + rng.normal(0.0, draw["noise"], base.shape).astype(np.float32)
    if "clip_min" in draw:
        x = np.maximum(x, draw["clip_min"])
    if draw.get("normalize"):
        x /= np.linalg.norm(x, axis=1, keepdims=True)
    return x.astype(np.float32)


@dataclasses.dataclass
class Schedule:
    seconds: float
    s_due: np.ndarray  # [S] seconds after the window opens
    queries: np.ndarray  # [S, D]
    m_due: np.ndarray  # [M]
    m_kind: np.ndarray  # [M] index into KINDS
    m_vecs: np.ndarray  # [M, D] insert / update rows (zeros for deletes)
    m_ids: np.ndarray  # [M] delete / update target (-1 for inserts)


def _due(rng, rate: float, seconds: float) -> np.ndarray:
    return np.sort(rng.uniform(0.0, seconds, int(round(rate * seconds))))


def kind_counts(mix: dict, n: int) -> np.ndarray:
    """Counts per kind (``KINDS`` order) from shares, summing to ``n``."""
    share = np.array([float(mix.get(k, 0.0)) for k in KINDS])
    share /= share.sum()
    counts = np.floor(share * n).astype(int)
    counts[np.argmax(share)] += n - counts.sum()
    return counts


def make_schedule(traffic: dict, draw: dict, corpus: np.ndarray,
                  targets: np.ndarray, seconds: float, seed: int) -> Schedule:
    """The window's schedule.  ``targets`` are corpus ids free for delete
    and update, in the order they are to be used."""
    rng = np.random.default_rng([seed, 1])
    s_due = _due(rng, traffic["search_qps"], seconds)
    queries = draw_rows(rng, corpus, len(s_due), draw)
    m_due = _due(rng, traffic.get("mutation_rps", 0.0), seconds)
    counts = kind_counts(traffic.get("mix", {"insert": 1.0}), len(m_due))
    m_kind = rng.permutation(np.repeat(np.arange(len(KINDS)), counts))
    m_vecs = np.zeros((len(m_due), corpus.shape[1]), np.float32)
    writes = m_kind != KINDS.index("delete")
    m_vecs[writes] = draw_rows(rng, corpus, int(writes.sum()), draw)
    m_ids = np.full(len(m_due), -1, np.int64)
    touch = m_kind != KINDS.index("insert")
    if touch.sum() > len(targets):
        raise ValueError(f"{touch.sum()} delete/update targets wanted, "
                         f"{len(targets)} free")
    m_ids[touch] = targets[: touch.sum()]
    return Schedule(seconds, s_due, queries, m_due, m_kind, m_vecs, m_ids)
