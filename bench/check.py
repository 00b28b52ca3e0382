"""The comparison that decides ``correct``: what the timed path served and
stored, against the benchmark's own plain reference.

The benchmark knows every row it sent and when each mutation was sent and
acknowledged, so it knows for each search which rows were surely live
(acked before the search was sent, and not deleted before it resolved),
surely absent, or in flight.  A row whose mutation overlapped a search may
be served or not.  Each number is compared with a limit from the
configuration file; exact ones have the limit 0.

* ``foreign_ids``: served ids that no live-or-in-flight row of a probed
  list carries (a deleted id among them).
* ``dist_gap``: widest gap between a served distance and the reference's
  distance of that row.
* ``missed_gap``: widest margin by which a surely live row of a surely
  probed list lies closer than the farthest row served, unserved.
* ``resident_mismatch``: live ids missing, duplicated, or never acked.
* ``list_mismatch``: live rows stored in a list that is not their nearest.
* ``stored_mismatch``: stored rows that differ from the sent vector.
* ``invariant_faults``: breaches of the pool's own bookkeeping.
* ``kmeans_excess``: by how much the k-means objective of the program's
  centroids over the corpus (the rows the build trained on) exceeds that
  of the benchmark's own k-means, run as the configuration states it, as
  a share.  Every other number takes the program's centroids as given;
  this one judges the build that made them.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from bench.reference import kmeans
from bench.reference.common import assign, probe, sq_dists

INF = np.inf
#: ``missed_gap`` when fewer than k rows were served and a live one was not
UNBOUNDED = 1e30


@dataclasses.dataclass
class Versions:
    """Every row the index may hold, with when it became and stopped being
    live as (sent, acked) bounds: -inf before the first request, inf for
    never (or for an ack that never came)."""

    ids: np.ndarray  # [V] i64
    vecs: np.ndarray  # [V, D] f32
    start_lo: np.ndarray
    start_hi: np.ndarray
    end_lo: np.ndarray
    end_hi: np.ndarray

    def live_at_end(self, surely: bool) -> np.ndarray:
        if surely:
            return (self.start_hi < INF) & (self.end_lo == INF)
        return (self.start_lo < INF) & (self.end_hi == INF)


class History:
    """The corpus, then each admitted mutation as sent, in order."""

    def __init__(self, corpus: np.ndarray):
        self.corpus = corpus
        self.muts = []

    def add(self, kind: str, ids, vecs, sent: float, done: float, ok: bool):
        """``ids``: the acked ids of an insert (none if it failed), or the
        targets of a delete or update."""
        self.muts.append((kind, np.asarray(ids, np.int64).reshape(-1), vecs,
                          sent, done if ok else INF))

    def versions(self) -> Versions:
        n, dim = self.corpus.shape
        current, ends = {}, {}
        new_ids, new_vecs, new_lo, new_hi = [], [], [], []
        for kind, ids, vecs, sent, done in self.muts:
            if kind != "insert":
                for i in ids.tolist():
                    ends[current.get(i, i)] = (sent, done)
            if kind != "delete":
                for i, x in zip(ids.tolist(), vecs):
                    current[i] = n + len(new_ids)
                    new_ids.append(i)
                    new_vecs.append(x)
                    new_lo.append(sent)
                    new_hi.append(done)
        total = n + len(new_ids)
        end_lo, end_hi = np.full(total, INF), np.full(total, INF)
        for j, (lo, hi) in ends.items():
            end_lo[j], end_hi[j] = lo, hi
        return Versions(
            ids=np.concatenate([np.arange(n), np.asarray(new_ids, np.int64)]),
            vecs=np.concatenate(
                [self.corpus, np.asarray(new_vecs, np.float32).reshape(-1, dim)]),
            start_lo=np.concatenate([np.full(n, -INF), new_lo]),
            start_hi=np.concatenate([np.full(n, -INF), new_hi]),
            end_lo=end_lo, end_hi=end_hi)


@dataclasses.dataclass
class Readback:
    """The index state after the window, as plain arrays."""

    live_ids: np.ndarray  # [R] id of every live slot
    live_lists: np.ndarray  # [R] list owning that slot's block
    stored_rows: dict  # id -> stored vector, for a sample of rows
    invariant_faults: int


@dataclasses.dataclass
class Served:
    queries: np.ndarray  # [S, D]
    sent: np.ndarray  # [S]
    done: np.ndarray  # [S]
    ids: np.ndarray  # [S, k]
    dists: np.ndarray  # [S, k]


def pool_invariant_faults(st: dict, block_size: int) -> int:
    """Breaches of the pool's bookkeeping, counted: each list's block table
    names blocks it owns and nothing past its length, no block serves two
    lists, each list's row count fits its blocks, every live slot lies
    inside its list's rows, the live count matches, and nothing dropped."""
    owner, table = st["block_owner"], st["cluster_blocks"]
    nblocks, length = st["cluster_nblocks"], st["cluster_len"]
    live = st["pool_live"] != 0
    slot = np.broadcast_to(np.arange(table.shape[1])[None], table.shape)
    lists = np.broadcast_to(np.arange(len(table))[:, None], table.shape)
    used = slot < nblocks[:, None]
    faults = int((table[~used] != -1).sum()) + int((table[used] < 0).sum())
    ok = used & (table >= 0)
    blocks = table[ok]
    faults += int((owner[blocks] != lists[ok]).sum())
    faults += len(blocks) - len(np.unique(blocks))
    faults += int(((length > nblocks * block_size)
                   | (length <= (nblocks - 1) * block_size)).sum())
    pos = np.full(len(owner), -1)
    pos[blocks] = slot[ok]
    b, off = np.nonzero(live)
    inside = (owner[b] >= 0) & (pos[b] >= 0)
    faults += int((~inside).sum())
    at = pos[b[inside]] * block_size + off[inside]
    faults += int((at >= length[owner[b[inside]]]).sum())
    faults += int(live.sum() != int(st["num_vectors"]))
    faults += int(st["num_dropped"])
    faults += int((st["pool_ids"][live] < 0).sum())
    return faults


class Checker:
    """Holds the reference's view of the index after the window."""

    def __init__(self, ref_module, centroids, nprobe: int, k: int,
                 kmeans_iters: int, versions: Versions, readback: Readback):
        self.ref = ref_module
        self.centroids = np.asarray(centroids, np.float32)
        self.nprobe, self.k = nprobe, k
        self.v, self.rb = versions, readback
        self.best, self.second, self.tie = assign(versions.vecs, centroids)
        v = versions
        corpus = v.start_lo == -INF  # the rows the build trained on
        rows = v.vecs[corpus]
        own = kmeans.lloyd(rows, len(self.centroids), kmeans_iters)
        self.kmeans_excess = kmeans.objective(
            rows, self.centroids, self.best[corpus]) / kmeans.objective(
            rows, own, assign(rows, own)[0]) - 1.0
        # one version per id that may be live at the end, surely live first
        must, may = v.live_at_end(True), v.live_at_end(False)
        order = np.lexsort((~must, v.ids))
        order = order[may[order]]
        first = np.r_[True, v.ids[order][1:] != v.ids[order][:-1]]
        self._ids, self._ver = v.ids[order][first], order[first]
        self.slot_version = self.version_of(readback.live_ids)
        ids, counts = np.unique(readback.live_ids, return_counts=True)
        self.resident_mismatch = (
            int((self.slot_version < 0).sum()) + int((counts - 1).sum())
            + len(np.setdiff1d(v.ids[must], ids)))
        # the list a version is scanned in: where it is stored, or for a
        # row gone by the end its nearest (either of two on a tie)
        held = self.slot_version >= 0
        self.primary = self.best.copy()
        self.primary[self.slot_version[held]] = readback.live_lists[held]
        self.known = np.zeros(len(v.ids), bool)
        self.known[self.slot_version[held]] = True
        self.order = np.argsort(self.primary, kind="stable")
        self.starts = np.searchsorted(self.primary[self.order],
                                      np.arange(len(self.centroids) + 1))
        self.loose = np.flatnonzero(self.tie & ~self.known)

    def version_of(self, ids: np.ndarray) -> np.ndarray:
        pos = np.minimum(np.searchsorted(self._ids, ids), len(self._ids) - 1)
        return np.where(self._ids[pos] == ids, self._ver[pos], -1)

    def numbers(self, served: Served, control: Optional[str] = None) -> dict:
        """Every compared number.  ``control`` puts the reference at that
        precision in the program's place: its answers and its stored rows."""
        if control is not None:
            served = self.control_answers(served, control)
        out = self.served_numbers(served)
        out.update(self.state_numbers(control))
        return out

    # ------------------------------------------------------------ state --
    def state_numbers(self, control: Optional[str]) -> dict:
        rb, sv = self.rb, self.slot_version
        held = sv >= 0
        vers, lists = sv[held], rb.live_lists[held]
        wrong = (lists != self.best[vers]) & ~(
            self.tie[vers] & (lists == self.second[vers]))
        out = {"resident_mismatch": self.resident_mismatch,
               "list_mismatch": int(wrong.sum()),
               "invariant_faults": rb.invariant_faults}
        ref = self.ref.Scorer(self.centroids, "highest")
        stored = self.ref.Scorer(self.centroids, control) if control else None
        ids = np.fromiter(rb.stored_rows, np.int64, len(rb.stored_rows))
        vj = self.version_of(ids)
        bad = int((vj < 0).sum())
        for i, j in zip(ids[vj >= 0].tolist(), vj[vj >= 0].tolist()):
            want = ref.encode(self.v.vecs[j : j + 1])[0]
            got = (stored.encode(self.v.vecs[j : j + 1])[0] if stored
                   else rb.stored_rows[i])
            bad += int(not np.array_equal(got, want))
        out["stored_mismatch"] = bad
        out["kmeans_excess"] = self.kmeans_excess
        return out

    # ---------------------------------------------------------- searches --
    def candidates(self, lists: np.ndarray) -> np.ndarray:
        """Versions scanned in any of ``lists``, or that may be."""
        parts = [self.order[self.starts[l] : self.starts[l + 1]]
                 for l in lists.tolist()]
        parts.append(self.loose[np.isin(self.second[self.loose], lists)])
        return np.unique(np.concatenate(parts))

    def score(self, scorer, q: np.ndarray, cand: np.ndarray) -> np.ndarray:
        return scorer.scores(q, self.v.vecs[cand])

    def served_numbers(self, served: Served) -> dict:
        v = self.v
        scorer = self.ref.Scorer(self.centroids, "highest")
        sure_p, may_p = probe(served.queries, self.centroids, self.nprobe)
        foreign, dist_gap, missed = 0, 0.0, 0.0
        for j in range(len(served.queries)):
            sent, done = served.sent[j], served.done[j]
            cand = self.candidates(np.flatnonzero(may_p[j]))
            cand = cand[(v.start_lo[cand] < done) & (v.end_hi[cand] > sent)]
            d = self.score(scorer, served.queries[j], cand)
            keep = served.ids[j] >= 0
            got = served.ids[j][keep]
            far = []
            for i, dj in zip(got.tolist(), served.dists[j][keep].tolist()):
                hit = np.flatnonzero(v.ids[cand] == i)
                if not len(hit):
                    foreign += 1
                    continue
                err = np.abs(d[hit] - dj)
                dist_gap = max(dist_gap, float(err.min()))
                far.append(float(d[hit[err.argmin()]]))
            kth = max(far, default=INF) if len(got) == self.k else INF
            must = ((v.start_hi[cand] <= sent) & (v.end_lo[cand] >= done)
                    & sure_p[j][self.primary[cand]]
                    & (self.known[cand] | ~self.tie[cand])
                    & ~np.isin(v.ids[cand], got))
            if must.any():
                gap = kth - float(d[must].min())
                missed = max(missed, UNBOUNDED if gap == INF else gap)
        return {"foreign_ids": foreign, "dist_gap": dist_gap,
                "missed_gap": missed}

    def control_answers(self, served: Served, precision: str) -> Served:
        """The reference's own probe and scan of the same queries over the
        rows surely live at each search, at ``precision``."""
        v = self.v
        scorer = self.ref.Scorer(self.centroids, precision)
        d_c, _ = sq_dists(served.queries, self.centroids, precision)
        top = np.argsort(d_c, axis=1, kind="stable")[:, : self.nprobe]
        ids = np.full((len(top), self.k), -1, np.int64)
        dists = np.full((len(top), self.k), np.inf, np.float32)
        for j in range(len(top)):
            cand = self.candidates(top[j])
            cand = cand[np.isin(self.primary[cand], top[j])
                        & (v.start_hi[cand] <= served.sent[j])
                        & (v.end_lo[cand] >= served.done[j])]
            d = self.score(scorer, served.queries[j], cand).astype(np.float32)
            sel = np.argsort(d, kind="stable")[: self.k]
            ids[j, : len(sel)] = v.ids[cand[sel]]
            dists[j, : len(sel)] = d[sel]
        return Served(served.queries, served.sent, served.done, ids, dists)
