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
* ``stored_mismatch``: stored rows that differ from the reference's
  encoding of the sent vector in their list (the reference module's
  ``stored_numbers`` says what differ means for its payload; a PQ payload
  adds ``code_mismatch_share``).
* ``invariant_faults``: breaches of the pool's own bookkeeping.
* ``kmeans_excess``: by how much the k-means objective of the program's
  centroids over the corpus (the rows the build trained on) exceeds that
  of the benchmark's own k-means, run as the configuration states it, as
  a share.  Every other number takes the program's quantizer as given;
  this one judges the build that made its centroids.
* the reference module's ``quantizer_numbers`` judge what the payload adds
  to the quantizer (PQ: ``codebook_excess``, the same for the codebooks).
* ``ref_assign_mismatch``: the reference checking itself: of a seeded
  sample of the rows it assigned on the device (every version against the
  program's centroids, and the corpus against its own k-means'), those
  whose list is not their f64 nearest, ties within rounding excepted; the
  module's ``encode_numbers`` do the same for its encoding (PQ:
  ``ref_code_mismatch``).

The reference module is the configuration's ``reference``; its contract
is in ``bench/reference/common.py``.  Each stage's seconds are kept in
``Checker.seconds``.
"""

from __future__ import annotations

import copy
import dataclasses
import time
from typing import Optional

import numpy as np

from bench.reference import kmeans
from bench.reference.common import assign, assign_mismatch, probe, sq_dists

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
    stored_rows: dict  # id -> stored payload row, for a sample of rows
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
    """Holds the reference's view of the index after the window.

    ``quantizer``: host arrays of the index's trained quantizer,
    ``centroids`` and, for a PQ payload, ``codebooks``.  ``config``: the
    configuration's ``nprobe``, ``k`` and ``kmeans_iters``, and what its
    reference module reads of it."""

    def __init__(self, ref_module, quantizer: dict, config: dict,
                 versions: Versions, readback: Readback, seed: int = 0):
        self.ref = ref_module
        self.quantizer = quantizer
        self.centroids = np.asarray(quantizer["centroids"], np.float32)
        self.nprobe, self.k = config["nprobe"], config["k"]
        self.seed = seed
        self.v = versions
        self.seconds = {}
        self._scorers, self._forms = {}, {}
        t = time.perf_counter()
        self.best, self.second, self.tie, dist = assign(versions.vecs,
                                                        self.centroids)
        t = self._stage("assign", t)
        v = versions
        corpus = v.start_lo == -INF  # the rows the build trained on
        rows = v.vecs[corpus]
        own = kmeans.lloyd(rows, len(self.centroids), config["kmeans_iters"])
        own_best, _, _, own_dist = assign(rows, own)
        # the k-means objective: each row's squared distance to its nearest
        # centroid, summed in f64
        self.kmeans_excess = (np.sum(dist[corpus], dtype=np.float64)
                              / np.sum(own_dist, dtype=np.float64) - 1.0)
        t = self._stage("k-means", t)
        rng = np.random.default_rng([seed, 5])
        self.ref_assign_mismatch = (
            assign_mismatch(v.vecs, self.centroids, self.best, rng)
            + assign_mismatch(rows, own, own_best, rng))
        t = self._stage("assign check", t)
        self.quantizer_numbers = self.ref.quantizer_numbers(
            quantizer, rows, self.best[corpus], config,
            np.random.default_rng([seed, 7]))
        self._stage("codebooks", t)
        self._index(readback)

    def with_readback(self, readback: Readback) -> "Checker":
        """The same reference over another read-back index: what the
        quantizer's judgement costs is not paid again."""
        other = copy.copy(self)
        other.seconds, other._forms = dict(self.seconds), {}
        other._index(readback)
        return other

    def _index(self, readback: Readback) -> None:
        """Where each version is stored, and which lists scan what."""
        t = time.perf_counter()
        v = self.v
        self.rb = readback
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
        self._stage("versions by list", t)

    def _stage(self, name: str, since: float) -> float:
        now = time.perf_counter()
        self.seconds[name] = self.seconds.get(name, 0.0) + now - since
        return now

    def scorer(self, precision: str):
        if precision not in self._scorers:
            self._scorers[precision] = self.ref.Scorer(self.quantizer,
                                                       precision)
        return self._scorers[precision]

    def form(self, precision: str) -> np.ndarray:
        """Every version as the reference at ``precision`` would store it in
        the list it is scanned in."""
        if precision not in self._forms:
            self._forms[precision] = self.scorer(precision).encode(
                self.v.vecs, self.primary)
        return self._forms[precision]

    def version_of(self, ids: np.ndarray) -> np.ndarray:
        pos = np.minimum(np.searchsorted(self._ids, ids), len(self._ids) - 1)
        return np.where(self._ids[pos] == ids, self._ver[pos], -1)

    def numbers(self, served: Served, control: Optional[str] = None) -> dict:
        """Every compared number.  ``control`` puts the reference at that
        precision in the program's place: its answers and its stored rows."""
        t = time.perf_counter()
        if control is not None:
            served = self.control_answers(served, control)
        out = self.served_numbers(served)
        t = self._stage("served", t)
        out.update(self.state_numbers(control))
        self._stage("state", t)
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
        ids = np.fromiter(rb.stored_rows, np.int64, len(rb.stored_rows))
        vj = self.version_of(ids)
        ok = vj >= 0
        want = self.form("highest")[vj[ok]]
        if control:
            got = self.form(control)[vj[ok]]
        else:
            got = np.array([rb.stored_rows[i] for i in ids[ok].tolist()]
                           ).reshape(want.shape)
        out.update(self.ref.stored_numbers(want, got))
        out["stored_mismatch"] += int((~ok).sum())
        out["kmeans_excess"] = self.kmeans_excess
        out.update(self.quantizer_numbers)
        out["ref_assign_mismatch"] = self.ref_assign_mismatch
        out.update(self.scorer("highest").encode_numbers(
            self.v.vecs, self.primary, self.form("highest"),
            np.random.default_rng([self.seed, 6])))
        return out

    # ---------------------------------------------------------- searches --
    def candidates(self, lists: np.ndarray) -> np.ndarray:
        """Versions scanned in any of ``lists``, or that may be."""
        parts = [self.order[self.starts[l] : self.starts[l + 1]]
                 for l in lists.tolist()]
        parts.append(self.loose[np.isin(self.second[self.loose], lists)])
        return np.unique(np.concatenate(parts))

    def score(self, precision: str, q: np.ndarray,
              cand: np.ndarray) -> np.ndarray:
        """Distances of ``q`` to the versions ``cand`` as stored."""
        return self.scorer(precision).scores(
            q, self.form(precision)[cand], self.primary[cand])

    def served_numbers(self, served: Served) -> dict:
        v = self.v
        sure_p, may_p = probe(served.queries, self.centroids, self.nprobe)
        foreign, dist_gap, missed = 0, 0.0, 0.0
        for j in range(len(served.queries)):
            sent, done = served.sent[j], served.done[j]
            cand = self.candidates(np.flatnonzero(may_p[j]))
            cand = cand[(v.start_lo[cand] < done) & (v.end_hi[cand] > sent)]
            d = self.score("highest", served.queries[j], cand)
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
        d_c, _ = sq_dists(served.queries, self.centroids, precision)
        top = np.argsort(d_c, axis=1, kind="stable")[:, : self.nprobe]
        ids = np.full((len(top), self.k), -1, np.int64)
        dists = np.full((len(top), self.k), np.inf, np.float32)
        for j in range(len(top)):
            cand = self.candidates(top[j])
            cand = cand[np.isin(self.primary[cand], top[j])
                        & (v.start_hi[cand] <= served.sent[j])
                        & (v.end_lo[cand] >= served.done[j])]
            d = self.score(precision, served.queries[j], cand).astype(
                np.float32)
            sel = np.argsort(d, kind="stable")[: self.k]
            ids[j, : len(sel)] = v.ids[cand[sel]]
            dists[j, : len(sel)] = d[sel]
        return Served(served.queries, served.sent, served.done, ids, dists)
