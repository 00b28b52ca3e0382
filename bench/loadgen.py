"""Open-loop sender: submits each request of a schedule at its due time and
records when it was sent and when it resolved.

One thread sends; it sleeps until each due time and never waits for a
reply, so a stall in the system under test delays no arrival.  Latency is
taken from the due time, so the sender's own lateness counts against the
system and is reported apart (``sent - due``).  A refused or failed request
counts as slower than any completed one.

The sender keeps no request's future, only its result once it resolves, as
a client does: a window's worth of live futures would grow the
interpreter's heap and lengthen the garbage collector's pauses, which every
thread of the system under test waits out.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from functools import partial

import numpy as np

from bench.traffic import KINDS, Schedule

GRACE_S = 60.0  # after the window closes, how long answers may still come
FAILED_S = 1e3  # latency given to a refused or failed request


@dataclasses.dataclass
class Lane:
    due: np.ndarray  # absolute perf_counter seconds
    sent: np.ndarray
    done: np.ndarray  # nan until resolved
    admitted: np.ndarray  # bool: not refused at submit
    answered: np.ndarray  # bool: resolved with a result
    results: list  # the result of each answered request
    pending: int = 0  # admitted and not yet resolved
    cond: threading.Condition = dataclasses.field(
        default_factory=threading.Condition)

    @classmethod
    def empty(cls, due: np.ndarray) -> "Lane":
        n = len(due)
        return cls(due, np.full(n, np.nan), np.full(n, np.nan),
                   np.zeros(n, bool), np.zeros(n, bool), [None] * n)

    def latency_s(self) -> np.ndarray:
        lat = self.done - self.due
        lat[~self.answered] = FAILED_S
        return lat


@dataclasses.dataclass
class Drive:
    t0: float  # window opened
    t1: float  # window closed
    search: Lane
    mutation: Lane


def _resolved(lane: Lane, j: int, fut) -> None:
    lane.done[j] = time.perf_counter()
    if not fut.cancelled() and fut.exception() is None:
        lane.results[j] = fut.result()
        lane.answered[j] = True
    with lane.cond:
        lane.pending -= 1
        lane.cond.notify_all()


def submit_mutation(rt, kind: str, vecs: np.ndarray, ids: np.ndarray):
    if kind == "insert":
        return rt.submit_insert(vecs)
    if kind == "delete":
        return rt.submit_delete(ids.astype(np.int32))
    return rt.submit_update(vecs, ids.astype(np.int32))


def drive(rt, sched: Schedule) -> Drive:
    """Send ``sched`` through ``rt``; return once the window has closed
    (answers may still be outstanding: see ``settle``)."""
    from repro.core.admission import RequestRejected

    n_s = len(sched.s_due)
    due = np.concatenate([sched.s_due, sched.m_due])
    order = np.argsort(due, kind="stable")
    t0 = time.perf_counter()
    search = Lane.empty(t0 + sched.s_due)
    mutation = Lane.empty(t0 + sched.m_due)
    for e in order.tolist():
        lane, j = (search, e) if e < n_s else (mutation, e - n_s)
        wait = lane.due[j] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        lane.sent[j] = time.perf_counter()
        try:
            if lane is search:
                fut = rt.submit_search(sched.queries[j : j + 1])
            else:
                fut = submit_mutation(
                    rt, KINDS[sched.m_kind[j]], sched.m_vecs[j : j + 1],
                    sched.m_ids[j : j + 1])
        except RequestRejected:
            continue
        lane.admitted[j] = True
        with lane.cond:
            lane.pending += 1
        fut.add_done_callback(partial(_resolved, lane, j))
    t1 = t0 + sched.seconds
    time.sleep(max(0.0, t1 - time.perf_counter()))
    return Drive(t0, t1, search, mutation)


def settle(d: Drive) -> None:
    """Wait until every admitted request has resolved, at most ``GRACE_S``
    past the window's close."""
    for lane in (d.search, d.mutation):
        with lane.cond:
            lane.cond.wait_for(
                lambda: lane.pending == 0,
                timeout=max(0.0, d.t1 + GRACE_S - time.perf_counter()))


def percentile_ms(lat_s: np.ndarray, q: float) -> float:
    return float(np.percentile(lat_s, q) * 1e3)
