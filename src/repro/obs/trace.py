"""Per-request span tracing for the serving runtime.

A :class:`RequestTrace` is created (subject to sampling) when a request
enters ``submit_*`` and is carried on the queued item through the whole
serving path.  Each pipeline boundary calls :meth:`RequestTrace.stamp`
with a *stage name from the registered catalog* (:data:`SPAN_STAGES` —
inline string literals are rejected here at runtime and by the
``event-name`` lint rule at review time) and a monotonic timestamp.  A
stamp closes the span that *ends* at that boundary, so the recorded
spans are contiguous: they tile ``[t_start, t_last]`` with no gaps and
no overlaps, and therefore sum to the request's end-to-end latency by
construction.  ``benchmarks/obs.py`` asserts that this trace-internal
budget matches the load generator's externally measured latency within
5% at p50/p99.

Stage model (docs/observability.md):

``admission``   submit entry -> enqueued (validation, gate/slot acquire)
``queue``       enqueued -> popped by a worker loop
``batch_form``  popped -> batch closed / dispatch starts
``compile``     dispatch -> step returned, when this dispatch traced+
                compiled a new program (trace-cache detection, PR 9)
``execute``     same span when the jit cache was already warm
``device_wait`` step returned -> device results materialized on host
``ack``         results on host -> future resolved (callbacks ran)

Terminal outcomes: ``ok``, ``rejected`` (admission refused), ``shed``
(deadline passed in queue), ``error`` (lane failure / poison).

Dispatch spans (:func:`stage`): the runtime wraps the work of each
dispatch -- ``execute``, ``device_wait``, ``ack`` of a lane -- in one
context manager that stamps the batch's sampled traces on exit and, while
a profiler session is active, writes a host span named ``<lane>.<stage>``
(:data:`HOST_SPANS`) into the profiler's own trace.  Every such span
carries ``t_ns``, the ``perf_counter_ns()`` of its entry: the span's
start in the profile minus ``t_ns`` maps any ``perf_counter`` stamp onto
the device trace's clock.  Spans go around work only, never around a
wait for work, so an idle stretch of the device is labelled by the stage
the host was in.

Threading: a trace object is only ever touched by the thread that
currently owns the request (submitter, then exactly one worker loop —
the queue hand-off provides the happens-before edge), so traces need no
lock.  The ring and the sampler are shared and take a leaf lock.
"""

from __future__ import annotations

import threading
import time
from typing import Iterable, List, Optional, Tuple

from jax._src.lib import _profiler as _xprof
from jax.profiler import TraceAnnotation

# ---------------------------------------------------------------- catalog --
# Span stage names.  Register new stages here (and in the table in
# docs/observability.md); `stamp()` rejects anything else, and the
# `event-name` lint rule rejects inline literals at call sites.
STAGE_ADMISSION = "admission"
STAGE_QUEUE = "queue"
STAGE_BATCH = "batch_form"
STAGE_COMPILE = "compile"
STAGE_EXECUTE = "execute"
STAGE_DEVICE = "device_wait"
STAGE_ACK = "ack"

SPAN_STAGES = frozenset({
    STAGE_ADMISSION,
    STAGE_QUEUE,
    STAGE_BATCH,
    STAGE_COMPILE,
    STAGE_EXECUTE,
    STAGE_DEVICE,
    STAGE_ACK,
})

# Host spans on the profiler's clock, ``<lane>.<stage>`` (module
# docstring).  ``budget`` is a span only: the chain-budget refresh inside
# a search's ``execute``; no request trace stamps it.
LANE_SEARCH = "search"
LANE_MUTATION = "mutation"
STAGE_BUDGET = "budget"

SPAN_SEARCH_EXECUTE = "search.execute"
SPAN_SEARCH_BUDGET = "search.budget"
SPAN_SEARCH_DEVICE = "search.device_wait"
SPAN_SEARCH_ACK = "search.ack"
SPAN_MUTATION_EXECUTE = "mutation.execute"
SPAN_MUTATION_DEVICE = "mutation.device_wait"
SPAN_MUTATION_ACK = "mutation.ack"
SPAN_GC = "gc"  # a collection of the interpreter's garbage collector

_SPAN_OF = {
    (LANE_SEARCH, STAGE_EXECUTE): SPAN_SEARCH_EXECUTE,
    (LANE_SEARCH, STAGE_BUDGET): SPAN_SEARCH_BUDGET,
    (LANE_SEARCH, STAGE_DEVICE): SPAN_SEARCH_DEVICE,
    (LANE_SEARCH, STAGE_ACK): SPAN_SEARCH_ACK,
    (LANE_MUTATION, STAGE_EXECUTE): SPAN_MUTATION_EXECUTE,
    (LANE_MUTATION, STAGE_DEVICE): SPAN_MUTATION_DEVICE,
    (LANE_MUTATION, STAGE_ACK): SPAN_MUTATION_ACK,
}

HOST_SPANS = frozenset(_SPAN_OF.values()) | {SPAN_GC}

OUTCOME_OK = "ok"
OUTCOME_REJECTED = "rejected"
OUTCOME_SHED = "shed"
OUTCOME_ERROR = "error"

OUTCOMES = frozenset({
    OUTCOME_OK, OUTCOME_REJECTED, OUTCOME_SHED, OUTCOME_ERROR,
})


class RequestTrace:
    """Span timeline of one request; single-owner, no lock (see module
    docstring for the hand-off argument)."""

    __slots__ = ("trace_id", "kind", "t_start", "marks", "outcome",
                 "dispatches")

    def __init__(self, trace_id: int, kind: str, t_start: float):
        self.trace_id = trace_id
        self.kind = kind
        self.t_start = t_start
        # (stage, t) pairs; span i runs from marks[i-1].t (or t_start)
        # to marks[i].t.  A stage may legitimately repeat (per-item
        # poison retry re-dispatches), so this is a list, not a dict.
        self.marks: List[Tuple[str, float]] = []
        self.outcome: Optional[str] = None
        # per-lane sequence numbers of the dispatches this request rode
        # in (the ``dispatch`` of their ``execute`` spans); a poison retry
        # re-dispatches, so there may be more than one
        self.dispatches: List[int] = []

    def stamp(self, stage: str, t: Optional[float] = None) -> None:
        """Close the span ending now (or at explicit monotonic ``t``)."""
        if stage not in SPAN_STAGES:
            raise ValueError(
                f"unregistered span stage {stage!r}; known stages: "
                f"{sorted(SPAN_STAGES)} (register in repro.obs.trace)"
            )
        self.marks.append((stage, time.perf_counter() if t is None else t))

    def spans(self) -> List[Tuple[str, float, float]]:
        """Contiguous ``(stage, t0, t1)`` triples tiling the timeline."""
        out = []
        prev = self.t_start
        for stage, t in self.marks:
            out.append((stage, prev, t))
            prev = t
        return out

    def e2e_s(self) -> float:
        """End-to-end seconds, submit entry to last recorded boundary."""
        return (self.marks[-1][1] - self.t_start) if self.marks else 0.0

    def as_dict(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "kind": self.kind,
            "outcome": self.outcome,
            "t_start": self.t_start,
            "e2e_s": self.e2e_s(),
            "dispatches": list(self.dispatches),
            "spans": [
                {"stage": s, "t0": t0, "t1": t1, "dur_s": t1 - t0}
                for s, t0, t1 in self.spans()
            ],
        }


def _stamp_all(items: Iterable, stage: str, t: float) -> None:
    """Stamp ``stage`` on the sampled trace of every item of a batch (an
    item carries ``.trace``, a :class:`RequestTrace` or ``None``), all at
    the same instant.  Unsampled items cost one ``None`` check."""
    for it in items:
        if it.trace is not None:
            it.trace.stamp(stage, t)


class _Stage:
    """The context manager :func:`stage` returns."""

    __slots__ = ("_span", "_stamp_as", "_items", "_meta", "_ann")

    def __init__(self, span: str, stamp_as: str, items, meta: dict):
        self._span = span
        self._stamp_as = stamp_as
        self._items = items
        self._meta = meta
        self._ann = None

    def __enter__(self) -> "_Stage":
        t_ns = time.perf_counter_ns()
        if _xprof.TraceMe.is_enabled():
            self._ann = TraceAnnotation(self._span, t_ns=t_ns, **self._meta)
            self._ann.__enter__()
        if self._stamp_as == STAGE_EXECUTE:
            # the batch closes where its dispatch begins: one instant for
            # the request traces and the span
            _stamp_all(self._items, STAGE_BATCH, t_ns / 1e9)
        return self

    def compiled(self) -> None:
        """This dispatch traced and compiled a new program: the request
        traces stamp ``compile`` in place of ``execute``, and the span
        gets ``compiled=1``."""
        self._stamp_as = STAGE_COMPILE
        if self._ann is not None:
            self._ann.set_metadata(compiled=1)

    def __exit__(self, exc_type, exc, tb) -> bool:
        t = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        # a stage that raised stamps nothing: the failure path finishes
        # the traces with an ``error`` outcome
        if exc_type is None:
            _stamp_all(self._items, self._stamp_as, t)
        return False


def stage(lane: str, stage: str, items: Iterable = (), **meta) -> _Stage:
    """Context manager around one stage of one dispatch of ``lane``.

    On a clean exit it stamps ``stage`` on every sampled trace of
    ``items`` at one instant; an ``execute`` stage also stamps
    ``batch_form`` at its entry, the instant of its ``t_ns``, so the
    request traces' dispatch boundaries are the span's.  While a profiler
    session is active it also
    writes the host span ``<lane>.<stage>`` with ``meta`` and ``t_ns``
    (``perf_counter_ns()`` at entry) as its stats; otherwise it constructs
    no profiler object.  An ``execute`` stage with a ``dispatch`` number
    records it on each sampled trace.  Unregistered names raise
    ``ValueError``; the ``event-name`` lint rule keeps call sites on the
    ``LANE_*`` / ``STAGE_*`` constants."""
    span = _SPAN_OF.get((lane, stage))
    if span is None:
        raise ValueError(
            f"unregistered span {lane}.{stage}; known spans: "
            f"{sorted(HOST_SPANS)} (register in repro.obs.trace)"
        )
    if stage == STAGE_EXECUTE and "dispatch" in meta:
        for it in items:
            if it.trace is not None:
                it.trace.dispatches.append(meta["dispatch"])
    return _Stage(span, stage, items, meta)


class GcSpans:
    """A ``gc.callbacks`` hook that writes each collection of the
    interpreter's garbage collector as a ``gc`` host span, with its
    generation, while a profiler session is active.  A collection holds
    the interpreter lock, so every thread of the process waits it out;
    the span names that wait in the device trace's idle gaps."""

    def __init__(self):
        self._open = None

    def __call__(self, phase: str, info: dict) -> None:
        # collections do not nest, and one runs start to stop on one
        # thread, so one open span at a time
        if phase == "start":
            if _xprof.TraceMe.is_enabled():
                self._open = TraceAnnotation(
                    SPAN_GC, generation=info["generation"])
                self._open.__enter__()
        elif self._open is not None:
            ann, self._open = self._open, None
            ann.__exit__(None, None, None)


class TraceRing:
    """Bounded ring of finished traces (oldest evicted first).

    One leaf lock; ``record`` is O(1) and ``snapshot`` copies the live
    window oldest-to-newest.  Writers never block readers for longer
    than the copy."""

    def __init__(self, capacity: int):
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self._lock = threading.Lock()
        self._buf: List[Optional[RequestTrace]] = [None] * int(capacity)
        self._head = 0  # guarded-by: _lock (next write index)
        self._total = 0  # guarded-by: _lock (lifetime records)

    @property
    def capacity(self) -> int:
        return len(self._buf)

    @property
    def total(self) -> int:
        """Lifetime record count (evictions included)."""
        with self._lock:
            return self._total

    def record(self, trace: RequestTrace) -> None:
        with self._lock:
            self._buf[self._head] = trace
            self._head = (self._head + 1) % len(self._buf)
            self._total += 1

    def clear(self) -> None:
        with self._lock:
            for i in range(len(self._buf)):
                self._buf[i] = None
            self._head = 0

    def snapshot(self) -> List[RequestTrace]:
        """Live window, oldest first."""
        with self._lock:
            n = len(self._buf)
            ordered = [self._buf[(self._head + i) % n] for i in range(n)]
        return [t for t in ordered if t is not None]


class RequestTracer:
    """Sampling front-end over a :class:`TraceRing`.

    ``sample_rate`` in [0, 1] maps to a deterministic stride (every
    Nth submit is traced) so overhead and coverage are load-independent
    and tests are reproducible.  The disabled path (rate 0) is one
    ``None`` check per submit; the enabled path adds one leaf-lock
    counter increment — the "always-on cheap path" in the runbook, with
    the measured cost written to BENCH_obs.json."""

    def __init__(self, sample_rate: float, capacity: int = 2048):
        rate = min(1.0, max(0.0, float(sample_rate)))
        # rate 0 -> stride 0 (disabled); rate 1 -> stride 1 (trace all)
        self._stride = 0 if rate <= 0.0 else max(1, round(1.0 / rate))
        self.ring = TraceRing(capacity)
        self._lock = threading.Lock()
        self._count = 0  # guarded-by: _lock (submit counter for stride)
        self._seq = 0  # guarded-by: _lock (trace id allocator)

    @property
    def enabled(self) -> bool:
        return self._stride > 0

    @property
    def stride(self) -> int:
        return self._stride

    def start(self, kind: str,
              t: Optional[float] = None) -> Optional[RequestTrace]:
        """Return a live trace for this submit, or ``None`` (unsampled /
        disabled).  Callers must treat ``None`` as the no-op path."""
        if self._stride == 0:
            return None
        with self._lock:
            self._count += 1
            if self._count % self._stride:
                return None
            self._seq += 1
            tid = self._seq
        return RequestTrace(tid, kind,
                            time.perf_counter() if t is None else t)

    def finish(self, trace: RequestTrace, outcome: str) -> None:
        """Seal the trace with a terminal outcome and ring-record it.

        Idempotent: failure paths can race resolution paths on the same
        item (e.g. ``_fail_futures`` sweeping a lane whose batch already
        acked); first outcome wins, later calls are no-ops."""
        if outcome not in OUTCOMES:
            raise ValueError(
                f"unknown trace outcome {outcome!r}; known: {sorted(OUTCOMES)}"
            )
        if trace.outcome is not None:
            return
        trace.outcome = outcome
        self.ring.record(trace)


def decompose(traces) -> dict:
    """Per-stage latency budget over ``ok`` traces.

    Returns percentile summaries per stage plus the trace-internal
    end-to-end distribution and the per-trace span-sum distribution.
    Because spans are contiguous, ``span_sum`` equals ``e2e`` up to
    float rounding — exporting both keeps the invariant auditable."""
    # imported here: repro.core imports the runtime, which imports this
    from repro.core.metrics import percentile_summary

    per_stage: dict = {}
    e2e: List[float] = []
    sums: List[float] = []
    for tr in traces:
        if tr.outcome != OUTCOME_OK:
            continue
        total = 0.0
        for stage, t0, t1 in tr.spans():
            per_stage.setdefault(stage, []).append(t1 - t0)
            total += t1 - t0
        e2e.append(tr.e2e_s())
        sums.append(total)
    return {
        "stages": {s: percentile_summary(v) for s, v in sorted(per_stage.items())},
        "e2e": percentile_summary(e2e),
        "span_sum": percentile_summary(sums),
        "n_ok": len(e2e),
    }
