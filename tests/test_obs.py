"""Observability layer: tracing, flight recorder, exporters, bundles.

Three tiers:

* pure-unit — ring wraparound/concurrency, stage/outcome/event-name
  registry validation, sampler strides, decompose arithmetic, and the
  ``percentile_summary`` / ``ArrivalEstimator`` edge cases the exporters
  lean on;
* format — Perfetto ``trace_event`` and Prometheus text exposition
  checked against the format grammar, not just "is a string";
* end-to-end — a real ``ServingRuntime`` serving real traffic, asserting
  the span stages (including the compile-vs-execute split), terminal
  outcomes, flight-recorder transitions (WAL fsync/rotate, snapshot
  cut/publish, injected faults, worker restarts), ``reset_stats``
  semantics, and the debug bundle written on ``stop()``.
"""

import glob
import json
import os
import re
import threading
import time

import numpy as np
import pytest

from repro.core import build_ivf
from repro.core.admission import QueueFull
from repro.core.faults import FaultPlan
from repro.core.metrics import (
    ArrivalEstimator,
    LatencyHistogram,
    percentile_summary,
)
from repro.core.runtime import RuntimeConfig, ServingRuntime
from repro.obs import events as obs_events
from repro.obs import trace as obs_trace
from repro.obs.bundle import write_debug_bundle
from repro.obs.events import (
    EV_FAULT_INJECTED,
    EV_SNAPSHOT_CUT,
    EV_SNAPSHOT_PUBLISH,
    EV_WAL_FSYNC,
    EV_WAL_ROTATE,
    EV_WORKER_RESTART,
    EVENT_CATALOG,
    FlightRecorder,
)
from repro.obs.export import (
    PROM_COUNTER_KEYS,
    _prom_value,
    flatten_metrics,
    perfetto_trace,
    prometheus_text,
)
from repro.obs.programs import PROGRAM_INSERT, PROGRAMS, module_name
from repro.obs.trace import (
    HOST_SPANS,
    LANE_SEARCH,
    OUTCOME_OK,
    OUTCOME_REJECTED,
    SPAN_GC,
    SPAN_MUTATION_ACK,
    SPAN_MUTATION_DEVICE,
    SPAN_MUTATION_EXECUTE,
    SPAN_SEARCH_ACK,
    SPAN_SEARCH_DEVICE,
    SPAN_SEARCH_EXECUTE,
    SPAN_STAGES,
    STAGE_ACK,
    STAGE_ADMISSION,
    STAGE_BATCH,
    STAGE_COMPILE,
    STAGE_DEVICE,
    STAGE_EXECUTE,
    STAGE_QUEUE,
    GcSpans,
    RequestTrace,
    RequestTracer,
    TraceRing,
    decompose,
)

pytestmark = pytest.mark.obs

D = 16


def _data(n, d=D, seed=0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(8, d)).astype(np.float32) * 3
    return (
        centers[rng.integers(0, 8, n)]
        + rng.normal(size=(n, d)).astype(np.float32)
    ).astype(np.float32)


@pytest.fixture(scope="module")
def base_index():
    x = _data(1200)
    return x, lambda: build_ivf(
        x, n_clusters=4, block_size=16, max_chain=64, add_batch=256,
        capacity_vectors=8000,
    )


def _mk_trace(tid=1, kind="search", marks=()):
    tr = RequestTrace(tid, kind, t_start=0.0)
    for stage, t in marks:
        tr.stamp(stage, t)
    return tr


# ------------------------------------------------------------- trace unit --
def test_stamp_rejects_unregistered_stage():
    tr = RequestTrace(1, "search", 0.0)
    with pytest.raises(ValueError, match="unregistered span stage"):
        tr.stamp("warp_drive")


def test_spans_tile_timeline_and_sum_to_e2e_exactly():
    tr = _mk_trace(marks=[(STAGE_ADMISSION, 1.0), (STAGE_QUEUE, 2.25),
                          (STAGE_ACK, 3.5)])
    spans = tr.spans()
    assert spans == [(STAGE_ADMISSION, 0.0, 1.0), (STAGE_QUEUE, 1.0, 2.25),
                     (STAGE_ACK, 2.25, 3.5)]
    # contiguity: each span starts where the previous ended
    for (_, _, t1), (_, t0, _) in zip(spans, spans[1:]):
        assert t1 == t0
    assert sum(t1 - t0 for _, t0, t1 in spans) == tr.e2e_s() == 3.5
    d = tr.as_dict()
    assert d["e2e_s"] == 3.5 and len(d["spans"]) == 3


def test_repeated_stage_keeps_spans_contiguous():
    # per-item poison retries legitimately re-stamp a stage
    tr = _mk_trace(marks=[(STAGE_QUEUE, 1.0), (STAGE_QUEUE, 2.0),
                          (STAGE_ACK, 3.0)])
    assert sum(t1 - t0 for _, t0, t1 in tr.spans()) == tr.e2e_s() == 3.0


def test_trace_ring_wraparound_keeps_newest_oldest_first():
    ring = TraceRing(4)
    for i in range(1, 11):
        ring.record(_mk_trace(tid=i))
    assert [t.trace_id for t in ring.snapshot()] == [7, 8, 9, 10]
    assert ring.total == 10 and ring.capacity == 4
    ring.clear()
    assert ring.snapshot() == [] and ring.total == 10  # lifetime survives


def test_trace_ring_rejects_nonpositive_capacity():
    with pytest.raises(ValueError):
        TraceRing(0)


def test_trace_ring_concurrent_writers_lose_nothing():
    ring = TraceRing(64)
    n_threads, per = 8, 500

    def work(base):
        for i in range(per):
            ring.record(_mk_trace(tid=base + i))

    ts = [threading.Thread(target=work, args=(k * per,))
          for k in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert ring.total == n_threads * per
    assert len(ring.snapshot()) == 64  # exactly one full window survives


def test_sampler_strides():
    assert RequestTracer(0.0).enabled is False
    assert RequestTracer(0.0).start("search") is None
    every = RequestTracer(1.0)
    assert every.stride == 1
    assert all(every.start("search") is not None for _ in range(5))
    half = RequestTracer(0.5)
    assert half.stride == 2
    hits = [half.start("search") is not None for _ in range(10)]
    assert hits == [False, True] * 5  # deterministic: every 2nd submit
    assert RequestTracer(0.01).stride == 100
    assert RequestTracer(7.0).stride == 1  # rate clamped into [0, 1]


def test_finish_is_idempotent_and_validates_outcome():
    tracer = RequestTracer(1.0)
    tr = tracer.start("search")
    with pytest.raises(ValueError, match="unknown trace outcome"):
        tracer.finish(tr, "vanished")
    tracer.finish(tr, OUTCOME_OK)
    tracer.finish(tr, "error")  # resolution/failure race: first wins
    assert tr.outcome == OUTCOME_OK
    assert tracer.ring.total == 1  # recorded once, not twice


def test_decompose_uses_only_ok_traces():
    ok = _mk_trace(tid=1, marks=[(STAGE_ADMISSION, 1.0), (STAGE_ACK, 3.0)])
    ok.outcome = OUTCOME_OK
    rej = _mk_trace(tid=2, marks=[(STAGE_ADMISSION, 9.0)])
    rej.outcome = OUTCOME_REJECTED
    out = decompose([ok, rej])
    assert out["n_ok"] == 1
    assert out["stages"][STAGE_ADMISSION]["p50_ms"] == 1000.0
    assert out["stages"][STAGE_ACK]["p50_ms"] == 2000.0
    assert out["e2e"]["p50_ms"] == out["span_sum"]["p50_ms"] == 3000.0


# ---------------------------------------------------- flight-recorder unit --
def test_record_event_rejects_unregistered_name():
    rec = FlightRecorder(8)
    with pytest.raises(ValueError, match="unregistered event name"):
        rec.record_event("controller.window_rungg")  # event-ok: negative test


def test_every_ev_constant_is_in_the_catalog():
    consts = {v for k, v in vars(obs_events).items() if k.startswith("EV_")}
    assert consts == EVENT_CATALOG
    assert all(re.fullmatch(r"[a-z_]+\.[a-z_]+", n) for n in EVENT_CATALOG)


def test_flight_recorder_wraparound_count_and_clear():
    rec = FlightRecorder(4)
    for i in range(6):
        rec.record_event(EV_WAL_FSYNC, t=float(i), lsn=i)
    win = rec.snapshot()
    assert [e.fields["lsn"] for e in win] == [2, 3, 4, 5]  # oldest first
    assert rec.count(EV_WAL_FSYNC) == 4 and rec.total == 6
    assert win[0].as_dict() == {"seq": 3, "t": 2.0, "name": EV_WAL_FSYNC,
                                "lsn": 2}
    rec.clear()
    assert rec.snapshot() == [] and rec.count(EV_WAL_FSYNC) == 0


def test_flight_recorder_concurrent_emitters_get_unique_seqs():
    rec = FlightRecorder(4096)
    n_threads, per = 8, 200

    def work():
        for _ in range(per):
            rec.record_event(EV_WAL_FSYNC)

    ts = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    seqs = [e.seq for e in rec.snapshot()]
    assert len(seqs) == len(set(seqs)) == n_threads * per == rec.total


# ----------------------------------------------------- metrics edge cases --
def test_percentile_summary_empty_is_zeros_not_nan():
    out = percentile_summary([])
    assert out == {"p50_ms": 0.0, "p95_ms": 0.0, "p99_ms": 0.0,
                   "mean_ms": 0.0, "max_ms": 0.0, "n": 0}


def test_percentile_summary_single_sample_collapses():
    out = percentile_summary([0.25])
    assert out["n"] == 1
    assert (out["p50_ms"] == out["p95_ms"] == out["p99_ms"]
            == out["mean_ms"] == out["max_ms"] == 250.0)


def test_arrival_estimator_empty_and_single_arrival():
    est = ArrivalEstimator(tau_s=0.5)
    assert est.rate(now=100.0) == 0.0
    assert est.snapshot(now=100.0) == {"rate": 0.0, "queue_age_s": 0.0,
                                       "service_s": 0.0, "events": 0}
    est.observe_arrival(1, now=100.0)
    assert est.rate(now=100.0) == pytest.approx(1 / 0.5)
    # decay is monotone in elapsed silence
    assert est.rate(now=100.0) > est.rate(now=100.4) > est.rate(now=101.0)


def test_arrival_estimator_service_seeds_then_smooths():
    est = ArrivalEstimator(tau_s=0.5)
    assert est.service(default=0.123) == 0.123
    est.observe_service(1.0)
    assert est.service() == 1.0  # EWMA seeds on the first sample
    est.observe_service(0.0)
    assert est.service() == pytest.approx(0.7)


def test_arrival_estimator_reset_forgets_everything():
    est = ArrivalEstimator(tau_s=0.5)
    est.observe_arrival(5, now=10.0)
    est.observe_queue_age(0.4)
    est.observe_service(0.2)
    est.reset()
    assert est.snapshot(now=10.0) == {"rate": 0.0, "queue_age_s": 0.0,
                                      "service_s": 0.0, "events": 0}


# -------------------------------------------------------- exporter format --
def test_flatten_metrics_recurses_and_drops_strings():
    flat = flatten_metrics({
        "a": 1, "b": {"c": 2.5, "d": {"e": 3}}, "accepting": True,
        "label": "ignored",
    })
    assert flat == {"a": 1.0, "b_c": 2.5, "b_d_e": 3.0, "accepting": 1.0}


def test_prom_value_special_floats():
    assert _prom_value(float("nan")) == "NaN"
    assert _prom_value(float("inf")) == "+Inf"
    assert _prom_value(float("-inf")) == "-Inf"
    assert _prom_value(2.0) == "2.0"


_PROM_LINE = re.compile(
    r"^(# HELP [a-zA-Z_:][a-zA-Z0-9_:]* .+"
    r"|# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (counter|gauge)"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]* (NaN|[+-]Inf|[-+0-9.e]+))$"
)


def test_prometheus_text_grammar_and_typing():
    text = prometheus_text({"inserts": 3.0, "pending_mutations": 7.0,
                            "percentiles_search_p50_ms": 1.25})
    for line in text.strip().split("\n"):
        assert _PROM_LINE.match(line), f"bad exposition line: {line!r}"
    assert "# TYPE repro_inserts counter" in text
    assert "# TYPE repro_pending_mutations gauge" in text
    assert "# TYPE repro_percentiles_search_p50_ms gauge" in text
    assert "repro_inserts 3.0" in text


def test_perfetto_envelope_spans_and_instants():
    tr = _mk_trace(marks=[(STAGE_ADMISSION, 0.001), (STAGE_ACK, 0.003)])
    tr.outcome = OUTCOME_OK
    rec = FlightRecorder(8)
    rec.record_event(EV_WAL_ROTATE, t=0.002, segment=1)
    env = perfetto_trace([tr], rec.snapshot())
    json.loads(json.dumps(env))  # round-trips as JSON
    assert env["displayTimeUnit"] == "ms"
    xs = [e for e in env["traceEvents"] if e["ph"] == "X"]
    ins = [e for e in env["traceEvents"] if e["ph"] == "i"]
    assert len(xs) == 2 and len(ins) == 1
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] > 0 and e["tid"] == 1
        assert e["name"] in SPAN_STAGES
    assert ins[0]["name"] == EV_WAL_ROTATE and ins[0]["s"] == "g"
    # time_origin defaults to the earliest timestamp -> timeline starts at 0
    assert min(e["ts"] for e in env["traceEvents"]) == 0


def test_debug_bundle_roundtrip_and_jsonable_fallback(tmp_path):
    rec = FlightRecorder(8)
    rec.record_event(EV_SNAPSHOT_CUT, t=1.0, lsn=7)
    path = write_debug_bundle(
        str(tmp_path), reason="unit test!", events=rec.snapshot(),
        extra={"np_scalar": np.int32(5), "opaque": object()},
    )
    assert os.path.dirname(path) == str(tmp_path / "debug")
    payload = json.loads(open(path).read())
    assert payload["reason"] == "unit test!"
    assert payload["events"][0]["name"] == EV_SNAPSHOT_CUT
    assert payload["extra"]["np_scalar"] == 5
    assert payload["extra"]["opaque"].startswith("<object object")
    assert not [f for f in os.listdir(tmp_path / "debug")
                if f.endswith(".tmp")]  # atomic: no tmp residue


# ------------------------------------------------------------- end-to-end --
def test_runtime_traces_full_path_with_compile_execute_split(base_index):
    x, make = base_index
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", nprobe=4, k=5, flush_min=1,
                      flush_interval=0.02, trace_sample_rate=1.0),
    )
    try:
        for _ in range(4):
            rt.submit_search(x[:2]).result(timeout=60)
        rt.submit_insert(_data(3, seed=7)).result(timeout=60)
        traces = rt.traces()
        searches = [t for t in traces if t.kind == "search"]
        inserts = [t for t in traces if t.kind == "insert"]
        assert len(searches) == 4 and len(inserts) == 1
        for tr in traces:
            assert tr.outcome == OUTCOME_OK
            stages = [s for s, _, _ in tr.spans()]
            assert stages[0] == STAGE_ADMISSION and stages[-1] == STAGE_ACK
            assert set(stages) <= SPAN_STAGES
            # contiguous spans sum to e2e exactly (float-add associativity
            # aside): the invariant BENCH_obs.json certifies at scale
            assert sum(t1 - t0 for _, t0, t1 in tr.spans()) == \
                pytest.approx(tr.e2e_s(), rel=1e-9)
        # first dispatch of the shape traces+compiles; warm repeats execute
        assert STAGE_COMPILE in [s for s, _, _ in searches[0].spans()]
        assert STAGE_EXECUTE in [s for s, _, _ in searches[-1].spans()]
        assert decompose(traces)["n_ok"] == 5
    finally:
        rt.stop()


def test_runtime_rejected_submit_leaves_rejected_trace(base_index):
    x, make = base_index
    # hold the insert worker so the first submit's rows stay pending and
    # the second deterministically overflows the admission gate
    plan = FaultPlan().delay("insert_loop", 0.5, nth=0)
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", nprobe=4, k=5, flush_min=64,
                      flush_interval=0.05, trace_sample_rate=1.0,
                      max_pending_mutations=8),
        faults=plan,
    )
    try:
        first = rt.submit_insert(_data(8, seed=8))
        with pytest.raises(QueueFull):
            rt.submit_insert(_data(8, seed=8))
        first.result(timeout=60)
        rejected = [t for t in rt.traces() if t.outcome == OUTCOME_REJECTED]
        assert len(rejected) == 1 and rejected[0].kind == "insert"
        assert [s for s, _, _ in rejected[0].spans()] == [STAGE_ADMISSION]
    finally:
        rt.stop()


def test_reset_stats_clears_traces_but_keeps_flight_history(base_index):
    x, make = base_index
    plan = FaultPlan().delay("search_loop", 0.01, nth=0)
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", nprobe=4, k=5, flush_min=1,
                      flush_interval=0.02, trace_sample_rate=1.0),
        faults=plan,
    )
    try:
        rt.submit_search(x[:1]).result(timeout=60)
        injected = [e for e in rt.events() if e.name == EV_FAULT_INJECTED]
        assert injected and injected[0].fields["site"] == "search_loop"
        assert rt.traces() and rt.stats()["percentiles"]["search"]["n"] > 0
        rt.reset_stats()
        assert rt.traces() == []
        assert rt.stats()["percentiles"]["search"]["n"] == 0
        # the flight recorder is history, not a sampling window
        assert [e for e in rt.events() if e.name == EV_FAULT_INJECTED]
    finally:
        rt.stop()


def test_runtime_durability_events_and_shutdown_bundle(base_index, tmp_path):
    x, make = base_index
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", nprobe=4, k=5, flush_min=1,
                      flush_interval=0.02, trace_sample_rate=1.0,
                      persist_dir=str(tmp_path), wal_sync_interval=1),
    )
    try:
        rt.submit_insert(_data(4, seed=9)).result(timeout=60)
        rt.snapshot(wait=True)
        names = {e.name for e in rt.events()}
        assert {EV_WAL_FSYNC, EV_WAL_ROTATE, EV_SNAPSHOT_CUT,
                EV_SNAPSHOT_PUBLISH} <= names
    finally:
        rt.stop()
    bundles = list((tmp_path / "debug").glob("bundle-shutdown-*.json"))
    assert len(bundles) == 1
    payload = json.loads(bundles[0].read_text())
    assert payload["reason"] == "shutdown"
    assert {e["name"] for e in payload["events"]} >= {EV_WAL_FSYNC}
    assert payload["stats"]["inserts"] == 4
    assert payload["config"]["persist_dir"] == str(tmp_path)
    assert any(t["kind"] == "insert" for t in payload["traces"])


def test_worker_restart_emits_flight_event(base_index):
    x, make = base_index
    plan = FaultPlan().fail("search_loop", nth=2)
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", nprobe=4, k=5, flush_min=1,
                      flush_interval=0.02, restart_backoff=0.01),
        faults=plan,
    )
    try:
        deadline = time.perf_counter() + 30
        while plan.calls("search_loop") < 4:
            assert time.perf_counter() < deadline, "lane never restarted"
            time.sleep(0.01)
        rt.submit_search(x[:1]).result(timeout=60)
        restarts = [e for e in rt.events() if e.name == EV_WORKER_RESTART]
        assert restarts and restarts[0].fields["lane"] == "search_loop"
        assert restarts[0].fields["restarts"] == 1
    finally:
        rt.stop()


def test_runtime_exporters_are_format_valid(base_index):
    x, make = base_index
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", nprobe=4, k=5, flush_min=1,
                      flush_interval=0.02, trace_sample_rate=1.0),
    )
    try:
        for _ in range(3):
            rt.submit_search(x[:2]).result(timeout=60)
        text = rt.prometheus_text()
        for line in text.strip().split("\n"):
            assert _PROM_LINE.match(line), f"bad exposition line: {line!r}"
        # the counters the runbook's example queries rely on are present
        assert "repro_inserts" in text
        assert "repro_percentiles_search_p50_ms" in text
        env = rt.export_perfetto()
        json.loads(json.dumps(env))
        assert [e for e in env["traceEvents"] if e["ph"] == "X"]
        flat = rt.metrics()
        assert all(isinstance(v, float) for v in flat.values())
        assert flat["percentiles_search_n"] == 3.0
    finally:
        rt.stop()


# ------------------------------------------- dispatch spans on the profiler --
def _profiled(tmp_path, body):
    """Run ``body()`` under a CPU profiler session; return the host events
    of the recorded trace as ``(name, start_ns, end_ns, stats)``."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    return [(e.name, e.start_ns, e.end_ns, dict(e.stats))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


class _Item:
    def __init__(self, trace=None):
        self.trace = trace


def test_stage_disabled_path_constructs_no_trace_me(monkeypatch):
    made = []
    monkeypatch.setattr(obs_trace, "TraceAnnotation",
                        lambda *a, **k: made.append(a))
    tr = RequestTrace(1, "search", time.perf_counter())
    with obs_trace.stage(LANE_SEARCH, STAGE_EXECUTE, [_Item(tr), _Item()],
                         dispatch=7, n=2, kind="search") as ex:
        ex.compiled()
    hook = GcSpans()
    hook("start", {"generation": 2})
    hook("stop", {"generation": 2})
    assert made == []  # no profiler session: no TraceMe built
    assert [s for s, _ in tr.marks] == [STAGE_BATCH, STAGE_COMPILE]
    assert tr.dispatches == [7]


def test_stage_rejects_unregistered_span_and_stamps_nothing_on_error():
    with pytest.raises(ValueError, match="unregistered span"):
        obs_trace.stage(LANE_SEARCH, "warp_drive")  # event-ok: negative test
    tr = RequestTrace(1, "search", 0.0)
    with pytest.raises(RuntimeError):
        with obs_trace.stage(LANE_SEARCH, STAGE_DEVICE, [_Item(tr)]):
            raise RuntimeError("lost device")
    assert tr.marks == []  # the failure path finishes the trace instead
    assert set(obs_trace._SPAN_OF.values()) < HOST_SPANS


def test_lint_rejects_inline_span_names(tmp_path):
    from repro.analysis.lint import lint_file

    (tmp_path / "snippet.py").write_text(
        "def dispatch(items):\n"
        "    with stage('search', STAGE_EXECUTE, items):\n"
        "        pass\n"
        "    with stage(LANE_SEARCH, 'device_wait', items):\n"
        "        pass\n"
        "    with TraceAnnotation('search.ack'):\n"
        "        pass\n"
        "    with stage(LANE_SEARCH, STAGE_ACK, items):\n"
        "        pass\n"
    )
    findings = lint_file("snippet.py", repo_root=str(tmp_path))
    assert sorted((f.rule, f.line) for f in findings) == [
        ("event-name", 2), ("event-name", 4), ("event-name", 6)]


def test_runtime_dispatch_spans_on_the_profiler_clock(base_index, tmp_path):
    x, make = base_index
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", nprobe=4, k=5, flush_min=1,
                      flush_interval=0.02, trace_sample_rate=1.0),
    )
    try:
        rt.submit_search(x[:3]).result(timeout=60)  # compile outside
        rt.reset_stats()

        def traffic():
            for j in range(4):
                rt.submit_search(x[j : j + 2]).result(timeout=60)
            rt.submit_insert(_data(3, seed=11)).result(timeout=60)

        events = _profiled(tmp_path, traffic)
        traces = rt.traces()
        stats = rt.stats()
    finally:
        rt.stop()
    spans = {}
    for name, a, b, st in events:
        spans.setdefault(name, []).append((a, b, st))
    for name in (SPAN_SEARCH_EXECUTE, SPAN_SEARCH_DEVICE, SPAN_SEARCH_ACK,
                 SPAN_MUTATION_EXECUTE, SPAN_MUTATION_DEVICE,
                 SPAN_MUTATION_ACK):
        assert name in spans, name
    execute = {st["dispatch"]: (a, b, st)
               for a, b, st in spans[SPAN_SEARCH_EXECUTE]}
    assert len(execute) == 4
    for a, b, st in execute.values():
        assert st["n"] == 2 and st["kind"] == "search"
        assert st["oldest_ns"] <= st["t_ns"]
    assert {st["dispatch"] for _, _, st in spans[SPAN_SEARCH_DEVICE]} \
        == set(execute)
    assert stats["search_dispatches"] == 4 and stats["search_queries"] == 8
    assert stats["insert_dispatches"] == 1 and stats["inserts"] == 3
    searches = [t for t in traces if t.kind == "search"]
    assert sorted(d for t in searches for d in t.dispatches) \
        == sorted(execute)
    for tr in searches:
        (seq,) = tr.dispatches
        a, b, st = execute[seq]
        offset = a - st["t_ns"]  # the anchor: profile ns - perf_counter ns
        (t0, t1), = [(s0, s1) for s, s0, s1 in tr.spans()
                     if s == STAGE_EXECUTE]
        assert abs(t0 * 1e9 + offset - a) < 50e3
        assert abs(t1 * 1e9 + offset - b) < 50e3
        assert tr.as_dict()["dispatches"] == [seq]


def test_gc_span_while_profiling_and_hook_removed_on_stop(base_index,
                                                          tmp_path):
    import gc

    x, make = base_index
    rt = ServingRuntime(make(), RuntimeConfig(mode="parallel", nprobe=4, k=5))
    try:
        assert rt._gc_spans in gc.callbacks
        events = _profiled(tmp_path, lambda: gc.collect(1))
    finally:
        rt.stop()
    assert rt._gc_spans not in gc.callbacks
    assert any(name == SPAN_GC and st["generation"] == 1
               for name, _, _, st in events)


def test_runtime_programs_carry_catalog_names(base_index):
    x, make = base_index
    rt = ServingRuntime(make(), RuntimeConfig(nprobe=4, k=5))
    try:
        with rt._state_lock:
            steps = [rt._insert_step, rt._delete_step, rt._update_step,
                     rt._search_step_for(1), rt._fused_step_for(1)]
    finally:
        rt.stop()
    assert {s.__name__ for s in steps} == set(PROGRAMS)
    lowered = rt._insert_step.lower(
        rt.index.state, np.zeros((8, D), np.float32),
        np.zeros(8, np.int32), np.zeros(8, bool))
    assert f"module @{module_name(PROGRAM_INSERT)} " in lowered.as_text()


def _alternatives(pattern: str) -> list:
    """Each program name a ``^jit__(a|b)$``-style pattern spells out."""
    m = re.search(r"\(([^()]*)\)", pattern)
    if m is None:
        return [pattern]
    return [alt for a in m[1].split("|")
            for alt in _alternatives(pattern[:m.start()] + a
                                     + pattern[m.end():])]


def test_benchmark_program_patterns_match_the_catalog():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    patterns = []
    for path in sorted(glob.glob(os.path.join(root, "bench", "metrics",
                                              "*.py"))):
        src = open(path).read()
        patterns += re.findall(r"""r?["'](\^?jit__[^"']*)["']""", src)
    assert patterns  # the readers of search_step_ms & co. are there
    names = [module_name(p) for p in PROGRAMS]
    for pattern in patterns:
        for alt in _alternatives(pattern):
            assert [n for n in names if re.fullmatch(alt.strip("^$"), n)], \
                f"{alt!r} matches no program of repro.obs.programs"


# ------------------------------------------------------ latency histogram --
@pytest.mark.parametrize("scale_s", [1e-4, 5e-3, 0.3])
def test_latency_histogram_counts_exact_percentiles_within_a_bucket(scale_s):
    rng = np.random.default_rng(5)
    samples = scale_s * rng.lognormal(0.0, 1.0, 20_001)
    hist = LatencyHistogram()
    for s in samples:
        hist.record(float(s))
    got, want = hist.summary(), percentile_summary(samples)
    assert got["n"] == want["n"] == len(samples)
    assert got["max_ms"] == want["max_ms"]
    assert got["mean_ms"] == pytest.approx(want["mean_ms"], rel=1e-9)
    for key in ("p50_ms", "p95_ms", "p99_ms"):
        assert got[key] == pytest.approx(want[key], rel=0.01), key
    hist.reset()
    assert hist.summary()["n"] == 0


def test_latency_histogram_single_sample_and_timeouts():
    hist = LatencyHistogram()
    hist.record(0.25)
    s = hist.summary(timeout_ms=100.0)
    assert s["p50_ms"] == s["p99_ms"] == s["max_ms"] == 250.0
    assert s["timeouts"] == 1
    assert hist.summary(timeout_ms=300.0)["timeouts"] == 0


def test_dispatch_counters_export_as_prometheus_counters():
    text = prometheus_text({"search_dispatches": 4.0, "search_queries": 9.0,
                            "insert_dispatches": 1.0})
    for name in ("search_dispatches", "search_queries", "insert_dispatches"):
        assert f"# TYPE repro_{name} counter" in text
