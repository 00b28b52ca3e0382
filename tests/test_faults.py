"""Fault-tolerant serving: deterministic fault-injection coverage.

The invariant under test everywhere: **no submitted future ever hangs** —
under injected step failures, worker-loop crashes, deadline expiry, queue
overflow, and shutdown mid-traffic, every future resolves (result or typed
exception) within a bounded wait, in every mode, and a poisoned batch
fails only the poisoned item's future.  All failure paths are driven
through ``repro.core.faults.FaultPlan`` (no timing-dependent luck).

Determinism notes: ``delay("search_loop"/"insert_loop", t, nth=0)`` puts
the worker to sleep on its *first* iteration (the fault site sits before
any dequeue), so requests submitted right after construction are
guaranteed to be queued together when the worker wakes — which makes the
batch composition, and therefore the ``search_step``/``mutation_step``
call indices, deterministic.
"""

import threading
import time
from concurrent.futures import Future

import jax
import numpy as np
import pytest

from repro.core import build_ivf
from repro.core.admission import (
    AdmissionGate,
    DeadlineExceeded,
    DegradationLadder,
    QueueFull,
    RequestRejected,
    RuntimeShutdown,
)
from repro.core.block_pool import snapshot_ids
from repro.core.faults import FaultError, FaultPlan
from repro.core.metrics import CounterSet
from repro.core.runtime import RuntimeConfig, ServingRuntime, _Timed

pytestmark = pytest.mark.robust

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


def _resolved(fut: Future, timeout=30.0):
    """The no-hung-future assertion: resolves (result or exception) within
    a bounded wait."""
    return fut.exception(timeout=timeout)  # raises TimeoutError on a hang


# ------------------------------------------------------ poison isolation --
def test_mutation_batch_poison_fails_only_poisoned_item(base_index):
    """Call 0 = the 3-item batch, calls 1..3 = the per-item retries; fail
    the batch and the middle retry -> only item 1's future fails."""
    x, make = base_index
    plan = (FaultPlan()
            .delay("insert_loop", 0.3, nth=0)  # batch the 3 submits
            .fail("mutation_step", nth=[0, 2]))
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", nprobe=4, k=5, flush_min=64,
                      flush_interval=0.05),
        faults=plan,
    )
    try:
        futs = [rt.submit_insert(_data(4, seed=10 + i)) for i in range(3)]
        assert _resolved(futs[0]) is None and len(futs[0].result()) == 4
        assert isinstance(_resolved(futs[1]), FaultError)
        assert _resolved(futs[2]) is None and len(futs[2].result()) == 4
        s = rt.stats()
        assert s["poisoned"] == 1
        assert s["isolations"] == 1
        assert s["pending_mutations"] == 0  # admission rows all returned
    finally:
        rt.stop()


def test_search_batch_poison_fails_only_poisoned_item(base_index):
    x, make = base_index
    plan = (FaultPlan()
            .delay("search_loop", 0.3, nth=0)
            .fail("search_step", nth=[0, 2]))
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", nprobe=4, k=5, n_slots=8),
        faults=plan,
    )
    try:
        futs = [rt.submit_search(x[i : i + 1]) for i in range(3)]
        assert _resolved(futs[0]) is None
        assert futs[0].result()[1][0, 0] == 0
        assert isinstance(_resolved(futs[1]), FaultError)
        assert _resolved(futs[2]) is None
        assert futs[2].result()[1][0, 0] == 2
        s = rt.stats()
        assert s["poisoned"] == 1 and s["isolations"] == 1
        # all slots back: a full valid burst succeeds
        good = [rt.submit_search(x[i : i + 1]) for i in range(8)]
        for i, f in enumerate(good):
            assert f.result(timeout=30)[1][0, 0] == i
    finally:
        rt.stop()


def test_search_fetch_failure_after_launch_resolves_and_isolates(
        base_index, monkeypatch):
    """The fetch of the packed answers fails after the launch returned
    (the way a device error surfaces): fetch 0 is the 3-item batch,
    fetches 1..3 the per-item retries.  Failing the batch and the middle
    retry fails only item 1's future, and every slot comes back."""
    x, make = base_index
    fetch = ServingRuntime._fetch_answers
    calls = []

    def failing_fetch(self, packed):
        assert isinstance(packed, jax.Array)  # the launch has returned
        calls.append(len(calls))
        if calls[-1] in (0, 2):
            raise FaultError("injected failure @ fetch")
        return fetch(self, packed)

    monkeypatch.setattr(ServingRuntime, "_fetch_answers", failing_fetch)
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", nprobe=4, k=5, n_slots=8),
        faults=FaultPlan().delay("search_loop", 0.3, nth=0),
    )
    try:
        futs = [rt.submit_search(x[i : i + 1]) for i in range(3)]
        assert _resolved(futs[0]) is None
        assert futs[0].result()[1][0, 0] == 0
        assert isinstance(_resolved(futs[1]), FaultError)
        assert _resolved(futs[2]) is None
        assert futs[2].result()[1][0, 0] == 2
        s = rt.stats()
        assert s["poisoned"] == 1 and s["isolations"] == 1
        assert len(calls) == 4
        # all slots back: a full valid burst succeeds
        good = [rt.submit_search(x[i : i + 1]) for i in range(8)]
        for i, f in enumerate(good):
            assert f.result(timeout=30)[1][0, 0] == i
    finally:
        rt.stop()


@pytest.mark.parametrize("with_delete", [False, True])
def test_fused_step_failure_decomposes_and_isolates(base_index, with_delete):
    """A failed fused search+mutation program falls back to the two
    separate lanes; both sides resolve, nothing hangs.  With a delete in
    the same drained batch, the delete is a second run after the fused
    one: the fallback must apply it too (it once returned before the
    rest of the batch, stranding its futures)."""
    x, make = base_index
    plan = (FaultPlan()
            .delay("insert_loop", 0.25, nth=0)  # both mutations queue up
            .delay("search_loop", 0.35, nth=0)  # wake after insert handoff
            .fail("fused_step", nth=0))
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="fused", nprobe=4, k=5, flush_min=8,
                      flush_interval=0.02),
        faults=plan,
    )
    try:
        sf = rt.submit_search(x[:1])
        mf = rt.submit_insert(_data(4, seed=20))
        df = rt.submit_delete(np.array([5, 6], np.int32)) \
            if with_delete else None
        assert _resolved(sf) is None and sf.result()[1][0, 0] == 0
        assert _resolved(mf) is None and len(mf.result()) == 4
        if with_delete:
            assert _resolved(df) is None and list(df.result()) == [5, 6]
        assert rt.stats()["fused_fallbacks"] >= 1
    finally:
        rt.stop()


# ------------------------------------------------------ crash-safe workers --
@pytest.mark.parametrize("lane", ["search_loop", "insert_loop"])
def test_worker_crash_restarts_and_keeps_serving(base_index, lane):
    x, make = base_index
    plan = FaultPlan().fail(lane, nth=2)
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", nprobe=4, k=5, flush_min=1,
                      flush_interval=0.02, restart_backoff=0.01),
        faults=plan,
    )
    try:
        deadline = time.perf_counter() + 30
        while plan.calls(lane) < 4:  # crash happened and loop came back
            assert time.perf_counter() < deadline, "lane never restarted"
            time.sleep(0.01)
        assert rt.submit_search(x[:1]).result(timeout=30)[1][0, 0] == 0
        assert len(rt.submit_insert(_data(3, seed=30)).result(timeout=30)) \
            == 3
        assert rt.stats()["worker_restarts"] >= 1
    finally:
        rt.stop()


def test_restart_budget_exhausted_fails_queue_and_admission(base_index):
    """A permanently-crashing lane must terminate loudly: queued futures
    resolve with RuntimeShutdown, later submits raise — never a silent
    wedge."""
    x, make = base_index
    plan = FaultPlan().fail("insert_loop", nth=None)  # every iteration
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", nprobe=4, k=5, flush_min=1,
                      flush_interval=0.02, max_worker_restarts=2,
                      restart_backoff=0.005),
        faults=plan,
    )
    try:
        fut = rt.submit_insert(_data(2, seed=40))
        exc = _resolved(fut, timeout=30)
        assert isinstance(exc, (RuntimeShutdown, FaultError)), exc
        deadline = time.perf_counter() + 30
        while rt.stats()["accepting"]:
            assert time.perf_counter() < deadline
            time.sleep(0.01)
        with pytest.raises(RuntimeShutdown, match="insert_loop"):
            rt.submit_insert(_data(2, seed=41))
        assert rt.stats()["worker_restarts"] == 3  # 2 restarts + final crash
    finally:
        rt.stop()


# --------------------------------------------------- deadlines & shedding --
def test_expired_search_shed_with_deadline_exceeded(base_index):
    x, make = base_index
    n_slots = 4
    plan = FaultPlan().delay("search_loop", 0.3, nth=0)
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", nprobe=4, k=5, n_slots=n_slots),
        faults=plan,
    )
    try:
        doomed = rt.submit_search(x[:1], deadline=0.05)
        fine = rt.submit_search(x[1:2])  # no deadline: dispatched late, fine
        assert isinstance(_resolved(doomed), DeadlineExceeded)
        assert _resolved(fine) is None and fine.result()[1][0, 0] == 1
        assert rt.stats()["shed_search"] == 1
        # the shed request's slot came back
        burst = [rt.submit_search(x[i : i + 1]) for i in range(n_slots)]
        for i, f in enumerate(burst):
            assert f.result(timeout=30)[1][0, 0] == i
    finally:
        rt.stop()


@pytest.mark.parametrize("mode", ["serial", "parallel", "fused"])
def test_expired_mutation_shed_and_gate_released(base_index, mode):
    x, make = base_index
    lane = "search_loop" if mode == "serial" else "insert_loop"
    plan = FaultPlan().delay(lane, 0.3, nth=0)
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode=mode, nprobe=4, k=5, flush_min=1,
                      flush_interval=0.02, max_pending_mutations=64),
        faults=plan,
    )
    try:
        doomed = rt.submit_insert(_data(4, seed=50), deadline=0.05)
        assert isinstance(_resolved(doomed), DeadlineExceeded)
        deadline = time.perf_counter() + 30
        while rt.stats()["pending_mutations"] != 0:  # admission rows back
            assert time.perf_counter() < deadline
            time.sleep(0.01)
        assert rt.stats()["shed_mutation"] == 1
        ok = rt.submit_insert(_data(4, seed=51))
        assert len(ok.result(timeout=30)) == 4
    finally:
        rt.stop()


def test_default_deadline_config_applies(base_index):
    x, make = base_index
    plan = FaultPlan().delay("search_loop", 0.3, nth=0)
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", nprobe=4, k=5,
                      default_deadline=0.05),
        faults=plan,
    )
    try:
        doomed = rt.submit_search(x[:1])  # inherits the config deadline
        assert isinstance(_resolved(doomed), DeadlineExceeded)
    finally:
        rt.stop()


# ------------------------------------------------------- admission control --
def test_mutation_queue_overflow_rejects(base_index):
    x, make = base_index
    plan = FaultPlan().delay("insert_loop", 0.5, nth=None)  # slow lane
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", nprobe=4, k=5, flush_min=1,
                      flush_interval=0.02, max_pending_mutations=8,
                      admission="reject"),
        faults=plan,
    )
    try:
        f1 = rt.submit_insert(_data(4, seed=60))
        f2 = rt.submit_insert(_data(4, seed=61))
        with pytest.raises(QueueFull):
            rt.submit_insert(_data(1, seed=62))
        s = rt.stats()
        assert s["rejected_mutation"] == 1
        assert s["pending_mutations"] == 8
        for f in (f1, f2):  # admitted work still completes
            assert len(f.result(timeout=30)) == 4
    finally:
        rt.stop()


def test_mutation_queue_overflow_block_policy(base_index):
    """``block`` admission waits (bounded) for capacity: the blocked submit
    succeeds once the lane drains, or raises QueueFull at the timeout."""
    x, make = base_index
    plan = FaultPlan().delay("insert_loop", 0.2, nth=0)
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", nprobe=4, k=5, flush_min=1,
                      flush_interval=0.02, max_pending_mutations=8,
                      admission="block", admission_timeout=10.0),
        faults=plan,
    )
    try:
        rt.submit_insert(_data(8, seed=63))  # fills the budget
        t0 = time.perf_counter()
        fut = rt.submit_insert(_data(4, seed=64))  # blocks until drain
        assert time.perf_counter() - t0 > 0.05  # actually waited
        assert len(fut.result(timeout=30)) == 4
    finally:
        rt.stop()

    # timeout flavour: capacity never frees -> QueueFull after the wait
    plan = FaultPlan().delay("insert_loop", 5.0, nth=None)
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", nprobe=4, k=5, flush_min=1,
                      max_pending_mutations=8, admission="block",
                      admission_timeout=0.1),
        faults=plan,
    )
    try:
        rt.submit_insert(_data(8, seed=65))
        t0 = time.perf_counter()
        with pytest.raises(QueueFull):
            rt.submit_insert(_data(4, seed=66))
        assert time.perf_counter() - t0 >= 0.09
        assert rt.stats()["rejected_mutation"] == 1
    finally:
        rt.stop(drain=False)


def test_oversized_item_admitted_alone():
    """A single request larger than the whole budget is admitted when the
    gate is empty (never-split-an-item) instead of deadlocking."""
    gate = AdmissionGate(8, "reject")
    gate.acquire(20)  # oversized, gate empty: admitted
    with pytest.raises(QueueFull):
        gate.acquire(1)
    gate.release(20)
    gate.acquire(8)
    with pytest.raises(QueueFull):
        gate.acquire(20)  # oversized but gate non-empty
    gate.release(8)
    assert gate.pending() == 0


# ------------------------------------------------------ graceful shutdown --
@pytest.mark.parametrize("mode", ["serial", "parallel", "fused"])
def test_stop_drains_queued_mutations_and_fails_searches(base_index, mode):
    """Regression: stop() used to abandon queued items (serial-mode
    pending, fused hand-offs, anything in the queues) — their futures hung
    forever.  Now queued mutations are flushed and queued searches fail
    with RuntimeShutdown, in every mode."""
    x, make = base_index
    lane = "search_loop" if mode == "serial" else "insert_loop"
    plan = (FaultPlan()
            .delay(lane, 0.4, nth=0)
            .delay("search_loop", 0.4, nth=0))
    idx = make()
    before = idx.ntotal
    rt = ServingRuntime(
        idx,
        RuntimeConfig(mode=mode, nprobe=4, k=5, flush_min=1,
                      flush_interval=0.02),
        faults=plan,
    )
    try:
        m1 = rt.submit_insert(_data(4, seed=70))
        m2 = rt.submit_delete(np.arange(3, dtype=np.int32))
        s1 = rt.submit_search(x[:1])
    finally:
        rt.stop()
    assert _resolved(m1) is None and len(m1.result()) == 4  # flushed
    assert _resolved(m2) is None and len(m2.result()) == 3
    # the search either dispatched before stop (result) or was failed with
    # RuntimeShutdown — but it must have resolved either way
    s_exc = _resolved(s1)
    assert s_exc is None or isinstance(s_exc, RuntimeShutdown)
    assert rt.index.ntotal == before + 4 - 3
    with pytest.raises(RuntimeShutdown):
        rt.submit_search(x[:1])
    with pytest.raises(RuntimeShutdown):
        rt.submit_insert(_data(2, seed=71))


def test_stop_serial_mode_flushes_instance_pending(base_index):
    """Serial-mode items pulled into the pending list (but below
    flush_min) used to be loop-locals lost at stop; they now flush."""
    x, make = base_index
    idx = make()
    before = idx.ntotal
    rt = ServingRuntime(
        idx,
        RuntimeConfig(mode="serial", nprobe=4, k=5, flush_min=10_000,
                      flush_interval=60.0),
    )
    try:
        fut = rt.submit_insert(_data(4, seed=72))
        deadline = time.perf_counter() + 30
        while not rt._serial_pending:  # pulled off the queue, not flushed
            assert time.perf_counter() < deadline
            time.sleep(0.005)
    finally:
        rt.stop()
    assert _resolved(fut) is None and len(fut.result()) == 4
    assert rt.index.ntotal == before + 4


def test_stop_without_drain_fails_mutations(base_index):
    x, make = base_index
    plan = FaultPlan().delay("insert_loop", 0.4, nth=0)
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", nprobe=4, k=5, flush_min=1,
                      flush_interval=0.02),
        faults=plan,
    )
    fut = rt.submit_insert(_data(4, seed=73))
    rt.stop(drain=False)
    assert isinstance(_resolved(fut), RuntimeShutdown)
    assert rt.stats()["pending_mutations"] == 0  # gate rows returned


# ------------------------------------------------- fused / ordering corners --
def test_fused_standalone_mutation_path(base_index):
    """Fused mode with NO paired search: the hand-off batch drains through
    the standalone-mutation path and resolves (previously untested)."""
    x, make = base_index
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="fused", nprobe=4, k=5, flush_min=1,
                      flush_interval=0.02),
    )
    try:
        ins = rt.submit_insert(_data(4, seed=80))
        ids = ins.result(timeout=30)
        assert len(ids) == 4
        dele = rt.submit_delete(ids[:2])
        assert len(dele.result(timeout=30)) == 2
        assert rt.stats()["deletes"] == 2
    finally:
        rt.stop()


def test_split_flush_kind_switch_ordering():
    """Unit: a kind switch ends the batch (same-kind runs dispatch as one
    step, arrival order across kinds preserved), flush_max bounds rows,
    and the remainder is never dropped."""
    rt = ServingRuntime.__new__(ServingRuntime)  # no threads needed
    rt.cfg = RuntimeConfig(flush_max=8)

    def item(kind, rows, tag):
        payload = {
            "insert": np.zeros((rows, 4), np.float32),
            "delete": np.zeros((rows,), np.int32),
            "update": (np.zeros((rows, 4), np.float32),
                       np.zeros((rows,), np.int32)),
        }[kind]
        t = _Timed(Future(), 0.0, payload, kind=kind)
        t.tag = tag
        return t

    items = [item("insert", 3, 0), item("insert", 3, 1), item("delete", 2, 2),
             item("delete", 1, 3), item("insert", 2, 4), item("update", 1, 5)]
    runs = []
    while items:
        take, items = rt._split_flush(items)
        runs.append((take[0].kind, [t.tag for t in take]))
    assert runs == [
        ("insert", [0, 1]),   # same-kind run batched together
        ("delete", [2, 3]),   # kind switch ended the previous batch
        ("insert", [4]),      # arrival order across kinds preserved
        ("update", [5]),
    ]
    # flush_max: whole-item prefix within the cap, remainder kept
    items = [item("insert", 6, 0), item("insert", 6, 1), item("insert", 6, 2)]
    take, rest = rt._split_flush(items)
    assert [t.tag for t in take] == [0] and [t.tag for t in rest] == [1, 2]


def test_mixed_kind_arrival_order_never_reorders(base_index):
    """update-then-delete of one id, batched into a single drain, must
    leave the id dead (reversing the runs would resurrect it)."""
    x, make = base_index
    plan = FaultPlan().delay("insert_loop", 0.3, nth=0)  # batch both
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", nprobe=4, k=5, flush_min=64,
                      flush_interval=0.05),
        faults=plan,
    )
    try:
        victim = np.asarray([7], np.int32)
        u = rt.submit_update(_data(1, seed=90) * 0.5, victim)
        d = rt.submit_delete(victim)
        assert _resolved(u) is None and _resolved(d) is None
        live = {i for ids in
                snapshot_ids(rt.index.state, rt.pool_cfg).values()
                for i in ids}
        assert 7 not in live
    finally:
        rt.stop()


# ------------------------------------------------------ degradation ladder --
def test_ladder_unit_hysteresis_and_params():
    lad = DegradationLadder(("no_rerank", "half_nprobe", "half_budget"),
                            high_s=0.1, low_s=0.02, patience=2)
    assert lad.level == 0 and lad.rung == "full"
    lad.observe(0.5)
    assert lad.level == 0  # patience not yet reached
    lad.observe(0.5)
    assert lad.level == 1 and lad.rung == "no_rerank"
    for _ in range(4):
        lad.observe(0.5)
    assert lad.level == 3  # bottom rung, clamped
    lad.observe(0.5)
    assert lad.level == 3
    # cumulative params at the bottom: no rerank, nprobe/2, budget/2
    assert lad.apply(16, True, 32) == (8, False, 16)
    assert lad.apply(16, True, 32, level=1) == (16, False, 32)
    # recovery needs `patience` consecutive cool observations
    lad.observe(0.01)
    lad.observe(0.5)  # pressure back: resets the cool streak
    assert lad.level == 3
    for _ in range(2 * 2):  # patience * two step-ups
        lad.observe(0.01)
    assert lad.level == 1
    lad.observe(0.05)  # inside the hysteresis band: no movement
    assert lad.level == 1
    assert lad.transitions == 5
    with pytest.raises(ValueError, match="unknown degradation rungs"):
        DegradationLadder(("half_recall",))


def test_ladder_e2e_steps_down_and_recovers(base_index):
    """Queue-age pressure steps the runtime down the ladder; clearing it
    steps back up.  Degraded dispatches reuse cached jit steps — at most
    one compile per (bucket, rung), never one per request."""
    x, make = base_index
    plan = FaultPlan().delay("search_step", 0.08, nth=range(8))  # slow svc
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode="parallel", nprobe=4, k=5, n_slots=32,
                      max_search_batch=1,
                      degradation_ladder=("no_rerank", "half_nprobe"),
                      overload_high=0.05, overload_low=0.01,
                      overload_patience=2),
        faults=plan,
    )
    try:
        futs = [rt.submit_search(x[i : i + 1]) for i in range(10)]
        for f in futs:
            assert _resolved(f) is None  # degraded, never failed
        s = rt.stats()
        assert s["degradation_level"] >= 1, s["degradation_rung"]
        assert s["degradation_transitions"] >= 1
        # pressure cleared: a slow trickle steps back up to full service
        deadline = time.perf_counter() + 30
        while rt.stats()["degradation_level"] > 0:
            assert time.perf_counter() < deadline, "never recovered"
            rt.submit_search(x[:1]).result(timeout=30)
        assert rt.stats()["degradation_rung"] == "full"
        # bounded compile count: base rung + at most one per ladder rung
        assert len(rt._search_steps) <= 3
    finally:
        rt.stop()


# ---------------------------------------------------------- counters etc. --
def test_counter_set_is_thread_safe():
    c = CounterSet()

    def bump():
        for _ in range(10_000):
            c.inc("x")

    ts = [threading.Thread(target=bump) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c["x"] == 80_000
    assert c.snapshot() == {"x": 80_000}


def test_fault_plan_counts_and_resets():
    # sites are registered now; test-private ones use the escape hatch
    plan = FaultPlan(extra_sites=("s",)).fail("s", nth=1).delay("s", 0.0, nth=0)
    plan.check("s")  # call 0: delay only
    with pytest.raises(FaultError):
        plan.check("s")  # call 1: fail
    plan.check("s")  # call 2: nothing
    assert plan.calls("s") == 3
    plan.reset()
    assert plan.calls("s") == 0
    plan.check("s")  # no rules left


# ------------------------------------------------ the headline invariant --
@pytest.mark.parametrize("mode", ["serial", "parallel", "fused"])
def test_no_hung_future_under_combined_faults(base_index, mode):
    """The acceptance bar: step failures + a worker crash + deadline expiry
    + queue overflow + shutdown mid-traffic, all at once, in every mode —
    every accepted future resolves (result or typed exception) within a
    bounded wait."""
    x, make = base_index
    plan = (FaultPlan()
            .fail("search_step", nth=[1, 4])
            .fail("mutation_step", nth=[1, 3])
            .fail("fused_step", nth=0)
            .fail("insert_loop" if mode != "serial" else "search_loop",
                  nth=3))
    rt = ServingRuntime(
        make(),
        RuntimeConfig(mode=mode, nprobe=4, k=5, flush_min=4,
                      flush_interval=0.02, n_slots=8,
                      max_pending_mutations=64, restart_backoff=0.01,
                      degradation_ladder=("no_rerank",)),
        faults=plan,
    )
    futures: list[Future] = []
    rejected = 0
    try:
        rng = np.random.default_rng(3)
        for i in range(40):
            kind = i % 4
            try:
                if kind == 0:
                    futures.append(rt.submit_search(
                        x[i % len(x) : i % len(x) + 1],
                        deadline=0.001 if i % 8 == 0 else None,
                    ))
                elif kind == 1:
                    futures.append(rt.submit_insert(_data(3, seed=100 + i)))
                elif kind == 2:
                    futures.append(rt.submit_delete(
                        rng.integers(0, 1000, 2).astype(np.int32)
                    ))
                else:
                    ids = rng.integers(0, 1000, 2).astype(np.int32)
                    futures.append(rt.submit_update(_data(2, seed=i), ids))
            except (RequestRejected, RuntimeShutdown):
                rejected += 1
            if i == 25:
                time.sleep(0.05)
    finally:
        rt.stop()  # mid-traffic shutdown: drains mutations, fails searches
    hung = []
    for i, f in enumerate(futures):
        try:
            exc = f.exception(timeout=30)
        except TimeoutError:
            hung.append(i)
            continue
        if exc is not None:
            assert isinstance(
                exc,
                (FaultError, DeadlineExceeded, RuntimeShutdown, QueueFull),
            ), (i, exc)
    assert not hung, f"futures {hung} never resolved"
    assert len(futures) + rejected == 40
