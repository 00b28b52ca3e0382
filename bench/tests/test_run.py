"""Whole runs of the harness on the CPU at a tiny size, past its look for a
chip: a sound run is correct; the control, and each fault a cell can have
planted in the timed path, is not."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

from bench.calibrate import readings, short_kmeans
from bench.run import collect, run_cell, setup, window
from bench.spec import BENCH, load_cell, load_json
from repro.core.runtime import ServingRuntime
from repro.launch import serve

TINY = {"sift1m_flat": 0.01}
CELLS = ["sift1m_flat.steady", "sift1m_flat.churn"]


def tiny_cell(workload: str):
    """A cell at a tiny load.  ``sift1m_flat.churn`` is the SIFT
    configuration under the churn traffic file, which no cell of
    ``BENCHMARK.json`` runs yet (see PERF.md): it drives deletes and
    updates."""
    if workload == "sift1m_flat.churn":
        cell = dataclasses.replace(
            load_cell("sift1m_flat.steady"), name=workload,
            traffic=load_json(BENCH / "traffic" / "sift1m_churn.json"))
    else:
        cell = load_cell(workload)
    cell.traffic.update(search_qps=10, mutation_rps=20, check_searches=24,
                        warm_rows={k: [16, 8] for k in cell.traffic["warm_rows"]})
    return cell


def tiny_run(workload: str, control: bool = False) -> dict:
    cell = tiny_cell(workload)
    return run_cell(cell, 2**31 + 12345, 2.0, False, time.perf_counter(),
                    control=control, scale=TINY[cell.config["name"]])


def _wrap_search(monkeypatch, change):
    make = ServingRuntime._make_search

    def patched(self, budget, nprobe, rerank):
        step = make(self, budget, nprobe, rerank)
        return lambda state, queries, valid: change(step, state, queries,
                                                    valid)

    monkeypatch.setattr(ServingRuntime, "_make_search", patched)


def _unchanged_state(monkeypatch):
    build = ServingRuntime._build_steps

    def patched(self):
        build(self)
        self._insert_step = jax.jit(lambda state, *args: state)

    monkeypatch.setattr(ServingRuntime, "_build_steps", patched)


def _half_batch(monkeypatch):
    def drop_odd(step, state, queries, valid):
        return step(state, queries,
                    valid & (jnp.arange(valid.shape[0]) % 2 == 0))

    _wrap_search(monkeypatch, drop_odd)


def _altered_answer(monkeypatch):
    def shift(step, state, queries, valid):
        d, i = step(state, queries, valid)
        return d, i.at[:, 0].add(1)

    _wrap_search(monkeypatch, shift)


def _one_lloyd_step(monkeypatch):
    name = "ivfflat_sift1m"
    monkeypatch.setitem(serve.INDEXES, name,
                        short_kmeans(serve.INDEXES[name], 1))


FAULTS = {"unchanged_state": _unchanged_state, "half_batch": _half_batch,
          "altered_answer": _altered_answer, "one_lloyd_step": _one_lloyd_step}


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    out = tiny_run(workload)
    assert out["correct"], out["checks"]
    assert out["device"]["platform"] == "cpu"
    assert set(out["metrics"]) >= {"setup_s", "search_p50_ms"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    out = tiny_run(workload, control=True)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_timed_path_is_not_correct(fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    out = tiny_run("sift1m_flat.churn")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_calibration_separates_sound_from_control_and_faults(workload):
    """The readings the limits are set from: the program's pass every
    limit; the control's and each planted fault's fail one at least."""
    cell = tiny_cell(workload)
    s = setup(cell, 7, scale=TINY[cell.config["name"]])
    try:
        d, sched = window(s, 8, 2.0)
        got = readings(cell, collect(s, d, sched, 8))
    finally:
        s.rt.stop()
    limits = cell.config["limits"]
    assert all(got["program"][n] <= limits[n] for n in limits), got
    for way in ("control", "altered", "half"):
        assert any(got[way][n] > limits[n] for n in limits), (way, got[way])
