"""Whole runs of the harness on the CPU at a tiny size, past its look for a
chip: a sound run is correct; the control, and each fault a cell can have
planted in the timed path, is not.

The runs cover every configuration file under ``bench/configs`` (each
cell of ``BENCHMARK.json`` that runs it) and the PQ fixture, each at its
own ``tiny_scale``; and for each configuration a churn variant of its
first cell, whose traffic also deletes and updates.  A configuration
added later is covered with no edit here.
"""

import dataclasses
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench.calibrate import FAULTS as EVIDENCE_FAULTS
from bench.calibrate import readings, short_codebooks, short_kmeans
from bench.run import collect, run_cell, setup, window
from bench.spec import BENCH, ROOT, Cell, load_cell, load_json
from bench.traffic import KINDS
from repro.core import pq, runtime
from repro.core.runtime import ServingRuntime
from repro.launch import serve

FIXTURES = Path(__file__).resolve().parent / "fixtures"
#: a PQ configuration and its traffic, run by no cell of BENCHMARK.json
PQ_FIXTURE = ("pq_tiny.steady", FIXTURES / "pq_tiny.json",
              FIXTURES / "pq_tiny_steady.json")
#: the mutation mix of each configuration's churn variant
CHURN_MIX = load_json(BENCH / "traffic" / "sift1m_churn.json")["mix"]
SEED = 2**31 + 12345


def fixture_cell(name: str, config: Path, traffic: Path) -> Cell:
    """A cell read from a configuration and a traffic file, reporting the
    end-to-end metrics that every cell reports."""
    spec = load_json(ROOT / "BENCHMARK.json")
    return Cell(name, 1, load_json(config), load_json(traffic),
                [m for m in spec["end_to_end"] if "workloads" not in m],
                [m for m in spec["per_layer"] if "workloads" not in m])


def cells() -> dict:
    """Every cell of every configuration file, the PQ fixture's, and a
    churn variant of each configuration's first cell, by name."""
    spec = load_json(ROOT / "BENCHMARK.json")
    out, first = {}, {}
    for path in sorted((BENCH / "configs").glob("*.json")):
        for w in spec["workloads"]:
            if w["config"] == path.stem:
                out[w["name"]] = load_cell(w["name"])
                first.setdefault(path.stem, out[w["name"]])
    fixture = out[PQ_FIXTURE[0]] = fixture_cell(*PQ_FIXTURE)
    first[fixture.config["name"]] = fixture
    for config, cell in first.items():
        traffic = dict(cell.traffic, mix=CHURN_MIX)
        out[f"{config}.churn"] = dataclasses.replace(
            cell, name=f"{config}.churn", traffic=traffic)
    return out


CELLS = cells()
CHURN = sorted(n for n in CELLS if n.endswith(".churn"))


def tiny_cell(name: str) -> Cell:
    """The cell at a tiny load, warming what its mix sends."""
    cell = CELLS[name]
    mix = cell.traffic.get("mix", {"insert": 1.0})
    traffic = dict(cell.traffic, search_qps=10, mutation_rps=20,
                   check_searches=24,
                   warm_rows={k: [16, 8] for k in KINDS if mix.get(k)})
    return dataclasses.replace(cell, traffic=traffic)


def tiny_run(cell: Cell, control: bool = False) -> dict:
    return run_cell(cell, SEED, 2.0, False, time.perf_counter(),
                    control=control, scale=cell.config["tiny_scale"])


def _wrap_search(monkeypatch, change):
    make = ServingRuntime._make_search

    def patched(self, budget, nprobe, rerank):
        step = make(self, budget, nprobe, rerank)
        return lambda state, queries, valid: change(step, state, queries,
                                                    valid)

    monkeypatch.setattr(ServingRuntime, "_make_search", patched)


def _unchanged_state(monkeypatch, cell):
    build = ServingRuntime._build_steps

    def patched(self):
        build(self)
        self._insert_step = jax.jit(lambda state, *args: state)

    monkeypatch.setattr(ServingRuntime, "_build_steps", patched)


def _half_batch(monkeypatch, cell):
    def drop_odd(step, state, queries, valid):
        return step(state, queries,
                    valid & (jnp.arange(valid.shape[0]) % 2 == 0))

    _wrap_search(monkeypatch, drop_odd)


def _altered_answer(monkeypatch, cell):
    def shift(step, state, queries, valid):
        d, i = step(state, queries, valid)
        return d, i.at[:, 0].add(1)

    _wrap_search(monkeypatch, shift)


def _one_lloyd_step(monkeypatch, cell):
    name = cell.config["index"]
    monkeypatch.setitem(serve.INDEXES, name,
                        short_kmeans(serve.INDEXES[name], 1))


def _wrong_list(monkeypatch, cell):
    """The serving insert step stores every tenth row of a batch in the
    list after its nearest."""
    nearest = runtime.assign_clusters

    def shifted(centroids, vectors):
        a = nearest(centroids, vectors)
        return a.at[::10].set((a[::10] + 1) % centroids.shape[0])

    monkeypatch.setattr(runtime, "assign_clusters", shifted)


def _one_codebook_step(monkeypatch, cell):
    """The build's PQ codebooks stop after one Lloyd step."""
    monkeypatch.setattr(pq, "train_pq", short_codebooks(pq.train_pq, 1))


FAULTS = {"unchanged_state": _unchanged_state, "half_batch": _half_batch,
          "altered_answer": _altered_answer, "one_lloyd_step": _one_lloyd_step,
          "wrong_list": _wrong_list, "one_codebook_step": _one_codebook_step}
#: faults that only a payload can have, by payload
PAYLOAD_FAULTS = {"one_codebook_step": "pq"}
#: (fault, workload) pairs: every fault in each churn variant that can
#: have it
FAULT_CASES = [(f, w) for f in sorted(FAULTS) for w in CHURN
               if PAYLOAD_FAULTS.get(f, CELLS[w].config["payload"])
               == CELLS[w].config["payload"]]


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_sound_run_is_correct(workload):
    out = tiny_run(tiny_cell(workload))
    assert out["correct"], out["checks"]
    assert out["device"]["platform"] == "cpu"
    assert set(out["metrics"]) >= {"setup_s", "search_p50_ms"}
    assert list(out)[-1] == "checks"
    assert out["checks"]["ref_assign_mismatch"]["value"] == 0


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_control_is_not_correct(workload):
    out = tiny_run(tiny_cell(workload), control=True)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault, workload", FAULT_CASES,
                         ids=[f"{f}-{w}" for f, w in FAULT_CASES])
def test_fault_in_timed_path_is_not_correct(fault, workload, monkeypatch):
    cell = tiny_cell(workload)
    FAULTS[fault](monkeypatch, cell)
    out = tiny_run(cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("workload", sorted(CELLS))
def test_calibration_separates_sound_from_control_and_faults(workload):
    """The readings the limits are set from: the program's pass every
    limit; the control's and each planted fault's fail one at least."""
    cell = tiny_cell(workload)
    s = setup(cell, 7, scale=cell.config["tiny_scale"])
    try:
        d, sched = window(s, 8, 2.0)
        got = readings(cell, collect(s, d, sched, 8))
    finally:
        s.rt.stop()
    limits = cell.config["limits"]
    assert all(got["program"][n] <= limits[n] for n in limits), got
    for way in ("control", *EVIDENCE_FAULTS):
        assert any(got[way][n] > limits[n] for n in limits), (way, got[way])
