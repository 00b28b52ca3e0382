"""A new cell, traffic file or metric reader is found by its name alone."""

import gc
import json
import shutil
import time
from pathlib import Path

from bench import spec

REPO = Path(__file__).resolve().parents[2]


def test_benchmark_json_names_files_that_exist():
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    for c in b["configs"]:
        assert (REPO / c["file"]).is_file()
    for w in b["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["search_qps"] > 0
    for m in b["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


def test_new_cell_traffic_and_metric_found_by_name(tmp_path):
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    (tmp_path / "bench" / "traffic" / "new_mix.json").write_text(
        json.dumps({"search_qps": 7, "mutation_rps": 1,
                    "mix": {"insert": 1.0}, "check_searches": 1,
                    "warm_rows": {"insert": [8]}, "warm_search_rows": [1]}))
    (tmp_path / "bench" / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    b["workloads"].append({"name": "sift1m_flat.new", "config": "sift1m_flat",
                           "traffic": "new_mix", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "new_metric", "unit": "x",
                           "better": "lower", "source": "host_clock",
                           "layer": "runtime", "moves": "search_p99_ms",
                           "workloads": ["sift1m_flat.new"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.load_cell("sift1m_flat.new", root=tmp_path)
    assert cell.traffic["search_qps"] == 7
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    assert spec.metric_reader("new_metric", root=tmp_path)(None) == 42.0
    other = spec.load_cell("sift1m_flat.steady", root=tmp_path)
    assert "new_metric" not in [m["name"] for m in other.per_layer]


def test_unknown_device_has_no_peaks():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    try:
        spec.peaks("some other chip")
    except KeyError:
        return
    raise AssertionError("an unknown device must be an error")


def test_gc_pauses_record_a_collection():
    from bench.run import GcPauses

    pauses = GcPauses()
    try:
        t0 = time.perf_counter()
        gc.collect()
        t1 = time.perf_counter()
    finally:
        pauses.stop()
    spans = pauses.between(t0, t1)
    assert spans and all(t0 <= a <= b <= t1 for a, b in spans)
