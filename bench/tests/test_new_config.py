"""A configuration added later needs only new files: a copy of the
benchmark given the PQ fixture as a configuration file of its own, a
traffic file and a workload entry runs that cell correctly, with no file
of the harness edited."""

import json
import shutil
import time

from bench.run import run_cell
from bench.spec import BENCH, ROOT, load_cell

FIXTURES = BENCH / "tests" / "fixtures"


def test_added_config_runs_with_no_edit(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(FIXTURES / "pq_tiny.json", tmp_path / "bench" / "configs")
    shutil.copy(FIXTURES / "pq_tiny_steady.json",
                tmp_path / "bench" / "traffic")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append({
        "name": "pq_tiny.steady", "config": "pq_tiny",
        "traffic": "pq_tiny_steady", "chips": 1,
        "why": "the PQ fixture as a cell"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = load_cell("pq_tiny.steady", root=tmp_path)
    out = run_cell(cell, 2**31 + 4242, 2.0, False, time.perf_counter(),
                   scale=cell.config["tiny_scale"])
    assert out["correct"], out["checks"]
    assert "code_mismatch_share" in out["checks"]
