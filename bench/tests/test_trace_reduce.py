"""The trace-to-metrics reduction on a small trace recorded on a TPU v5e
(``record_trace_fixture.py``): 40 searches and 2 insert batches of a tiny
index, profiled under the benchmark's ``bench.traced`` span."""

from pathlib import Path

import pytest

from bench.trace_reduce import reduce_trace

FIXTURE = Path(__file__).parent / "fixtures" / "small.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return reduce_trace(str(FIXTURE))


def test_window_and_busy(red):
    assert 0 < red.busy_s < red.window_s < 10


def test_programs_found_by_jit_name(red):
    assert len(red.launches(r"^jit__search$")) == 40
    assert len(red.launches(r"^jit__insert$")) == 2
    assert all(s > 0 for s in red.launches(r"^jit__search$"))


def test_ops_and_gaps(red):
    assert red.ops and all(t > 0 for t in red.ops.values())
    assert sum(red.ops.values()) >= red.busy_s * 0.999
    assert red.gaps and all(isinstance(n, str) and t > 0
                            for n, t in red.gaps)
    assert red.gaps == sorted(red.gaps, key=lambda g: -g[1])
