"""The span readers on a small trace recorded on a TPU v5e
(``record_span_fixture.py``): 40 searches in waves of 4 and two insert
batches of a tiny index, profiled under the benchmark's ``bench.traced``
span, with the runtime's dispatch spans on the trace's clock."""

import dataclasses
import math
import shutil
from pathlib import Path

import pytest

from bench import spans
from bench.spec import metric_reader
from repro.obs.trace import (
    SPAN_GC,
    SPAN_MUTATION_EXECUTE,
    SPAN_SEARCH_ACK,
    SPAN_SEARCH_DEVICE,
    SPAN_SEARCH_EXECUTE,
)

FIXTURE = Path(__file__).parent / "fixtures" / "spans.xplane.pb"
READERS = ["search_dispatch_ms", "search_return_ms",
           "search_queries_per_dispatch", "search_stall_share"]


@pytest.fixture(scope="module")
def sp():
    return spans.read_spans(str(FIXTURE))


def test_catalog_spans_and_search_launches(sp):
    for name in (SPAN_SEARCH_EXECUTE, SPAN_SEARCH_DEVICE, SPAN_SEARCH_ACK,
                 SPAN_MUTATION_EXECUTE, SPAN_GC):
        assert sp.host.get(name), name
    execute = sp.dispatches(SPAN_SEARCH_EXECUTE)
    assert len(execute) >= 10  # 40 queries in batches of at most 4
    assert sp.search_launches and sp.device_busy
    assert len(spans.search_launch_pairs(sp)) == len(execute)


def test_execute_spans_anchor_one_clock(sp):
    offsets = [a - st["t_ns"]
               for a, _, st in sp.host[SPAN_SEARCH_EXECUTE]
               + sp.host[SPAN_MUTATION_EXECUTE]]
    assert max(offsets) - min(offsets) < 50_000  # ns


@pytest.mark.parametrize("name", READERS)
def test_reader_finite_on_fixture(sp, name):
    v = metric_reader(name).__globals__["from_spans"](sp)
    assert v is not None and math.isfinite(v)
    if name == "search_stall_share":
        assert 0.0 <= v <= 1.0


@pytest.mark.parametrize("name", ["search_return_ms", "search_stall_share"])
def test_reader_does_not_depend_on_the_device_clock_offset(sp, name):
    """The device plane's clock is offset from the host's by an amount
    that moves from profile to profile: moving the whole device plane by
    half a millisecond either way leaves the reading as it is."""
    read = metric_reader(name).__globals__["from_spans"]

    def moved(c):
        return dataclasses.replace(
            sp,
            search_launches=[(a + c, b + c) for a, b in sp.search_launches],
            device_busy=[[a + c, b + c] for a, b in sp.device_busy])

    assert read(moved(-500_000)) == read(sp) == read(moved(500_000))


def test_readers_find_newest_trace_under_the_output_dir(tmp_path,
                                                        monkeypatch):
    monkeypatch.setattr(spans, "OUT", tmp_path)
    assert metric_reader("search_dispatch_ms")(None) is None  # no trace
    (tmp_path / "cell" / "plugins").mkdir(parents=True)
    shutil.copy(FIXTURE, tmp_path / "cell" / "plugins" / "t.xplane.pb")
    assert metric_reader("search_dispatch_ms")(None) > 0
