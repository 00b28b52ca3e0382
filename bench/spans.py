"""The runtime's own host spans, on the device trace's clock.

The program writes each stage of each dispatch as a host span into the
profiler's trace (``repro.obs.trace.HOST_SPANS``: ``search.execute``,
``search.device_wait``, ``search.ack``, ...).  Every ``execute`` span
carries ``dispatch`` (a per-lane sequence number), ``n`` (queries or
rows), ``kind``, ``t_ns`` (``perf_counter_ns()`` at its entry) and
``oldest_ns`` (``perf_counter_ns`` of the batch's oldest arrival); its
start in the trace minus ``t_ns`` maps any ``perf_counter`` stamp of the
serving process onto the trace's clock.

:func:`load` reads the newest ``.xplane.pb`` under ``.bench_out`` once
(cached on path and modification time) and keeps, inside the benchmark's
``bench.traced`` span: the catalog's host spans with their stats, the
launches of the search program with start and end, and the union of the
device's operations.  Against a program that writes no such spans it
returns ``None``, and so do the readers built on it.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from pathlib import Path

from bench.trace_reduce import MODULES_LINE, OPS_LINE, WINDOW_SPAN, _union

OUT = Path(__file__).resolve().parents[1] / ".bench_out"


@dataclasses.dataclass
class Spans:
    window: tuple  # (start_ns, end_ns) of the bench.traced span
    host: dict  # span name -> [(start_ns, end_ns, stats)], by start
    search_launches: list  # [(start_ns, end_ns)] of the search program
    device_busy: list  # merged [[start_ns, end_ns]] of device operations

    def dispatches(self, name: str) -> dict:
        """``dispatch`` number -> (start_ns, end_ns, stats) of ``name``."""
        return {st["dispatch"]: (a, b, st)
                for a, b, st in self.host.get(name, ())
                if "dispatch" in st}


_cache: dict = {}


def _names():
    """The program's catalogs, or ``None`` where it has none."""
    try:
        from repro.obs.programs import PROGRAM_SEARCH, module_name
        from repro.obs.trace import HOST_SPANS
    except ImportError:
        return None
    return HOST_SPANS, module_name(PROGRAM_SEARCH)


def read_spans(path: str):
    """The spans of the trace at ``path``; ``None`` where the program
    writes none."""
    names = _names()
    if names is None:
        return None
    key = (str(path), os.path.getmtime(path))
    if key not in _cache:
        _cache.clear()
        _cache[key] = _read(str(path), *names)
    return _cache[key]


def load(out_dir: Path | None = None):
    """:func:`read_spans` of the newest trace under ``out_dir``."""
    paths = glob.glob(os.path.join(OUT if out_dir is None else out_dir,
                                   "**", "*.xplane.pb"), recursive=True)
    if not paths:
        return None
    return read_spans(max(paths, key=os.path.getmtime))


def _read(path: str, host_spans, search_program: str):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host = [p for p in pd.planes if p.name.startswith("/host:")]
    marks = [(e.start_ns, e.end_ns) for p in host for line in p.lines
             for e in line.events if e.name == WINDOW_SPAN]
    if not marks:
        return None
    w0, w1 = marks[0]
    spans: dict = {}
    for p in host:
        for line in p.lines:
            for e in line.events:
                if e.name in host_spans and w0 <= e.start_ns < w1:
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.end_ns, dict(e.stats)))
    for v in spans.values():
        v.sort(key=lambda s: s[0])
    launches, busy = [], []
    for plane in pd.planes:
        lines = {line.name: line for line in plane.lines}
        if not plane.name.startswith("/device:") or OPS_LINE not in lines:
            continue
        if MODULES_LINE in lines:
            launches = sorted(
                (e.start_ns, e.end_ns) for e in lines[MODULES_LINE].events
                if w0 <= e.start_ns < w1
                and e.name.split("(")[0] == search_program)
        busy = _union([(max(e.start_ns, w0), min(e.end_ns, w1))
                       for e in lines[OPS_LINE].events
                       if min(e.end_ns, w1) > max(e.start_ns, w0)])
        break  # the cell's one chip
    return Spans((w0, w1), spans, launches, busy)


def search_launch_pairs(sp: Spans) -> dict:
    """``dispatch`` number -> its ``execute`` span, its launch of the search
    program and its ``device_wait`` span, as ``((e0, e1), (l0, l1), (w0,
    w1))``, for each search dispatch whose launch is in the profile.  Its
    launch is the one that ends between the start of its ``execute`` and
    the end of its ``device_wait``: the search lane has one dispatch in
    flight, and the fetch ends after the program does.  The launch's end,
    not its start, is matched because the device's clock in a TPU trace
    can run ahead of the host's: a launch may appear to start before the
    host began the dispatch that made it.  The offset moves from profile
    to profile, so a reader compares host times with host times and takes
    only lengths from the device."""
    from repro.obs.trace import SPAN_SEARCH_DEVICE, SPAN_SEARCH_EXECUTE

    waits = sp.dispatches(SPAN_SEARCH_DEVICE)
    ends = [b for _, b in sp.search_launches]
    out = {}
    for seq, (e0, e1, _) in sp.dispatches(SPAN_SEARCH_EXECUTE).items():
        if seq not in waits:
            continue
        w0, w1, _ = waits[seq]
        j = bisect.bisect_left(ends, e0)
        if j < len(ends) and ends[j] <= w1:
            out[seq] = ((e0, e1), sp.search_launches[j], (w0, w1))
    return out
