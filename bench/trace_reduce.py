"""From a profiler trace (``.xplane.pb``) to device numbers.

The traced window is the span of the benchmark's own ``bench.traced``
annotation.  On each device plane (``/device:...``) the programs are the
events of the ``XLA Modules`` line, named after their jitted function
(``jit__search(...)``), and the operations those of ``XLA Ops``.  Busy time
is the union of the operations' intervals inside the window, averaged over
the devices.  Idle gaps are the window's stretches outside that union, each
labelled by the host event that covers most of it (the runtime's dispatch,
a compile, ...), or ``host idle`` where none does.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict

WINDOW_SPAN = "bench.traced"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float  # union of device operations, mean over devices
    programs: dict  # program name -> [seconds of each launch]
    ops: dict  # operation name -> total seconds (all devices)
    gaps: list  # [(label, seconds)], longest first

    def launches(self, pattern: str) -> list:
        """Seconds of every launch of the programs matching ``pattern``."""
        rx = re.compile(pattern)
        return [s for name, runs in self.programs.items() if rx.search(name)
                for s in runs]


def _union(iv: list) -> list:
    out = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def find_trace(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def reduce_trace(path: str, top: int = 10) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            host.append(plane)
    marks = [(e.start_ns, e.end_ns) for p in host for line in p.lines
             for e in line.events if e.name == WINDOW_SPAN]
    if not marks:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    w0, w1 = marks[0]
    programs, ops = defaultdict(list), defaultdict(float)
    busy, n_dev = 0.0, 0
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        n_dev += 1
        if MODULES_LINE in lines:
            for e in lines[MODULES_LINE].events:
                if w0 <= e.start_ns < w1:
                    programs[e.name.split("(")[0]].append(e.duration_ns / 1e9)
        iv = []
        for e in lines[OPS_LINE].events:
            a, b = max(e.start_ns, w0), min(e.end_ns, w1)
            if b > a:
                iv.append((a, b))
                ops[e.name] += (b - a) / 1e9
        merged = _union(iv)
        busy += sum(b - a for a, b in merged)
        if n_dev == 1:
            edges = [w0] + [x for ab in merged for x in ab] + [w1]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    if not n_dev:
        raise ValueError(f"no device plane with an {OPS_LINE!r} line in {path}")
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    host_ev = [(e.start_ns, e.end_ns, e.name) for p in host for line in p.lines
               for e in line.events
               if e.duration_ns > 0 and not e.name.startswith("bench.")]
    labelled = []
    for a, b in gaps:
        cover = defaultdict(float)
        for s, t, name in host_ev:
            if s < b and t > a:
                cover[name] += min(b, t) - max(a, s)
        label = max(cover, key=cover.get) if cover else "host idle"
        labelled.append((label, (b - a) / 1e9))
    return Reduced(
        window_s=(w1 - w0) / 1e9, busy_s=busy / n_dev / 1e9,
        programs=dict(programs),
        ops=dict(sorted(ops.items(), key=lambda kv: -kv[1])),
        gaps=labelled)
