"""Benchmark entry: one cell of ``BENCHMARK.json``, one run.

    python bench/run.py --workload sift1m_flat.steady --seed 7 \
        --seconds 20 --trace 0

Set-up builds the cell's index through the program's own build path
(``repro.launch.serve.build_index``), starts a ``ServingRuntime`` with the
program's default policy, and warms every request shape the window can
reach.  The window then sends the cell's traffic open-loop for
``--seconds``.  Afterwards the served answers and the stored index are
compared with the benchmark's plain reference (``bench/check.py``).  The
last line of stdout is one JSON object: the cell's end-to-end metrics
(``--trace 0``) or its per-layer metrics read from a profiled run
(``--trace 1``), the device, and every compared number with its limit.

The benchmark runs on a TPU only: with no TPU, or fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
# JAX's persistent compilation cache, at a fixed path inside the checkout
# (the path is part of the cache key)
CACHE_DIR = ROOT / ".jax_cache"
# libtpu's own logs would go to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

from bench import loadgen  # noqa: E402
from bench.check import Checker, History, Readback, Served  # noqa: E402
from bench.check import pool_invariant_faults  # noqa: E402
from bench.spec import Cell, load_cell, metric_reader, peaks  # noqa: E402
from bench.traffic import KINDS, draw_rows, make_schedule  # noqa: E402

#: JAX's monitoring event for one backend compile
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
OUT = ROOT / ".bench_out"  # profiles; git-ignored
TRACE_RING = 1 << 18  # span traces kept in the traced run
#: an end-to-end latency metric: a percentile of one lane, due time to answer
LATENCY = re.compile(r"(search|mutation)_p(\d+(?:\.\d+)?)_ms")
#: the program's state read back after the window, by field
STATE_FIELDS = ("pool_ids", "pool_live", "block_owner", "cluster_blocks",
                "cluster_nblocks", "cluster_len", "num_vectors",
                "num_dropped")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def use_cache() -> str:
    """Turn on JAX's persistent compilation cache at ``CACHE_DIR``: given to
    the program through the variable its ``use_compile_cache`` reads, and
    set in JAX's config, which read that variable when JAX was imported.
    Every program is kept, however fast it compiled, so that a run after
    the first compiles nothing."""
    import jax

    from repro.launch.serve import use_compile_cache

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return use_compile_cache()


class Compiles:
    """Times of backend compiles, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.at: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_):
        if event == BACKEND_COMPILE:
            self.at.append(time.perf_counter())

    def between(self, a: float, b: float) -> int:
        return sum(a <= t <= b for t in self.at)


class GcPauses:
    """Pauses of the interpreter's garbage collector, from ``gc.callbacks``.
    A collection holds the interpreter lock, so every thread of the runtime
    and of the load generator waits it out."""

    def __init__(self):
        self.spans: list = []
        self._start = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict):
        now = time.perf_counter()
        if phase == "start":
            self._start = now
        else:
            self.spans.append((self._start, now))

    def stop(self):
        gc.callbacks.remove(self._on)

    def between(self, a: float, b: float) -> list:
        return [(s, e) for s, e in self.spans if a <= s <= b]


@dataclasses.dataclass
class Session:
    """A built index, its runtime, and what the benchmark sent to it."""

    cell: Cell
    index: object
    rt: object
    corpus: np.ndarray
    history: History
    targets: np.ndarray  # corpus ids still free for delete / update

    def take_targets(self, n: int) -> np.ndarray:
        out, self.targets = self.targets[:n], self.targets[n:]
        return out

    def record(self, kind: str, ok: bool, result, vecs, ids, sent: float,
               done: float):
        """Add an admitted mutation to the history once it resolved;
        ``result`` is what it answered (an insert's new ids)."""
        if kind == "insert":
            ids = result if ok else np.zeros(0, np.int64)
        self.history.add(kind, ids, vecs, sent, done, ok)


def check_sizes(index, cfg: dict) -> None:
    """The built index has the configuration file's sizes."""
    got = {"n_clusters": index.cfg.n_clusters, "dim": index.cfg.dim,
           "block_size": index.cfg.block_size, "nprobe": index.cfg.nprobe,
           "k": index.cfg.k, "payload": index.cfg.payload,
           "pool_blocks": index.pool_cfg.n_blocks,
           "pq_m": index.cfg.pq_m, "rows": index.ntotal}
    want = {k: cfg[k] for k in got}
    if got != want:
        raise RuntimeError(f"built index {got} is not the configuration "
                           f"{cfg['name']} {want}")


def setup(cell: Cell, seed: int, *, trace: bool = False,
          scale: float | None = None) -> Session:
    """Build, start the runtime, and warm every shape the window reaches."""
    from repro.core.runtime import RuntimeConfig, ServingRuntime
    from repro.launch.serve import build_index

    cfg, traffic = cell.config, cell.traffic
    t_build = time.perf_counter()
    index, corpus = build_index(cfg["index"], cfg["scale"] if scale is None
                                else scale, cfg["data_seed"])
    t_warm = time.perf_counter()
    log(f"build {t_warm - t_build:.3f}s (corpus, k-means, insert replay), "
        f"{t_build - T_START:.3f}s after the process started")
    if scale is None:
        check_sizes(index, cfg)
    extra = ({"trace_sample_rate": 1.0, "trace_buffer": TRACE_RING}
             if trace else {})
    rt = ServingRuntime(index, RuntimeConfig(nprobe=cfg["nprobe"], k=cfg["k"],
                                             **extra))
    rng = np.random.default_rng([seed, 2])
    warm = traffic["warm_rows"]  # kind -> request sizes the window reaches
    targets = np.zeros(0, np.int64)
    if set(warm) - {"insert"}:
        targets = rng.permutation(len(corpus)).astype(np.int64)
    s = Session(cell, index, rt, corpus, History(corpus), targets)
    # mutation buckets first: their rows can grow a chain past the search
    # step's budget rung, and searches must warm at the window's rung
    for rows in sorted({r for sizes in warm.values() for r in sizes},
                       reverse=True):
        sent = []
        for kind in (k for k in KINDS if rows in warm.get(k, ())):
            vecs = draw_rows(rng, corpus, rows, cfg["draw"])
            ids = s.take_targets(rows) if kind != "insert" else None
            t = time.perf_counter()
            sent.append((kind, loadgen.submit_mutation(rt, kind, vecs, ids),
                         vecs, ids, t))
        for kind, fut, vecs, ids, t in sent:
            s.record(kind, True, fut.result(timeout=900), vecs, ids, t,
                     time.perf_counter())
    for rows in traffic["warm_search_rows"]:
        rt.submit_search(draw_rows(rng, corpus, rows, cfg["draw"])).result(
            timeout=900)
    rt.reset_stats()
    log(f"warm-up {time.perf_counter() - t_warm:.3f}s")
    return s


class Profiler(threading.Thread):
    """Profiles ``length`` seconds starting ``lead`` seconds from now."""

    def __init__(self, log_dir: Path, lead: float, length: float):
        super().__init__(daemon=True)
        self.log_dir, self.lead, self.length = log_dir, lead, length
        self.span = (np.nan, np.nan)

    def run(self):
        import jax

        time.sleep(self.lead)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.traced"):
            a = time.perf_counter()
            time.sleep(self.length)
            b = time.perf_counter()
        jax.profiler.stop_trace()
        self.span = (a, b)


def readback(s: Session, check_ids: np.ndarray) -> Readback:
    """The state after the window; the rows of ``check_ids`` that are live,
    as stored."""
    import jax
    import jax.numpy as jnp

    state, pc = s.index.state, s.index.pool_cfg
    st = jax.device_get({f: getattr(state, f) for f in STATE_FIELDS})
    b, off = np.nonzero(st["pool_live"])
    live_ids = st["pool_ids"][b, off].astype(np.int64)
    flat = b.astype(np.int64) * pc.block_size + off
    payload = state.pool_payload.reshape(pc.n_blocks * pc.block_size, -1)
    srt = np.argsort(live_ids)
    pos = np.minimum(np.searchsorted(live_ids[srt], check_ids), len(srt) - 1)
    found = live_ids[srt][pos] == check_ids
    rows = np.asarray(payload[jnp.asarray(flat[srt][pos][found], jnp.int32)])
    return Readback(live_ids, st["block_owner"][b].astype(np.int64),
                    dict(zip(check_ids[found].tolist(), rows)),
                    pool_invariant_faults(st, pc.block_size))


def search_now(s: Session, queries: np.ndarray, wave: int = 8):
    """Serve ``queries`` one per request, a wave at a time; returns the
    sent and done times and the answers."""
    sent, done, ids, dists = [], [], [], []
    for w in range(0, len(queries), wave):
        futs = []
        for q in queries[w : w + wave]:
            sent.append(time.perf_counter())
            futs.append(s.rt.submit_search(q[None]))
        for f in futs:
            d, i = f.result(timeout=300)
            done.append(time.perf_counter())
            ids.append(i[0])
            dists.append(d[0])
    return (np.array(sent), np.array(done), np.array(ids, np.int64),
            np.array(dists, np.float32))


def window(s: Session, seed: int, seconds: float, traffic: dict | None = None,
           profile: Profiler | None = None) -> tuple:
    """Send the traffic for ``seconds``; returns the drive and its schedule."""
    traffic = s.cell.traffic if traffic is None else traffic
    n_touch = int(np.ceil(traffic.get("mutation_rps", 0) * seconds))
    sched = make_schedule(traffic, s.cell.config["draw"], s.corpus,
                          s.targets[:n_touch], seconds, seed)
    gc.collect()
    if profile is not None:
        profile.start()
    d = loadgen.drive(s.rt, sched)
    loadgen.settle(d)
    n_used = int((sched.m_ids >= 0).sum())
    s.take_targets(n_used)
    m = d.mutation
    for j in range(len(sched.m_due)):
        if not m.admitted[j]:
            continue  # refused: never admitted, never applied
        s.record(KINDS[sched.m_kind[j]], bool(m.answered[j]), m.results[j],
                 sched.m_vecs[j : j + 1], sched.m_ids[j : j + 1], m.sent[j],
                 m.done[j])
    return d, sched


def end_to_end(d, seconds: float, setup_s: float, names) -> dict:
    """``setup_s``, ``search_qps`` and each ``<lane>_p<q>_ms`` of ``names``:
    the q-th percentile over every request of that lane in the window."""
    lanes = {"search": d.search.latency_s(), "mutation": d.mutation.latency_s()}
    in_window = (d.search.done >= d.t0) & (d.search.done <= d.t1)
    out = {"setup_s": setup_s,
           "search_qps": float((in_window & d.search.answered).sum() / seconds)}
    for name in names:
        m = LATENCY.fullmatch(name)
        if m and len(lanes[m[1]]):
            out[name] = loadgen.percentile_ms(lanes[m[1]], float(m[2]))
    return out


def log_tails(d, gc_spans: list, compiles: int) -> None:
    """The latency ladder of both lanes, the collector's pauses and the
    sender's lateness in the window, on stderr: what a tail's spread from
    run to run follows."""
    ladder = {f"{lane}_p{q}_ms": loadgen.percentile_ms(lat, q)
              for lane, lat in (("search", d.search.latency_s()),
                                ("mutation", d.mutation.latency_s()))
              if len(lat) for q in (50, 90, 95, 99, 99.9, 100)}
    pause_ms = [1e3 * (e - s) for s, e in gc_spans]
    lag = np.concatenate([d.search.sent - d.search.due,
                          d.mutation.sent - d.mutation.due])
    lag = lag[np.isfinite(lag)]
    ladder.update(gc_pauses=len(pause_ms), gc_pauses_over_10ms=sum(
        p > 10 for p in pause_ms), gc_pause_max_ms=max(pause_ms, default=0.0),
        gen_lag_p99_ms=loadgen.percentile_ms(lag, 99) if len(lag) else 0.0,
        compiles_in_window=compiles)
    log("tails " + json.dumps(ladder))


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""

    drive: object
    traces: list  # finished request traces of the traced run
    device: object  # trace_reduce.Reduced
    compiles_in_window: int
    rows_acked_traced: int  # mutation rows acked inside the profile
    gc_pauses: list  # (start, end) of each collection, window to last answer


@dataclasses.dataclass
class Evidence:
    """What the check reads from the program after a window."""

    served: Served
    versions: object  # check.Versions
    readback: Readback
    quantizer: dict  # host arrays: centroids, and codebooks for PQ
    lost: int  # admitted, and never answered or answered with an error
    seed: int
    seconds: dict  # what reading it took, by stage


def quantizer(index) -> dict:
    """The index's trained quantizer as host arrays."""
    out = {"centroids": np.asarray(index.state.centroids)}
    if index.pq is not None:
        out["codebooks"] = np.asarray(index.pq.codebooks)
    return out


def collect(s: Session, d, sched, seed: int) -> Evidence:
    """A seeded sample of the window's answered searches, searches that
    read back acked writes and deletes, and the index as stored."""
    t = time.perf_counter()
    rng = np.random.default_rng([seed, 3])
    ok = np.flatnonzero(d.search.answered)
    pick = rng.choice(ok, min(len(ok), s.cell.traffic["check_searches"]),
                      replace=False)
    res = [d.search.results[j] for j in pick]
    versions = s.history.versions()
    t_versions = time.perf_counter()
    recent = np.flatnonzero(versions.start_hi > d.t0)
    gone = np.flatnonzero(versions.end_hi < np.inf)
    again = np.concatenate([
        rng.choice(recent, min(len(recent), 64), replace=False),
        rng.choice(gone, min(len(gone), 64), replace=False)])
    rb_sent, rb_done, rb_ids, rb_d = search_now(s, versions.vecs[again])
    k = s.cell.config["k"]
    served = Served(
        np.concatenate([sched.queries[pick], versions.vecs[again]]),
        np.concatenate([d.search.sent[pick], rb_sent]),
        np.concatenate([d.search.done[pick], rb_done]),
        np.concatenate([np.array([r[1][0] for r in res], np.int64)
                        .reshape(-1, k), rb_ids.reshape(-1, k)]),
        np.concatenate([np.array([r[0][0] for r in res], np.float32)
                        .reshape(-1, k), rb_d.reshape(-1, k)]))
    written = versions.ids[versions.start_hi > -np.inf]
    check_ids = np.unique(np.concatenate([
        written, rng.choice(len(s.corpus), min(256, len(s.corpus)),
                            replace=False)]))
    lost = int(sum((lane.admitted & ~lane.answered).sum()
                   for lane in (d.search, d.mutation)))
    t_served = time.perf_counter()
    rb = readback(s, check_ids)
    return Evidence(served, versions, rb, quantizer(s.index), lost, seed,
                    {"versions": t_versions - t,
                     "read-after-write searches": t_served - t_versions,
                     "readback": time.perf_counter() - t_served})


def checker(cell: Cell, ev: Evidence) -> Checker:
    """The cell's reference over what ``collect`` read from the program."""
    import importlib

    ref = importlib.import_module(f"bench.reference.{cell.config['reference']}")
    return Checker(ref, ev.quantizer, cell.config, ev.versions, ev.readback,
                   ev.seed)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, control: bool = False,
             scale: float | None = None) -> dict:
    import jax

    log(f"compile cache {use_cache()}")
    compiles = Compiles()
    s = setup(cell, seed, trace=trace, scale=scale)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f}s: {s.index.ntotal} rows, longest list "
        f"{int(np.asarray(s.index.state.cluster_len).max())} rows")
    prof = None
    pauses = GcPauses()
    if trace:
        shutil.rmtree(OUT / cell.name, ignore_errors=True)
        length = min(cell.traffic.get("trace_seconds", 3.0), seconds / 2)
        prof = Profiler(OUT / cell.name, (seconds - length) / 2, length)
    d, sched = window(s, seed, seconds, profile=prof)
    if prof is not None:
        prof.join()
    pauses.stop()
    t_settled = time.perf_counter()
    log(f"window closed, answers settled {t_settled - d.t1:.3f}s later")
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    e2e = end_to_end(d, seconds, setup_s, [m["name"] for m in cell.end_to_end])
    log("end to end " + json.dumps(e2e))
    n_in_window = compiles.between(d.t0, t_settled)
    log_tails(d, pauses.between(d.t0, t_settled), n_in_window)

    ev = collect(s, d, sched, seed)
    log(f"read back {len(ev.readback.live_ids)} live rows at "
        f"{time.perf_counter() - t_settled:.3f}s")
    traces = [t.as_dict() for t in s.rt.traces()] if trace else []
    s.rt.stop()
    del s.rt, s.index
    gc.collect()
    # the control puts the reference, one precision step down, in the
    # program's place
    chk = checker(cell, ev)
    numbers = chk.numbers(
        ev.served, control=cell.config["control_precision"] if control else None)
    numbers["lost_answers"] = ev.lost
    log(f"compared at {time.perf_counter() - t_settled:.3f}s")
    log("check " + json.dumps({n: round(t, 3) for n, t in
                               {**ev.seconds, **chk.seconds}.items()}))
    limits = cell.config["limits"]
    checks = {n: {"value": numbers[n], "limit": limits[n]} for n in limits}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    out = {"correct": bool(correct),
           "attempted": int(len(sched.s_due) + len(sched.m_due)),
           "failed": int((~d.search.answered).sum()
                         + (~d.mutation.answered).sum())}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": int(mem)}
    if not trace:
        out["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end if m["name"] in e2e}
        out["device"] = device
    else:
        from bench.trace_reduce import find_trace, reduce_trace

        red = reduce_trace(find_trace(str(OUT / cell.name)))
        a, b = prof.span
        rows = int(sum(1 for j in range(len(sched.m_due))
                       if a <= d.mutation.done[j] <= b))
        ctx = Context(d, traces, red, n_in_window, rows,
                      pauses.between(d.t0, t_settled))
        out["metrics"] = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                out["metrics"][m["name"]] = {"value": float(v),
                                             "unit": m["unit"]}
        out["device"] = dict(device, busy_s=red.busy_s, window_s=red.window_s)
        out["breakdown"] = {
            "device_ops": [[n, t] for n, t in list(red.ops.items())[:10]],
            "idle_gaps": [[n, t] for n, t in red.gaps[:10]],
        }
    out["checks"] = checks  # last: the numbers compared, with their limits
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="put the reference, one precision step down, in "
                         "the program's place (a check of the check)")
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
    except (OSError, KeyError, ValueError) as e:
        print(f"bench: cannot load {args.workload!r}: {e}", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    try:
        peaks(devices[0].device_kind)
    except KeyError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        import repro.launch.serve  # noqa: F401
    except ImportError as e:
        print(f"bench: the program is not beside the benchmark ({e})",
              file=sys.stderr)
        return 2
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START,
                   control=bool(args.control))
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
