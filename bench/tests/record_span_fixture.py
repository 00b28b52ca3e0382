"""Record the small device trace that ``test_spans.py`` reads.

    python bench/tests/record_span_fixture.py OUT_DIR

Run on the chip: serves a tiny SIFT-like index (1% scale) through the
runtime while profiling, under the benchmark's ``bench.traced`` span,
40 searches sent in waves of 4 (so that dispatches batch), two insert
batches of 128 rows and one collection of the garbage collector; then
copies the ``.xplane.pb`` to ``OUT_DIR/spans.xplane.pb`` and prints what
the span readers read from it.
"""

import gc
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    import jax

    from bench import spans
    from bench.spec import metric_reader
    from bench.trace_reduce import find_trace, reduce_trace
    from repro.core.runtime import RuntimeConfig, ServingRuntime
    from repro.launch.serve import build_index, use_compile_cache

    use_compile_cache()
    index, corpus = build_index("ivfflat_sift1m", 0.01, 0)
    rt = ServingRuntime(index, RuntimeConfig(nprobe=32, k=10,
                                             trace_sample_rate=1.0))
    q = corpus[:128] + 1.0
    for n in (1, 4):
        for f in [rt.submit_search(q[j : j + 1]) for j in range(n)]:
            f.result()
    rt.submit_insert(q).result()
    log_dir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.traced"):
        for w in range(0, 40, 4):
            for f in [rt.submit_search(q[j : j + 1]) for j in range(w, w + 4)]:
                f.result()
            if w % 20 == 0:
                rt.submit_insert(q + w).result()
            if w == 16:
                gc.collect(0)
    jax.profiler.stop_trace()
    rt.stop()
    out = Path(sys.argv[1])
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(find_trace(log_dir), out / "spans.xplane.pb")
    sp = spans.read_spans(str(out / "spans.xplane.pb"))
    red = reduce_trace(str(out / "spans.xplane.pb"))
    print({"spans": {k: len(v) for k, v in sp.host.items()},
           "search_launches": len(sp.search_launches),
           "gaps": red.gaps[:5],
           **{name: metric_reader(name).__globals__["from_spans"](sp)
              for name in ("search_dispatch_ms", "search_return_ms",
                           "search_queries_per_dispatch",
                           "search_stall_share")}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
