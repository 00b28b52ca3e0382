"""Record the small device trace that ``test_trace_reduce.py`` reads.

    python bench/tests/record_trace_fixture.py OUT_DIR

Run on the chip: serves a tiny SIFT-like index (1% scale) through the
runtime while profiling 40 searches and 2 insert batches under the
benchmark's ``bench.traced`` span, then copies the ``.xplane.pb`` to
``OUT_DIR/small.xplane.pb`` and prints what the reduction reads from it.
"""

import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    import jax
    import numpy as np

    from bench.trace_reduce import find_trace, reduce_trace
    from repro.core.runtime import RuntimeConfig, ServingRuntime
    from repro.launch.serve import build_index, use_compile_cache

    use_compile_cache()
    index, corpus = build_index("ivfflat_sift1m", 0.01, 0)
    rt = ServingRuntime(index, RuntimeConfig(nprobe=32, k=10))
    q = corpus[:40] + 1.0
    rt.submit_search(q[:1]).result()
    rt.submit_insert(q[:128]).result()
    log_dir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.traced"):
        for j in range(40):
            rt.submit_search(q[j : j + 1]).result()
            if j % 20 == 0:
                rt.submit_insert(q[:128] + j).result()
    jax.profiler.stop_trace()
    rt.stop()
    out = Path(sys.argv[1])
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(find_trace(log_dir), out / "small.xplane.pb")
    red = reduce_trace(str(out / "small.xplane.pb"))
    print({"window_s": red.window_s, "busy_s": red.busy_s,
           "programs": {k: len(v) for k, v in red.programs.items()},
           "top_ops": list(red.ops.items())[:5], "gaps": red.gaps[:5]})
    return 0


if __name__ == "__main__":
    sys.exit(main())
