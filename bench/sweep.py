"""Find a cell's knee on the chip: one build, then the cell's traffic at
stepped rates of one lane, each step a window of its own.

    python bench/sweep.py --workload sift1m_flat.steady --lane search \
        --rates 1000,1500,2000,2500 --step-seconds 8 --seed 11

The knee is the highest offered rate with no refusal, no failure and no
backlog that grows across the step: the swept lane's median latency over
the step's last quarter stays under twice that over its first quarter.
Each step prints one JSON line; the last line names the knee.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

import numpy as np  # noqa: E402

from bench.run import Compiles, end_to_end, setup, use_cache, window  # noqa: E402
from bench.spec import load_cell  # noqa: E402

GROWTH = 2.0


def growth(lane) -> float:
    """Median latency over the last quarter of due times over the first."""
    lat, due = lane.latency_s(), lane.due
    q1, q3 = np.quantile(due, [0.25, 0.75])
    first, last = lat[due <= q1], lat[due >= q3]
    return float(np.median(last) / max(np.median(first), 1e-9))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--lane", choices=("search", "mutation"), required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--step-seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("sweep: JAX found no TPU", file=sys.stderr)
        return 2
    use_cache()
    cell = load_cell(args.workload)
    compiles = Compiles()
    s = setup(cell, args.seed)
    key = "search_qps" if args.lane == "search" else "mutation_rps"
    knee = None
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic = dict(cell.traffic, **{key: rate})
        d, _ = window(s, args.seed + 1 + i, args.step_seconds, traffic)
        n_compiles = compiles.between(d.t0, time.perf_counter())
        lane = d.search if args.lane == "search" else d.mutation
        row = {"rate": rate, "lane": args.lane,
               "refused": int((~lane.admitted).sum()),
               "failed": int((~lane.answered).sum()),
               "growth": growth(lane), "compiles": n_compiles,
               **end_to_end(d, args.step_seconds, 0.0)}
        row.pop("setup_s")
        print(json.dumps(row), flush=True)
        if row["failed"] == 0 and row["growth"] < GROWTH:
            knee = rate
        else:
            break
    s.rt.stop()
    print(json.dumps({"workload": args.workload, "lane": args.lane,
                      "knee": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
