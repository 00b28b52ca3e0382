"""Readings that the limits of ``correct`` are set from, on the chip.

    python bench/calibrate.py --workload sift1m_flat.steady \
        --seeds 11,12,13 --seconds 10

One build, then for each seed a window of the cell's own traffic at its own
load, and the check after it, as ``run.py`` makes them.  Each window's
served sample and stored index is judged four ways: as the program served
it (``program``); with the reference one precision step below the
configuration's in the program's place (``control``); and with two faults
planted in the program's answers (``altered``: each answer's first id
changed; ``half``: every other answer left out).  One JSON line per seed,
then one with each number's largest ``program`` reading and smallest
reading of the others.  ``--kmeans-iters n`` plants a fault in the
program's build, whose k-means then stops after ``n`` Lloyd steps: its
``program`` readings are that fault's.  The benchmark's own runs never run
this.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

import numpy as np  # noqa: E402

from bench.run import checker, collect, setup, use_cache, window  # noqa: E402
from bench.spec import load_cell  # noqa: E402


def altered(served):
    ids = served.ids.copy()
    ids[:, 0] = np.where(ids[:, 0] >= 0, ids[:, 0] + 1, ids[:, 0])
    return dataclasses.replace(served, ids=ids)


def half(served):
    ids, dists = served.ids.copy(), served.dists.copy()
    ids[1::2], dists[1::2] = -1, np.inf
    return dataclasses.replace(served, ids=ids, dists=dists)


def short_kmeans(entry: tuple, iters: int) -> tuple:
    """An entry of the program's ``INDEXES`` whose k-means stops after
    ``iters`` Lloyd steps."""
    make_cfg, n_full, generate = entry
    return (lambda scale: dataclasses.replace(make_cfg(scale),
                                              kmeans_iters=iters),
            n_full, generate)


def readings(cell, ev) -> dict:
    chk = checker(cell, ev)
    out = {"program": chk.numbers(ev.served),
           "control": chk.numbers(ev.served,
                                  control=cell.config["control_precision"]),
           "altered": chk.numbers(altered(ev.served)),
           "half": chk.numbers(half(ev.served))}
    for numbers in out.values():
        numbers["lost_answers"] = ev.lost
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--kmeans-iters", type=int, default=0,
                    help="plant the fault: k-means stops after this many "
                         "Lloyd steps (0: the program as it is)")
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: JAX found no TPU", file=sys.stderr)
        return 2
    use_cache()
    cell = load_cell(args.workload)
    if args.kmeans_iters:
        from repro.launch import serve

        name = cell.config["index"]
        serve.INDEXES[name] = short_kmeans(serve.INDEXES[name],
                                           args.kmeans_iters)
    seeds = [int(x) for x in args.seeds.split(",")]
    s = setup(cell, seeds[0])
    limits = cell.config["limits"]
    worst = {}
    for seed in seeds:
        d, sched = window(s, seed, args.seconds)
        got = readings(cell, collect(s, d, sched, seed))
        got["correct"] = all(got["program"][n] <= limits[n] for n in limits)
        got["seed"] = seed
        print(json.dumps(got), flush=True)
        for way in ("program", "control", "altered", "half"):
            for n in limits:
                v = got[way][n]
                w = worst.setdefault(way, {})
                w[n] = (max if way == "program" else min)(w.get(n, v), v)
    s.rt.stop()
    print(json.dumps({"workload": args.workload, "seeds": len(seeds),
                      "kmeans_iters": args.kmeans_iters or "program",
                      "largest_program": worst["program"],
                      "smallest": {w: worst[w] for w in
                                   ("control", "altered", "half")}}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
