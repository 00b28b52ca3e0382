"""Readings that the limits of ``correct`` are set from.

    python bench/calibrate.py --workload sift1m_flat.steady \
        --seeds 11,12,13 --seconds 10
    python bench/calibrate.py --config bench/tests/fixtures/pq_tiny.json \
        --traffic bench/tests/fixtures/pq_tiny_steady.json \
        --seeds 1,2,3 --seconds 2 --cpu

One build, then for each seed a window of the cell's own traffic at its own
load, and the check after it, as ``run.py`` makes them.  Each window's
served sample and stored index is judged as the program served and stored
it (``program``); with the reference one precision step below the
configuration's in the program's place (``control``); and with each fault
of ``FAULTS`` planted in what was read from the program: ``altered`` (each
answer's first id changed), ``half`` (every other answer left out),
``altered_row`` (the first value of every stored row read back changed:
one code of a PQ row) and ``wrong_list`` (every tenth live row moved to
its second-nearest list).  One JSON line per seed, then one with each
number's largest ``program`` reading and smallest reading of the others.
``--kmeans-iters n`` plants a fault in the program's build, whose k-means
then stops after ``n`` Lloyd steps, and ``--pq-iters n`` one whose PQ
codebooks do: the ``program`` readings are then that fault's.

A cell is a workload of ``BENCHMARK.json``, or a configuration and a
traffic file (``--config``, ``--traffic``), which may be a test fixture.
The readings are taken on the chip; ``--cpu`` lets a tiny configuration
be read on the CPU, for a fixture's limits.  The benchmark's own runs
never run this.
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
from bench.spec import Cell, load_cell, load_json  # noqa: E402


def altered(ev, chk):
    ids = ev.served.ids.copy()
    ids[:, 0] = np.where(ids[:, 0] >= 0, ids[:, 0] + 1, ids[:, 0])
    return dataclasses.replace(ev, served=dataclasses.replace(ev.served,
                                                              ids=ids))


def half(ev, chk):
    ids, dists = ev.served.ids.copy(), ev.served.dists.copy()
    ids[1::2], dists[1::2] = -1, np.inf
    return dataclasses.replace(ev, served=dataclasses.replace(
        ev.served, ids=ids, dists=dists))


def altered_row(ev, chk):
    rows = {}
    for i, row in ev.readback.stored_rows.items():
        rows[i] = row.copy()
        rows[i].reshape(-1)[:1] += np.ones(1, row.dtype)  # a code wraps
    return dataclasses.replace(ev, readback=dataclasses.replace(
        ev.readback, stored_rows=rows))


def wrong_list(ev, chk):
    lists = ev.readback.live_lists.copy()
    held = np.flatnonzero(chk.slot_version >= 0)[::10]
    lists[held] = chk.second[chk.slot_version[held]]
    return dataclasses.replace(ev, readback=dataclasses.replace(
        ev.readback, live_lists=lists))


#: faults planted in what was read from the program, by name
FAULTS = {"altered": altered, "half": half, "altered_row": altered_row,
          "wrong_list": wrong_list}


def short_kmeans(entry: tuple, iters: int) -> tuple:
    """An entry of the program's ``INDEXES`` whose k-means stops after
    ``iters`` Lloyd steps."""
    make_cfg, n_full, generate = entry
    return (lambda scale: dataclasses.replace(make_cfg(scale),
                                              kmeans_iters=iters),
            n_full, generate)


def short_codebooks(train_pq, iters: int):
    """The program's ``train_pq``, stopping after ``iters`` Lloyd steps."""
    return lambda residuals, m, **kw: train_pq(residuals, m,
                                               **dict(kw, n_iter=iters))


def readings(cell, ev) -> dict:
    chk = checker(cell, ev)
    out = {"program": chk.numbers(ev.served),
           "control": chk.numbers(ev.served,
                                  control=cell.config["control_precision"])}
    for name, fault in FAULTS.items():
        bad = fault(ev, chk)
        judge = (chk if bad.readback is ev.readback
                 else chk.with_readback(bad.readback))
        out[name] = judge.numbers(bad.served)
    for numbers in out.values():
        numbers["lost_answers"] = ev.lost
    out["seconds"] = {**ev.seconds, **chk.seconds}  # what the check took
    return out


def file_cell(config: str, traffic: str) -> Cell:
    cfg, tr = load_json(Path(config)), load_json(Path(traffic))
    return Cell(f"{cfg['name']}.{Path(traffic).stem}", 1, cfg, tr, [], [])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--config", help="a configuration file, with --traffic")
    ap.add_argument("--traffic")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--cpu", action="store_true",
                    help="read a tiny configuration on the CPU")
    ap.add_argument("--kmeans-iters", type=int, default=0,
                    help="plant the fault: k-means stops after this many "
                         "Lloyd steps (0: the program as it is)")
    ap.add_argument("--pq-iters", type=int, default=0,
                    help="plant the fault: the PQ codebooks stop after this "
                         "many Lloyd steps (0: the program as it is)")
    args = ap.parse_args(argv)
    if bool(args.workload) == bool(args.config and args.traffic):
        ap.error("give --workload, or --config and --traffic")

    import jax

    if jax.devices()[0].platform != "tpu" and not args.cpu:
        print("calibrate: JAX found no TPU", file=sys.stderr)
        return 2
    use_cache()
    cell = (load_cell(args.workload) if args.workload
            else file_cell(args.config, args.traffic))
    if args.kmeans_iters:
        from repro.launch import serve

        name = cell.config["index"]
        serve.INDEXES[name] = short_kmeans(serve.INDEXES[name],
                                           args.kmeans_iters)
    if args.pq_iters:
        from repro.core import pq

        pq.train_pq = short_codebooks(pq.train_pq, args.pq_iters)
    seeds = [int(x) for x in args.seeds.split(",")]
    s = setup(cell, seeds[0])
    limits = cell.config["limits"]
    ways = ("program", "control", *FAULTS)
    worst = {}
    for seed in seeds:
        d, sched = window(s, seed, args.seconds)
        got = readings(cell, collect(s, d, sched, seed))
        got["correct"] = all(got["program"][n] <= limits[n] for n in limits)
        got["seed"] = seed
        print(json.dumps(got), flush=True)
        for way in ways:
            w = worst.setdefault(way, {})
            for n, v in got[way].items():
                w[n] = (max if way == "program" else min)(w.get(n, v), v)
    s.rt.stop()
    print(json.dumps({"cell": cell.name, "seeds": len(seeds),
                      "kmeans_iters": args.kmeans_iters or "program",
                      "pq_iters": args.pq_iters or "program",
                      "largest_program": worst["program"],
                      "smallest": {w: worst[w] for w in ways[1:]}}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
