"""The SIFT configuration's check is not loosened: over one fixed tiny
index, with mutations and searches sent one at a time (so that nothing
depends on timing), every number it compares is what the harness gave
before its reference took the index's quantizer and assigned rows by one
contraction at ``HIGHEST`` with a best-two reduction.  ``BEFORE`` was
recorded by running ``evidence`` and that harness's
``Checker(ivf_flat, centroids, ...)`` on the same seed."""

import dataclasses
import time

import numpy as np

from bench import loadgen
from bench.check import Served
from bench.run import Evidence, checker, readback, search_now, setup
from bench.spec import load_cell
from bench.traffic import draw_rows

SEED = 2**31 + 777
#: the check's numbers from the harness before the change, same seed
BEFORE = {
    "foreign_ids": 0,
    "dist_gap": 0.4640229728538543,
    "missed_gap": 0.0,
    "resident_mismatch": 0,
    "list_mismatch": 0,
    "invariant_faults": 0,
    "stored_mismatch": 0,
    "kmeans_excess": -0.04000974586641426,
}


def evidence(cell, seed: int, scale: float) -> tuple:
    """(served, versions, readback, centroids) of a fixed sequence: the
    cell's set-up, inserts, deletes and updates each acked before the next,
    then 24 searches one at a time."""
    cfg = cell.config
    s = setup(cell, seed, scale=scale)
    try:
        rng = np.random.default_rng([seed, 9])
        targets = rng.choice(len(s.corpus), 6, replace=False)
        for kind, n, ids in (("insert", 4, None), ("delete", 3, targets[:3]),
                             ("insert", 4, None), ("update", 3, targets[3:])):
            vecs = draw_rows(rng, s.corpus, n, cfg["draw"])
            t = time.perf_counter()
            res = loadgen.submit_mutation(s.rt, kind, vecs, ids).result(
                timeout=300)
            s.record(kind, True, res, vecs, ids, t, time.perf_counter())
        queries = draw_rows(rng, s.corpus, 24, cfg["draw"])
        sent, done, ids, dists = search_now(s, queries, wave=1)
        k = cfg["k"]
        served = Served(queries, sent, done, ids.reshape(-1, k),
                        dists.reshape(-1, k))
        versions = s.history.versions()
        written = versions.ids[versions.start_hi > -np.inf]
        check_ids = np.unique(np.concatenate([
            written, rng.choice(len(s.corpus), 256, replace=False)]))
        return (served, versions, readback(s, check_ids),
                np.asarray(s.index.state.centroids))
    finally:
        s.rt.stop()


def test_sift_check_reads_as_before():
    cell = load_cell("sift1m_flat.steady")
    cell = dataclasses.replace(cell, traffic=dict(
        cell.traffic, warm_rows={"insert": [16, 8]}))
    served, versions, rb, centroids = evidence(
        cell, SEED, cell.config["tiny_scale"])
    ev = Evidence(served, versions, rb, {"centroids": centroids}, 0, SEED, {})
    got = checker(cell, ev).numbers(served)
    assert got["ref_assign_mismatch"] == 0
    for name, before in BEFORE.items():
        if name == "kmeans_excess":
            assert abs(got[name] - before) <= 1e-6, (name, got[name], before)
        else:
            assert got[name] == before, (name, got[name], before)
