"""The benchmark's references against brute force, at tiny sizes on the
CPU, and the precision steps its controls take."""

import numpy as np
import pytest

from bench.check import Checker, History, Readback, Served
from bench.reference import ivf_flat, ivf_pq, kmeans
from bench.reference.common import assign, dot, probe, sq_dists


def sift_rows(rng, n, d=128):
    centers = rng.gamma(2.0, 20.0, (8, d))
    x = centers[rng.integers(0, 8, n)] + rng.normal(0, 8.0, (n, d))
    return np.maximum(x, 0).astype(np.float32)


def test_dot_precisions_order():
    rng = np.random.default_rng(0)
    a, b = sift_rows(rng, 64), sift_rows(rng, 256)
    exact = dot(a, b, "highest")
    err = {p: np.abs(dot(a, b, p) - exact).max() for p in ("high", "fp8")}
    assert 0 < err["high"] < err["fp8"]
    assert err["high"] < 1e-4 * np.abs(exact).max()


def test_assign_is_nearest_list():
    rng = np.random.default_rng(1)
    x, c = sift_rows(rng, 500), sift_rows(rng, 20)
    best, second, tie = assign(x, c, chunk=64)
    d = ((x[:, None].astype(np.float64) - c[None]) ** 2).sum(-1)
    order = np.argsort(d, 1)
    clear = ~tie
    assert (best[clear] == order[clear, 0]).all()
    assert (second[clear] == order[clear, 1]).all()


def test_probe_sure_lists_are_the_nearest():
    rng = np.random.default_rng(2)
    q, c = sift_rows(rng, 30), sift_rows(rng, 50)
    sure, may = probe(q, c, nprobe=8)
    d, _ = sq_dists(q, c)
    top = np.argsort(d, 1)[:, :8]
    for j in range(len(q)):
        assert set(np.flatnonzero(sure[j])) <= set(top[j]) <= set(
            np.flatnonzero(may[j]))


def test_pq_scores_are_adc_over_codes():
    rng = np.random.default_rng(3)
    c = rng.normal(size=(4, 8)).astype(np.float32)
    books = rng.normal(size=(2, 256, 4)).astype(np.float32)
    s = ivf_pq.Scorer(c, books, "highest")
    x = rng.normal(size=(20, 8)).astype(np.float32)
    lists = rng.integers(0, 4, 20)
    codes = s.encode(x, lists)
    r = x - c[lists]
    for j in range(2):
        d = ((r[:, None, 4 * j : 4 * j + 4] - books[j][None]) ** 2).sum(-1)
        assert (codes[:, j] == d.argmin(1)).all()
    q = rng.normal(size=8).astype(np.float32)
    recon = c[lists] + np.concatenate([books[0][codes[:, 0]],
                                       books[1][codes[:, 1]]], 1)
    want = (((q - c[lists]) - (recon - c[lists])) ** 2).sum(-1)
    np.testing.assert_allclose(s.scores(q, None, lists, codes), want,
                               rtol=1e-5)


def _tiny_index(rng, n=600, lists=12):
    x = sift_rows(rng, n)
    c = x[rng.choice(n, lists, replace=False)]
    best, _, _ = assign(x, c)
    return x, c, best


def test_exact_answers_pass_and_high_answers_fail():
    """The checker passes the reference's own answers at HIGHEST; a
    control one precision step down fails on the distance gap."""
    rng = np.random.default_rng(4)
    x, c, best = _tiny_index(rng)
    hist = History(x)
    versions = hist.versions()
    rb = Readback(np.arange(len(x)), best,
                  {i: x[i] for i in range(0, len(x), 7)}, 0)
    chk = Checker(ivf_flat, c, nprobe=4, k=10, kmeans_iters=10,
                  versions=versions, readback=rb)
    q = sift_rows(rng, 40)
    t = np.zeros(len(q))
    blank = Served(q, t, t + 1, np.zeros((40, 10), np.int64),
                   np.zeros((40, 10), np.float32))
    exact = chk.control_answers(blank, "highest")
    good = chk.numbers(exact)
    assert good["foreign_ids"] == good["resident_mismatch"] == 0
    assert good["dist_gap"] < 0.05 and good["missed_gap"] == 0
    assert good["stored_mismatch"] == good["list_mismatch"] == 0
    bad = chk.numbers(blank, control="high")
    assert bad["dist_gap"] > 10 * max(good["dist_gap"], 0.01)


@pytest.mark.parametrize("fault", ["foreign", "deleted", "short"])
def test_checker_catches_wrong_answers(fault):
    rng = np.random.default_rng(5)
    x, c, best = _tiny_index(rng)
    hist = History(x)
    gone = np.arange(0, len(x), 3)
    hist.add("delete", gone, None, 0.0, 0.5, True)
    versions = hist.versions()
    keep = np.setdiff1d(np.arange(len(x)), gone)
    rb = Readback(keep, best[keep], {}, 0)
    chk = Checker(ivf_flat, c, nprobe=4, k=10, kmeans_iters=10,
                  versions=versions, readback=rb)
    q = sift_rows(rng, 20)
    t = np.ones(len(q))
    blank = Served(q, t, t + 1, np.zeros((20, 10), np.int64),
                   np.zeros((20, 10), np.float32))
    ans = chk.control_answers(blank, "highest")
    assert chk.numbers(ans)["foreign_ids"] == 0
    if fault == "foreign":
        ans.ids[:, 0] = len(x) + 5
    elif fault == "deleted":
        ans.ids[:, 0] = gone[:20]
    else:
        ans.ids[:, 5:] = -1
    n = chk.numbers(ans)
    assert n["foreign_ids"] > 0 or n["missed_gap"] > 1.0


def test_reference_kmeans_objective():
    """The objective is the plain sum of squared distances to the nearest
    centroid; Lloyd steps lower it from the starting sample, and the first
    step lowers it most."""
    rng = np.random.default_rng(6)
    x = sift_rows(rng, 3000)
    costs = []
    for iters in (0, 1, 10):
        c = kmeans.lloyd(x, 12, iters, chunk=512)
        best, _, _ = assign(x, c)
        d = ((x[:, None].astype(np.float64) - c[None]) ** 2).sum(-1)
        np.testing.assert_allclose(kmeans.objective(x, c, best, chunk=700),
                                   d.min(1).sum(), rtol=1e-6)
        costs.append(kmeans.objective(x, c, best))
    assert costs[0] > costs[1] >= costs[2]
    assert costs[0] - costs[1] > costs[1] - costs[2]
