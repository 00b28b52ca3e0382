"""The benchmark's references against brute force, at tiny sizes on the
CPU, and the precision steps its controls take."""

import numpy as np
import pytest

from bench.check import Checker, History, Readback, Served
from bench.reference import ivf_flat, ivf_pq, kmeans
from bench.reference.common import (assign, assign_mismatch, dot, probe,
                                    sq_dists)

#: what the checker reads of a configuration
CONFIG = {"nprobe": 4, "k": 10, "kmeans_iters": 10, "pq_iters": 15,
          "pq_train_rows": 65536}


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
    best, second, tie, dist = assign(x, c, chunk=64)
    d = ((x[:, None].astype(np.float64) - c[None]) ** 2).sum(-1)
    order = np.argsort(d, 1)
    clear = ~tie
    assert (best[clear] == order[clear, 0]).all()
    assert (second[clear] == order[clear, 1]).all()
    np.testing.assert_allclose(dist, d.min(1), rtol=1e-5)


def unit_rows(rng, n, d=64, topics=16):
    """DSSM-like rows: unit-norm, clustered round a few topics."""
    x = rng.normal(size=(topics, d))[rng.integers(0, topics, n)]
    x = x + 0.3 * rng.normal(size=(n, d))
    return (x / np.linalg.norm(x, axis=1, keepdims=True)).astype(np.float32)


def test_assign_is_exact_on_unit_rows():
    """Many lists close together, as in the DSSM corpus: the device's
    nearest list is the f64 nearest of every row, ties excepted, and the
    reference's check of itself reads 0; a wrong list is counted."""
    rng = np.random.default_rng(11)
    x, c = unit_rows(rng, 3000), unit_rows(rng, 400)
    best, second, tie, _ = assign(x, c)
    d = ((x[:, None].astype(np.float64) - c[None]) ** 2).sum(-1)
    clear = ~tie
    assert (best[clear] == d.argmin(1)[clear]).all()
    assert assign_mismatch(x, c, best, np.random.default_rng(0),
                           sample=3000) == 0
    wrong = np.where(clear, second, best)
    assert assign_mismatch(x, c, wrong, np.random.default_rng(0),
                           sample=3000) == clear.sum()


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
    s = ivf_pq.Scorer({"centroids": c, "codebooks": books}, "highest")
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
    np.testing.assert_allclose(s.scores(q, codes, lists), want, rtol=1e-5)
    fp8 = ivf_pq.Scorer({"centroids": c, "codebooks": books}, "fp8")
    assert (fp8.encode(x, lists) != codes).any()


def _tiny_index(rng, n=600, lists=12):
    x = sift_rows(rng, n)
    c = x[rng.choice(n, lists, replace=False)]
    best = assign(x, c)[0]
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
    chk = Checker(ivf_flat, {"centroids": c}, CONFIG, versions, rb)
    q = sift_rows(rng, 40)
    t = np.zeros(len(q))
    blank = Served(q, t, t + 1, np.zeros((40, 10), np.int64),
                   np.zeros((40, 10), np.float32))
    exact = chk.control_answers(blank, "highest")
    good = chk.numbers(exact)
    assert good["foreign_ids"] == good["resident_mismatch"] == 0
    assert good["dist_gap"] < 0.05 and good["missed_gap"] == 0
    assert good["stored_mismatch"] == good["list_mismatch"] == 0
    assert good["ref_assign_mismatch"] == 0
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
    chk = Checker(ivf_flat, {"centroids": c}, CONFIG, versions, rb)
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


def _tiny_pq_index(rng, n=600, lists=12, m=4):
    """Unit rows in their nearest lists, stored as the reference's codes."""
    x = unit_rows(rng, n, d=16)
    c = x[rng.choice(n, lists, replace=False)]
    best, second, tie, _ = assign(x, c)
    r = (x - c[best]).reshape(n, m, -1)
    books = np.stack([r[rng.choice(n, 256), j] for j in range(m)])
    quantizer = {"centroids": c, "codebooks": books.astype(np.float32)}
    codes = ivf_pq.Scorer(quantizer, "highest").encode(x, best)
    return x, quantizer, best, second, tie, codes


def _pq_checker(x, quantizer, lists, rows):
    rb = Readback(np.arange(len(x)), lists, rows, 0)
    return Checker(ivf_pq, quantizer, CONFIG, History(x).versions(), rb)


def test_pq_reference_answers_pass_and_fp8_fails():
    """Through the contract: the reference's own answers and codes pass;
    its control at fp8 fails on the distance gap and the codes."""
    rng = np.random.default_rng(7)
    x, quantizer, best, _, _, codes = _tiny_pq_index(rng)
    chk = _pq_checker(x, quantizer, best,
                      {i: codes[i] for i in range(0, len(x), 3)})
    q = unit_rows(rng, 40, d=16)
    t = np.zeros(len(q))
    blank = Served(q, t, t + 1, np.zeros((40, 10), np.int64),
                   np.zeros((40, 10), np.float32))
    good = chk.numbers(chk.control_answers(blank, "highest"))
    assert good["foreign_ids"] == good["resident_mismatch"] == 0
    assert good["dist_gap"] < 1e-6 and good["missed_gap"] == 0
    assert good["stored_mismatch"] == good["list_mismatch"] == 0
    assert good["code_mismatch_share"] == 0
    assert good["ref_assign_mismatch"] == 0
    bad = chk.numbers(blank, control="fp8")
    assert bad["dist_gap"] > 1e-3
    assert bad["code_mismatch_share"] > 0.1


@pytest.mark.parametrize("fault", ["altered_code", "garbled_row",
                                   "second_list"])
def test_pq_checker_catches_stored_faults(fault):
    rng = np.random.default_rng(8)
    x, quantizer, best, second, tie, codes = _tiny_pq_index(rng)
    lists = best.copy()
    rows = {i: codes[i].copy() for i in range(0, len(x), 3)}
    if fault == "altered_code":
        rows[0][1] += 1
    elif fault == "garbled_row":
        rows[0] = rows[0] ^ np.uint8(1)
    else:  # stored, and encoded, in the list after its nearest
        j = np.flatnonzero(~tie)[0]
        lists[j] = second[j]
        rows[j] = ivf_pq.Scorer(quantizer, "highest").encode(
            x[j : j + 1], lists[j : j + 1])[0]
    n = _pq_checker(x, quantizer, lists, rows).numbers(Served(
        x[:0], np.zeros(0), np.zeros(0), np.zeros((0, 10), np.int64),
        np.zeros((0, 10), np.float32)))
    if fault == "altered_code":
        assert n["code_mismatch_share"] > 0 and n["stored_mismatch"] == 0
    elif fault == "garbled_row":
        assert n["stored_mismatch"] == 1
    else:
        assert n["list_mismatch"] == 1
        assert n["stored_mismatch"] == 0


def test_reference_kmeans_objective():
    """The objective, the sum of the distances ``assign`` returns, is the
    plain sum of squared distances to the nearest centroid; Lloyd steps
    lower it from the starting sample, and the first step lowers it most."""
    rng = np.random.default_rng(6)
    x = sift_rows(rng, 3000)
    costs = []
    for iters in (0, 1, 10):
        c = kmeans.lloyd(x, 12, iters, chunk=512)
        cost = np.sum(assign(x, c)[3], dtype=np.float64)
        d = ((x[:, None].astype(np.float64) - c[None]) ** 2).sum(-1)
        np.testing.assert_allclose(cost, d.min(1).sum(), rtol=1e-6)
        costs.append(cost)
    assert costs[0] > costs[1] >= costs[2]
    assert costs[0] - costs[1] > costs[1] - costs[2]


def test_pq_reference_checks_its_own_codes():
    """The reference's device codes agree with an f64 encoding on the
    host; a code moved to another codeword is counted, once per row."""
    rng = np.random.default_rng(9)
    x, quantizer, best, _, _, codes = _tiny_pq_index(rng)
    s = ivf_pq.Scorer(quantizer, "highest")
    n = s.encode_numbers(x, best, codes, np.random.default_rng(0),
                         sample=len(x))
    assert n == {"ref_code_mismatch": 0}
    bad = codes.copy()
    bad[::4, 1] += 1
    bad[::4, 2] += 1
    n = s.encode_numbers(x, best, bad, np.random.default_rng(0),
                         sample=len(x))
    assert n["ref_code_mismatch"] == len(x[::4])


@pytest.mark.parametrize("iters, low, high", [(15, -0.02, 0.02),
                                              (1, 0.03, np.inf)])
def test_codebook_excess_reads_the_training(iters, low, high):
    """Codebooks trained as the configuration states read an excess near
    0 against the reference's own; codebooks stopped after one Lloyd step
    read more."""
    rng = np.random.default_rng(10)
    x = unit_rows(rng, 6000, d=16)
    c = x[rng.choice(len(x), 12, replace=False)]
    best = assign(x, c)[0]
    res = x - c[best]
    # the program's training: its own starting codewords and sample
    start = np.random.default_rng(1).permutation(len(x))
    books = ivf_pq.train_codebooks(res[start], 4, iters)
    n = ivf_pq.quantizer_numbers({"centroids": c, "codebooks": books}, x,
                                 best, CONFIG, np.random.default_rng(2))
    assert low < n["codebook_excess"] < high, n
