"""The one contract between a top-k search implementation and the
f32-``HIGHEST`` reference (``repro.core.reference``).

Implementations accumulate distances in different orders (XLA fusions,
Mosaic tiles, the interpreter), so exact equality holds on no platform.
What must hold, per query row and rank ``j``:

* distances agree within ``8 * eps_f32 * (|q|^2 + |v|^2)``, the rounding
  bound of the ``|q|^2 + |v|^2 - 2 q.v`` form;
* ids are equal, except where the reference distances tie within that
  bound: a returned id that differs from the reference's must itself lie
  at the reference distance of rank ``j``, within the bound;
* padding (id -1, distance inf) sits exactly where the reference has it,
  and no id repeats within a row.
"""

import numpy as np

from repro.core.reference import l2_sq

EPS32 = float(np.finfo(np.float32).eps)


def assert_topk_contract(queries, table, got, ref, err_msg=""):
    """``table[id]`` is the stored vector of ``id``; ``got`` and ``ref``
    are ``(dists, ids)`` pairs of shape [Q, k]."""
    queries = np.asarray(queries, np.float32)
    table = np.asarray(table)
    gd, gi = (np.asarray(a) for a in got)
    rd, ri = (np.asarray(a) for a in ref)
    assert gd.shape == rd.shape and gi.shape == ri.shape, err_msg
    pad = ri == -1
    np.testing.assert_array_equal(gi == -1, pad, err_msg=err_msg)
    assert np.isinf(gd[pad]).all() and np.isinf(rd[pad]).all(), err_msg
    for row in gi:
        live = row[row != -1]
        assert len(np.unique(live)) == len(live), (err_msg, row)

    def dist_and_norm(ids):
        v = table[np.where(ids == -1, 0, ids)]  # [Q, k, D]
        d = np.stack([
            np.asarray(l2_sq(q[None], vq))[0] for q, vq in zip(queries, v)
        ])
        vf = v.astype(np.float32)
        return d, np.sum(vf * vf, axis=-1)

    d_got_ids, n_got = dist_and_norm(gi)
    _, n_ref = dist_and_norm(ri)
    qn = np.sum(queries * queries, axis=-1)[:, None]
    tol = 8 * EPS32 * (qn + np.maximum(n_ref, n_got))
    ok = ~pad
    _within(np.abs(gd - rd)[ok], tol[ok],
            f"{err_msg}: distances outside the rounding bound")
    swapped = ok & (gi != ri)
    _within(np.abs(d_got_ids - rd)[swapped], tol[swapped],
            f"{err_msg}: an id differs where the reference does not tie")


def _within(err, tol, what):
    bad = err > tol
    assert not bad.any(), (
        f"{what}: {int(bad.sum())} entries, worst {err[bad].max()} "
        f"against a bound of {tol[bad][np.argmax(err[bad])]}"
    )


def id_table(live):
    """Dense ``table[id] = stored row`` over a pool's live rows
    (``repro.core.reference.live_rows``)."""
    rows = np.asarray(live.rows)
    table = np.zeros((int(live.ids.max()) + 1, rows.shape[1]), rows.dtype)
    table[live.ids] = rows
    return table
