"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracle."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.ivf_scan import (
    ivf_block_scan,
    ivf_block_topk,
    ivf_block_topk_scan,
)
from repro.kernels.paged_attention import paged_decode_attention
from repro.kernels.pq_adc import pq_adc


@pytest.mark.parametrize(
    "q,d,p,t,c",
    [
        (8, 64, 16, 128, 4),
        (16, 128, 32, 256, 9),
        (8, 32, 7, 8, 7),  # odd sizes
        (1, 128, 4, 64, 2),
    ],
)
def test_ivf_block_scan_matches_ref(q, d, p, t, c):
    rng = np.random.default_rng(q * 1000 + t)
    queries = jnp.asarray(rng.normal(size=(q, d)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(p, t, d)), jnp.float32)
    ids = jnp.asarray(rng.integers(-1, p, size=(c,)), jnp.int32)
    got = ivf_block_scan(queries, pool, ids, interpret=True)
    want = ref.ivf_block_scan_ref(queries, pool, ids)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def _topk_inputs(q, d, p, t, c, seed, hole_frac=0.25, empty_frac=0.3,
                 ncl=8, nprobe=6, dead_frac=0.2):
    """Union-scan shaped inputs: hole blocks (-1 in the NULL-padded union),
    empty (-1) id slots, tombstoned (live == 0) rows, and owner/probe-list
    routing (membership is derived in-kernel: a query owns a candidate iff
    its distinct probe list contains the candidate's owner; NULL slots own
    -1)."""
    rng = np.random.default_rng(seed)
    queries = jnp.asarray(rng.normal(size=(q, d)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(p, t, d)), jnp.float32)
    ids = rng.integers(0, p, size=(c,)).astype(np.int32)
    ids[rng.random(c) < hole_frac] = -1  # hole blocks
    pool_ids = rng.permutation(p * t).astype(np.int32).reshape(p, t)
    pool_ids[rng.random((p, t)) < empty_frac] = -1  # empty slots
    # occupied rows are live unless tombstoned (deleted rows keep their id)
    live = (pool_ids != -1) & (rng.random((p, t)) >= dead_frac)
    owners = rng.integers(0, ncl, size=(c,)).astype(np.int32)
    owners[ids == -1] = -1  # NULL slots own nothing
    probe = np.stack(
        [rng.permutation(ncl)[:nprobe] for _ in range(q)]
    ).astype(np.int32)
    return (queries, pool, jnp.asarray(ids), jnp.asarray(owners),
            jnp.asarray(pool_ids), jnp.asarray(live.astype(np.uint8)),
            jnp.asarray(probe))


@pytest.mark.parametrize(
    "q,d,p,t,c,kp",
    [
        (8, 64, 16, 128, 4, 16),
        (13, 32, 9, 16, 11, 8),  # Q not a multiple of 8 (pad path)
        (5, 128, 4, 64, 3, 256),  # kprime > live candidates
        (1, 64, 6, 8, 7, 4),
        (130, 32, 8, 16, 5, 8),  # Q > q_tile default tile split
    ],
)
def test_ivf_block_topk_matches_ref(q, d, p, t, c, kp):
    queries, pool, ids, owners, pool_ids, live, probe = _topk_inputs(
        q, d, p, t, c, seed=q + c
    )
    want_d, want_i = ref.ivf_block_topk_ref(
        queries, pool, ids, owners, pool_ids, live, probe, kprime=kp
    )
    got_d, got_i = ivf_block_topk(
        queries, pool, ids, owners, pool_ids, live, probe, kprime=kp,
        interpret=True,
    )
    np.testing.assert_allclose(got_d, want_d, rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(got_i, want_i)
    sc_d, sc_i = ivf_block_topk_scan(
        queries, pool, ids, owners, pool_ids, live, probe, kprime=kp,
        chunk=4,
    )
    np.testing.assert_allclose(sc_d, want_d, rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(sc_i, want_i)
    # tombstoned locations never appear in any impl's survivor set
    dead_locs = np.flatnonzero(
        (np.asarray(pool_ids).ravel() != -1)
        & (np.asarray(live).ravel() == 0)
    )
    for out in (want_i, got_i, sc_i):
        assert not np.isin(np.asarray(out), dead_locs).any()


def test_ivf_block_topk_all_holes_returns_inf():
    """A NULL-padded union with every candidate masked yields (inf, -1)."""
    q, d, p, t, c = 4, 16, 3, 8, 5
    rng = np.random.default_rng(0)
    queries = jnp.asarray(rng.normal(size=(q, d)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(p, t, d)), jnp.float32)
    ids = jnp.full((c,), -1, jnp.int32)
    owners = jnp.full((c,), -1, jnp.int32)  # NULL slots own nothing
    pool_ids = jnp.zeros((p, t), jnp.int32)
    live = jnp.ones((p, t), jnp.uint8)
    probe = jnp.asarray(rng.integers(0, 4, size=(q, 3)), jnp.int32)
    d_out, i_out = ivf_block_topk(
        queries, pool, ids, owners, pool_ids, live, probe, kprime=8,
        interpret=True,
    )
    assert np.isinf(np.asarray(d_out)).all()
    assert (np.asarray(i_out) == -1).all()


@pytest.fixture(scope="module")
def fused_index():
    from repro.core import build_ivf

    rng = np.random.default_rng(3)
    corpus = rng.normal(size=(1200, 32)).astype(np.float32)
    idx = build_ivf(corpus, n_clusters=8, block_size=16, max_chain=32,
                    nprobe=4, k=10, add_batch=512)
    q = jnp.asarray(corpus[rng.integers(0, len(corpus), 6)] + 0.001)
    return corpus, idx, q


@pytest.mark.parametrize("k", [1, 10, 100])
def test_union_fused_bit_identical_to_union(fused_index, k):
    """Acceptance: union, union_fused and union_fused_scan all meet the
    top-k contract against the IVF-exact f32-HIGHEST reference, k in
    {1, 10, 100} (exact equality holds on no platform: tests/topk_contract)."""
    from repro.core.reference import ivf_exact_topk, live_rows
    from repro.core.search import make_search_fn
    from topk_contract import assert_topk_contract, id_table

    corpus, idx, q = fused_index
    live = live_rows(idx.state)
    ref = ivf_exact_topk(idx.state, q, nprobe=4, k=k, live=live)
    for path in ("union", "union_fused", "union_fused_scan"):
        got = make_search_fn(idx.pool_cfg, nprobe=4, k=k, path=path)(
            idx.state, q
        )
        assert_topk_contract(q, id_table(live), got, ref, err_msg=path)


def test_union_fused_full_probe_matches_exact_oracle(fused_index):
    """Probing every cluster, the fused path must equal brute force."""
    from repro.core.search import exact_search, make_search_fn

    corpus, idx, q = fused_index
    d, i = make_search_fn(
        idx.pool_cfg, nprobe=idx.pool_cfg.n_clusters, k=10, path="union_fused"
    )(idx.state, q)
    de, ie = exact_search(jnp.asarray(corpus), q, 10)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ie))
    np.testing.assert_allclose(np.asarray(d), np.asarray(de), rtol=1e-5,
                               atol=1e-4)


def test_union_fused_k_exceeds_live_candidates(fused_index):
    """k > vectors in the probed lists: tail must be (inf, NULL)."""
    from repro.core.search import make_search_fn

    corpus, idx, q = fused_index
    d, i = make_search_fn(idx.pool_cfg, nprobe=1, k=300, path="union_fused")(
        idx.state, q
    )
    d, i = np.asarray(d), np.asarray(i)
    assert np.isinf(d).any(), "expected padded tail past the probed list"
    assert (i[np.isinf(d)] == -1).all()
    live = ~np.isinf(d)
    assert (i[live] >= 0).all()


@pytest.mark.parametrize(
    "r,m,n,tile",
    [(4, 8, 256, 128), (2, 16, 100, 64), (1, 4, 1024, 1024), (3, 32, 77, 32)],
)
def test_pq_adc_matches_ref(r, m, n, tile):
    rng = np.random.default_rng(r * 100 + n)
    lut = jnp.asarray(rng.normal(size=(r, m, 256)) ** 2, jnp.float32)
    codes = jnp.asarray(rng.integers(0, 256, size=(r, n, m)), jnp.int32)
    got = pq_adc(lut, codes, tile_n=tile, interpret=True)
    want = ref.pq_adc_ref(lut, codes)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize(
    "b,h,kvh,dh,t,nb,dtype",
    [
        (2, 8, 2, 64, 16, 4, jnp.float32),
        (1, 4, 4, 128, 32, 2, jnp.float32),  # MHA (G=1)
        (3, 8, 1, 64, 8, 5, jnp.float32),  # MQA
        (2, 8, 2, 64, 16, 4, jnp.bfloat16),
    ],
)
def test_paged_attention_matches_ref(b, h, kvh, dh, t, nb, dtype):
    rng = np.random.default_rng(b * 10 + h)
    p = nb * b + 2
    q = jnp.asarray(rng.normal(size=(b, h, dh)), dtype)
    k_pool = jnp.asarray(rng.normal(size=(p, t, kvh, dh)), dtype)
    v_pool = jnp.asarray(rng.normal(size=(p, t, kvh, dh)), dtype)
    # each sequence owns nb blocks; random lengths, some partial, one zero
    perm = rng.permutation(p)[: b * nb].reshape(b, nb).astype(np.int32)
    lengths = rng.integers(0, nb * t + 1, size=(b,)).astype(np.int32)
    lengths[0] = 0  # empty-cache edge case
    if b > 1:
        lengths[1] = nb * t  # full
    tables = np.where(
        np.arange(nb)[None, :] * t < np.maximum(lengths, 1)[:, None], perm, -1
    ).astype(np.int32)
    got = paged_decode_attention(
        q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lengths),
        interpret=True,
    )
    want = ref.paged_decode_attention_ref(
        q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lengths)
    )
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )
