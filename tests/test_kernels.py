"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantization import quantize, quantize_per_cluster
from repro.kernels import (flash_attention, flash_attention_ref, gleanvec_ip,
                           gleanvec_ip_ref, gleanvec_sq, gleanvec_sq_ref,
                           gleanvec_sq_sorted_ref, gleanvec_sq_topk,
                           gleanvec_sq_topk_ref, graph_scan_beam_step,
                           graph_scan_beam_step_ref, ip_topk, ip_topk_ref,
                           ivf_scan_topk, ivf_scan_topk_ref, kmeans_assign,
                           kmeans_assign_ref, sq_dot, sq_dot_ref)

RNG = np.random.default_rng(0)


def _randn(*shape, dtype=np.float32):
    return jnp.asarray(RNG.standard_normal(shape).astype(dtype))


def _sq_inputs(m, n, c, d):
    """Random per-cluster int8 database + query-side folded affine terms."""
    x_low = _randn(n, d)
    tags = jnp.asarray(RNG.integers(0, c, n).astype(np.int32))
    db = quantize_per_cluster(x_low, tags, c)
    q_views = _randn(m, c, d)
    q_scaled = q_views * db.delta[None]
    q_lo = jnp.einsum("mcd,cd->mc", q_views, db.lo)
    return q_scaled, q_lo, tags, db.codes


@pytest.mark.parametrize("m,n,d,k,tm,tn", [
    (8, 256, 32, 5, 8, 64),
    (20, 1000, 96, 10, 8, 128),     # non-divisible m/n -> padding
    (1, 513, 64, 16, 8, 256),
    (33, 4096, 160, 100, 16, 512),  # paper-scale d=160, k=100
])
def test_ip_topk_matches_ref(m, n, d, k, tm, tn):
    q, x = _randn(m, d), _randn(n, d)
    v, i = ip_topk(q, x, k, tm=tm, tn=tn, interpret=True)
    vr, ir = ip_topk_ref(q, x, k)
    np.testing.assert_allclose(np.asarray(v), np.asarray(vr), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))


@pytest.mark.parametrize("dtype", [np.float32, np.float16])
def test_ip_topk_dtypes(dtype):
    q, x = _randn(4, 32, dtype=dtype), _randn(128, 32, dtype=dtype)
    v, i = ip_topk(q, x, 5, tm=4, tn=64, interpret=True)
    vr, ir = ip_topk_ref(q, x, 5)
    np.testing.assert_allclose(np.asarray(v), np.asarray(vr), rtol=1e-2,
                               atol=1e-2)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ir))


@pytest.mark.parametrize("m,n,c,d,tm,tn", [
    (3, 300, 8, 24, 2, 128),
    (5, 700, 16, 48, 4, 256),
    (1, 100, 48, 192, 1, 64),       # paper C=48, d=192 (t2i)
])
def test_gleanvec_ip_matches_ref(m, n, c, d, tm, tn):
    q_views = _randn(m, c, d)
    tags = jnp.asarray(RNG.integers(0, c, n).astype(np.int32))
    x_low = _randn(n, d)
    a = gleanvec_ip(q_views, tags, x_low, tm=tm, tn=tn, interpret=True)
    b = gleanvec_ip_ref(q_views, tags, x_low)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.tier1
@pytest.mark.parametrize("m,n,c,d,tm,tn", [
    (3, 300, 8, 24, 2, 128),
    (5, 1000, 16, 48, 4, 256),      # non-divisible m/n -> padding
    (1, 100, 48, 192, 1, 64),       # paper C=48, d=192 (t2i)
])
def test_gleanvec_sq_matches_ref(m, n, c, d, tm, tn):
    """Fused tag-select + int8 dot + per-cluster affine == jnp oracle."""
    q_scaled, q_lo, tags, codes = _sq_inputs(m, n, c, d)
    a = gleanvec_sq(q_scaled, q_lo, tags, codes, tm=tm, tn=tn,
                    interpret=True)
    b = gleanvec_sq_ref(q_scaled, q_lo, tags, codes)
    scale = float(jnp.abs(b).max())
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-2,
                               atol=1e-2 * scale)


@pytest.mark.tier1
@pytest.mark.parametrize("m,nb,c,d,lb,tn", [
    (4, 8, 6, 32, 128, 64),         # layout_block % tn == 0
    (3, 5, 8, 48, 64, 256),         # tn shrunk to the layout block
    (2, 6, 4, 16, 96, 256),         # neither divides -> gathered fallback
])
def test_gleanvec_sq_sorted_matches_ref(m, nb, c, d, lb, tn):
    """Single-tag-per-tile sorted path == expanded-tags oracle, including
    the tile-shrink and gathered fallbacks of the dispatcher."""
    n = nb * lb
    q_scaled, q_lo, _, codes = _sq_inputs(m, n, c, d)
    block_tags = jnp.asarray(RNG.integers(0, c, nb).astype(np.int32))
    a = gleanvec_sq(q_scaled, q_lo, block_tags, codes, layout_block=lb,
                    tm=2, tn=tn, interpret=True)
    b = gleanvec_sq_sorted_ref(q_scaled, q_lo, block_tags, codes, lb)
    scale = float(jnp.abs(b).max())
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-2,
                               atol=1e-2 * scale)


@pytest.mark.tier1
@pytest.mark.parametrize("m,n,c,d,k", [(4, 700, 8, 24, 10), (9, 300, 5, 16, 7)])
def test_gleanvec_sq_topk_matches_ref(m, n, c, d, k):
    """Fused blocked top-k (no dense (m, n)) == dense-then-top_k oracle."""
    q_scaled, q_lo, tags, codes = _sq_inputs(m, n, c, d)
    v1, i1 = gleanvec_sq_topk(q_scaled, q_lo, tags, codes, k, tm=4, tn=128,
                              interpret=True)
    v2, i2 = gleanvec_sq_topk_ref(q_scaled, q_lo, tags, codes, k)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-4,
                               atol=1e-3)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


@pytest.mark.tier1
def test_gleanvec_sq_topk_sorted_emits_external_ids():
    """row_ids (the sort permutation) come straight out of the kernel and
    -1 padding rows can never win."""
    m, nb, c, d, lb, k = 3, 6, 4, 16, 128, 12
    n = nb * lb
    q_scaled, q_lo, _, codes = _sq_inputs(m, n, c, d)
    block_tags = jnp.asarray(RNG.integers(0, c, nb).astype(np.int32))
    perm = np.full(n, -1, np.int32)
    valid = RNG.permutation(n)[: n - 100]           # 100 padding rows
    perm[np.sort(valid)] = RNG.permutation(len(valid)).astype(np.int32)
    perm = jnp.asarray(perm)
    v1, i1 = gleanvec_sq_topk(q_scaled, q_lo, block_tags, codes, k,
                              row_ids=perm, layout_block=lb, tm=2, tn=64,
                              interpret=True)
    v2, i2 = gleanvec_sq_topk_ref(q_scaled, q_lo, block_tags, codes, k,
                                  row_ids=perm, layout_block=lb)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-4,
                               atol=1e-3)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    assert (np.asarray(i1) >= 0).all()              # padding never wins


def _scan_inputs(m, nb, c, d, lb, p, n_pad=0, f32=False, seed=1):
    """Random sorted-layout inputs + a -1-padded per-query probe of ``p``
    cluster ids (repeats and unprobed clusters included -- the kernel must
    score each probed cluster's rows once and never an unprobed one)."""
    rng = np.random.default_rng(seed)
    n = nb * lb
    q_scaled, q_lo, _, codes = _sq_inputs(m, n, c, d)
    if f32:
        codes = _randn(n, d)
    block_tags = jnp.asarray(rng.integers(0, c, nb).astype(np.int32))
    perm = np.arange(n, dtype=np.int32)
    if n_pad:
        perm[rng.permutation(n)[:n_pad]] = -1        # dead/padding rows
    probe = rng.integers(-1, c, (m, p)).astype(np.int32)
    return (q_scaled, q_lo, block_tags, jnp.asarray(perm), codes,
            jnp.asarray(probe))


def _assert_scan_matches_ref(args, k, lb, tn, atol=1e-3):
    v1, i1 = ivf_scan_topk(*args, k, layout_block=lb, tn=tn, interpret=True)
    v2, i2 = ivf_scan_topk_ref(*args, k, layout_block=lb)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-4,
                               atol=atol)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    return np.asarray(v1), np.asarray(i1)


@pytest.mark.tier1
@pytest.mark.parametrize("m,nb,c,d,lb,p,tn", [
    (4, 8, 6, 32, 128, 3, 64),      # layout_block % tn == 0
    (3, 5, 8, 48, 64, 5, 256),      # tn > layout_block -> tile shrink
    (1, 6, 4, 16, 96, 2, 64),       # tn does not divide -> tile shrink
])
def test_ivf_scan_topk_matches_ref(m, nb, c, d, lb, p, tn):
    """Block-major range-scan kernel == set-of-probed-blocks oracle: the
    union of the batch's probed blocks streams once, each query scores
    only its own clusters' rows, -1 probe pads and -1 row_ids never win."""
    args = _scan_inputs(m, nb, c, d, lb, p, n_pad=40)
    _assert_scan_matches_ref(args, 7, lb, tn)


@pytest.mark.tier1
def test_ivf_scan_topk_f32_rows_and_empty_schedule():
    """The unquantized sorted scorer's f32 rows ride the same kernel, and
    an all -1 probe row returns (-inf, -1) everywhere."""
    qs, ql, bt, rid, codes, probe = _scan_inputs(2, 6, 4, 24, 64, 4,
                                                 f32=True)
    probe = probe.at[1].set(-1)                      # query 1: no clusters
    v1, i1 = _assert_scan_matches_ref((qs, ql, bt, rid, codes, probe), 5,
                                      64, 64, atol=1e-4)
    assert (i1[1] == -1).all()
    assert (v1[1] < -1e37).all()


def _clustered_layout(per, lb, slack=0, n_dead=0, seed=5):
    """A sorted layout as ``sort_by_tag`` makes it: cluster ``t``'s
    ``per[t]`` blocks contiguous, ascending by tag, then ``slack`` blocks of
    dead rows (a streaming store's free room); ``n_dead`` more rows die at
    random (removed rows)."""
    rng = np.random.default_rng(seed)
    live_block = np.concatenate([np.arange(p + slack) < p for p in per])
    tags = np.repeat(np.arange(len(per)), np.asarray(per) + slack)
    n = tags.size * lb
    perm = rng.permutation(n).astype(np.int32)
    perm[~np.repeat(live_block, lb)] = -1
    perm[rng.permutation(n)[:n_dead]] = -1
    return jnp.asarray(tags.astype(np.int32)), jnp.asarray(perm), n


@pytest.mark.tier1
@pytest.mark.parametrize("case", ["shared64", "dead_and_slack"])
def test_ivf_scan_topk_batch_cases(case):
    """(a) a 64-row batch whose queries share clusters, each probing its
    own clusters in its own order, with repeats and pads; (b) dead rows
    (``row_ids == -1``) and slack blocks of dead rows in every cluster.
    Each query's answer is the oracle's over its probed clusters alone,
    whatever the other queries of the batch probe."""
    rng = np.random.default_rng(11)
    c, d, lb = 6, 32, 128
    if case == "shared64":
        m, slack, n_dead = 64, 0, 0
        per = rng.integers(1, 4, c)
    else:
        m, slack, n_dead = 8, 2, 300
        per = rng.integers(1, 3, c)
    bt, rid, n = _clustered_layout(per, lb, slack=slack, n_dead=n_dead)
    q_scaled, q_lo, _, codes = _sq_inputs(m, n, c, d)
    probe = np.stack([rng.permutation(c)[:3] for _ in range(m)])
    probe[::5, 2] = probe[::5, 0]                    # a repeated cluster
    probe[::7, 1:] = -1                              # pads
    probe = jnp.asarray(probe.astype(np.int32))
    args = (q_scaled, q_lo, bt, rid, codes, probe)
    _, ids = _assert_scan_matches_ref(args, 10, lb, 64)
    # each row alone gives the same answer as inside the batch
    for i in (0, 5, 7, m - 1):
        one = (q_scaled[i:i + 1], q_lo[i:i + 1], bt, rid, codes,
               probe[i:i + 1])
        np.testing.assert_array_equal(
            _assert_scan_matches_ref(one, 10, lb, 64)[1][0], ids[i])
    live = np.asarray(rid)
    assert np.isin(ids[ids >= 0], live[live >= 0]).all()


@pytest.mark.tier1
@pytest.mark.parametrize("mode", ["gleanvec-sorted", "gleanvec-int8-sorted"])
def test_ivf_scan_topk_single_query_matches_gathered(mode):
    """(c) M=1: the kernel over one query's probe returns the gathered IVF
    path's (value, id) set for both sorted scorer families."""
    from helpers import assert_same_topk
    from repro.core import gleanvec as gv, scorer as sc
    from repro.data import vectors
    from repro.index import ivf
    from repro.index.protocol import replace

    ds = vectors.make_dataset("ivfscan-m1", n=1024, d=32, n_queries=4,
                              ood=True, seed=9)
    X = jnp.asarray(ds.database)
    model = gv.fit(jax.random.PRNGKey(0), jnp.asarray(ds.queries_learn), X,
                   c=6, d=16)
    build = (sc.sorted_gleanvec_scorer if mode == "gleanvec-sorted"
             else sc.sorted_gleanvec_quantized_scorer)
    s = build(model, X, block=64)
    iva = ivf.build_aligned(model, X, nprobe=2)
    q1 = jnp.asarray(ds.queries_test[:1])
    qs = iva.prepare_queries(s, q1)
    probe = jax.lax.top_k(ivf.coarse_scores(iva, qs), iva.nprobe)[1]
    if mode == "gleanvec-sorted":
        views, lo, rows = qs.qstate, jnp.zeros(qs.qstate.shape[:2]), s.x_low
    else:
        views, lo, rows = qs.qstate.q_scaled, qs.qstate.q_lo, s.codes
    got = ivf_scan_topk(views, lo, s.block_tags, s.perm, rows, probe, 10,
                        layout_block=s.layout_block, tn=64, interpret=True)
    want = replace(iva, aligned_layout=False).search(q1, s, 10)
    assert_same_topk(got, want, mode, rtol=1e-4, atol=1e-3)


def _graph_scan_inputs(m, nb, c, d, lb, s, b, n_pad=0, f32=False, seed=3):
    """Random sorted-layout inputs + per-query neighbor sorted-row lists
    (with -1 pads and repeats) + a random incoming beam (distinct ids,
    some empty slots)."""
    rng = np.random.default_rng(seed)
    n = nb * lb
    q_scaled, q_lo, _, codes = _sq_inputs(m, n, c, d)
    if f32:
        codes = _randn(n, d)
    block_tags = jnp.asarray(rng.integers(0, c, nb).astype(np.int32))
    perm = rng.permutation(n).astype(np.int32)
    if n_pad:
        perm[rng.permutation(n)[:n_pad]] = -1        # dead/padding rows
    nbr = rng.integers(-1, n, (m, s)).astype(np.int32)
    nbr[0, 1:] = nbr[0, 0]                           # repeated rows
    bvals = 50.0 * rng.standard_normal((m, b)).astype(np.float32)
    bids = np.stack([rng.choice(n, b, replace=False)
                     for _ in range(m)]).astype(np.int32)
    empty = rng.random((m, b)) < 0.25                # unfilled beam slots
    bvals[empty] = np.float32(-3.4e38)
    bids[empty] = -1
    return (q_scaled, q_lo, block_tags, jnp.asarray(perm), codes,
            jnp.asarray(nbr), jnp.asarray(bvals), jnp.asarray(bids))


def _assert_same_beam(kv, ki, rv, ri):
    """Kernel beams are slot-ordered, the oracle's are score-sorted --
    compare as (id -> value) maps: beam ids are distinct (-1 empties all
    ride the -inf sentinel), so sorting by id aligns the multisets."""
    kv, ki = np.asarray(kv), np.asarray(ki)
    rv, ri = np.asarray(rv), np.asarray(ri)
    ko, ro = np.argsort(ki, axis=1), np.argsort(ri, axis=1)
    np.testing.assert_array_equal(np.take_along_axis(ki, ko, 1),
                                  np.take_along_axis(ri, ro, 1))
    np.testing.assert_allclose(np.take_along_axis(kv, ko, 1),
                               np.take_along_axis(rv, ro, 1),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.tier1
@pytest.mark.parametrize("m,nb,c,d,lb,s,b,tn", [
    (4, 8, 6, 32, 128, 40, 12, 8),   # layout_block % tn == 0
    (3, 5, 8, 48, 64, 24, 8, 48),    # tn does not divide -> tile shrink
    (1, 6, 4, 16, 96, 10, 6, 128),   # tn > layout_block -> tile shrink
])
def test_graph_scan_beam_step_matches_ref(m, nb, c, d, lb, s, b, tn):
    """Fused beam-step kernel == gather/top_k oracle: slab streaming from
    the neighbor-row schedule, repeated rows score once, dead rows and
    in-beam candidates never enter, beam multiset identical."""
    qs, ql, bt, rid, codes, nbr, bv, bi = _graph_scan_inputs(
        m, nb, c, d, lb, s, b, n_pad=30)
    kv, ki = graph_scan_beam_step(qs, ql, bt, rid, codes, nbr, bv, bi,
                                  layout_block=lb, tn=tn, interpret=True)
    rv, ri = graph_scan_beam_step_ref(qs, ql, bt, rid, codes, nbr, bv, bi,
                                      layout_block=lb)
    _assert_same_beam(kv, ki, rv, ri)


@pytest.mark.tier1
def test_graph_scan_f32_rows_and_empty_expansion():
    """The unquantized sorted scorer's f32 rows ride the same kernel, and
    an all-padding neighbor row leaves that query's beam untouched."""
    qs, ql, bt, rid, codes, nbr, bv, bi = _graph_scan_inputs(
        3, 6, 4, 24, 64, 16, 8, f32=True)
    nbr = nbr.at[1].set(-1)                          # query 1: no neighbors
    kv, ki = graph_scan_beam_step(qs, ql, bt, rid, codes, nbr, bv, bi,
                                  layout_block=64, tn=8, interpret=True)
    rv, ri = graph_scan_beam_step_ref(qs, ql, bt, rid, codes, nbr, bv, bi,
                                      layout_block=64)
    _assert_same_beam(kv, ki, rv, ri)
    np.testing.assert_array_equal(np.asarray(ki)[1], np.asarray(bi)[1])
    np.testing.assert_allclose(np.asarray(kv)[1], np.asarray(bv)[1])


@pytest.mark.parametrize("n,c,d,tn", [
    (500, 13, 64, 128), (2048, 48, 200, 512), (100, 4, 16, 64)])
def test_kmeans_assign_matches_ref(n, c, d, tn):
    x, cent = _randn(n, d), _randn(c, d)
    t1, s1 = kmeans_assign(x, cent, tn=tn, interpret=True)
    t2, s2 = kmeans_assign_ref(x, cent)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("m,n,d,tm,tn", [
    (4, 300, 48, 4, 128), (9, 1000, 160, 8, 256)])
def test_sq_dot_matches_ref(m, n, d, tm, tn):
    x = _randn(n, d)
    db = quantize(x)
    q = _randn(m, d)
    s1 = sq_dot(q, db.codes, db.lo, db.delta, tm=tm, tn=tn,
                interpret=True)
    s2 = sq_dot_ref(q, db.codes, db.lo, db.delta)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("b,h,kv,s,dh,bq,bk,window", [
    (1, 4, 4, 64, 16, 32, 32, None),     # MHA
    (2, 4, 2, 96, 32, 32, 32, None),     # GQA
    (2, 8, 2, 128, 16, 64, 32, None),    # GQA group 4
    (1, 4, 2, 128, 32, 32, 32, 48),      # sliding window
    (2, 4, 2, 80, 32, 32, 32, None),     # padded seq
])
def test_flash_attention_matches_ref(b, h, kv, s, dh, bq, bk, window):
    q = _randn(b, h, s, dh)
    k = _randn(b, kv, s, dh)
    v = _randn(b, kv, s, dh)
    o1 = flash_attention(q, k, v, causal=True, window=window, bq=bq, bk=bk,
                         interpret=True)
    o2 = flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=2e-4,
                               atol=2e-4)


def test_flash_attention_bf16():
    q = _randn(1, 2, 64, 32).astype(jnp.bfloat16)
    k = _randn(1, 2, 64, 32).astype(jnp.bfloat16)
    v = _randn(1, 2, 64, 32).astype(jnp.bfloat16)
    o1 = flash_attention(q, k, v, bq=32, bk=32, interpret=True)
    o2 = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), rtol=3e-2,
                               atol=3e-2)
