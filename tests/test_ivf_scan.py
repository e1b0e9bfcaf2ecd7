"""Gather-free traversals: the fused sorted-IVF range scan and the
multi-expansion beam search.

Three layers of guarantees:

* PARITY -- the fused fine step (``IVFIndex(aligned_layout=True)`` ->
  ``scorer.scan_lists`` -> ``kernels/ivf_scan``) returns EXACTLY the
  gathered ``score_ids`` path's (value, id) sets for both sorted scorer
  families, on ID and OOD queries, with ``slack_blocks``, after streaming
  insert/remove cycles (dead slots), and per-shard under ``ShardedIndex``;
  ``expand=1`` beam search reproduces the classic best-first loop
  bit-for-bit and ``expand>1`` holds recall while cutting hop count.
* SERVING -- a ``ServingEngine`` compiled with the fused path swaps
  streamed states with ZERO recompiles (``compile_counter``).
* COST -- the fused fine step's HBM traffic (fixed by the kernel's
  BlockSpecs, ``fine_step_bytes``) is >= 4x below the compiled gathered
  fine step's ``cost_analysis`` bytes at the paper's proportions, and the
  fused path compiles WITHOUT the (m, nprobe*L) gather the old path
  materializes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gleanvec as gv, metrics, streaming
from repro.core import scorer as sc
from repro.core import search as msearch
from repro.data import vectors
from repro.index import distributed, graph, ivf
from repro.index.protocol import replace
from repro.index.topk import NEG_INF
from repro.kernels.ivf_scan import fine_step_bytes
from repro.serve.engine import ServingEngine
from repro.analysis import assert_rules
from repro.analysis.hlo_rules import BufferPresent, NoDenseScoreMatrix
from repro.utils import hlo_analysis

from helpers import assert_same_topk as _assert_same_topk

pytestmark = pytest.mark.tier1

SORTED_MODES = ("gleanvec-sorted", "gleanvec-int8-sorted")


def _sorted_scorer(mode, model, X, block=64, slack_blocks=0):
    if mode == "gleanvec-sorted":
        return sc.sorted_gleanvec_scorer(model, X, block=block,
                                         slack_blocks=slack_blocks)
    return sc.sorted_gleanvec_quantized_scorer(model, X, block=block,
                                               slack_blocks=slack_blocks)


@pytest.fixture(scope="module")
def setup():
    ds = vectors.make_dataset("ivfscan", n=2048, d=64, n_queries=32,
                              ood=True, seed=9)
    ds_id = vectors.make_dataset("ivfscan-id", n=2048, d=64, n_queries=32,
                                 ood=False, seed=9)
    X = jnp.asarray(ds.database)
    gvm = gv.fit(jax.random.PRNGKey(0), jnp.asarray(ds.queries_learn), X,
                 c=8, d=24)
    return ds, ds_id, X, gvm


@pytest.mark.parametrize("slack", [0, 2])
@pytest.mark.parametrize("regime", ["ood", "id"])
@pytest.mark.parametrize("mode", SORTED_MODES)
def test_fused_matches_gathered(setup, mode, regime, slack):
    """Aligned-IVF fused range scan == gathered score_ids path, exactly,
    for both sorted families, ID and OOD queries, with and without
    streaming slack blocks."""
    ds, ds_id, X, gvm = setup
    QT = jnp.asarray((ds if regime == "ood" else ds_id).queries_test)
    s = _sorted_scorer(mode, gvm, X, slack_blocks=slack)
    iva = ivf.build_aligned(gvm, X, nprobe=4)
    fused = iva.search(QT, s, 10)
    gathered = replace(iva, aligned_layout=False).search(QT, s, 10)
    _assert_same_topk(fused, gathered, f"{mode}/{regime}/slack={slack}")


def test_fused_composes_with_reduced_probe(setup):
    """The R^d coarse probe and the fused fine step are orthogonal: same
    results as the full-D probe at matched nprobe (identical probe order
    -- the companion scores the same centers)."""
    ds, _, X, gvm = setup
    QT = jnp.asarray(ds.queries_test)
    s = _sorted_scorer("gleanvec-int8-sorted", gvm, X)
    iva = ivf.build_aligned(gvm, X, nprobe=4)
    ivr = ivf.with_reduced_centers(iva, s, gvm)
    assert ivr.aligned_layout and ivr.center_scorer is not None
    _assert_same_topk(iva.search(QT, s, 10), ivr.search(QT, s, 10))


def test_fused_unfilled_slots_strip_to_minus_one(setup):
    """Fewer live candidates than k: the -inf winners' ids come back -1 on
    BOTH paths (never a resurrected padding slot)."""
    ds, _, X, gvm = setup
    QT = jnp.asarray(ds.queries_test[:4])
    s = _sorted_scorer("gleanvec-sorted", gvm, X[:64], block=64)
    iva = ivf.build_aligned(gvm, X[:64], nprobe=1)   # one tiny cluster
    vals, ids = iva.search(QT, s, 60)
    vals, ids = np.asarray(vals), np.asarray(ids)
    assert (ids[vals <= NEG_INF] == -1).all()
    assert (vals > NEG_INF).any()


@pytest.mark.parametrize("mode", SORTED_MODES)
def test_fused_after_streaming_cycles(setup, mode, compile_counter):
    """Insert/remove cycles through the fixed-capacity store + aligned
    posting lists: the fused path stays EXACT vs the gathered path on the
    churned state, and the compiled engine swaps every cycle with zero
    recompiles."""
    ds, _, X, gvm = setup
    N0, CAP, STEP = 1536, 2048, 128
    arts = streaming.build_streaming_artifacts(mode, X[:N0], gvm,
                                               capacity=CAP, sort_block=64,
                                               slack_blocks=3)
    # the clustering is skewed (the largest list holds ~1/4 of the base and
    # takes ~1/3 of the inserts), so the per-list slack covers every row
    # the three cycles insert rather than an even share of them
    index = ivf.with_list_slack(ivf.build_aligned(gvm, X[:N0], nprobe=3),
                                3 * STEP)
    index = ivf.with_reduced_centers(index, arts.scorer, gvm)
    engine = ServingEngine(msearch.make_state(arts, index=index), k=10,
                           kappa=20, batch_size=16, dim=X.shape[1])
    QT = np.asarray(ds.queries_test[:16])

    def cycle_fn(cycle):
        engine.submit(QT)
        rows = X[N0 + cycle * STEP: N0 + (cycle + 1) * STEP]
        arts2, new_ids = streaming.insert_rows(engine.state.artifacts, rows)
        idx2 = ivf.insert_ids(engine.state.index, rows, new_ids)
        rm = np.arange(cycle * 20, cycle * 20 + 10, dtype=np.int32)
        arts2 = streaming.remove_rows(arts2, rm)
        idx2 = ivf.remove_ids(idx2, rm)
        engine.swap(engine.state._replace(artifacts=arts2, index=idx2))

    # cycle 0 is the warmup: compiles the serving step AND every eager op
    # of the host-side streaming loop once
    cycle_fn(0)
    compile_counter.reset()
    for cycle in (1, 2):
        cycle_fn(cycle)
    assert compile_counter.count == 0, \
        f"{mode}: {compile_counter.count} recompiles across swap cycles"
    assert engine.n_compiles in (None, 1)
    # the churned store: dead slots and filled slack must agree exactly
    st = engine.state
    fused = st.index.search(jnp.asarray(QT), st.artifacts.scorer, 10)
    gathered = replace(st.index, aligned_layout=False).search(
        jnp.asarray(QT), st.artifacts.scorer, 10)
    _assert_same_topk(fused, gathered, mode)
    assert not (np.asarray(fused[1]) < 0).all()


@pytest.mark.parametrize("mode", SORTED_MODES)
def test_fused_sharded_matches_gathered(setup, mode):
    """Per-shard aligned sub-indexes under ShardedIndex (stacked, padded
    leaves) return exactly the per-shard gathered results after the
    all-gather merge -- the fused path survives leaf padding."""
    ds, _, X, gvm = setup
    QT = jnp.asarray(ds.queries_test)
    sh, stacked = distributed.build_sharded_index(
        "ivf", mode, X, gvm, n_shards=4, nprobe=4, aligned=True,
        sort_block=64)
    assert sh.sub_index.aligned_layout
    fused = sh.search_local(QT, stacked, 10, kappa=20)
    sh_g = replace(sh, sub_index=replace(sh.sub_index,
                                         aligned_layout=False))
    gathered = sh_g.search_local(QT, stacked, 10, kappa=20)
    _assert_same_topk(fused, gathered, mode)


def test_sharded_aligned_needs_sorted_mode(setup):
    _, _, X, gvm = setup
    with pytest.raises(ValueError, match="sorted"):
        distributed.build_sharded_index("ivf", "gleanvec", X, gvm,
                                        n_shards=4, aligned=True)


def test_fused_fine_step_moves_4x_fewer_bytes():
    """Cost assertion at the paper's proportions (d = D/4, int8 codes,
    full-ish blocks): the range-scan kernel's BlockSpec-determined HBM
    traffic is >= 4x below the compiled gathered fine step's
    ``cost_analysis`` bytes, and the fused HLO contains no
    (m, nprobe * max_len) gather buffer."""
    ds = vectors.make_dataset("ivfscan-cost", n=4096, d=256, n_queries=32,
                              ood=True, seed=13)
    X = jnp.asarray(ds.database)
    gvm = gv.fit(jax.random.PRNGKey(0), jnp.asarray(ds.queries_learn), X,
                 c=16, d=64)
    s = sc.sorted_gleanvec_quantized_scorer(gvm, X, block=64)
    iva = ivf.build_aligned(gvm, X, nprobe=4)
    QT = jnp.asarray(ds.queries_test)
    m, kappa = QT.shape[0], 50

    ivg = replace(iva, aligned_layout=False)
    qs = ivg.prepare_queries(s, QT)
    gathered_cost = hlo_analysis.normalize_cost(
        ivf._probe_and_score.lower(qs, s, ivg, kappa).compile()
        .cost_analysis())
    gathered_bytes = float(gathered_cost["bytes accessed"])

    ranges = np.asarray(s.list_block_ranges)
    visited = m * iva.nprobe * (ranges >= 0).sum() / ranges.shape[0]
    fused_bytes = fine_step_bytes(m, visited, s.layout_block,
                                  s.codes.shape[1], gvm.n_clusters,
                                  code_bytes=1, k=kappa)
    assert fused_bytes * 4 <= gathered_bytes, (fused_bytes, gathered_bytes)

    # no (m, nprobe*L) candidate/score matrix in the fused program, in
    # any dtype of interest -- and the gathered path really materializes
    # it (the registry rules own both contracts; see docs/static_analysis)
    p = iva.nprobe * iva.max_len
    assert_rules(ivf._probe_and_score.lower(qs, s, ivg, kappa).compile(),
                 [BufferPresent(m, p, dtypes=("f32",))],
                 target="ivf/gathered")
    assert_rules(ivf._probe_and_scan.lower(
        iva.prepare_queries(s, QT), s, iva, kappa).compile(),
                 [NoDenseScoreMatrix(m, p)], target="ivf/fused")


def test_insert_ids_vectorized_matches_sequential(setup):
    """The argsort/bincount slot assignment == the per-insert first-free
    reference, and out-of-slack raises the same message."""
    _, _, X, gvm = setup
    iva = ivf.with_list_slack(ivf.build_aligned(gvm, X[:1024], nprobe=3),
                              40)
    rng = np.random.default_rng(4)
    rows = X[1024:1024 + 64]
    ids = rng.permutation(np.arange(5000, 5064)).astype(np.int32)
    got = ivf.insert_ids(iva, rows, ids)
    # sequential reference (the pre-vectorization semantics)
    from repro.core import spherical_kmeans
    x_unit = spherical_kmeans.normalize_rows(jnp.asarray(rows, jnp.float32))
    tags = np.asarray(spherical_kmeans.assign(x_unit, iva.centers))
    ref = np.asarray(iva.lists).copy()
    for t, i in zip(tags, ids):
        free = np.nonzero(ref[t] < 0)[0]
        ref[t, free[0]] = int(i)
    np.testing.assert_array_equal(np.asarray(got.lists), ref)
    # out-of-slack: same error, names the full list
    tight = ivf.build_aligned(gvm, X[:64], nprobe=2)
    with pytest.raises(ValueError, match="posting list .* is full"):
        ivf.insert_ids(tight, X[64:1064],
                       np.arange(2000, 3000, dtype=np.int32))


# ---------------------------------------------------------------------------
# Multi-expansion beam search.
# ---------------------------------------------------------------------------


def _legacy_beam(qstate, scorer, g, k, beam, max_hops):
    """The pre-multi-expansion traversal (argmax pop, O(beam*R*beam)
    dedupe broadcast), kept verbatim as the expand=1 exactness oracle."""
    batch = qstate.shape[0]
    nbr_tbl = g.neighbors
    r = nbr_tbl.shape[1]

    def score_ids(ids):
        return scorer.score_ids(qstate, jnp.where(ids >= 0, ids, 0))

    n_entry = g.entries.shape[0]
    entry = jnp.broadcast_to(g.entries[None, :], (batch, n_entry))
    e_scores = jnp.where(entry >= 0, score_ids(entry), NEG_INF)
    ids = jnp.concatenate(
        [entry, jnp.full((batch, beam - n_entry), -1, jnp.int32)], 1)
    scores = jnp.concatenate(
        [e_scores, jnp.full((batch, beam - n_entry), NEG_INF)], 1)
    visited = jnp.zeros((batch, beam), bool)
    hop = 0
    while hop < max_hops:
        expandable = (~visited) & (ids >= 0)
        if not bool(jnp.any(expandable)):
            break
        masked = jnp.where(expandable, scores, NEG_INF)
        best = jnp.argmax(masked, 1)
        has_work = jnp.any(expandable, 1)
        best_ids = jnp.take_along_axis(ids, best[:, None], 1)[:, 0]
        visited = visited.at[jnp.arange(batch), best].set(
            visited[jnp.arange(batch), best] | has_work)
        nbrs = nbr_tbl[jnp.where(best_ids >= 0, best_ids, 0)]
        nbrs = jnp.where((nbrs >= 0) & has_work[:, None], nbrs, -1)
        nscores = jnp.where(nbrs >= 0, score_ids(nbrs), NEG_INF)
        present = jnp.any(nbrs[:, :, None] == ids[:, None, :], 2)
        nscores = jnp.where(present, NEG_INF, nscores)
        all_scores = jnp.concatenate([scores, nscores], 1)
        all_ids = jnp.concatenate([ids, nbrs], 1)
        all_vis = jnp.concatenate(
            [visited, jnp.zeros((batch, r), bool)], 1)
        scores, sel = jax.lax.top_k(all_scores, beam)
        ids = jnp.take_along_axis(all_ids, sel, 1)
        visited = jnp.take_along_axis(all_vis, sel, 1)
        hop += 1
    top, sel = jax.lax.top_k(scores, k)
    return top, jnp.take_along_axis(ids, sel, 1), hop


@pytest.fixture(scope="module")
def graph_setup(setup):
    ds, _, X, gvm = setup
    g = graph.build(ds.database, r=16, n_iters=4, seed=0)
    s = sc.gleanvec_scorer(gvm, X)
    return ds, X, gvm, g, s


def test_expand1_reproduces_classic_traversal(graph_setup):
    """expand=1 == the legacy argmax/broadcast loop: identical visit
    order (same hop count), identical winner ids, scores equal to jit
    fusion rounding -- the sort-based dedupe is a pure refactor."""
    ds, X, gvm, g, s = graph_setup
    qstate = s.prepare_queries(jnp.asarray(ds.queries_test))
    v_ref, i_ref, hops_ref = _legacy_beam(qstate, s, g, 10, 48, 120)
    v, i, hops, _ = graph._beam_qstate(qstate, s, g, 10, 48, 120, expand=1)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
    np.testing.assert_allclose(np.asarray(v), np.asarray(v_ref),
                               rtol=1e-6, atol=1e-4)
    assert int(hops) == hops_ref


@pytest.mark.parametrize("expand", [2, 4])
def test_expand_cuts_hops_at_matched_recall(graph_setup, expand):
    """Multi-expansion: ~expand-fold fewer while_loop iterations, recall
    within tolerance of the classic traversal at the same beam."""
    ds, X, gvm, g, s = graph_setup
    QT = jnp.asarray(ds.queries_test)
    gt = jnp.asarray(ds.gt[:, :10])
    qstate = s.prepare_queries(QT)
    v1, i1, h1, _ = graph._beam_qstate(qstate, s, g, 10, 48, 120, expand=1)
    ve, ie, he, _ = graph._beam_qstate(qstate, s, g, 10, 48, 120,
                                       expand=expand)
    r1 = float(metrics.recall_at_k(i1, gt))
    re = float(metrics.recall_at_k(ie, gt))
    assert int(he) * (expand - 1) < int(h1) * expand, (int(h1), int(he))
    assert re >= r1 - 0.03, (expand, r1, re)
    # the protocol honors the static field
    ge = replace(g, beam=48, max_hops=120, expand=expand)
    _, i_proto = ge.search(QT, s, 10)
    np.testing.assert_array_equal(
        np.asarray(i_proto),
        np.asarray(jnp.where(ve > NEG_INF, ie, -1)))


def test_graph_candidates_strip_inf_ids(graph_setup):
    """Unfilled beam slots (-inf) come back as id -1 from
    GraphIndex.candidates, like the IVF path."""
    ds, X, gvm, g, s = graph_setup
    QT = jnp.asarray(ds.queries_test[:4])
    g0 = replace(g, beam=48, max_hops=0)       # no hops: only the entries
    vals, ids = g0.search(QT, s, 40)
    vals, ids = np.asarray(vals), np.asarray(ids)
    assert (ids[vals <= NEG_INF] == -1).all()
    assert (vals > NEG_INF).any()
