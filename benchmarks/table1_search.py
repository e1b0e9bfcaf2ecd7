"""Paper Table 1 / throughput axis: end-to-end multi-step search QPS and
recall at the paper's operating point (10-recall@10 target ~0.9) for
full-precision vs LeanVec-Sphering vs GleanVec databases across the Index
protocol's traversals: flat scan, graph, IVF with the full-D vs
reduced-space coarse probe toggle, and the sharded (4-way) IVF / graph
placements. Rows land in ``BENCH_table1_search.json`` via
``common.write_json_results``.

CPU wall times characterize relative speedups (D/d bandwidth scaling);
absolute TPU numbers come from the roofline analysis. The ``probe_flops``
derived field on the IVF rows is the compiled coarse-step cost
(``normalize_cost``): the ``ivf-rprobe`` row must show ~D/d fewer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import dataset, declare, emit, time_fn
from repro.core import gleanvec as gv, leanvec_sphering as lvs, metrics
from repro.core.quantization import quantize
from repro.core.scorer import (gleanvec_quantized_scorer, gleanvec_scorer,
                               sorted_gleanvec_quantized_scorer,
                               sorted_gleanvec_scorer)
from repro.index import bruteforce, distributed, graph, ivf
from repro.index.protocol import replace
from repro.kernels.graph_scan import beam_step_bytes, fresh_slab_count
from repro.kernels.ivf_scan import fine_step_bytes
from repro.utils import hlo_analysis

# Regression guard (smoke-enforced): the fused beam step's cost-modelled
# per-hop HBM bytes must sit at least this far below the compiled gathered
# hop's, even at smoke shapes (n=1500 measures ~2.75x; the paper-
# proportioned >= 3x floor is asserted in tests/test_graph_scan.py).
GRAPH_FUSED_MIN_RATIO = 2.0


def _probe_flops(index, scorer, queries) -> float:
    """Compiled cost of the coarse step alone (the R^d assertion's data)."""
    qs = index.prepare_queries(scorer, queries)
    cost = hlo_analysis.normalize_cost(
        jax.jit(ivf.coarse_scores).lower(index, qs).compile()
        .cost_analysis())
    return float(cost.get("flops", 0.0))


def _fine_bytes_gathered(index, scorer, queries, kappa) -> float:
    """Compiled HBM bytes of the GATHERED fine step (``_probe_and_score``:
    posting-list gather + ``score_ids``), via ``normalize_cost``."""
    qs = index.prepare_queries(scorer, queries)
    cost = hlo_analysis.normalize_cost(
        ivf._probe_and_score.lower(qs, scorer, index, kappa).compile()
        .cost_analysis())
    return float(cost.get("bytes accessed", 0.0))


def _fine_bytes_fused(index, scorer, queries, kappa: int) -> float:
    """HBM bytes of the FUSED range-scan fine step: the kernel's traffic
    is fixed by its BlockSpecs (``fine_step_bytes``) and the batch's
    probes (the union of the probed clusters' blocks)."""
    qs = index.prepare_queries(scorer, queries)
    probe = jax.lax.top_k(ivf.coarse_scores(index, qs), index.nprobe)[1]
    rows = getattr(scorer, "codes", None)
    if rows is None:
        rows = scorer.x_low
    return fine_step_bytes(probe, scorer.block_tags, scorer.layout_block,
                           rows.shape[1],
                           code_bytes=np.dtype(rows.dtype).itemsize,
                           k=kappa)


def _beam_step_bytes_gathered(scorer, queries, nbr_tbl, beam, e, best):
    """Compiled HBM bytes of one GATHERED hop merge (neighbor gather +
    ``score_ids`` + top_k merge), via ``normalize_cost``."""
    m = queries.shape[0]
    qs = scorer.prepare_queries(queries)
    vals = jnp.full((m, beam), -3.4e38)
    ids = jnp.full((m, beam), -1, jnp.int32)
    vis = jnp.zeros((m, beam), bool)
    ok = jnp.ones((m, e), bool)

    def hop(scorer, qs, nbr_tbl, vals, ids, vis, best, ok):
        def score_ids(cids):
            return scorer.score_ids(qs, jnp.where(cids >= 0, cids, 0))
        return graph.gathered_beam_step(score_ids, nbr_tbl, vals, ids,
                                        vis, best, ok, beam)

    cost = hlo_analysis.normalize_cost(
        jax.jit(hop).lower(scorer, qs, nbr_tbl, vals, ids, vis,
                           jnp.asarray(best), ok).compile()
        .cost_analysis())
    return float(cost.get("bytes accessed", 0.0))


def _beam_step_bytes_fused(gf, scorer, c, beam, best):
    """HBM bytes of the same hop through the fused kernel: fixed by the
    BlockSpecs + the tn-slab schedule over the hop's ACTUAL fresh-slab
    count (``beam_step_bytes``)."""
    m = best.shape[0]
    nrows = np.asarray(gf.nbr_rows)[best].reshape(m, -1)
    rows = getattr(scorer, "codes", None)
    if rows is None:
        rows = scorer.x_low
    return beam_step_bytes(m, fresh_slab_count(nrows, gf.scan_tn),
                           gf.scan_tn, rows.shape[1], c, beam,
                           nrows.shape[1],
                           code_bytes=np.dtype(rows.dtype).itemsize)


def run():
    declare("table1_search/flat/", "table1_search/ivf/",
            "table1_search/ivf-rprobe/", "table1_search/ivf-sorted-fused/",
            "table1_search/ivf-sharded/", "table1_search/graph/",
            "table1_search/graph-expand1/", "table1_search/graph-expand4/",
            "table1_search/graph-fused/", "table1_search/graph-sharded/",
            "table1_search/graph-build-numpy/",
            "table1_search/graph-build-device/")
    ds = dataset("laion-OOD")
    X = jnp.asarray(ds.database)
    Q = jnp.asarray(ds.queries_learn)
    QT = jnp.asarray(ds.queries_test)
    gt = jnp.asarray(ds.gt[:, :10])
    dim = X.shape[1]
    d = dim // 4
    kappa = 50
    nq = QT.shape[0]

    def finish(cand):
        vecs = X[jnp.where(cand >= 0, cand, 0)]
        full = jnp.einsum("mkd,md->mk", vecs, QT)
        top = jax.lax.top_k(jnp.where(cand >= 0, full, -3.4e38), 10)[1]
        return jnp.take_along_axis(cand, top, axis=1)

    def bench(name, search, extra=""):
        us = time_fn(search)
        rec = float(metrics.recall_at_k(search(), gt))
        emit(f"table1_search/{name}", us,
             f"recall10={rec:.3f};qps={nq / (us / 1e6):.0f}" + extra)

    # full-D flat (baseline search)
    bench("flat/fullD", lambda: finish(bruteforce.search(QT, X, 10)[1]))

    # sphering flat + rerank
    m = lvs.fit(Q, X, d)
    q_low = QT @ m.a.T
    x_low = X @ m.b.T
    bench(f"flat/sphering-d{d}",
          lambda: finish(bruteforce.search(q_low, x_low, kappa)[1]))

    # gleanvec flat + rerank
    model = gv.fit(jax.random.PRNGKey(0), Q, X, c=48, d=d)
    tags, xg_low = gv.encode_database(model, X)
    q_views = gv.project_queries_eager(model, QT)
    bench(f"flat/gleanvec-d{d}",
          lambda: finish(bruteforce.search_gleanvec(q_views, tags, xg_low,
                                                    kappa)[1]))

    # int8-quantized sphering (compounded compression)
    db = quantize(x_low)
    bench(f"flat/sphering-d{d}-int8",
          lambda: finish(bruteforce.search_quantized(
              q_low, db.codes, db.lo, db.delta, kappa)[1]))

    # gleanvec + per-cluster int8 (Scorer-protocol composition: DR stacked
    # with SQ -- d bytes per vector instead of D*4)
    gq = gleanvec_quantized_scorer(model, X)
    bench(f"flat/gleanvec-d{d}-int8",
          lambda: finish(bruteforce.search_scorer(QT, gq, kappa)[1]))

    # tag-sorted (cluster-contiguous) layouts: one query view per block, so
    # the scan is a plain matmul (f32) / int8 matmul + offset (int8) -- the
    # Scorer protocol translates the sorted row order back to original ids.
    sgl = sorted_gleanvec_scorer(model, X, block=256)
    bench(f"flat/gleanvec-d{d}-sorted",
          lambda: finish(bruteforce.search_scorer(QT, sgl, kappa)[1]))

    sgq = sorted_gleanvec_quantized_scorer(model, X, block=256)
    bench(f"flat/gleanvec-d{d}-int8-sorted",
          lambda: finish(bruteforce.search_scorer(QT, sgq, kappa)[1]))

    # IVF through the Index protocol: full-D coarse probe vs the centers
    # projected into the scorer's reduced space (same nprobe, same lists;
    # probe_flops is the compiled coarse-step cost -- the rprobe row moves
    # ~D/d fewer)
    iv = ivf.build(jax.random.PRNGKey(1), X, n_lists=32)
    ivr = ivf.with_reduced_centers(iv, gq, model)
    for name, index in ((f"ivf/gleanvec-d{d}-int8", iv),
                        (f"ivf-rprobe/gleanvec-d{d}-int8", ivr)):
        bench(name,
              lambda index=index: finish(
                  ivf.search_scorer(QT, gq, index, k=kappa, nprobe=8)[1]),
              extra=f";probe_flops={_probe_flops(index, gq, QT):.0f}")

    # fused sorted-IVF range scan: the coarse quantizer IS the GleanVec
    # clustering (build_aligned), so the fine step streams the probed
    # clusters' single-tag slabs (scan_lists) -- no posting-list gather,
    # no (m, nprobe*L) matrix. fine_bytes is the range-scan kernel's
    # BlockSpec-determined HBM traffic; fine_bytes_gathered is the
    # compiled gathered fine step's (normalize_cost) for the same probe.
    iva = ivf.build_aligned(model, X, nprobe=8)
    fb_fused = _fine_bytes_fused(iva, sgq, QT, kappa)
    fb_gather = _fine_bytes_gathered(replace(iva, aligned_layout=False),
                                     sgq, QT, kappa)
    bench(f"ivf-sorted-fused/gleanvec-d{d}-int8-sorted",
          lambda: finish(iva.search(QT, sgq, kappa)[1]),
          extra=f";fine_bytes={fb_fused:.0f}"
                f";fine_bytes_gathered={fb_gather:.0f}"
                f";vs_gathered_bytes={fb_gather / fb_fused:.1f}x")

    # graph index (reduced space) + rerank
    g = graph.build(np.asarray(xg_low), r=24, n_iters=5, seed=0)
    bench(f"graph/gleanvec-d{d}",
          lambda: finish(graph.beam_search_gleanvec(
              q_views, tags, xg_low, g, k=kappa, beam=96,
              max_hops=200)[1]))

    # multi-expansion beam search: expand=E pops the top-E frontier
    # vertices per hop (E x fewer while_loop iterations, E x wider MXU
    # contractions); expand=1 is the classic traversal. hops comes from
    # the traced traversal at matched beam/recall.
    gsc = gleanvec_scorer(model, X)
    for e in (1, 4):
        _, _, hops, _ = graph.beam_search_scorer(
            QT, gsc, g, k=kappa, beam=96, max_hops=200, expand=e,
            trace=True)
        bench(f"graph-expand{e}/gleanvec-d{d}",
              lambda e=e: finish(graph.beam_search_scorer(
                  QT, gsc, g, k=kappa, beam=96, max_hops=200,
                  expand=e)[1]),
              extra=f";hops={int(hops)}")

    # gather-free fused traversal: the graph bound to the tag-sorted int8
    # layout (with_fused_scan), every hop a graph_scan kernel launch --
    # no (m, expand*R) neighbor gather, no (m, beam+expand*R) merge
    # matrix in HBM. fine_bytes is the kernel's schedule-determined
    # per-hop traffic on a representative frontier; vs_gathered compares
    # the compiled gathered hop on the SAME frontier.
    gfused = graph.with_fused_scan(
        replace(g, beam=96, max_hops=200, expand=4), sgq)
    _, _, ghops, _ = graph._beam_qstate(sgq.prepare_queries(QT), sgq,
                                        gfused, kappa, 96, 200, expand=4)
    rng = np.random.default_rng(0)
    frontier = rng.integers(0, X.shape[0], size=(nq, 4)).astype(np.int32)
    hb_fused = _beam_step_bytes_fused(gfused, sgq, model.n_clusters, 96,
                                      frontier)
    hb_gather = _beam_step_bytes_gathered(sgq, QT, gfused.neighbors, 96,
                                          4, frontier)
    if hb_fused * GRAPH_FUSED_MIN_RATIO > hb_gather:
        raise RuntimeError(
            f"fused beam step regression: only {hb_gather / hb_fused:.2f}x "
            f"below the gathered hop (declared {GRAPH_FUSED_MIN_RATIO}x)")
    bench(f"graph-fused/gleanvec-d{d}-int8-sorted",
          lambda: finish(gfused.search(QT, sgq, kappa)[1]),
          extra=f";hops={int(ghops)}"
                f";fine_bytes={hb_fused:.0f}"
                f";vs_gathered={hb_gather / hb_fused:.1f}x")

    # graph construction: numpy NN-descent vs the on-device CAGRA-style
    # build (fused-kernel k-NN self-join + rank pruning) -- the default
    # at n >= 8192 via build(method="auto").
    for method in ("numpy", "device"):
        built = {}

        def build_once(method=method, built=built):
            built["g"] = graph.build(np.asarray(xg_low), r=24, n_iters=5,
                                     seed=0, method=method)
            return built["g"].neighbors

        us = time_fn(build_once, warmup=0, iters=1)
        gb = built["g"]
        rec = float(metrics.recall_at_k(
            finish(graph.beam_search_scorer(QT, gsc, gb, k=kappa, beam=96,
                                            max_hops=200)[1]), gt))
        emit(f"table1_search/graph-build-{method}/gleanvec-d{d}", us,
             f"recall10={rec:.3f};n={X.shape[0]};r=24")

    # sharded placements (4 shards; mesh-free reference path on one chip,
    # the same per-shard searches shard_map distributes on a real mesh)
    n_shards = next(s for s in (4, 2, 1) if X.shape[0] % s == 0)
    sh_iv, st_iv = distributed.build_sharded_index(
        "ivf", "gleanvec-int8", X, model, n_shards=n_shards,
        key=jax.random.PRNGKey(1), n_lists=32, nprobe=8)
    bench(f"ivf-sharded/gleanvec-d{d}-int8",
          lambda: finish(sh_iv.search(QT, st_iv, kappa)[1]))

    sh_g, st_g = distributed.build_sharded_index(
        "graph", "gleanvec", X, model, n_shards=n_shards, beam=96,
        max_hops=200, graph_kwargs={"r": 16, "n_iters": 4, "seed": 0})
    bench(f"graph-sharded/gleanvec-d{d}",
          lambda: finish(sh_g.search(QT, st_g, kappa)[1]))


if __name__ == "__main__":
    run()
