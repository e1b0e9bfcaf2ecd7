"""Pallas TPU kernels for the perf-critical compute hot-spots.

Each kernel package ships <name>.py (pl.pallas_call + BlockSpec tiling),
ops.py (dispatching jit wrapper) and ref.py (pure-jnp oracle used by tests
and as the differentiable/CPU fallback).

This module is also the SINGLE place where a Scorer
(:mod:`repro.core.scorer`) lowers to its kernel: ``scorer_scores`` /
``scorer_topk`` map each protocol implementation to the matching Pallas
kernel on TPU (``ip_topk`` / ``gleanvec_ip`` / ``sq_dot`` /
``gleanvec_sq``) and to the jnp mirrors elsewhere. Index code never
mentions kernels; it talks to scorers, and scorers lower here.
"""
from repro.kernels.flash_attention import flash_attention, flash_attention_ref
from repro.kernels.gleanvec_ip import gleanvec_ip, gleanvec_ip_ref
from repro.kernels.graph_scan import (beam_step_bytes, fresh_slab_count,
                                      graph_scan_beam_step,
                                      graph_scan_beam_step_ref,
                                      graph_scan_scores_ref)
from repro.kernels.gleanvec_sq import (gleanvec_sq, gleanvec_sq_ref,
                                       gleanvec_sq_sorted_ref,
                                       gleanvec_sq_topk,
                                       gleanvec_sq_topk_ref)
from repro.kernels.ip_topk import ip_topk, ip_topk_ref
from repro.kernels.ivf_scan import (fine_step_bytes, ivf_scan_scores_ref,
                                    ivf_scan_topk, ivf_scan_topk_ref)
from repro.kernels.kmeans_assign import kmeans_assign, kmeans_assign_ref
from repro.kernels.sq_dot import sq_dot, sq_dot_ref

__all__ = [
    "flash_attention", "flash_attention_ref",
    "gleanvec_ip", "gleanvec_ip_ref",
    "gleanvec_sq", "gleanvec_sq_ref", "gleanvec_sq_sorted_ref",
    "gleanvec_sq_topk", "gleanvec_sq_topk_ref",
    "ip_topk", "ip_topk_ref",
    "graph_scan_beam_step", "graph_scan_beam_step_ref",
    "graph_scan_scores_ref", "beam_step_bytes", "fresh_slab_count",
    "ivf_scan_topk", "ivf_scan_topk_ref", "ivf_scan_scores_ref",
    "fine_step_bytes",
    "kmeans_assign", "kmeans_assign_ref",
    "sq_dot", "sq_dot_ref",
    "scorer_scores", "scorer_topk",
]


def _mask_live(scorer, scores):
    """Dead slots of a fixed-capacity streaming store score -inf; the
    ``live=None`` static path is untouched (identical HLO)."""
    import jax.numpy as jnp

    from repro.core import scorer as sc

    live = getattr(scorer, "live", None)
    if live is None:
        return scores
    return jnp.where(live[None, :], scores, sc.NEG_INF)


def scorer_scores(scorer, queries, *, use_pallas=None, interpret=False):
    """Dense (m, n) scores of ``queries`` against a scorer's database,
    lowered to the scorer's kernel (TPU) or jnp mirror (elsewhere).

    ``n`` spans the scorer's INTERNAL row space: for the sorted scorers
    column j is sorted row j (translate through ``scorer.translate_ids`` to
    reach original ids); for every other scorer it is the original id.
    Scorers carrying a streaming ``live`` mask get dead columns set to
    -inf after the kernel.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import linalg, scorer as sc

    kw = dict(use_pallas=use_pallas, interpret=interpret)
    if isinstance(scorer, sc.LinearScorer):
        q_low = scorer.prepare_queries(queries)
        return _mask_live(scorer, linalg.dot(q_low, scorer.x_low.T))
    if isinstance(scorer, sc.GleanVecScorer):
        q_views = scorer.prepare_queries(queries)
        return _mask_live(scorer, gleanvec_ip(q_views, scorer.tags,
                                              scorer.x_low, **kw))
    if isinstance(scorer, sc.QuantizedScorer):
        q = queries.astype(jnp.float32)
        q_low = q if scorer.a is None else linalg.dot(q, scorer.a.T)
        return _mask_live(scorer, sq_dot(q_low, scorer.codes, scorer.lo,
                                         scorer.delta, **kw))
    if isinstance(scorer, sc.GleanVecQuantizedScorer):
        qs = scorer.prepare_queries(queries)
        return _mask_live(scorer, gleanvec_sq(qs.q_scaled, qs.q_lo,
                                              scorer.tags, scorer.codes,
                                              **kw))
    if isinstance(scorer, sc.SortedGleanVecScorer):
        q_views = scorer.prepare_queries(queries)
        q_lo = jnp.zeros(q_views.shape[:2], jnp.float32)   # no affine term
        scores = gleanvec_sq(q_views, q_lo, scorer.block_tags, scorer.x_low,
                             layout_block=scorer.layout_block, **kw)
        return jnp.where(scorer.perm[None, :] >= 0, scores, sc.NEG_INF)
    if isinstance(scorer, sc.SortedGleanVecQuantizedScorer):
        qs = scorer.prepare_queries(queries)
        scores = gleanvec_sq(qs.q_scaled, qs.q_lo, scorer.block_tags,
                             scorer.codes,
                             layout_block=scorer.layout_block, **kw)
        return jnp.where(scorer.perm[None, :] >= 0, scores, sc.NEG_INF)
    raise TypeError(f"no kernel lowering for {type(scorer).__name__}")


def scorer_topk(scorer, queries, k: int, *, use_pallas=None,
                interpret=False):
    """Fused MIPS top-k of ``queries`` against a scorer's database.

    Every scorer lowers to a fused scan that never materializes the dense
    (m, n) score matrix: ``LinearScorer`` to ``ip_topk``,
    ``QuantizedScorer`` to ``ip_topk`` over the codes (the query-constant
    <Aq, lo> offset is rank-invariant and added to the returned values),
    and the GleanVec family (eager, int8 and both sorted layouts) to
    ``gleanvec_sq_topk``. Returns (vals (m, k) f32, ids (m, k) i32) with
    ids ALWAYS in the original database space (sorted scorers emit ids
    through their permutation inside the kernel).
    """
    import jax
    import jax.numpy as jnp

    from repro.core import scorer as sc

    kw = dict(use_pallas=use_pallas, interpret=interpret)
    live = getattr(scorer, "live", None)
    live_ids = (None if live is None else
                jnp.where(live, jnp.arange(live.shape[0], dtype=jnp.int32),
                          -1))
    if isinstance(scorer, (sc.LinearScorer, sc.QuantizedScorer)) \
            and live is not None:
        # ip_topk has no row-id masking input; a live-masked linear store
        # falls back to dense scores + top_k (streaming stores are served
        # through the blocked scan anyway).
        scores = scorer_scores(scorer, queries, **kw)
        return jax.lax.top_k(scores, k)
    if isinstance(scorer, sc.LinearScorer):
        q_low = scorer.prepare_queries(queries)
        return ip_topk(q_low, scorer.x_low, k, **kw)
    if isinstance(scorer, sc.QuantizedScorer):
        qs = scorer.prepare_queries(queries)
        vals, ids = ip_topk(qs.q_scaled, scorer.codes, k, **kw)
        return vals + qs.q_lo[:, None], ids
    if isinstance(scorer, sc.GleanVecScorer):
        q_views = scorer.prepare_queries(queries)
        q_lo = jnp.zeros(q_views.shape[:2], jnp.float32)   # no affine term
        return gleanvec_sq_topk(q_views, q_lo, scorer.tags, scorer.x_low,
                                k, row_ids=live_ids, **kw)
    if isinstance(scorer, sc.GleanVecQuantizedScorer):
        qs = scorer.prepare_queries(queries)
        return gleanvec_sq_topk(qs.q_scaled, qs.q_lo, scorer.tags,
                                scorer.codes, k, row_ids=live_ids, **kw)
    if isinstance(scorer, sc.SortedGleanVecScorer):
        q_views = scorer.prepare_queries(queries)
        q_lo = jnp.zeros(q_views.shape[:2], jnp.float32)   # no affine term
        return gleanvec_sq_topk(q_views, q_lo, scorer.block_tags,
                                scorer.x_low, k, row_ids=scorer.perm,
                                layout_block=scorer.layout_block, **kw)
    if isinstance(scorer, sc.SortedGleanVecQuantizedScorer):
        qs = scorer.prepare_queries(queries)
        return gleanvec_sq_topk(qs.q_scaled, qs.q_lo, scorer.block_tags,
                                scorer.codes, k, row_ids=scorer.perm,
                                layout_block=scorer.layout_block, **kw)
    raise TypeError(f"no kernel lowering for {type(scorer).__name__}")
