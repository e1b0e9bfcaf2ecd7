"""Flat (exact within the reduced space) index: ONE blocked brute-force MIPS
scan over any :mod:`repro.core.scorer` implementation.

This module is the compute substrate of
:class:`repro.index.protocol.FlatIndex` -- the Index-protocol face of the
flat scan that `core.search`, the serving layer and the sharded placement
wrapper consume; call that when you want an index object, call
``search_scorer`` when you want a function.

``scan_scorer`` is the single scan: it pads the scorer's rows to a block
multiple, scores (batch, block) tiles via ``scorer.score_block``, keeps a
running top-k, and maps the winning rows to external ids through the
protocol's ``translate_ids`` -- so scorers with a private internal layout
(the tag-sorted ones, whose ``layout_block`` also overrides the scan block
so every block stays single-tag) return original database ids like everyone
else. The historical per-representation entry points (``search`` /
``search_gleanvec`` / ``search_gleanvec_sorted`` / ``search_quantized``)
are thin wrappers that build the corresponding scorer; they are kept
because their signatures mirror the Pallas kernels (``ip_topk`` /
``gleanvec_ip`` / ``gleanvec_sq``) they lower to on TPU (see
``repro.kernels.scorer_topk``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.scorer import (GleanVecScorer, LinearScorer,
                               QuantizedScorer, SortedGleanVecScorer,
                               batch_of)
from repro.index import topk

__all__ = ["scan_scorer", "search_scorer", "search", "search_gleanvec",
           "search_gleanvec_sorted", "search_quantized"]


@functools.partial(jax.jit, static_argnames=("k", "block"))
def scan_scorer(scorer, qstate, k: int, block: int = 4096):
    """Blocked top-k scan of any scorer with prepared queries ``qstate``.

    Returns (vals, ids): (m, k) each, ids in the scorer's EXTERNAL id
    space; peak memory one (m, block) tile. Scorers with a fixed internal
    layout (``layout_block`` attribute) override ``block``.
    """
    n = scorer.n_rows
    m = batch_of(qstate)
    block = getattr(scorer, "layout_block", block)
    with jax.named_scope("search.scan"):
        padded = scorer.pad_rows((-n) % block)

    def score_block(start):
        return padded.score_block(qstate, start, block)

    vals, ids = topk.blocked_topk(score_block, n, k, block, m)
    with jax.named_scope("search.merge"):
        return vals, scorer.translate_ids(ids)


def search_scorer(queries: jax.Array, scorer, k: int, block: int = 4096):
    """Prepare + scan: ``queries (m, D or d)`` -> (vals, ids) (m, k)."""
    return scan_scorer(scorer, scorer.prepare_queries(queries), k, block)


def search(q_low: jax.Array, x_low: jax.Array, k: int, block: int = 4096):
    """Linear path: ``q_low (m, d)``, ``x_low (n, d)`` -> (vals, ids)."""
    return scan_scorer(LinearScorer(x_low=x_low), q_low, k, block)


def search_gleanvec(q_views: jax.Array, tags: jax.Array, x_low: jax.Array,
                    k: int, block: int = 4096):
    """Eager GleanVec path (Alg. 4): ``q_views (m, C, d)``, ``tags (n,)``."""
    return scan_scorer(GleanVecScorer(x_low=x_low, tags=tags), q_views, k,
                       block)


def search_quantized(q_low: jax.Array, codes: jax.Array, lo: jax.Array,
                     delta: jax.Array, k: int, block: int = 4096):
    """Int8 scalar-quantized path: codes (n, d) uint8, lo/delta (d,)."""
    scorer = QuantizedScorer(codes=codes, lo=lo, delta=delta)
    return scan_scorer(scorer, scorer.prepare_queries(q_low), k, block)


def search_gleanvec_sorted(q_views: jax.Array, block_tags: jax.Array,
                           x_low: jax.Array, k: int, block: int = 4096):
    """Eager GleanVec over a TAG-SORTED (cluster-contiguous) database: one
    query view per block, one (m, d) x (d, block) matmul per block (the
    13x-lower-HBM-write layout the Perf log quantifies).

    Thin wrapper over the same blocked scan: builds a
    :class:`~repro.core.scorer.SortedGleanVecScorer` with an IDENTITY
    permutation, so -- like the historical entry point -- the returned ids
    live in the sorted row space and callers who built the layout with
    ``gleanvec.sort_by_tag`` translate through their own permutation. New
    code should build the scorer with ``sorted_gleanvec_scorer`` instead
    and let the protocol translate ids.
    """
    n = x_low.shape[0]
    ident = jnp.arange(n, dtype=jnp.int32)
    scorer = SortedGleanVecScorer(x_low=x_low, block_tags=block_tags,
                                  perm=ident, inv_perm=ident)
    return scan_scorer(scorer, q_views, k, block)
