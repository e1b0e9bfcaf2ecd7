"""IVF (inverted-file) index with padded posting lists (JAX-friendly).

Coarse quantizer = spherical k-means centers (reused from the paper's
Appendix A implementation). Lists are stored as one permutation array plus
offsets; search gathers ``nprobe`` padded lists and scores them in one
contraction, so the whole query batch stays on the MXU.

``IVFIndex`` implements the Index protocol (:mod:`repro.index.protocol`):
fine scoring goes through the unified Scorer protocol
(:mod:`repro.core.scorer`) -- ``candidates`` scores the gathered posting
lists with ``scorer.score_ids``, so tag gathers, dequant-free int8 dots and
sorted-layout id translation come with the scorer, not with this index:
posting lists always store ORIGINAL ids.

The coarse probe has two modes. By default the centers live in R^D and the
probe scores the raw queries against them (D*4 bytes per center per
query-batch sweep). :func:`with_reduced_centers` projects the centers into
the scorer's reduced space at build time (``scorer.encode_centers``): the
probe then consumes the scorer's ALREADY-PREPARED queries and touches d
bytes per center instead of D -- the coarse step inherits the paper's D/d
bandwidth cut and needs no full-D query anywhere in the search.

The FINE step has two modes too. The default gathers the probed posting
lists and scores them with ``scorer.score_ids`` -- per-row gathers that
work for every scorer family. When the coarse quantizer is ALIGNED with a
tag-sorted scorer's clustering (:func:`build_aligned`: the centers are the
GleanVec model's landmarks, so posting list c == cluster c == a contiguous
run of single-tag blocks), ``candidates`` instead dispatches to the
scorer's gather-free ``scan_lists`` (``kernels/ivf_scan``): the probed
clusters' slabs stream through the fused single-tag kernel with a running
top-k in VMEM, no ``(m, nprobe*L)`` candidate-id or score matrix ever
reaches HBM, and the posting lists themselves are never read (they are
kept only so streaming ``insert_ids`` / ``remove_ids`` stay available).

Both fine steps name their phases for the profiler (``jax.named_scope``):
``search.probe`` (coarse scores and the ``nprobe`` top-k),
``search.scan`` (the fine scoring) and ``search.merge`` (the gathered
path's final top-k).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gleanvec as gv, linalg, spherical_kmeans
from repro.core.scorer import LinearScorer
from repro.index.protocol import (_offset_ids, register_index_pytree,
                                  replace, stacked_specs)
from repro.index.topk import NEG_INF

__all__ = ["IVFIndex", "IVFQueryState", "build", "build_sharded",
           "build_aligned", "build_aligned_sharded",
           "with_reduced_centers", "with_list_slack", "insert_ids",
           "remove_ids", "coarse_scores", "search", "search_scorer"]


class IVFQueryState(NamedTuple):
    """Prepared IVF query state: the scorer's qstate for fine scoring plus
    the full-D queries for the coarse probe -- ``q_coarse`` is None when
    the index carries reduced-space centers (the probe then reuses
    ``qstate``, so the full-D queries are never needed after prepare)."""

    qstate: Any
    q_coarse: Optional[jax.Array]


@dataclass(frozen=True, eq=False)
class IVFIndex:
    """Inverted-file index. ``center_scorer`` (optional) is a companion
    scorer over the C centers in the fine scorer's reduced representation;
    ``nprobe`` is static protocol-search configuration (override per call
    via :func:`search_scorer` or ``dataclasses.replace``). With
    ``aligned_layout`` (set by :func:`build_aligned`) the coarse clusters
    ARE the scorer's GleanVec clusters and ``candidates`` takes the
    gather-free range-scan path for sorted scorers."""

    centers: jax.Array                    # (C, D) coarse centroids (unit)
    lists: jax.Array                      # (C, max_len) int32 ids, -1 pad
    center_scorer: Any = None             # reduced-space probe companion
    nprobe: int = 8
    aligned_layout: bool = False          # clusters == sorted-layout tags

    @property
    def n_lists(self) -> int:
        return self.centers.shape[0]

    @property
    def max_len(self) -> int:
        return self.lists.shape[1]

    # ---- Index protocol ----------------------------------------------------

    def prepare_queries(self, scorer, queries: jax.Array) -> IVFQueryState:
        q_coarse = (queries.astype(jnp.float32)
                    if self.center_scorer is None else None)
        return IVFQueryState(qstate=scorer.prepare_queries(queries),
                             q_coarse=q_coarse)

    def candidates(self, qstate: IVFQueryState, scorer, k: int):
        if self.aligned_layout and hasattr(scorer, "scan_lists"):
            return _probe_and_scan(qstate, scorer, self, k)
        return _probe_and_score(qstate, scorer, self, k)

    def search(self, queries: jax.Array, scorer, k: int):
        return self.candidates(self.prepare_queries(scorer, queries),
                               scorer, k)

    def shard_specs(self, axes):
        return stacked_specs(self, axes)

    def globalize_ids(self, scorer, ids: jax.Array, row_start) -> jax.Array:
        return _offset_ids(ids, row_start)

    def refreshed(self, scorer, model) -> "IVFIndex":
        """Streaming-refresh hook: the reduced-space center companion was
        derived from the OLD model's projections, so re-encode it under
        the refreshed scorer/model (same treedef: ``encode_centers``
        returns the same companion class with the same shapes)."""
        if self.center_scorer is None:
            return self
        return replace(self,
                       center_scorer=scorer.encode_centers(self.centers,
                                                           model))


register_index_pytree(IVFIndex,
                      data_fields=("centers", "lists", "center_scorer"),
                      static_fields=("nprobe", "aligned_layout"))


# ---------------------------------------------------------------------------
# Build (host-side list packing, vectorized).
# ---------------------------------------------------------------------------


def _pack_lists(tags: np.ndarray, n_lists: int,
                min_len: int = 1) -> np.ndarray:
    """Bucket row ids by tag into a (n_lists, max_len) -1-padded table.

    One argsort + bincount pass (no per-list ``np.where`` sweep -- the
    O(C * n) packing dominated build time at C >= 4k lists)."""
    n = tags.shape[0]
    counts = np.bincount(tags, minlength=n_lists)
    max_len = max(min_len, int(counts.max()) if n else min_len)
    order = np.argsort(tags, kind="stable")
    starts = np.zeros(n_lists, np.int64)
    starts[1:] = np.cumsum(counts)[:-1]
    rank = np.arange(n) - starts[tags[order]]     # within-list slot
    lists = np.full((n_lists, max_len), -1, np.int32)
    lists[tags[order], rank] = order
    return lists


def _fit_and_tag(key, x, n_lists: int, n_iters: int):
    km = spherical_kmeans.fit(key, x, n_lists, n_iters)
    x_unit = spherical_kmeans.normalize_rows(jnp.asarray(x, jnp.float32))
    tags = np.asarray(spherical_kmeans.assign(x_unit, km.centers))
    return km.centers, tags


def build(key, x, n_lists: int, n_iters: int = 20,
          nprobe: int = 8) -> IVFIndex:
    """Cluster and bucket the database (host-side list packing)."""
    centers, tags = _fit_and_tag(key, x, n_lists, n_iters)
    return IVFIndex(centers=centers,
                    lists=jnp.asarray(_pack_lists(tags, n_lists)),
                    nprobe=nprobe)


def build_sharded(key, x, n_lists: int, n_shards: int, n_iters: int = 20,
                  nprobe: int = 8):
    """Row-sharded IVF: ONE coarse quantizer fit on the full database
    (identical to :func:`build` with the same key), per-shard posting
    lists over each shard's row range in LOCAL ids.

    Because every shard replicates the centers, each shard probes exactly
    the globally-top-``nprobe`` lists; the union of per-shard candidates
    is then precisely the single-device candidate set, which makes the
    all-gather merge of :class:`repro.index.distributed.ShardedIndex`
    return identical results. Lists are padded to a common ``max_len`` so
    the per-shard tables stack. Returns a list of ``n_shards`` IVFIndex.
    """
    n = jnp.asarray(x).shape[0]
    if n % n_shards:
        raise ValueError(f"n={n} not divisible by n_shards={n_shards}")
    per = n // n_shards
    centers, tags = _fit_and_tag(key, x, n_lists, n_iters)
    packed = [_pack_lists(tags[s * per:(s + 1) * per], n_lists)
              for s in range(n_shards)]
    max_len = max(p.shape[1] for p in packed)
    packed = [np.pad(p, ((0, 0), (0, max_len - p.shape[1])),
                     constant_values=-1) for p in packed]
    return [IVFIndex(centers=centers, lists=jnp.asarray(p), nprobe=nprobe)
            for p in packed]


def build_aligned(model, database, nprobe: int = 8) -> IVFIndex:
    """IVF whose coarse quantizer IS the GleanVec model's clustering.

    The centers are the model's k-means landmarks, so posting list ``c``
    holds exactly the rows a tag-sorted scorer stores in cluster ``c``'s
    contiguous single-tag blocks -- the precondition for the gather-free
    range-scan fine step (``scorer.scan_lists``, dispatched automatically
    by ``candidates``). The packed lists are kept ONLY for streaming
    ``insert_ids`` / ``remove_ids`` and for non-sorted scorers; the fused
    serving path never reads them. A host store (``rerank_tier.is_host``)
    is tagged a chunk at a time (``gv.assign_tags``), never sent whole."""
    tags = np.asarray(gv.assign_tags(model, database))
    return IVFIndex(centers=jnp.asarray(model.centers, jnp.float32),
                    lists=jnp.asarray(_pack_lists(tags, model.n_clusters)),
                    nprobe=min(nprobe, model.n_clusters),
                    aligned_layout=True)


def build_aligned_sharded(model, database, n_shards: int,
                          nprobe: int = 8):
    """Per-shard :func:`build_aligned`: one shared coarse quantizer (the
    model's landmarks), per-shard posting lists in LOCAL row ids, padded to
    a common ``max_len`` so the tables stack under ``ShardedIndex``."""
    X = jnp.asarray(database, jnp.float32)
    n = X.shape[0]
    if n % n_shards:
        raise ValueError(f"n={n} not divisible by n_shards={n_shards}")
    per = n // n_shards
    tags = np.asarray(gv.assign_tags(model, X))
    packed = [_pack_lists(tags[s * per:(s + 1) * per], model.n_clusters)
              for s in range(n_shards)]
    max_len = max(p.shape[1] for p in packed)
    packed = [np.pad(p, ((0, 0), (0, max_len - p.shape[1])),
                     constant_values=-1) for p in packed]
    return [IVFIndex(centers=jnp.asarray(model.centers, jnp.float32),
                     lists=jnp.asarray(p),
                     nprobe=min(nprobe, model.n_clusters),
                     aligned_layout=True) for p in packed]


def with_reduced_centers(index: IVFIndex, scorer, model=None) -> IVFIndex:
    """Project the coarse centers into ``scorer``'s reduced space: the
    probe will consume the scorer's prepared queries (R^d) instead of the
    raw full-D queries -- D/d less HBM traffic in the coarse step."""
    return replace(index,
                   center_scorer=scorer.encode_centers(index.centers,
                                                       model))


def with_list_slack(index: IVFIndex, extra: int) -> IVFIndex:
    """Widen every posting list by ``extra`` -1 slots (build-time only --
    this CHANGES the lists' shape). Streaming serving pre-allocates the
    slack here so later :func:`insert_ids` calls never reshape the index
    under a compiled engine.

    ``extra`` is PER LIST and sets the probe's gather width for the whole
    run: size it to the expected per-list fill (plus skew headroom), not
    the total insert count."""
    lists = jnp.pad(index.lists, ((0, 0), (0, extra)), constant_values=-1)
    return replace(index, lists=lists)


def insert_ids(index: IVFIndex, vecs: jax.Array, ids) -> IVFIndex:
    """Append external ``ids`` (with full-D ``vecs``) to their nearest
    centers' posting lists, filling pre-allocated -1 slots (host-side;
    shape-preserving). Raises when a list is out of slack.

    One argsort/bincount slot-assignment pass like ``_pack_lists`` -- no
    per-insert ``np.nonzero`` scan over the slot table (that loop was
    O(inserts * max_len) and dominated streaming cycles at wide slack)."""
    x_unit = spherical_kmeans.normalize_rows(jnp.asarray(vecs, jnp.float32))
    tags = np.asarray(spherical_kmeans.assign(x_unit, index.centers))
    ids_np = np.asarray(ids)
    lists = np.asarray(index.lists).copy()
    free = lists < 0                                    # (C, max_len)
    need = np.bincount(tags, minlength=lists.shape[0])
    short = np.nonzero(need > free.sum(axis=1))[0]
    if short.size:
        raise ValueError(
            f"posting list {int(short[0])} is full; pre-allocate slack "
            "with with_list_slack before serving streams")
    # slot_of_rank[t, r] = column of list t's r-th free slot; each insert's
    # within-list rank comes from the same argsort/cumsum bucketing as
    # _pack_lists, so the fill order matches the sequential reference.
    frank = np.cumsum(free, axis=1) - 1
    slot_of_rank = np.zeros_like(lists)
    rows_f, cols_f = np.nonzero(free)
    slot_of_rank[rows_f, frank[rows_f, cols_f]] = cols_f
    order = np.argsort(tags, kind="stable")
    starts = np.zeros(lists.shape[0], np.int64)
    starts[1:] = np.cumsum(need)[:-1]
    rank = np.arange(tags.size) - starts[tags[order]]
    lists[tags[order], slot_of_rank[tags[order], rank]] = \
        ids_np[order].astype(lists.dtype)
    return replace(index, lists=jnp.asarray(lists))


def remove_ids(index: IVFIndex, ids) -> IVFIndex:
    """Drop external ``ids`` from every posting list (slots return to the
    -1 free pool; shape-preserving)."""
    lists = np.asarray(index.lists).copy()
    lists[np.isin(lists, np.asarray(ids))] = -1
    return replace(index, lists=jnp.asarray(lists))


# ---------------------------------------------------------------------------
# Search.
# ---------------------------------------------------------------------------


def coarse_scores(index: IVFIndex, qstate: IVFQueryState) -> jax.Array:
    """(m, C) query-center scores: full-D when the index has no reduced
    centers, else one reduced-space ``score_block`` over all C centers
    (this is the function the probe-bandwidth assertion compiles)."""
    if index.center_scorer is None:
        return linalg.dot(qstate.q_coarse, index.centers.T)
    return index.center_scorer.score_block(qstate.qstate, 0, index.n_lists)


@functools.partial(jax.jit, static_argnames=("k",))
def _probe_and_scan(qstate: IVFQueryState, scorer, index: IVFIndex,
                    k: int):
    """Aligned fine step: probe ``nprobe`` clusters, stream their sorted
    slabs through the scorer's gather-free ``scan_lists``. ``index.lists``
    is never read (XLA drops the unused leaf), so the posting-list HBM
    footprint vanishes from the compiled sorted serving path."""
    with jax.named_scope("search.probe"):
        coarse = coarse_scores(index, qstate)               # (m, C)
        _, probe = jax.lax.top_k(coarse, index.nprobe)      # (m, nprobe)
    with jax.named_scope("search.scan"):
        return scorer.scan_lists(qstate.qstate, probe, k)


@functools.partial(jax.jit, static_argnames=("k",))
def _probe_and_score(qstate: IVFQueryState, scorer, index: IVFIndex,
                     k: int):
    """Probe ``index.nprobe`` lists per query, score via the scorer."""
    m = jax.tree_util.tree_leaves(qstate.qstate)[0].shape[0]
    with jax.named_scope("search.probe"):
        coarse = coarse_scores(index, qstate)               # (m, C)
        _, probe = jax.lax.top_k(coarse, index.nprobe)      # (m, nprobe)
    with jax.named_scope("search.scan"):
        cand = index.lists[probe].reshape(m, -1)            # (m, nprobe*L)
        safe = jnp.where(cand >= 0, cand, 0)
        scores = scorer.score_ids(qstate.qstate, safe)      # (m, nprobe*L)
        scores = jnp.where(cand >= 0, scores, NEG_INF)
    with jax.named_scope("search.merge"):
        vals, sel = jax.lax.top_k(scores, k)
        ids = jnp.take_along_axis(cand, sel, axis=1)
        # -inf winners are padding slots or tombstoned (dead) rows a
        # streaming store masked; strip their ids so the rerank never
        # resurrects them.
        return vals, jnp.where(vals > NEG_INF, ids, -1)


def search_scorer(queries: jax.Array, scorer, index: IVFIndex, k: int,
                  nprobe: int = 8):
    """Unified-protocol search: ``queries (m, D)`` in the FULL dimension.

    The coarse step scores the centers in R^D (or in R^d through the
    index's reduced centers); the fine step scores the gathered posting
    lists through any scorer. Returns (vals, ids): (m, k).
    """
    return replace(index, nprobe=nprobe).search(queries, scorer, k)


def search(q_low: jax.Array, q_full: jax.Array, x_low: jax.Array,
           index: IVFIndex, k: int, nprobe: int = 8):
    """Legacy linear entry point: pre-reduced ``q_low`` + raw ``x_low``.

    Always probes in FULL dimension: a reduced-centers companion is built
    for a specific scorer family's qstate, and this signature gives no way
    to know that ``q_low`` matches it -- use :func:`search_scorer` (or the
    Index protocol) for reduced-space probing."""
    qstate = IVFQueryState(qstate=q_low,
                           q_coarse=q_full.astype(jnp.float32))
    return _probe_and_score(qstate, LinearScorer(x_low=x_low),
                            replace(index, nprobe=nprobe,
                                    center_scorer=None), k)
