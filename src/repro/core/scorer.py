"""Unified Scorer protocol: one database representation + scoring contract
shared by every index (flat / IVF / graph / distributed) and the serving
stack.

The paper's multi-step search (Algorithm 1) is index-agnostic: any index can
run its main search in a compressed representation as long as it can score a
query against (a) a contiguous block of database rows (flat scans) or (b) an
arbitrary gathered id set (IVF posting lists, graph neighbor expansions).
A *scorer* packages a database representation together with those two
operations:

    qstate = scorer.prepare_queries(q)            # Alg. 1 line 1
    scores = scorer.score_block(qstate, start, B) # (m, B), contiguous rows
    scores = scorer.score_ids(qstate, ids)        # (m, P), gathered rows

plus the layout plumbing every consumer needs: ``pad_rows`` (blocked scans),
``shard_specs`` (row-sharding under shard_map), ``encode_centers``
(auxiliary vectors -- IVF coarse centers -- encoded into a companion
scorer that consumes THIS scorer's prepared queries, so the coarse probe
runs in R^d), and the id-translation
contract (``translate_ids`` / ``globalize_ids``): a scorer may store its
rows in a private internal layout, and consumers map the row indices a scan
produces back to the external (original database) id space by calling
``translate_ids`` at the boundary. For the four row-aligned scorers this is
the identity; the SORTED scorers carry a sort permutation and translate
through it. Scorers are NamedTuples, so they are jax pytrees: they pass
through ``jit`` / ``shard_map`` boundaries as regular arguments and their
class is part of the (static) treedef.

Concrete implementations and what they store per database vector:

    ==========================  =========================  ================
    scorer                      storage                    scoring
    ==========================  =========================  ================
    LinearScorer                f32 x_low = Bx (d dims)    <Aq, Bx>
    GleanVecScorer              f32 B_c x + tag (Alg. 4)   <A_c q, B_c x>
    QuantizedScorer             u8 codes of Bx + (d) scale <Aq*delta, u>+...
    GleanVecQuantizedScorer     u8 codes of B_c x + tag    per-cluster SQ
                                + (C, d) per-cluster scale
    SortedGleanVecScorer        f32 B_c x, TAG-SORTED      <A_c q, B_c x>,
                                + per-block tag + perm     one view/block
    SortedGleanVecQuantized-    u8 codes, TAG-SORTED       per-cluster SQ,
    Scorer                      + per-block tag + perm     one view/block
    ==========================  =========================  ================

The sorted scorers store the database cluster-contiguously (rows sorted by
tag, each cluster padded to a ``layout_block`` multiple): every block has
ONE tag, so a blocked scan degenerates to a single (m, d) x (d, block)
matmul per block -- no per-row view gather, no one-hot -- which is the 13x
HBM-write reduction the Perf log quantifies. The price is a private row
order: ``perm`` (sorted row -> original id, -1 on padding) and ``inv_perm``
(original id -> sorted row) translate at the consumer boundary, so IVF
posting lists, graph neighbors and rerank candidates keep speaking original
ids. They additionally carry ``list_block_ranges`` ((C, max_blocks) block
indices per cluster, -1-padded, derived from ``block_tags``) and expose
``scan_lists(qstate, probe, k)`` -- the gather-free IVF fine step: an
ALIGNED coarse quantizer's probed clusters stream slab-by-slab through the
``kernels/ivf_scan`` range-scan kernel instead of a posting-list gather.

``GleanVecQuantizedScorer`` is the composition the LeanVec line of work
endorses (DR stacked with scalar quantization): the per-cluster reduced
vectors are int8-quantized with per-cluster per-dimension scales, and the
affine terms fold into the prepared query views so scoring stays a pure
int8 contraction.

``LinearScorer`` with ``a=None`` doubles as the exact full-precision
scorer (identity query transform over the stored vectors) -- the "full"
serving mode and the rerank reference are the same object.

The kernel lowering lives in :mod:`repro.kernels` (``scorer_topk`` /
``scorer_scores``): on TPU a scorer lowers to its Pallas kernel
(``ip_topk`` / ``gleanvec_ip`` / ``sq_dot`` / ``gleanvec_sq``), elsewhere
to the jnp mirrors used here.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from repro.core import gleanvec as gv
from repro.core import linalg
from repro.core import quantization as quant
from repro.core.quantization import ClusteredSQDatabase

__all__ = [
    "LinearScorer", "GleanVecScorer", "QuantizedScorer",
    "GleanVecQuantizedScorer", "SortedGleanVecScorer",
    "SortedGleanVecQuantizedScorer", "QuantQueryState", "Scorer", "MODES",
    "build_scorer", "linear_scorer", "exact_scorer", "gleanvec_scorer",
    "quantized_scorer", "gleanvec_quantized_scorer",
    "sorted_gleanvec_scorer", "sorted_gleanvec_quantized_scorer",
    "batch_of",
]

# Mirrors index.topk.NEG_INF (importing it would cycle: index -> bruteforce
# -> this module). Keep the value in sync.
NEG_INF = jnp.float32(-3.4e38)


def _globalize_row_aligned(ids: jax.Array, shard_idx, n_rows: int):
    """Default ``globalize_ids``: offset local ids by the shard row count."""
    return jnp.where(ids >= 0, ids + shard_idx * n_rows, -1)


def _translate_sorted(perm: jax.Array, ids: jax.Array):
    """Sorted-layout ``translate_ids``: sorted rows -> original ids via the
    sort permutation; invalid slots and padding rows map to -1."""
    orig = perm[jnp.where(ids >= 0, ids, 0)]
    return jnp.where(ids >= 0, orig, -1)


def _list_block_ranges(block_tags, c: int) -> jax.Array:
    """(C, max_blocks) table of layout-block indices per cluster, -1-padded
    (host-side, once at build; derivable from ``block_tags`` because
    ``sort_by_tag`` keeps each cluster's blocks -- slack blocks included --
    contiguous). ``ranges[probe]`` IS the probe schedule the gather-free
    range-scan kernel consumes: one argsort/bincount pass, no per-cluster
    sweep."""
    import numpy as np
    bt = np.asarray(block_tags)
    blocks = np.nonzero(bt >= 0)[0]           # stacked shards pad with -1
    t = bt[blocks]
    counts = np.bincount(t, minlength=c) if t.size else np.zeros(c, int)
    maxb = max(1, int(counts.max()) if t.size else 1)
    starts = np.zeros(c, np.int64)
    starts[1:] = np.cumsum(counts)[:-1]
    order = np.argsort(t, kind="stable")
    rank = np.arange(t.size) - starts[t[order]]
    out = np.full((c, maxb), -1, np.int32)
    out[t[order], rank] = blocks[order].astype(np.int32)
    return jnp.asarray(out)


def _center_views_scorer(centers: jax.Array, model) -> "GleanVecScorer":
    """Probe companion for the eager-view qstate family (GleanVec and its
    sorted layout): centers tagged and projected per cluster."""
    if model is None:
        raise ValueError("encode_centers on a GleanVec-family scorer "
                         "needs the GleanVec model")
    tags, low = gv.encode_database(model, jnp.asarray(centers, jnp.float32))
    return GleanVecScorer(x_low=low, tags=tags)


def _center_pseudo_scorer(centers: jax.Array, model, lo, delta,
                          a) -> "GleanVecQuantizedScorer":
    """Probe companion for the folded per-cluster int8 qstate family
    (GleanVec∘int8 and its sorted layout): projected centers stored as f32
    PSEUDO-codes ``(B_t c - lo_t) / delta_t`` under the DATABASE's scales,
    so ``q_scaled . codes + q_lo == <A_t q, B_t c>`` exactly."""
    if model is None:
        raise ValueError("encode_centers on a GleanVec-family scorer "
                         "needs the GleanVec model")
    tags, low = gv.encode_database(model, jnp.asarray(centers, jnp.float32))
    return GleanVecQuantizedScorer(codes=(low - lo[tags]) / delta[tags],
                                   tags=tags, lo=lo, delta=delta, a=a)


class QuantQueryState(NamedTuple):
    """Prepared query for int8 scorers: the affine terms folded query-side.

    ``q_scaled``: (m, d) [linear] or (m, C, d) [per-cluster] = Aq * delta;
    ``q_lo``:     (m,)               or (m, C)              = <Aq, lo>.
    """

    q_scaled: jax.Array
    q_lo: jax.Array


def batch_of(qstate) -> int:
    """Query-batch size of any prepared query state (first leaf, dim 0)."""
    return jax.tree_util.tree_leaves(qstate)[0].shape[0]


def _pad0(x: jax.Array, pad: int) -> jax.Array:
    return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))


# ---------------------------------------------------------------------------
# Streaming-store helpers (the ``live`` mask + row-level update machinery).
#
# A scorer built by ``streaming.build_streaming_artifacts`` is a FIXED-
# CAPACITY store: its row arrays are pre-allocated and an optional ``live``
# mask ((n,) bool) marks which slots currently hold a vector. Dead slots
# score -inf and translate to id -1, so they can never reach the rerank;
# ``insert_rows`` / ``remove_rows`` flip slots without changing any leaf
# shape -- which is what lets the serving engine swap the updated scorer in
# with zero recompiles. ``live=None`` (the default everywhere) means "all
# rows live" and keeps the static path's pytree structure and HLO
# unchanged.
# ---------------------------------------------------------------------------


def _encode_rows_gleanvec(model, rows: jax.Array):
    """Tag + per-cluster projection of full-D ``rows`` -- the SAME
    Eq. 14-15 pipeline as build time, so streamed inserts can never drift
    from the original encoding."""
    return gv.encode_database(model, jnp.asarray(rows, jnp.float32))


def _mask_live_block(live, start, block: int, scores: jax.Array):
    if live is None:
        return scores
    lv = jax.lax.dynamic_slice_in_dim(live, start, block, axis=0)
    return jnp.where(lv[None, :], scores, NEG_INF)


def _mask_live_ids(live, ids: jax.Array, scores: jax.Array):
    if live is None:
        return scores
    return jnp.where(live[ids], scores, NEG_INF)


def _translate_live(live, n_rows: int, ids: jax.Array) -> jax.Array:
    """Row-aligned ``translate_ids`` under a live mask: dead (or padding)
    rows map to -1 so downstream consumers drop them like sorted-layout
    padding."""
    if live is None:
        return ids
    safe = jnp.clip(ids, 0, n_rows - 1)
    ok = (ids >= 0) & (ids < n_rows) & live[safe]
    return jnp.where(ok, ids, -1)


def _set_live(live, ids: jax.Array, value: bool, n_rows: int):
    """Functional live-mask update; materializes the mask on first remove
    (which changes the scorer's treedef -- streaming stores pre-materialize
    it at build time precisely so later updates don't)."""
    if live is None:
        if value:
            return None         # all rows already live
        live = jnp.ones((n_rows,), jnp.bool_)
    return live.at[ids].set(value)


def _sorted_claim_slots(perm, inv_perm, block_tags, layout_block: int,
                        ids, tags):
    """Host-side slot allocation for the sorted layouts: for each new row's
    cluster tag, claim the first padding slot (perm == -1) inside that
    cluster's single-tag blocks. An id that is ALREADY live releases its
    old slot first (re-insert == overwrite, matching the row-aligned
    scorers -- never two sorted rows translating to one external id).
    Returns ``(slots, freed_old_slots)``; raises when a cluster is out of
    slack."""
    import numpy as np
    perm_np = np.asarray(perm).copy()
    old = np.asarray(inv_perm)[np.asarray(ids)]
    freed = old[old >= 0]
    perm_np[freed] = -1
    row_tags = np.asarray(block_tags)[
        np.arange(perm_np.shape[0]) // layout_block]
    free = perm_np < 0
    slots = np.empty(len(tags), np.int64)
    for j, t in enumerate(np.asarray(tags)):
        cand = np.nonzero(free & (row_tags == int(t)))[0]
        if cand.size == 0:
            raise ValueError(
                f"sorted layout: cluster {int(t)} has no free slots; "
                "rebuild the layout with more slack_blocks")
        slots[j] = cand[0]
        free[cand[0]] = False
    return slots, freed


class LinearScorer(NamedTuple):
    """Linear DR scoring: <Aq, Bx>. ``a=None`` means identity (exact MIPS
    over whatever ``x_low`` stores -- including the full-precision x)."""

    x_low: jax.Array                 # (n, d)
    a: Optional[jax.Array] = None    # (d, D) query transform
    live: Optional[jax.Array] = None  # (n,) bool slot mask (None = all)

    @property
    def n_rows(self) -> int:
        return self.x_low.shape[0]

    def prepare_queries(self, queries: jax.Array) -> jax.Array:
        q = queries.astype(jnp.float32)
        return q if self.a is None else linalg.dot(q, self.a.T)

    def pad_rows(self, pad: int) -> "LinearScorer":
        if not pad:
            return self
        return self._replace(
            x_low=_pad0(self.x_low, pad),
            live=None if self.live is None else _pad0(self.live, pad))

    def score_block(self, qstate: jax.Array, start, block: int) -> jax.Array:
        blk = jax.lax.dynamic_slice_in_dim(self.x_low, start, block, axis=0)
        return _mask_live_block(self.live, start, block,
                                linalg.dot(qstate, blk.T))

    def score_ids(self, qstate: jax.Array, ids: jax.Array) -> jax.Array:
        vecs = self.x_low[ids]                          # (m, p, d)
        return _mask_live_ids(self.live, ids,
                              jnp.einsum("mpd,md->mp", vecs, qstate,
                                         precision=linalg.F32))

    def shard_specs(self, axes) -> "LinearScorer":
        from jax.sharding import PartitionSpec as P
        return LinearScorer(x_low=P(tuple(axes), None),
                            a=None if self.a is None else P(),
                            live=None if self.live is None
                            else P(tuple(axes)))

    def translate_ids(self, ids: jax.Array) -> jax.Array:
        return _translate_live(self.live, self.n_rows, ids)

    def globalize_ids(self, ids: jax.Array, shard_idx) -> jax.Array:
        return _globalize_row_aligned(ids, shard_idx, self.n_rows)

    # ---- streaming row-level ops (Section 3.2) ----------------------------

    def insert_rows(self, ids: jax.Array, rows: jax.Array,
                    model=None) -> "LinearScorer":
        """Encode full-D ``rows`` into slots ``ids`` and mark them live."""
        rows = jnp.asarray(rows, jnp.float32)
        enc = rows if self.a is None else linalg.dot(rows, model.b.T)
        return self._replace(
            x_low=self.x_low.at[ids].set(enc),
            live=_set_live(self.live, ids, True, self.n_rows))

    def remove_rows(self, ids: jax.Array) -> "LinearScorer":
        """Tombstone slots ``ids`` (their contents stop mattering)."""
        return self._replace(live=_set_live(self.live, ids, False,
                                            self.n_rows))

    def refresh(self, model, transition=None, x_full=None,
                pending=None) -> "LinearScorer":
        """Re-encode under a refreshed ``model``: via the Eq. (12)
        transition matrix over the STORED reduced vectors (default), or
        exactly from ``x_full`` when given. ``pending`` ((n,) bool)
        selects the lazy subset; unmarked rows keep their old projection."""
        if self.a is None:
            return self     # exact scorer: stores the raw vectors
        if x_full is not None:
            new_low = linalg.dot(jnp.asarray(x_full, jnp.float32), model.b.T)
        else:
            new_low = linalg.dot(self.x_low, transition.T)
        if pending is not None:
            new_low = jnp.where(pending[:, None], new_low, self.x_low)
        return self._replace(x_low=new_low, a=model.a)

    def encode_centers(self, centers: jax.Array,
                       model=None) -> "LinearScorer":
        """Companion probe scorer over full-D ``centers`` (C, D): scoring
        it with THIS scorer's qstate computes <Aq, B c> in R^d. With
        ``a=None`` (exact scorer) the centers pass through unprojected."""
        c = jnp.asarray(centers, jnp.float32)
        if self.a is None:
            return LinearScorer(x_low=c)
        if model is None:
            raise ValueError("encode_centers on a reduced LinearScorer "
                             "needs the DR model (its B matrix)")
        return LinearScorer(x_low=linalg.dot(c, model.b.T))


class GleanVecScorer(NamedTuple):
    """Eager GleanVec scoring (Alg. 4): tag-selected per-cluster views."""

    x_low: jax.Array                 # (n, d) = B_{tag_i} x_i
    tags: jax.Array                  # (n,) int32 cluster of each vector
    a: Optional[jax.Array] = None    # (C, d, D) per-cluster query maps
    live: Optional[jax.Array] = None  # (n,) bool slot mask (None = all)

    @property
    def n_rows(self) -> int:
        return self.x_low.shape[0]

    def prepare_queries(self, queries: jax.Array) -> jax.Array:
        if self.a is None:
            raise ValueError("GleanVecScorer without `a` cannot prepare "
                             "queries; pass precomputed (m, C, d) views")
        return jnp.einsum("cdk,mk->mcd", self.a,
                          queries.astype(jnp.float32), precision=linalg.F32)

    def pad_rows(self, pad: int) -> "GleanVecScorer":
        if not pad:
            return self
        return self._replace(x_low=_pad0(self.x_low, pad),
                             tags=_pad0(self.tags, pad),
                             live=None if self.live is None
                             else _pad0(self.live, pad))

    def score_block(self, qstate: jax.Array, start, block: int) -> jax.Array:
        blk = jax.lax.dynamic_slice_in_dim(self.x_low, start, block, axis=0)
        tag = jax.lax.dynamic_slice_in_dim(self.tags, start, block, axis=0)
        q_sel = qstate[:, tag, :]                       # (m, block, d)
        return _mask_live_block(self.live, start, block,
                                jnp.einsum("mbd,bd->mb", q_sel, blk,
                                           precision=linalg.F32))

    def score_ids(self, qstate: jax.Array, ids: jax.Array) -> jax.Array:
        vecs = self.x_low[ids]                          # (m, p, d)
        tag = self.tags[ids]                            # (m, p)
        m = qstate.shape[0]
        q_sel = qstate[jnp.arange(m)[:, None], tag]     # (m, p, d)
        return _mask_live_ids(self.live, ids,
                              jnp.sum(q_sel * vecs, axis=-1))

    def shard_specs(self, axes) -> "GleanVecScorer":
        from jax.sharding import PartitionSpec as P
        return GleanVecScorer(x_low=P(tuple(axes), None),
                              tags=P(tuple(axes)),
                              a=None if self.a is None else P(),
                              live=None if self.live is None
                              else P(tuple(axes)))

    def translate_ids(self, ids: jax.Array) -> jax.Array:
        return _translate_live(self.live, self.n_rows, ids)

    def globalize_ids(self, ids: jax.Array, shard_idx) -> jax.Array:
        return _globalize_row_aligned(ids, shard_idx, self.n_rows)

    # ---- streaming row-level ops (Section 3.2) ----------------------------

    def insert_rows(self, ids: jax.Array, rows: jax.Array,
                    model=None) -> "GleanVecScorer":
        tags_new, enc = _encode_rows_gleanvec(model, rows)
        return self._replace(
            x_low=self.x_low.at[ids].set(enc),
            tags=self.tags.at[ids].set(tags_new.astype(self.tags.dtype)),
            live=_set_live(self.live, ids, True, self.n_rows))

    def remove_rows(self, ids: jax.Array) -> "GleanVecScorer":
        return self._replace(live=_set_live(self.live, ids, False,
                                            self.n_rows))

    def refresh(self, model, transition=None, x_full=None,
                pending=None) -> "GleanVecScorer":
        """Per-cluster Eq. (12): row i maps through T_{tag_i} ((C, d, d)
        ``transition``), or re-encodes exactly from ``x_full``. Tags are
        untouched -- the k-means landmarks are fixed under streaming."""
        if x_full is not None:
            new_low = jnp.einsum("ndk,nk->nd", model.b[self.tags],
                                 jnp.asarray(x_full, jnp.float32),
                                 precision=linalg.F32)
        else:
            new_low = jnp.einsum("nij,nj->ni", transition[self.tags],
                                 self.x_low, precision=linalg.F32)
        if pending is not None:
            new_low = jnp.where(pending[:, None], new_low, self.x_low)
        return self._replace(x_low=new_low, a=model.a)

    def encode_centers(self, centers: jax.Array,
                       model=None) -> "GleanVecScorer":
        """Companion probe scorer: centers tagged and projected per cluster
        (B_{t_j} c_j), scored with this scorer's eager (m, C, d) views."""
        return _center_views_scorer(centers, model)


class QuantizedScorer(NamedTuple):
    """Int8 SQ over linearly-reduced vectors, per-dimension affine scales
    folded into the query: <q, u*delta + lo> = <q*delta, u> + <q, lo>."""

    codes: jax.Array                 # (n, d) uint8
    lo: jax.Array                    # (d,)
    delta: jax.Array                 # (d,)
    a: Optional[jax.Array] = None    # (d, D) query transform
    live: Optional[jax.Array] = None  # (n,) bool slot mask (None = all)

    @property
    def n_rows(self) -> int:
        return self.codes.shape[0]

    def prepare_queries(self, queries: jax.Array) -> QuantQueryState:
        q = queries.astype(jnp.float32)
        if self.a is not None:
            q = linalg.dot(q, self.a.T)
        return QuantQueryState(q_scaled=q * self.delta[None, :],
                               q_lo=linalg.dot(q, self.lo))

    def pad_rows(self, pad: int) -> "QuantizedScorer":
        if not pad:
            return self
        return self._replace(
            codes=_pad0(self.codes, pad),
            live=None if self.live is None else _pad0(self.live, pad))

    def score_block(self, qstate: QuantQueryState, start,
                    block: int) -> jax.Array:
        c = jax.lax.dynamic_slice_in_dim(self.codes, start, block, axis=0)
        return _mask_live_block(self.live, start, block,
                                linalg.dot(qstate.q_scaled,
                                           c.astype(jnp.float32).T)
                                + qstate.q_lo[:, None])

    def score_ids(self, qstate: QuantQueryState, ids: jax.Array) -> jax.Array:
        c = self.codes[ids].astype(jnp.float32)         # (m, p, d)
        return _mask_live_ids(self.live, ids,
                              jnp.einsum("mpd,md->mp", c, qstate.q_scaled,
                                         precision=linalg.F32)
                              + qstate.q_lo[:, None])

    def shard_specs(self, axes) -> "QuantizedScorer":
        from jax.sharding import PartitionSpec as P
        return QuantizedScorer(codes=P(tuple(axes), None), lo=P(), delta=P(),
                               a=None if self.a is None else P(),
                               live=None if self.live is None
                               else P(tuple(axes)))

    def translate_ids(self, ids: jax.Array) -> jax.Array:
        return _translate_live(self.live, self.n_rows, ids)

    def globalize_ids(self, ids: jax.Array, shard_idx) -> jax.Array:
        return _globalize_row_aligned(ids, shard_idx, self.n_rows)

    # ---- streaming row-level ops (Section 3.2) ----------------------------

    def insert_rows(self, ids: jax.Array, rows: jax.Array,
                    model=None) -> "QuantizedScorer":
        """New rows are coded under the EXISTING scales (clipped if they
        fall outside the fitted range); the next ``refresh`` refits them.
        Streaming row ops assume the serving modes' 8-bit coding (the
        scorer stores no ``bits`` field; sub-8-bit stores would need
        one)."""
        rows = jnp.asarray(rows, jnp.float32)
        low = rows if self.a is None else linalg.dot(rows, model.b.T)
        levels = 255
        enc = jnp.clip(jnp.round((low - self.lo[None, :])
                                 / self.delta[None, :]), 0,
                       levels).astype(self.codes.dtype)
        return self._replace(
            codes=self.codes.at[ids].set(enc),
            live=_set_live(self.live, ids, True, self.n_rows))

    def remove_rows(self, ids: jax.Array) -> "QuantizedScorer":
        return self._replace(live=_set_live(self.live, ids, False,
                                            self.n_rows))

    def refresh(self, model, transition=None, x_full=None,
                pending=None) -> "QuantizedScorer":
        """Dequantize -> Eq. (12) reproject (or re-encode from ``x_full``)
        -> requantize with freshly fitted scales over the live rows."""
        old_low = self.codes.astype(jnp.float32) * self.delta[None, :] \
            + self.lo[None, :]
        if x_full is not None:
            new_low = linalg.dot(jnp.asarray(x_full, jnp.float32), model.b.T)
        else:
            new_low = linalg.dot(old_low, transition.T)
        if pending is not None:
            new_low = jnp.where(pending[:, None], new_low, old_low)
        db = quant.quantize(new_low, valid=self.live)
        return self._replace(codes=db.codes, lo=db.lo, delta=db.delta,
                             a=model.a)

    def encode_centers(self, centers: jax.Array,
                       model=None) -> "QuantizedScorer":
        """Companion probe scorer consuming this scorer's folded-scale
        qstate. The C centers are stored as f32 PSEUDO-codes
        ``(Bc - lo) / delta`` (not rounded to u8), so
        ``q_scaled @ codes + q_lo == <Aq, Bc>`` exactly -- probe precision
        equals the linear scorer's at C rows of negligible HBM cost."""
        if model is None:
            raise ValueError("encode_centers on a QuantizedScorer needs "
                             "the DR model (its B matrix)")
        low = linalg.dot(jnp.asarray(centers, jnp.float32), model.b.T)
        return QuantizedScorer(codes=(low - self.lo[None, :])
                               / self.delta[None, :],
                               lo=self.lo, delta=self.delta)


class GleanVecQuantizedScorer(NamedTuple):
    """GleanVec ∘ int8: the per-cluster reduced vectors B_c x are scalar-
    quantized with per-cluster per-dimension scales; the affine terms fold
    into the eager query views, so scoring is tag-select + int8 dot."""

    codes: jax.Array                 # (n, d) uint8 codes of B_{tag_i} x_i
    tags: jax.Array                  # (n,) int32
    lo: jax.Array                    # (C, d) per-cluster lower bounds
    delta: jax.Array                 # (C, d) per-cluster steps
    a: jax.Array                     # (C, d, D) per-cluster query maps
    live: Optional[jax.Array] = None  # (n,) bool slot mask (None = all)

    @property
    def n_rows(self) -> int:
        return self.codes.shape[0]

    def prepare_queries(self, queries: jax.Array) -> QuantQueryState:
        qv = jnp.einsum("cdk,mk->mcd", self.a,
                        queries.astype(jnp.float32),
                        precision=linalg.F32)                 # (m, C, d)
        return QuantQueryState(q_scaled=qv * self.delta[None],
                               q_lo=jnp.einsum("mcd,cd->mc", qv, self.lo,
                                               precision=linalg.F32))

    def pad_rows(self, pad: int) -> "GleanVecQuantizedScorer":
        if not pad:
            return self
        return self._replace(codes=_pad0(self.codes, pad),
                             tags=_pad0(self.tags, pad),
                             live=None if self.live is None
                             else _pad0(self.live, pad))

    def score_block(self, qstate: QuantQueryState, start,
                    block: int) -> jax.Array:
        c = jax.lax.dynamic_slice_in_dim(self.codes, start, block, axis=0)
        tag = jax.lax.dynamic_slice_in_dim(self.tags, start, block, axis=0)
        q_sel = qstate.q_scaled[:, tag, :]              # (m, block, d)
        scores = jnp.einsum("mbd,bd->mb", q_sel, c.astype(jnp.float32),
                            precision=linalg.F32)
        return _mask_live_block(self.live, start, block,
                                scores + qstate.q_lo[:, tag])

    def score_ids(self, qstate: QuantQueryState, ids: jax.Array) -> jax.Array:
        c = self.codes[ids].astype(jnp.float32)         # (m, p, d)
        tag = self.tags[ids]                            # (m, p)
        m = tag.shape[0]
        q_sel = qstate.q_scaled[jnp.arange(m)[:, None], tag]
        lo_sel = jnp.take_along_axis(qstate.q_lo, tag, axis=1)
        return _mask_live_ids(self.live, ids,
                              jnp.sum(q_sel * c, axis=-1) + lo_sel)

    def shard_specs(self, axes) -> "GleanVecQuantizedScorer":
        from jax.sharding import PartitionSpec as P
        return GleanVecQuantizedScorer(codes=P(tuple(axes), None),
                                       tags=P(tuple(axes)),
                                       lo=P(), delta=P(), a=P(),
                                       live=None if self.live is None
                                       else P(tuple(axes)))

    def translate_ids(self, ids: jax.Array) -> jax.Array:
        return _translate_live(self.live, self.n_rows, ids)

    def globalize_ids(self, ids: jax.Array, shard_idx) -> jax.Array:
        return _globalize_row_aligned(ids, shard_idx, self.n_rows)

    # ---- streaming row-level ops (Section 3.2) ----------------------------

    def insert_rows(self, ids: jax.Array, rows: jax.Array,
                    model=None) -> "GleanVecQuantizedScorer":
        """Tag + project + code new rows under the EXISTING per-cluster
        scales (clipped); the next ``refresh`` refits them. 8-bit coding
        assumed, as everywhere on the streaming path."""
        tags_new, low = _encode_rows_gleanvec(model, rows)
        enc = jnp.clip(jnp.round((low - self.lo[tags_new])
                                 / self.delta[tags_new]), 0,
                       255).astype(self.codes.dtype)
        return self._replace(
            codes=self.codes.at[ids].set(enc),
            tags=self.tags.at[ids].set(tags_new.astype(self.tags.dtype)),
            live=_set_live(self.live, ids, True, self.n_rows))

    def remove_rows(self, ids: jax.Array) -> "GleanVecQuantizedScorer":
        return self._replace(live=_set_live(self.live, ids, False,
                                            self.n_rows))

    def refresh(self, model, transition=None, x_full=None,
                pending=None) -> "GleanVecQuantizedScorer":
        """Per-cluster dequantize -> T_{tag} reproject (or exact re-encode
        from ``x_full``) -> per-cluster requantize over live rows."""
        old_low = self.codes.astype(jnp.float32) * self.delta[self.tags] \
            + self.lo[self.tags]
        if x_full is not None:
            new_low = jnp.einsum("ndk,nk->nd", model.b[self.tags],
                                 jnp.asarray(x_full, jnp.float32),
                                 precision=linalg.F32)
        else:
            new_low = jnp.einsum("nij,nj->ni", transition[self.tags],
                                 old_low, precision=linalg.F32)
        if pending is not None:
            new_low = jnp.where(pending[:, None], new_low, old_low)
        db = quant.quantize_per_cluster(new_low, self.tags,
                                        self.lo.shape[0], valid=self.live)
        return self._replace(codes=db.codes, lo=db.lo, delta=db.delta,
                             a=model.a)

    def encode_centers(self, centers: jax.Array,
                       model=None) -> "GleanVecQuantizedScorer":
        """Companion probe scorer: per-cluster projected centers stored as
        f32 pseudo-codes under THIS scorer's per-cluster (lo, delta), so
        the probe is exact <A_t q, B_t c> from the folded qstate."""
        return _center_pseudo_scorer(centers, model, self.lo, self.delta,
                                     self.a)


class SortedGleanVecScorer(NamedTuple):
    """Eager GleanVec over a TAG-SORTED (cluster-contiguous) database.

    Rows are sorted by cluster tag and each cluster is padded to a
    ``layout_block`` multiple (``core.gleanvec.sort_by_tag``), so every
    block is single-tag and a blocked scan is one (m, d) x (d, block)
    matmul per block -- the FLOPs and bytes of the plain LeanVec scan plus
    one tag lookup per block. ``perm`` / ``inv_perm`` implement the
    id-translation contract; ``score_ids`` accepts ORIGINAL ids.
    """

    x_low: jax.Array                 # (ns, d) sorted, cluster-padded rows
    block_tags: jax.Array            # (ns // layout_block,) int32
    perm: jax.Array                  # (ns,) sorted row -> original id (-1)
    inv_perm: jax.Array              # (n,)  original id -> sorted row
    a: Optional[jax.Array] = None    # (C, d, D) per-cluster query maps
    # (C, max_blocks) layout-block indices per cluster, -1-padded (the
    # range-scan probe schedule source; None on hand-rolled layouts)
    list_block_ranges: Optional[jax.Array] = None

    @property
    def n_rows(self) -> int:
        return self.x_low.shape[0]

    @property
    def layout_block(self) -> int:
        """Rows per single-tag block (static: derived from leaf shapes)."""
        return self.x_low.shape[0] // self.block_tags.shape[0]

    def prepare_queries(self, queries: jax.Array) -> jax.Array:
        if self.a is None:
            raise ValueError("SortedGleanVecScorer without `a` cannot "
                             "prepare queries; pass precomputed (m, C, d) "
                             "views")
        return jnp.einsum("cdk,mk->mcd", self.a,
                          queries.astype(jnp.float32), precision=linalg.F32)

    def pad_rows(self, pad: int) -> "SortedGleanVecScorer":
        if pad:
            raise ValueError("sorted layout is pre-padded per cluster; "
                             "scan with block == layout_block")
        return self

    def _block_views(self, qstate, start, block):
        """(m, block, d) tag-selected views of a contiguous row range."""
        lb = self.layout_block
        if block == lb:     # single-tag fast path (static branch)
            tag = jax.lax.dynamic_index_in_dim(self.block_tags, start // lb,
                                               keepdims=False)
            return jnp.take(qstate, tag, axis=1), None
        tag = self.block_tags[(start + jnp.arange(block)) // lb]
        return None, qstate[:, tag, :]

    def score_block(self, qstate: jax.Array, start, block: int) -> jax.Array:
        blk = jax.lax.dynamic_slice_in_dim(self.x_low, start, block, axis=0)
        pm = jax.lax.dynamic_slice_in_dim(self.perm, start, block, axis=0)
        q_one, q_per_row = self._block_views(qstate, start, block)
        if q_one is not None:
            scores = linalg.dot(q_one, blk.T)              # (m, block)
        else:
            scores = jnp.einsum("mbd,bd->mb", q_per_row, blk,
                                precision=linalg.F32)
        return jnp.where(pm[None, :] >= 0, scores, NEG_INF)

    def score_ids(self, qstate: jax.Array, ids: jax.Array) -> jax.Array:
        rows = self.inv_perm[ids]                           # (m, p)
        ok = rows >= 0                # absent / removed ids score -inf
        rows = jnp.where(ok, rows, 0)
        vecs = self.x_low[rows]                             # (m, p, d)
        tag = self.block_tags[rows // self.layout_block]    # (m, p)
        m = qstate.shape[0]
        q_sel = qstate[jnp.arange(m)[:, None], tag]         # (m, p, d)
        return jnp.where(ok, jnp.sum(q_sel * vecs, axis=-1), NEG_INF)

    def scan_lists(self, qstate: jax.Array, probe: jax.Array, k: int):
        """Gather-free IVF fine step (``kernels/ivf_scan``): stream the
        probed clusters' single-tag slabs through the range-scan kernel --
        no posting-list gather, no (m, nprobe*L) candidate or score matrix.
        ``probe (m, nprobe)`` holds cluster ids that must equal this
        layout's tags (an ALIGNED coarse quantizer: ``ivf.build_aligned``).
        Returns (vals, ids) (m, k) with ORIGINAL ids; padding slots and
        removed rows (perm == -1) score -inf and strip to id -1."""
        from repro.kernels.ivf_scan import ivf_scan_topk
        if self.list_block_ranges is None:
            raise ValueError(
                "scan_lists needs list_block_ranges; build the scorer "
                "through its factory (sorted_gleanvec_scorer)")
        sched = self.list_block_ranges[probe].reshape(probe.shape[0], -1)
        q_lo = jnp.zeros(qstate.shape[:2], jnp.float32)   # no affine term
        return ivf_scan_topk(qstate, q_lo, self.block_tags, self.perm,
                             self.x_low, sched, k,
                             layout_block=self.layout_block)

    def scan_neighbors(self, qstate: jax.Array, nbr_rows: jax.Array,
                       beam_vals: jax.Array, beam_ids: jax.Array,
                       tn: int = 8):
        """Gather-free graph hop (``kernels/graph_scan``): fold one
        neighbor expansion -- given as SORTED-ROW indices ``nbr_rows
        (m, S)``, -1 padded -- into the beam, streaming the rows' ``tn``-
        slabs of this layout instead of gathering them. Returns the merged
        ``(vals, ids) (m, beam)`` with ORIGINAL ids (slot order)."""
        from repro.kernels.graph_scan import graph_scan_beam_step
        q_lo = jnp.zeros(qstate.shape[:2], jnp.float32)   # no affine term
        return graph_scan_beam_step(qstate, q_lo, self.block_tags,
                                    self.perm, self.x_low, nbr_rows,
                                    beam_vals, beam_ids,
                                    layout_block=self.layout_block, tn=tn)

    def shard_specs(self, axes) -> "SortedGleanVecScorer":
        # Row-shard the sorted layout: the shard count must divide the
        # BLOCK count so no single-tag block straddles shards, and ``perm``
        # must hold GLOBAL original ids (build the layout before sharding).
        # ``list_block_ranges`` indexes the GLOBAL block space, so it stays
        # replicated (the row-sharded flat scan never consumes it).
        from jax.sharding import PartitionSpec as P
        return SortedGleanVecScorer(x_low=P(tuple(axes), None),
                                    block_tags=P(tuple(axes)),
                                    perm=P(tuple(axes)), inv_perm=P(),
                                    a=None if self.a is None else P(),
                                    list_block_ranges=None
                                    if self.list_block_ranges is None
                                    else P())

    def translate_ids(self, ids: jax.Array) -> jax.Array:
        return _translate_sorted(self.perm, ids)

    def globalize_ids(self, ids: jax.Array, shard_idx) -> jax.Array:
        return ids          # perm already yields global original ids

    def encode_centers(self, centers: jax.Array,
                       model=None) -> "GleanVecScorer":
        """The sorted layout prepares the SAME (m, C, d) eager views as the
        row-aligned GleanVec scorer, so its probe companion is one too."""
        return _center_views_scorer(centers, model)

    # ---- streaming row-level ops (Section 3.2) ----------------------------

    def insert_rows(self, ids: jax.Array, rows: jax.Array,
                    model=None) -> "SortedGleanVecScorer":
        """Claim free padding slots inside each new row's cluster blocks
        (host-side allocation; the layout's shape never changes).
        Already-live ids release their old slot first (re-insert ==
        overwrite)."""
        tags_new, enc = _encode_rows_gleanvec(model, rows)
        slots, freed = _sorted_claim_slots(self.perm, self.inv_perm,
                                           self.block_tags,
                                           self.layout_block, ids,
                                           tags_new)
        perm = self.perm
        if freed.size:
            perm = perm.at[jnp.asarray(freed)].set(-1)
        slots = jnp.asarray(slots)
        ids = jnp.asarray(ids)
        return self._replace(
            x_low=self.x_low.at[slots].set(enc),
            perm=perm.at[slots].set(ids.astype(self.perm.dtype)),
            inv_perm=self.inv_perm.at[ids].set(
                slots.astype(self.inv_perm.dtype)))

    def remove_rows(self, ids: jax.Array) -> "SortedGleanVecScorer":
        import numpy as np
        slots = np.asarray(self.inv_perm)[np.asarray(ids)]
        slots = jnp.asarray(slots[slots >= 0])
        return self._replace(
            perm=self.perm.at[slots].set(-1),
            inv_perm=self.inv_perm.at[jnp.asarray(ids)].set(-1))

    def refresh(self, model, transition=None, x_full=None,
                pending=None) -> "SortedGleanVecScorer":
        """Per-cluster Eq. (12) over the SORTED rows (one T per single-tag
        block); padding rows stay masked by ``perm``."""
        row_tags = self.block_tags[jnp.arange(self.n_rows)
                                   // self.layout_block]
        valid = self.perm >= 0
        if x_full is not None:
            safe = jnp.where(valid, self.perm, 0)
            full_rows = jnp.asarray(x_full, jnp.float32)[safe]
            new_low = jnp.einsum("ndk,nk->nd", model.b[row_tags], full_rows,
                                 precision=linalg.F32)
            new_low = jnp.where(valid[:, None], new_low, 0.0)
        else:
            new_low = jnp.einsum("nij,nj->ni", transition[row_tags],
                                 self.x_low, precision=linalg.F32)
        if pending is not None:
            p_rows = valid & pending[jnp.where(valid, self.perm, 0)]
            new_low = jnp.where(p_rows[:, None], new_low, self.x_low)
        return self._replace(x_low=new_low, a=model.a)


class SortedGleanVecQuantizedScorer(NamedTuple):
    """GleanVec ∘ int8 over the TAG-SORTED layout: sorted per-cluster int8
    codes, per-block tags, and the same id-translation contract as
    :class:`SortedGleanVecScorer`. A blocked scan is one int8 matmul plus
    one broadcast offset add per block (d bytes of HBM per vector)."""

    codes: jax.Array                 # (ns, d) uint8, sorted/cluster-padded
    block_tags: jax.Array            # (ns // layout_block,) int32
    perm: jax.Array                  # (ns,) sorted row -> original id (-1)
    inv_perm: jax.Array              # (n,)  original id -> sorted row
    lo: jax.Array                    # (C, d) per-cluster lower bounds
    delta: jax.Array                 # (C, d) per-cluster steps
    a: jax.Array                     # (C, d, D) per-cluster query maps
    # (C, max_blocks) layout-block indices per cluster, -1-padded (the
    # range-scan probe schedule source; None on hand-rolled layouts)
    list_block_ranges: Optional[jax.Array] = None

    @property
    def n_rows(self) -> int:
        return self.codes.shape[0]

    @property
    def layout_block(self) -> int:
        """Rows per single-tag block (static: derived from leaf shapes)."""
        return self.codes.shape[0] // self.block_tags.shape[0]

    def prepare_queries(self, queries: jax.Array) -> QuantQueryState:
        qv = jnp.einsum("cdk,mk->mcd", self.a,
                        queries.astype(jnp.float32),
                        precision=linalg.F32)                 # (m, C, d)
        return QuantQueryState(q_scaled=qv * self.delta[None],
                               q_lo=jnp.einsum("mcd,cd->mc", qv, self.lo,
                                               precision=linalg.F32))

    def pad_rows(self, pad: int) -> "SortedGleanVecQuantizedScorer":
        if pad:
            raise ValueError("sorted layout is pre-padded per cluster; "
                             "scan with block == layout_block")
        return self

    def score_block(self, qstate: QuantQueryState, start,
                    block: int) -> jax.Array:
        c = jax.lax.dynamic_slice_in_dim(self.codes, start, block, axis=0)
        pm = jax.lax.dynamic_slice_in_dim(self.perm, start, block, axis=0)
        lb = self.layout_block
        if block == lb:     # single-tag fast path (static branch)
            tag = jax.lax.dynamic_index_in_dim(self.block_tags, start // lb,
                                               keepdims=False)
            q_sel = jnp.take(qstate.q_scaled, tag, axis=1)  # (m, d)
            scores = linalg.dot(q_sel, c.astype(jnp.float32).T) \
                + jnp.take(qstate.q_lo, tag, axis=1)[:, None]
        else:
            tag = self.block_tags[(start + jnp.arange(block)) // lb]
            q_sel = qstate.q_scaled[:, tag, :]              # (m, block, d)
            scores = jnp.einsum("mbd,bd->mb", q_sel,
                                c.astype(jnp.float32), precision=linalg.F32) \
                + qstate.q_lo[:, tag]
        return jnp.where(pm[None, :] >= 0, scores, NEG_INF)

    def score_ids(self, qstate: QuantQueryState, ids: jax.Array) -> jax.Array:
        rows = self.inv_perm[ids]                           # (m, p)
        ok = rows >= 0                # absent / removed ids score -inf
        rows = jnp.where(ok, rows, 0)
        c = self.codes[rows].astype(jnp.float32)            # (m, p, d)
        tag = self.block_tags[rows // self.layout_block]    # (m, p)
        m = tag.shape[0]
        q_sel = qstate.q_scaled[jnp.arange(m)[:, None], tag]
        lo_sel = jnp.take_along_axis(qstate.q_lo, tag, axis=1)
        return jnp.where(ok, jnp.sum(q_sel * c, axis=-1) + lo_sel, NEG_INF)

    def scan_lists(self, qstate: QuantQueryState, probe: jax.Array, k: int):
        """Gather-free IVF fine step over the sorted int8 codes: same
        contract as :meth:`SortedGleanVecScorer.scan_lists`, with the
        per-cluster affine terms riding the folded qstate."""
        from repro.kernels.ivf_scan import ivf_scan_topk
        if self.list_block_ranges is None:
            raise ValueError(
                "scan_lists needs list_block_ranges; build the scorer "
                "through its factory (sorted_gleanvec_quantized_scorer)")
        sched = self.list_block_ranges[probe].reshape(probe.shape[0], -1)
        return ivf_scan_topk(qstate.q_scaled, qstate.q_lo, self.block_tags,
                             self.perm, self.codes, sched, k,
                             layout_block=self.layout_block)

    def scan_neighbors(self, qstate: QuantQueryState, nbr_rows: jax.Array,
                       beam_vals: jax.Array, beam_ids: jax.Array,
                       tn: int = 8):
        """Gather-free graph hop over the sorted int8 codes: same contract
        as :meth:`SortedGleanVecScorer.scan_neighbors`, with the
        per-cluster affine terms riding the folded qstate."""
        from repro.kernels.graph_scan import graph_scan_beam_step
        return graph_scan_beam_step(qstate.q_scaled, qstate.q_lo,
                                    self.block_tags, self.perm, self.codes,
                                    nbr_rows, beam_vals, beam_ids,
                                    layout_block=self.layout_block, tn=tn)

    def shard_specs(self, axes) -> "SortedGleanVecQuantizedScorer":
        # Same sharding contract as SortedGleanVecScorer: shard count must
        # divide the block count, perm must hold global original ids.
        from jax.sharding import PartitionSpec as P
        return SortedGleanVecQuantizedScorer(
            codes=P(tuple(axes), None), block_tags=P(tuple(axes)),
            perm=P(tuple(axes)), inv_perm=P(), lo=P(), delta=P(), a=P(),
            list_block_ranges=None if self.list_block_ranges is None
            else P())

    def translate_ids(self, ids: jax.Array) -> jax.Array:
        return _translate_sorted(self.perm, ids)

    def globalize_ids(self, ids: jax.Array, shard_idx) -> jax.Array:
        return ids          # perm already yields global original ids

    def encode_centers(self, centers: jax.Array,
                       model=None) -> "GleanVecQuantizedScorer":
        """Sorted-int8 prepares the same folded qstate as the row-aligned
        int8 scorer; probe companion is the pseudo-code variant."""
        return _center_pseudo_scorer(centers, model, self.lo, self.delta,
                                     self.a)

    # ---- streaming row-level ops (Section 3.2) ----------------------------

    @property
    def _row_tags(self) -> jax.Array:
        return self.block_tags[jnp.arange(self.n_rows) // self.layout_block]

    def insert_rows(self, ids: jax.Array, rows: jax.Array,
                    model=None) -> "SortedGleanVecQuantizedScorer":
        """Claim free padding slots in the new rows' clusters; code under
        the EXISTING per-cluster scales (refit at the next refresh).
        Already-live ids release their old slot first (re-insert ==
        overwrite)."""
        tags_new, low = _encode_rows_gleanvec(model, rows)
        enc = jnp.clip(jnp.round((low - self.lo[tags_new])
                                 / self.delta[tags_new]), 0,
                       255).astype(self.codes.dtype)
        slots, freed = _sorted_claim_slots(self.perm, self.inv_perm,
                                           self.block_tags,
                                           self.layout_block, ids,
                                           tags_new)
        perm = self.perm
        if freed.size:
            perm = perm.at[jnp.asarray(freed)].set(-1)
        slots = jnp.asarray(slots)
        ids = jnp.asarray(ids)
        return self._replace(
            codes=self.codes.at[slots].set(enc),
            perm=perm.at[slots].set(ids.astype(self.perm.dtype)),
            inv_perm=self.inv_perm.at[ids].set(
                slots.astype(self.inv_perm.dtype)))

    def remove_rows(self, ids: jax.Array) -> "SortedGleanVecQuantizedScorer":
        import numpy as np
        slots = np.asarray(self.inv_perm)[np.asarray(ids)]
        slots = jnp.asarray(slots[slots >= 0])
        return self._replace(
            perm=self.perm.at[slots].set(-1),
            inv_perm=self.inv_perm.at[jnp.asarray(ids)].set(-1))

    def refresh(self, model, transition=None, x_full=None,
                pending=None) -> "SortedGleanVecQuantizedScorer":
        """Per-cluster dequantize -> T_{tag} (or exact re-encode from
        ``x_full``) -> per-cluster requantize; padding rows are excluded
        from the refitted scale ranges."""
        row_tags = self._row_tags
        valid = self.perm >= 0
        old_low = self.codes.astype(jnp.float32) * self.delta[row_tags] \
            + self.lo[row_tags]
        if x_full is not None:
            safe = jnp.where(valid, self.perm, 0)
            full_rows = jnp.asarray(x_full, jnp.float32)[safe]
            new_low = jnp.einsum("ndk,nk->nd", model.b[row_tags], full_rows,
                                 precision=linalg.F32)
        else:
            new_low = jnp.einsum("nij,nj->ni", transition[row_tags],
                                 old_low, precision=linalg.F32)
        if pending is not None:
            p_rows = valid & pending[jnp.where(valid, self.perm, 0)]
            new_low = jnp.where(p_rows[:, None], new_low, old_low)
        db = quant.quantize_per_cluster(new_low, row_tags,
                                        self.lo.shape[0], valid=valid)
        return self._replace(codes=db.codes, lo=db.lo, delta=db.delta,
                             a=model.a)


Scorer = Union[LinearScorer, GleanVecScorer, QuantizedScorer,
               GleanVecQuantizedScorer, SortedGleanVecScorer,
               SortedGleanVecQuantizedScorer]


# ---------------------------------------------------------------------------
# Factories: model + database -> scorer (the encode step of Alg. 1 line 0).
# ---------------------------------------------------------------------------


def exact_scorer(database: jax.Array) -> LinearScorer:
    """Full-precision exact MIPS (the 'full' serving mode / rerank oracle)."""
    return LinearScorer(x_low=jnp.asarray(database, jnp.float32))


def linear_scorer(model, database: jax.Array) -> LinearScorer:
    """LeanVec-Sphering: x_low = Bx, queries mapped by A."""
    x_low = linalg.dot(jnp.asarray(database, jnp.float32), model.b.T)
    return LinearScorer(x_low=x_low, a=model.a)


def gleanvec_scorer(model, database: jax.Array) -> GleanVecScorer:
    """GleanVec (Alg. 5 model): tags + per-cluster reduced vectors."""
    tags, x_low = gv.encode_database(model, database)
    return GleanVecScorer(x_low=x_low, tags=tags, a=model.a)


def quantized_scorer(model, database: jax.Array,
                     bits: int = 8) -> QuantizedScorer:
    """LeanVec-Sphering + int8 SQ of the reduced vectors (LeanVec paper's
    compounded compression: D*4 bytes -> d bytes per vector)."""
    x_low = linalg.dot(jnp.asarray(database, jnp.float32), model.b.T)
    db = quant.quantize(x_low, bits)
    return QuantizedScorer(codes=db.codes, lo=db.lo, delta=db.delta,
                           a=model.a)


def _encode_quantized(model, database: jax.Array, bits: int):
    """Tags + per-cluster SQ codes of ``database`` in two passes over row
    slices -- ranges first, then codes -- so the f32 reduced vectors never
    exist for all rows at once (at 10M rows they would take as much device
    memory as the full-precision store). Same codes and scales as
    ``quantize_per_cluster(encode_database(...))``."""
    database = jnp.asarray(database, jnp.float32)
    tags = gv.assign_tags(model, database)
    c, d, d_full = model.b.shape
    block = linalg.block_rows(d * d_full * 4)

    def ranges(acc, t, x, fresh):
        lo, hi = quant.cluster_ranges(gv.encode_rows(model, t, x), t, c)
        return jnp.minimum(acc[0], lo), jnp.maximum(acc[1], hi)

    init = (jnp.full((c, d), jnp.inf), jnp.full((c, d), -jnp.inf))
    lo, delta = quant.cluster_steps(
        *linalg.fold_row_blocks(ranges, init, [tags, database], block), bits)
    codes = linalg.map_row_blocks(
        lambda t, x: quant.quantize_rows(gv.encode_rows(model, t, x), t, lo,
                                         delta, bits),
        [tags, database], block)
    return tags, ClusteredSQDatabase(codes=codes, lo=lo, delta=delta)


def gleanvec_quantized_scorer(model, database: jax.Array,
                              bits: int = 8) -> GleanVecQuantizedScorer:
    """GleanVec + per-cluster int8 SQ of the reduced vectors."""
    tags, db = _encode_quantized(model, database, bits)
    return GleanVecQuantizedScorer(codes=db.codes, tags=tags, lo=db.lo,
                                   delta=db.delta, a=model.a)


def sorted_gleanvec_scorer(model, database: jax.Array, block: int = 4096,
                           slack_blocks: int = 0) -> SortedGleanVecScorer:
    """GleanVec in the tag-sorted (cluster-contiguous) layout: each cluster
    padded to a ``block`` multiple, one tag per block. ``slack_blocks``
    reserves extra free blocks per cluster for streaming inserts."""
    tags, x_low = gv.encode_database(model, database)
    xs, block_tags, perm, _ = gv.sort_by_tag(tags, x_low, block=block,
                                             slack_blocks=slack_blocks)
    inv = gv.inverse_permutation(perm, x_low.shape[0])
    return SortedGleanVecScorer(x_low=xs, block_tags=block_tags,
                                perm=perm.astype(jnp.int32), inv_perm=inv,
                                a=model.a,
                                list_block_ranges=_list_block_ranges(
                                    block_tags, model.n_clusters))


def sorted_gleanvec_quantized_scorer(
        model, database: jax.Array, block: int = 4096,
        bits: int = 8,
        slack_blocks: int = 0) -> SortedGleanVecQuantizedScorer:
    """GleanVec + per-cluster int8 SQ in the tag-sorted layout: the SAME
    codes/scales as :func:`gleanvec_quantized_scorer` (quantize first, then
    sort), so scores match the unsorted scorer exactly."""
    tags, db = _encode_quantized(model, database, bits)
    cs, block_tags, perm, _ = gv.sort_by_tag(tags, db.codes, block=block,
                                             slack_blocks=slack_blocks)
    inv = gv.inverse_permutation(perm, tags.shape[0])
    return SortedGleanVecQuantizedScorer(
        codes=cs, block_tags=block_tags, perm=perm.astype(jnp.int32),
        inv_perm=inv, lo=db.lo, delta=db.delta, a=model.a,
        list_block_ranges=_list_block_ranges(block_tags, model.n_clusters))


MODES = ("full", "sphering", "gleanvec", "sphering-int8", "gleanvec-int8",
         "gleanvec-sorted", "gleanvec-int8-sorted")


def build_scorer(mode: str, database: jax.Array, model=None,
                 block: int = 4096) -> Scorer:
    """Mode-string dispatch used by the serving layer (no isinstance).

    ``block`` is the sorted layouts' per-cluster padding multiple (small
    per-shard databases want a small one); other modes ignore it."""
    if mode == "full":
        return exact_scorer(database)
    if model is None:
        raise ValueError(f"mode {mode!r} needs a DR model")
    if mode == "sphering":
        return linear_scorer(model, database)
    if mode == "gleanvec":
        return gleanvec_scorer(model, database)
    if mode == "sphering-int8":
        return quantized_scorer(model, database)
    if mode == "gleanvec-int8":
        return gleanvec_quantized_scorer(model, database)
    if mode == "gleanvec-sorted":
        return sorted_gleanvec_scorer(model, database, block=block)
    if mode == "gleanvec-int8-sorted":
        return sorted_gleanvec_quantized_scorer(model, database,
                                                block=block)
    raise ValueError(f"unknown scorer mode {mode!r}; one of {MODES}")
