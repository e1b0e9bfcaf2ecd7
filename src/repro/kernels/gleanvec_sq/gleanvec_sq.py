"""Pallas TPU kernel: fused GleanVec ∘ int8 scoring (LeanVec composition).

One pass over the codes does all three steps of the per-cluster scalar-
quantized scoring (core/scorer.GleanVecQuantizedScorer):

    tag-select   q_sel  = q_scaled[m, tags[n]]      (one-hot MXU matmul)
    int8 dot     s      = <q_sel, codes_n>          (u8 -> f32 on load)
    affine       score  = s + q_lo[m, tags[n]]      (per-cluster offset)

The per-cluster scales/offsets are folded into the prepared queries OUTSIDE
the N loop (<q_c, u*delta_c + lo_c> = <q_c*delta_c, u> + <q_c, lo_c>), so
HBM traffic per database vector is d bytes of codes + 4 bytes of tag --
versus d*4 + 4 for the float GleanVec kernel and 9*d + 8 for
dequantize-then-``gleanvec_ip`` (codes read + f32 round-trip + second read).

Two layouts share the kernel body:

  * gathered (``sorted_layout=False``): per-row ``tags (N,)``; the
    tag-selected views are materialized with a (TN, C) x (C, d) one-hot
    matmul per query row, exactly like ``gleanvec_ip`` (TPU has no efficient
    in-VMEM row gather; the one-hot FLOPs ride on idle MXU cycles in this
    bandwidth-bound regime).
  * sorted (``sorted_layout=True``): the database is tag-sorted and
    cluster-padded so every (TN, d) tile carries ONE tag -- scoring
    degenerates to a single (TM, d) x (d, TN) matmul plus a broadcast add,
    the same FLOPs and bytes as the plain int8 scan. ``tags`` shrinks to one
    entry per layout block.

The fused top-k variants fold each score tile into a running (TM, k) top-k
held in the revisited output block across the sequential N grid dimension
(same scheme as ``ip_topk``) -- the dense (M, N) score matrix never exists.
Candidate ids come from an explicit ``row_ids (N,)`` input (-1 = masked), so
sorted layouts emit ORIGINAL database ids straight from the kernel and
padding rows can never win.

VMEM per step (TM=8, TN=512, C=48, d=160): q views 240 KiB + offsets 1.5 KiB
+ codes 80 KiB (u8) + scores 16 KiB << 16 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._mosaic import NEG_INF, fold_topk, lanes, to_f32


def _sorted_scores(qs_ref, qlo_ref, tag, x):
    """(TM, TN) single-tag tile: ``qs_ref (C, TM, d)`` / ``qlo_ref
    (C, TM, 1)`` are tag-major, so the tile's tag picks a leading-axis
    slice (no in-register gather)."""
    s = jax.lax.dot_general(qs_ref[tag], x, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    return s + qlo_ref[tag]


def _row_scores(qs_ref, qlo_ref, tags, x, *, c: int):
    """(TM, TN) tile with per-row ``tags (1, TN)``: per query, the (C, TN)
    product of every cluster view with the tile is masked by the one-hot
    ``(C, TN)`` tag matrix and summed over C -- the tag select rides the
    MXU, and every selected score is an exact copy of its product entry.
    ``qs_ref (TM, C, d)``, ``qlo_ref (TM, C, 1)``."""
    tm = qs_ref.shape[0]
    onehot = tags == jax.lax.broadcasted_iota(jnp.int32, (c, x.shape[0]), 0)
    rows = jax.lax.broadcasted_iota(jnp.int32, (tm, x.shape[0]), 0)

    def per_query(mi, acc):
        g = jax.lax.dot_general(qs_ref[mi], x, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
        s = jnp.sum(jnp.where(onehot, g + qlo_ref[mi], 0.0), axis=0,
                    keepdims=True)                             # (1, TN)
        return jnp.where(rows == mi, s, acc)

    init = jnp.zeros((tm, x.shape[0]), jnp.float32)
    return jax.lax.fori_loop(0, tm, per_query, init)


def _tile_scores(tags_ref, qs_ref, qlo_ref, tagrow_ref, x_ref, *, c: int,
                 bpt: int):
    """Score tile of grid step (i, j). Sorted layout (``tags_ref`` is the
    scalar-prefetched per-block tag array): one tag per tile, ``bpt``
    tiles per layout block. Gathered layout: per-row tags in
    ``tagrow_ref (1, 1, TN)``."""
    x = to_f32(x_ref[...])                                     # (TN, d)
    if tags_ref is not None:
        tag = tags_ref[pl.program_id(1) // bpt]
        return _sorted_scores(qs_ref, qlo_ref, tag, x)
    return _row_scores(qs_ref, qlo_ref, tagrow_ref[0], x, c=c)


def _dense_kernel(*refs, c: int, bpt: int, sorted_layout: bool):
    if sorted_layout:
        tags_ref, qs_ref, qlo_ref, x_ref, out_ref = refs
        tagrow_ref = None
    else:
        qs_ref, qlo_ref, tagrow_ref, x_ref, out_ref = refs
        tags_ref = None
    out_ref[...] = _tile_scores(tags_ref, qs_ref, qlo_ref, tagrow_ref, x_ref,
                                c=c, bpt=bpt)


def _topk_kernel(*refs, c: int, k: int, bpt: int, sorted_layout: bool):
    if sorted_layout:
        tags_ref, qs_ref, qlo_ref, rid_ref, x_ref, vals_ref, ids_ref = refs
        tagrow_ref = None
    else:
        (qs_ref, qlo_ref, tagrow_ref, rid_ref, x_ref, vals_ref,
         ids_ref) = refs
        tags_ref = None
    nj = pl.program_id(1)

    @pl.when(nj == 0)
    def _init():
        vals_ref[...] = jnp.full_like(vals_ref, NEG_INF)
        ids_ref[...] = jnp.full_like(ids_ref, -1)

    scores = _tile_scores(tags_ref, qs_ref, qlo_ref, tagrow_ref, x_ref,
                          c=c, bpt=bpt)
    col_ids = jnp.broadcast_to(rid_ref[0], scores.shape)
    scores = jnp.where(col_ids >= 0, scores, NEG_INF)
    # fold the tile into the running top-k: k rounds of max/mask over the
    # running (TM, k) and the tile's (TM, TN) candidates (as ip_topk).
    vals_ref[...], ids_ref[...] = fold_topk(vals_ref[...], ids_ref[...],
                                            scores, col_ids, k)


def _pad0(x, pad, fill=0):
    if not pad:
        return x
    widths = ((0, pad),) + ((0, 0),) * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=fill)


def _call(kernel, q_scaled, q_lo, tags, codes, row_ids, *, name: str,
          layout_block: int, tm: int, tn: int, out_specs, out_shape,
          interpret: bool):
    """Shared pallas_call plumbing of the dense and top-k variants: pads M
    (and N on the gathered layout), lays the operands out for Mosaic and
    picks the grid spec of the layout."""
    m, c, d = q_scaled.shape
    n = codes.shape[0]
    srt = layout_block > 0
    if srt:
        assert n % layout_block == 0 and layout_block % tn == 0, \
            (n, layout_block, tn)
    tm = min(tm, max(1, m))
    m_pad = (-m) % tm
    n_pad = 0 if srt else (-n) % tn
    q_scaled = _pad0(q_scaled.astype(jnp.float32), m_pad)
    q_lo = _pad0(q_lo.astype(jnp.float32), m_pad)[..., None]  # (M, C, 1)
    codes = _pad0(codes, n_pad)
    rows = (m + m_pad, n + n_pad)
    grid = (rows[0] // tm, rows[1] // tn)
    # per-row operands as (N // TN, 1, TN): the block's last two dims then
    # equal the array's, whatever TN is
    row_ops = [] if row_ids is None else [
        _pad0(row_ids.astype(jnp.int32), n_pad, fill=-1)
        .reshape(grid[1], 1, tn)]
    row_spec = pl.BlockSpec((1, 1, tn), lambda i, j, *_: (j, 0, 0))
    x_spec = pl.BlockSpec((tn, d), lambda i, j, *_: (j, 0))
    if srt:
        # tag-major query views; the per-block tags ride in SMEM
        bpt = layout_block // tn
        in_specs = [pl.BlockSpec((c, tm, d), lambda i, j, t: (0, i, 0)),
                    pl.BlockSpec((c, tm, 1), lambda i, j, t: (0, i, 0))]
        in_specs += [row_spec] * len(row_ops) + [x_spec]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs)
        args = (tags.astype(jnp.int32), q_scaled.transpose(1, 0, 2),
                q_lo.transpose(1, 0, 2), *row_ops, codes)
    else:
        bpt = 1
        in_specs = [pl.BlockSpec((tm, c, d), lambda i, j: (i, 0, 0)),
                    pl.BlockSpec((tm, c, 1), lambda i, j: (i, 0, 0)),
                    row_spec]
        in_specs += [row_spec] * len(row_ops) + [x_spec]
        grid_spec = pl.GridSpec(grid=grid, in_specs=in_specs,
                                out_specs=out_specs)
        tag_rows = _pad0(tags.astype(jnp.int32), n_pad).reshape(
            grid[1], 1, tn)
        args = (q_scaled, q_lo, tag_rows, *row_ops, codes)
    return pl.pallas_call(
        functools.partial(kernel, c=c, bpt=bpt, sorted_layout=srt),
        name=name, grid_spec=grid_spec, out_shape=out_shape(rows),
        interpret=interpret)(*args)


@functools.partial(jax.jit, static_argnames=("layout_block", "tm", "tn",
                                             "interpret"))
def gleanvec_sq(q_scaled: jax.Array, q_lo: jax.Array, tags: jax.Array,
                codes: jax.Array, layout_block: int = 0, tm: int = 8,
                tn: int = 512, interpret: bool = False):
    """Dense fused scores. ``q_scaled (M, C, d)``, ``q_lo (M, C)``,
    ``codes (N, d)`` u8 (or f32 for the unquantized sorted scorer) ->
    ``(M, N) f32``.

    ``layout_block == 0``: gathered layout, ``tags (N,)`` per-row.
    ``layout_block > 0``: tag-sorted layout, ``tags (N // layout_block,)``
    per-block; requires ``layout_block % tn == 0``.
    """
    m, n = q_scaled.shape[0], codes.shape[0]
    tm_ = min(tm, max(1, m))
    out = _call(_dense_kernel, q_scaled, q_lo, tags, codes, None,
                name="gleanvec_sq", layout_block=layout_block, tm=tm, tn=tn,
                out_specs=pl.BlockSpec((tm_, tn), lambda i, j, *_: (i, j)),
                out_shape=lambda rows: jax.ShapeDtypeStruct(rows,
                                                            jnp.float32),
                interpret=interpret)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("k", "layout_block", "tm", "tn",
                                             "interpret"))
def gleanvec_sq_topk(q_scaled: jax.Array, q_lo: jax.Array, tags: jax.Array,
                     codes: jax.Array, k: int, row_ids=None,
                     layout_block: int = 0, tm: int = 8, tn: int = 512,
                     interpret: bool = False):
    """Fused scoring + blocked top-k: the (M, N) score matrix never
    materializes. Returns (vals (M, k) f32, ids (M, k) i32).

    ``row_ids (N,)`` optional external id of each row (-1 = padding, can
    never win); defaults to ``arange(N)``. Sorted layouts pass their sort
    permutation here so the kernel emits ORIGINAL database ids.
    """
    m, n = q_scaled.shape[0], codes.shape[0]
    if row_ids is None:
        row_ids = jnp.arange(n, dtype=jnp.int32)
    tm_ = min(tm, max(1, m))
    kp = lanes(k)                  # running top-k block, lane-aligned
    spec = pl.BlockSpec((tm_, kp), lambda i, j, *_: (i, 0))
    vals, ids = _call(
        functools.partial(_topk_kernel, k=k), q_scaled, q_lo, tags, codes,
        row_ids, name="gleanvec_sq_topk", layout_block=layout_block, tm=tm,
        tn=tn, out_specs=[spec, spec],
        out_shape=lambda rows: [
            jax.ShapeDtypeStruct((rows[0], kp), jnp.float32),
            jax.ShapeDtypeStruct((rows[0], kp), jnp.int32)],
        interpret=interpret)
    return vals[:m, :k], ids[:m, :k]
