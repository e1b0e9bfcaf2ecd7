"""Pallas TPU kernel: gather-free sorted-IVF range scan (fused fine step).

The sorted scorers (core/scorer.SortedGleanVec*Scorer) store every cluster
as a contiguous run of single-tag ``layout_block`` slabs. For an IVF whose
coarse quantizer IS that clustering, the fine step therefore never needs a
posting-list gather: probing cluster ``c`` means streaming ``c``'s slabs
through the single-tag scoring path (one (1, d) x (d, TN) contraction plus
a broadcast affine per tile) while a running (1, k) top-k lives in the
revisited output block. The winning ORIGINAL ids come straight from the
sort permutation (``row_ids``), exactly like ``gleanvec_sq_topk``.

The per-query probe schedule rides in as a SCALAR-PREFETCH operand
(``pltpu.PrefetchScalarGridSpec``): ``sched (M, S)`` holds the layout-block
indices each query must visit (-1 = padding). The BlockSpec index maps read
``sched`` to pick which codes/ids/tag slab the next grid step DMAs, so the
kernel never touches an unprobed block and nothing shaped
``(M, nprobe * L)`` -- neither a candidate-id matrix nor a dense score
matrix -- ever exists in HBM. The grid is ``(M, S * tiles_per_block)``;
queries are processed one per grid row because each query owns a private
schedule (the per-query views (1, C, d) stay resident across the whole
inner dimension -- their block index does not change with ``j``).

HBM traffic per grid step: TN * d bytes of codes (u8, or f32 for the
unquantized sorted scorer) + TN * 4 bytes of ids; per query: C * d * 4 +
C * 4 bytes of prepared views; per call: the block tags, 4 bytes per
layout block, into SMEM with the schedules. Nothing else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._mosaic import (NEG_INF, fold_topk, lanes,
                                   query_chunks, to_f32)


def _range_scan_kernel(sched_ref, fill_ref, tags_ref, qs_ref, qlo_ref,
                       rid_ref, x_ref, vals_ref, ids_ref, *, k: int,
                       bpt: int):
    """One (1, TN) tile of one query's schedule, folded into its running
    (1, k) top-k. ``sched_ref`` is the scalar-prefetched tile schedule (a
    negative entry marks a padding slot that must not score); ``fill_ref``
    is its forward-filled twin the BlockSpec index maps read, so a padding
    slot revisits the PREVIOUS slab (no fresh DMA) instead of fetching
    slab 0. ``tags_ref`` (SMEM) holds one tag per layout block."""
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        vals_ref[...] = jnp.full_like(vals_ref, NEG_INF)
        ids_ref[...] = jnp.full_like(ids_ref, -1)

    tag = tags_ref[fill_ref[i, j] // bpt]
    q = qs_ref[0, pl.ds(tag, 1), :]                        # (1, d)
    lo = qlo_ref[0, pl.ds(tag, 1), :]                      # (1, 1)
    x = to_f32(x_ref[...])                                 # (TN, d)
    scores = jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                                 precision=jax.lax.Precision.HIGHEST,
                                 preferred_element_type=jnp.float32) \
        + lo                                               # (1, TN)
    col_ids = rid_ref[0]                                   # (1, TN)
    ok = (col_ids >= 0) & (sched_ref[i, j] >= 0)
    scores = jnp.where(ok, scores, NEG_INF)
    # fold the tile into the running top-k: k rounds of max/mask over the
    # running (1, k) and the tile's (1, TN) candidates (as ip_topk).
    vals, ids = fold_topk(vals_ref[0], ids_ref[0], scores, col_ids, k)
    vals_ref[0] = vals
    ids_ref[0] = ids


@functools.partial(jax.jit, static_argnames=("k", "layout_block", "tn",
                                             "interpret"))
def ivf_scan_topk(q_scaled: jax.Array, q_lo: jax.Array, block_tags: jax.Array,
                  row_ids: jax.Array, codes: jax.Array, sched: jax.Array,
                  k: int, layout_block: int, tn: int = 512,
                  interpret: bool = False):
    """Fused sorted-IVF range scan + blocked top-k.

    ``q_scaled (M, C, d)`` / ``q_lo (M, C)``: prepared per-cluster query
    views (``q_lo`` zeros for the unquantized sorted scorer);
    ``block_tags (N // layout_block,)``: one tag per layout block;
    ``row_ids (N,)``: external id per sorted row (-1 = padding, never wins);
    ``codes (N, d)``: u8 codes or f32 rows of the tag-sorted layout;
    ``sched (M, S)``: per-query layout-block indices to visit (-1 = pad).

    Returns (vals (M, k) f32, ids (M, k) i32) with -inf winners' ids
    stripped to -1. ``tn`` must divide ``layout_block`` (the dispatcher in
    ops.py guarantees it). Per-query operands carry a unit axis --
    ``(M, 1, k)`` outputs, ``(M, C, 1)`` offsets, ``(N // TN, 1, TN)`` ids
    -- so every block's last two dims equal the array's (Mosaic's tiling
    rule); the block tags and schedules ride in SMEM.
    """
    m, c, d = q_scaled.shape
    n = codes.shape[0]
    assert n % layout_block == 0 and layout_block % tn == 0, \
        (n, layout_block, tn)
    s = sched.shape[1]
    bpt = layout_block // tn                  # tiles per layout block
    kp = lanes(k)                  # running top-k block, lane-aligned
    # expand the block schedule to tile indices (still -1-padded)
    sched_t = jnp.where(
        sched[:, :, None] >= 0,
        sched[:, :, None] * bpt + jnp.arange(bpt, dtype=sched.dtype),
        -1).reshape(m, s * bpt).astype(jnp.int32)
    # forward-filled twin for the index maps: a padding slot keeps the
    # last valid tile index, so its grid step revisits the already-resident
    # slab (the pipeline skips the DMA) instead of re-fetching tile 0 --
    # padding costs ~zero HBM traffic, matching ops.fine_step_bytes.
    sched_f = jnp.maximum(jax.lax.associative_scan(
        lambda a, b: jnp.where(b >= 0, b, a), sched_t, axis=1), 0)

    def run(sched_t, sched_f, q_scaled, q_lo):
        mc = q_scaled.shape[0]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(mc, s * bpt),
            in_specs=[
                pl.BlockSpec((1, c, d), lambda i, j, sr, fr, tg: (i, 0, 0)),
                pl.BlockSpec((1, c, 1), lambda i, j, sr, fr, tg: (i, 0, 0)),
                pl.BlockSpec((1, 1, tn),
                             lambda i, j, sr, fr, tg: (fr[i, j], 0, 0)),
                pl.BlockSpec((tn, d), lambda i, j, sr, fr, tg: (fr[i, j], 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, kp), lambda i, j, sr, fr, tg: (i, 0, 0)),
                pl.BlockSpec((1, 1, kp), lambda i, j, sr, fr, tg: (i, 0, 0)),
            ],
        )
        return pl.pallas_call(
            functools.partial(_range_scan_kernel, k=k, bpt=bpt),
            name="ivf_scan_topk",
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((mc, 1, kp), jnp.float32),
                jax.ShapeDtypeStruct((mc, 1, kp), jnp.int32),
            ],
            interpret=interpret,
        )(sched_t, sched_f, block_tags.astype(jnp.int32),
          q_scaled.astype(jnp.float32),
          q_lo.astype(jnp.float32)[..., None],
          row_ids.astype(jnp.int32).reshape(n // tn, 1, tn), codes)

    vals, ids = query_chunks(run, m, s * bpt, block_tags.shape[0],
                             sched_t, sched_f, q_scaled, q_lo)
    vals, ids = vals[:, 0, :k], ids[:, 0, :k]
    # the top-k fold can recycle an already-taken slot's id once everything
    # left is -inf; strip those ids like the gathered IVF path does.
    return vals, jnp.where(vals > NEG_INF, ids, -1)
