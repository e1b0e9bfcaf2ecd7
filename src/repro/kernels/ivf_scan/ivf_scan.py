"""Pallas TPU kernel: gather-free sorted-IVF range scan (fused fine step).

The sorted scorers (core/scorer.SortedGleanVec*Scorer) store every cluster
as a contiguous run of single-tag ``layout_block`` slabs. For an IVF whose
coarse quantizer IS that clustering, probing cluster ``c`` means scoring
the slabs tagged ``c``: the fine step never needs a posting-list gather.
The winning ORIGINAL ids come straight from the sort permutation
(``row_ids``), exactly like ``gleanvec_sq_topk``.

The scan is block-major. The wrapper takes the batch's ``probe (M, P)``
cluster ids and builds, in XLA, the union of the layout blocks whose tag
ANY query probes, in ascending block order, plus the ``(M, C)`` membership
mask of who probes what. The grid walks that union once: each grid step
DMAs one ``(TN, d)`` tile of codes, scores EVERY query of the batch
against it in one ``(M, d) x (d, TN)`` contraction plus the broadcast
affine term, masks to -inf the rows whose query does not probe the tile's
tag (and dead rows, ``row_ids < 0``), and folds the ``(M, TN)`` scores
into a running ``(M, k)`` top-k that lives in the output block across the
whole grid. A slab probed by many queries is therefore read from HBM once
per batch, not once per query, and each query still scores exactly the
rows of its own probed lists.

The union rides in as SCALAR-PREFETCH operands
(``pltpu.PrefetchScalarGridSpec``), at layout-block granularity: its
length, its block indices and their tags (``2 * NB + 1`` words of SMEM for
``NB`` layout blocks). The grid's length is dynamic, the union's length
times the tiles per block, so a batch that probes few clusters runs few
steps: a single query's grid is its own probed blocks. The query views
ride as a ``(C, M, d)`` transposed copy whose block is picked by the
tile's tag: they are DMA'd again only when the tag changes, i.e. once per
probed cluster, since the union is in block order and a cluster's blocks
are contiguous.

Ties go to the lower sorted row: tiles arrive in ascending row order and
the fold keeps the running entry, then the lower column, on equal scores.

HBM traffic per call (``ops.fine_step_bytes``): per union block its
``layout_block`` rows of codes (d bytes each, u8; 4d for the unquantized
sorted scorer's f32 rows) and ids (4 bytes each); per probed cluster the
``M x d`` f32 views, their offsets and the membership column; the
``(M, 128)`` top-k written once; the union into SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._mosaic import NEG_INF, fold_topk, lanes, to_f32


def _union_scan_kernel(cnt_ref, blk_ref, tag_ref, qs_ref, qlo_ref, qin_ref,
                       rid_ref, x_ref, vals_ref, ids_ref, *, k: int,
                       bpt: int):
    """One (TN, d) tile of the union, scored for every query and folded
    into the running (M, k) top-k. ``cnt_ref`` holds the union's length in
    layout blocks (an empty union still runs one block's steps, which do
    nothing); ``blk_ref`` / ``tag_ref`` (SMEM) its blocks and their tags,
    which only the index maps read."""
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        vals_ref[...] = jnp.full_like(vals_ref, NEG_INF)
        ids_ref[...] = jnp.full_like(ids_ref, -1)

    @pl.when(j // bpt < cnt_ref[0])
    def _scan():
        x = to_f32(x_ref[...])                             # (TN, d)
        scores = jax.lax.dot_general(
            qs_ref[0], x, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32) + qlo_ref[0]   # (M, TN)
        col_ids = jnp.broadcast_to(rid_ref[0], scores.shape)
        ok = (qin_ref[0] > 0) & (col_ids >= 0)
        scores = jnp.where(ok, scores, NEG_INF)
        vals, ids = fold_topk(vals_ref[...], ids_ref[...], scores, col_ids,
                              k)
        vals_ref[...] = vals
        ids_ref[...] = ids


def _union_blocks(block_tags: jax.Array, probe: jax.Array, c: int):
    """The batch's probed blocks: ``(count, blocks, tags, member)``. The
    first ``count`` entries of ``blocks (NB,)`` are the layout blocks whose
    tag some query of ``probe (M, P)`` holds, ascending, and ``tags`` are
    their tags (clamped to >= 0; entries past ``count`` are unspecified);
    ``member (M, C)`` says which query probes which cluster. Tag -1 blocks
    (stacked-shard padding) and probe -1 pads never match. Compares,
    reductions and one stable sort: no gather."""
    member = jnp.any(probe[:, :, None] == jnp.arange(c), axis=1)   # (M, C)
    hit = jnp.any(member, axis=0)                                   # (C,)
    inb = jnp.any((block_tags[:, None] == jnp.arange(c)) & hit, axis=1)
    nb = block_tags.shape[0]
    _, blocks, tags = jax.lax.sort(
        (jnp.where(inb, 0, 1), jnp.arange(nb, dtype=jnp.int32),
         block_tags.astype(jnp.int32)), num_keys=1, is_stable=True)
    count = jnp.sum(inb, dtype=jnp.int32)
    return count, blocks, jnp.maximum(tags, 0), member


@functools.partial(jax.jit, static_argnames=("k", "layout_block", "tn",
                                             "interpret"))
def ivf_scan_topk(q_scaled: jax.Array, q_lo: jax.Array, block_tags: jax.Array,
                  row_ids: jax.Array, codes: jax.Array, probe: jax.Array,
                  k: int, layout_block: int, tn: int = 512,
                  interpret: bool = False):
    """Fused sorted-IVF range scan + blocked top-k, block-major.

    ``q_scaled (M, C, d)`` / ``q_lo (M, C)``: prepared per-cluster query
    views (``q_lo`` zeros for the unquantized sorted scorer);
    ``block_tags (N // layout_block,)``: one tag per layout block (-1 =
    a padding block no query can probe);
    ``row_ids (N,)``: external id per sorted row (-1 = padding, never wins);
    ``codes (N, d)``: u8 codes or f32 rows of the tag-sorted layout;
    ``probe (M, P)``: each query's cluster ids (-1 = pad).

    Returns (vals (M, k) f32, ids (M, k) i32): each query's top-k over
    the rows of its probed clusters, ties to the lower sorted row, -inf
    winners' ids stripped to -1. ``tn`` must divide ``layout_block`` (the
    dispatcher in ops.py guarantees it). Operands whose block is a slice
    carry a unit axis -- ``(C, M, 1)`` offsets and membership, ``(N // TN,
    1, TN)`` ids -- so every block's last two dims equal the array's
    (Mosaic's tiling rule).
    """
    m, c, d = q_scaled.shape
    n = codes.shape[0]
    nb = block_tags.shape[0]
    assert n == nb * layout_block and layout_block % tn == 0, \
        (n, nb, layout_block, tn)
    bpt = layout_block // tn                  # tiles per layout block
    kp = lanes(k)                  # running top-k block, lane-aligned
    count, blocks, tags, member = _union_blocks(block_tags, probe, c)

    def tile(j, cnt, blk, tag):
        return blk[j // bpt] * bpt + j % bpt

    def view(j, cnt, blk, tag):
        return tag[j // bpt]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        # a dynamic bound: the union's tiles, and one block's worth of
        # skipped steps when nothing is probed (the outputs still init)
        grid=(jnp.maximum(count, 1) * bpt,),
        in_specs=[
            pl.BlockSpec((1, m, d), lambda j, *s: (view(j, *s), 0, 0)),
            pl.BlockSpec((1, m, 1), lambda j, *s: (view(j, *s), 0, 0)),
            pl.BlockSpec((1, m, 1), lambda j, *s: (view(j, *s), 0, 0)),
            pl.BlockSpec((1, 1, tn), lambda j, *s: (tile(j, *s), 0, 0)),
            pl.BlockSpec((tn, d), lambda j, *s: (tile(j, *s), 0)),
        ],
        out_specs=[
            pl.BlockSpec((m, kp), lambda j, *s: (0, 0)),
            pl.BlockSpec((m, kp), lambda j, *s: (0, 0)),
        ],
    )
    vals, ids = pl.pallas_call(
        functools.partial(_union_scan_kernel, k=k, bpt=bpt),
        name="ivf_scan_topk",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((m, kp), jnp.float32),
                   jax.ShapeDtypeStruct((m, kp), jnp.int32)],
        interpret=interpret,
    )(count[None], blocks, tags,
      jnp.swapaxes(q_scaled.astype(jnp.float32), 0, 1),
      q_lo.astype(jnp.float32).T[..., None],
      member.T[..., None].astype(jnp.int32),
      row_ids.astype(jnp.int32).reshape(n // tn, 1, tn), codes)
    vals, ids = vals[:, :k], ids[:, :k]
    # the top-k fold can recycle an already-taken slot's id once everything
    # left is -inf; strip those ids like the gathered IVF path does.
    return vals, jnp.where(vals > NEG_INF, ids, -1)
