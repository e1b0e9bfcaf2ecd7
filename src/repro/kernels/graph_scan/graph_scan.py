"""Pallas TPU kernel: gather-free graph beam step (fused hop fine step).

Graph beam search scores a ``(batch, expand * R)`` neighbor expansion every
hop. The gathered path materializes that candidate set three times over in
HBM -- a neighbor-id matrix, the gathered ``d``-dim rows and an f32 score
matrix -- before a ``top_k`` over ``(batch, beam + expand*R)`` merges it
into the beam. This kernel gives the hop the ``ivf_scan`` treatment
instead: the popped frontier vertices' neighbor lists arrive as SORTED-
LAYOUT row indices (ascending per query, -1 padded), are grouped into
``tn``-row slabs of the tag-sorted layout, and the slab indices ride in as
a scalar-prefetch schedule (``pltpu.PrefetchScalarGridSpec``). Each fresh
slab is DMAed ONCE; inside VMEM the kernel fuses

  * the single-tag dot (int8 codes or f32 rows) + per-cluster affine,
  * the neighbor-membership mask (slab rows that are not in this hop's
    neighbor set never score -- exact gathered-path candidate semantics,
    each distinct neighbor scored exactly once),
  * the beam dedupe (candidates whose ORIGINAL id -- read from the sort
    permutation ``row_ids`` -- is already in the incoming beam are
    dropped, mirroring ``graph._beam_member_mask``),
  * and the running top-``beam`` update: the output block holds the beam
    itself, initialized from the incoming (vals, ids) at ``j == 0`` and
    folded in place (strict-improvement replacement of the current min,
    the online equivalent of the gathered ``top_k`` merge).

Nothing shaped ``(batch, expand*R)`` in f32 -- neither gathered rows nor a
score matrix -- ever exists in HBM; only the int32 schedule / neighbor-row
arrays (4 bytes per candidate) ride along as scalar prefetch. HBM traffic
per fresh slab: TN * d bytes of codes + TN * 4 of ids; per query: C * d *
4 + C * 4 of prepared views plus the (beam) state in/out; per call: the
block tags (4 bytes per layout block) into SMEM with the schedules.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels._mosaic import (_INT_MIN, NEG_INF, pick, query_chunks,
                                   to_f32)


def _beam_step_kernel(sched_ref, fill_ref, tags_ref, qs_ref, qlo_ref,
                      nbr_ref, rid_ref, x_ref, bvals_ref, bids_ref,
                      vals_ref, ids_ref, *, tn: int, bpt: int):
    """One ``tn``-row slab of one query's hop schedule, folded into its
    running (1, beam) top-k. ``sched_ref`` holds the slab schedule (a
    negative entry marks a padding / repeated-slab slot that must not
    fold); ``fill_ref`` is its forward-filled twin the BlockSpec index
    maps read, so a padding slot revisits the PREVIOUS slab (no fresh
    DMA) instead of fetching slab 0. ``tags_ref`` (SMEM) holds one tag per
    layout block."""
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        vals_ref[...] = bvals_ref[...]
        ids_ref[...] = bids_ref[...]

    @pl.when(sched_ref[i, j] >= 0)
    def _fold_slab():
        slab = fill_ref[i, j]
        tag = tags_ref[slab // bpt]
        q = qs_ref[0, pl.ds(tag, 1), :]                        # (1, d)
        lo = qlo_ref[0, pl.ds(tag, 1), :]                      # (1, 1)
        x = to_f32(x_ref[...])                                 # (TN, d)
        scores = jax.lax.dot_general(q, x, (((1,), (1,)), ((), ())),
                                     precision=jax.lax.Precision.HIGHEST,
                                     preferred_element_type=jnp.float32) \
            + lo                                               # (1, TN)
        cand_ids = rid_ref[0]                                  # (1, TN)
        nbrs = nbr_ref[0]                                      # (1, S)
        beam_in = bids_ref[0]                                  # (1, B)
        iota_t = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)

        # fold: TN rounds of strict-improvement replacement of the running
        # beam's minimum -- the online form of top_k(concat([beam, cand])).
        # Slab row t folds only if it is in this hop's (scalar-prefetched)
        # neighbor set, is live, and its ORIGINAL id (from the sort
        # permutation) is not in the incoming beam: the gathered path's
        # _beam_member_mask dedupe.
        def fold(t, carry):
            vals, ids = carry                                  # (1, beam)
            v = pick(scores, iota_t, t, -jnp.inf)              # (1, 1)
            ci = pick(cand_ids, iota_t, t, _INT_MIN)           # (1, 1)
            member = jnp.any(nbrs == slab * tn + t, axis=1, keepdims=True)
            in_beam = jnp.any(beam_in == ci, axis=1, keepdims=True)
            v = jnp.where(member & (ci >= 0) & ~in_beam, v, NEG_INF)
            vmin = jnp.min(vals, axis=1, keepdims=True)        # (1, 1)
            iota_b = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
            amin = jnp.min(jnp.where(vals == vmin, iota_b, tn + vals.size),
                           axis=1, keepdims=True)              # first argmin
            hit = (iota_b == amin) & (v > vmin)
            return jnp.where(hit, v, vals), jnp.where(hit, ci, ids)

        vals, ids = jax.lax.fori_loop(0, tn, fold, (vals_ref[0], ids_ref[0]))
        vals_ref[0] = vals
        ids_ref[0] = ids


@functools.partial(jax.jit, static_argnames=("layout_block", "tn",
                                             "interpret"))
def graph_scan_beam_step(q_scaled: jax.Array, q_lo: jax.Array,
                         block_tags: jax.Array, row_ids: jax.Array,
                         codes: jax.Array, nbr_rows: jax.Array,
                         beam_vals: jax.Array, beam_ids: jax.Array,
                         layout_block: int, tn: int = 8,
                         interpret: bool = False):
    """Fused graph hop: merge one neighbor expansion into the beam.

    ``q_scaled (M, C, d)`` / ``q_lo (M, C)``: prepared per-cluster query
    views (``q_lo`` zeros for the unquantized sorted scorer);
    ``block_tags (N // layout_block,)``: one tag per layout block;
    ``row_ids (N,)``: external id per sorted row (-1 = padding/dead);
    ``codes (N, d)``: u8 codes or f32 rows of the tag-sorted layout;
    ``nbr_rows (M, S)``: this hop's neighbor SORTED-ROW indices per query
    (-1 = pad; need not be pre-sorted -- sorted/grouped here);
    ``beam_vals/beam_ids (M, B)``: incoming beam (ids ORIGINAL, -1 empty).

    Returns the merged ``(vals (M, B), ids (M, B))`` beam: the exact
    top-B multiset of {incoming beam} U {distinct live neighbors not
    already in the beam}, in slot order (NOT sorted -- the traversal's
    final ``top_k`` orders the winners). ``tn`` must divide
    ``layout_block`` (the dispatcher in ops.py guarantees it). Per-query
    operands carry a unit axis (``(M, 1, S)``, ``(M, 1, B)``, ``(M, C,
    1)``, ids ``(N // TN, 1, TN)``) so every block's last two dims equal
    the array's; the block tags and schedules ride in SMEM.
    """
    m, c, d = q_scaled.shape
    n = codes.shape[0]
    assert n % layout_block == 0 and layout_block % tn == 0, \
        (n, layout_block, tn)
    s = nbr_rows.shape[1]
    b = beam_vals.shape[1]
    bpt = layout_block // tn                  # slabs per layout block
    # group the hop's neighbor rows into slabs: ascending sort (invalid
    # rows to the sentinel end), then keep each slab's FIRST slot only --
    # one fold per distinct slab, membership picks out all its neighbors.
    sorted_rows = jnp.sort(jnp.where(nbr_rows >= 0, nbr_rows, n), axis=1)
    valid = sorted_rows < n
    slab = sorted_rows // tn
    fresh = valid & jnp.concatenate(
        [jnp.ones((m, 1), bool), slab[:, 1:] != slab[:, :-1]], axis=1)
    sched_t = jnp.where(fresh, slab, -1).astype(jnp.int32)
    nbr_sorted = jnp.where(valid, sorted_rows, -1).astype(jnp.int32)
    # forward-filled twin for the index maps: padding / repeated-slab
    # slots keep the last fresh slab index, so their grid steps revisit
    # the already-resident slab (the pipeline skips the DMA) -- matching
    # ops.beam_step_bytes.
    sched_f = jnp.maximum(jax.lax.associative_scan(
        lambda a, b: jnp.where(b >= 0, b, a), sched_t, axis=1), 0)

    def run(sched_t, sched_f, q_scaled, q_lo, nbr_sorted, beam_vals,
            beam_ids):
        mc = q_scaled.shape[0]

        def per_query(width):
            return pl.BlockSpec((1, 1, width),
                                lambda i, j, sr, fr, tg: (i, 0, 0))

        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(mc, s),
            in_specs=[
                pl.BlockSpec((1, c, d), lambda i, j, sr, fr, tg: (i, 0, 0)),
                pl.BlockSpec((1, c, 1), lambda i, j, sr, fr, tg: (i, 0, 0)),
                per_query(s),
                pl.BlockSpec((1, 1, tn),
                             lambda i, j, sr, fr, tg: (fr[i, j], 0, 0)),
                pl.BlockSpec((tn, d), lambda i, j, sr, fr, tg: (fr[i, j], 0)),
                per_query(b),
                per_query(b),
            ],
            out_specs=[per_query(b), per_query(b)],
        )
        return pl.pallas_call(
            functools.partial(_beam_step_kernel, tn=tn, bpt=bpt),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((mc, 1, b), jnp.float32),
                jax.ShapeDtypeStruct((mc, 1, b), jnp.int32),
            ],
            interpret=interpret,
        )(sched_t, sched_f, block_tags.astype(jnp.int32),
          q_scaled.astype(jnp.float32), q_lo.astype(jnp.float32)[..., None],
          nbr_sorted[:, None, :],
          row_ids.astype(jnp.int32).reshape(n // tn, 1, tn), codes,
          beam_vals.astype(jnp.float32)[:, None, :],
          beam_ids.astype(jnp.int32)[:, None, :])

    vals, ids = query_chunks(run, m, s, block_tags.shape[0], sched_t,
                             sched_f, q_scaled, q_lo, nbr_sorted, beam_vals,
                             beam_ids)
    return vals[:, 0], ids[:, 0]
