"""Pure-jnp oracle for the fused sorted-IVF range scan.

The oracle scores every row of the layout against its block's per-cluster
view through the same affine math as ``gleanvec_sq_ref`` (it may -- it is
the reference, not the fast path), keeps for each query the rows of the
blocks whose tag the query probes, masks dead rows (``row_ids < 0``) to
-inf, and reduces with ``top_k`` over the rows in sorted order, so equal
scores go to the lower sorted row. The per-row products are summed like
``scorer.score_ids`` sums them over a posting list holding the same rows,
so this oracle is ALSO the bridge the parity tests use between the fused
path and the gathered IVF path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -3.4e38


def ivf_scan_scores_ref(q_scaled: jax.Array, q_lo: jax.Array,
                        block_tags: jax.Array, row_ids: jax.Array,
                        codes: jax.Array, probe: jax.Array,
                        layout_block: int):
    """Dense scores over the sorted rows: returns ``(scores, ids)`` both
    ``(M, N)`` -- column ``r`` is sorted row ``r``; rows of unprobed
    blocks and dead rows score -inf with id -1."""
    m, c, d = q_scaled.shape
    nb = block_tags.shape[0]
    tag = jnp.maximum(block_tags, 0)                           # (NB,)
    member = jnp.any(probe[:, :, None] == jnp.arange(c), axis=1)  # (M, C)
    x = codes.reshape(nb, layout_block, d).astype(jnp.float32)
    q_sel = q_scaled[:, tag][:, :, None, :]                    # (M, NB, 1, d)
    scores = jnp.sum(q_sel * x, axis=-1) + q_lo[:, tag][:, :, None]
    rid = row_ids.reshape(nb, layout_block).astype(jnp.int32)
    ok = ((member[:, tag] & (block_tags >= 0))[:, :, None]
          & (rid >= 0)[None])                                  # (M, NB, LB)
    return (jnp.where(ok, scores, NEG_INF).reshape(m, -1),
            jnp.where(ok, rid, -1).reshape(m, -1))


def ivf_scan_topk_ref(q_scaled: jax.Array, q_lo: jax.Array,
                      block_tags: jax.Array, row_ids: jax.Array,
                      codes: jax.Array, probe: jax.Array, k: int,
                      layout_block: int):
    """Dense score + ``top_k`` oracle of :func:`ivf_scan_topk`: each
    query's top-k over the rows of its probed clusters, ties to the lower
    sorted row; -inf winners' ids are stripped to -1 exactly like the
    kernel."""
    scores, ids = ivf_scan_scores_ref(q_scaled, q_lo, block_tags, row_ids,
                                      codes, probe, layout_block)
    vals, sel = jax.lax.top_k(scores, k)
    out = jnp.take_along_axis(ids, sel, axis=1)
    return vals, jnp.where(vals > NEG_INF, out, -1)
