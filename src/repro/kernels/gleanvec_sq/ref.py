"""Pure-jnp oracle for the fused GleanVec ∘ int8 kernel.

score[m, n] = <q_scaled[m, tags[n]], codes[n]> + q_lo[m, tags[n]]

with the per-cluster scales/offsets already folded query-side
(q_scaled = (A_c q) * delta_c, q_lo = <A_c q, lo_c>).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -3.4e38


def gleanvec_sq_ref(q_scaled: jax.Array, q_lo: jax.Array, tags: jax.Array,
                    codes: jax.Array):
    """``q_scaled (M, C, d)``, ``q_lo (M, C)``, ``tags (N,)``,
    ``codes (N, d)`` u8/f32 -> scores ``(M, N) f32``."""
    q_sel = q_scaled[:, tags, :].astype(jnp.float32)   # (M, N, d)
    scores = jnp.einsum("mnd,nd->mn", q_sel, codes.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    return scores + q_lo[:, tags]


def gleanvec_sq_sorted_ref(q_scaled: jax.Array, q_lo: jax.Array,
                           block_tags: jax.Array, codes: jax.Array,
                           layout_block: int):
    """Sorted-layout oracle: expand the per-block tags to rows."""
    tags = jnp.repeat(block_tags, layout_block)
    return gleanvec_sq_ref(q_scaled, q_lo, tags, codes)


def gleanvec_sq_topk_ref(q_scaled: jax.Array, q_lo: jax.Array,
                         tags: jax.Array, codes: jax.Array, k: int,
                         row_ids=None, layout_block: int = 0):
    """Score densely, mask ``row_ids < 0`` and reduce with ``top_k``;
    returned ids come from ``row_ids`` (default ``arange(N)``)."""
    if layout_block > 0:
        scores = gleanvec_sq_sorted_ref(q_scaled, q_lo, tags, codes,
                                        layout_block)
    else:
        scores = gleanvec_sq_ref(q_scaled, q_lo, tags, codes)
    if row_ids is not None:
        row_ids = row_ids.astype(jnp.int32)
        scores = jnp.where(row_ids[None, :] >= 0, scores, NEG_INF)
    vals, idx = jax.lax.top_k(scores, k)
    ids = idx.astype(jnp.int32) if row_ids is None else row_ids[idx]
    return vals, ids
