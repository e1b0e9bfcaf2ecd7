"""Pallas TPU kernel: eager GleanVec inner products (paper Algorithm 4).

Per database tile, the tag-selected query views are materialized with a
one-hot (TN, C) x (C, d) MXU matmul per query row (no VMEM gathers -- TPU has
no efficient in-VMEM row gather), then contracted rowwise with the database
tile on the VPU:

    onehot  = (tags_tile[:, None] == iota_C)          # (TN, C)
    q_sel_m = onehot @ q_views[m]                     # (TN, d)  MXU
    scores[m, tile] = sum_d q_sel_m * x_tile          # (TN,)    VPU

The entire eager view set q_views (C, d) per query lives in VMEM: for the
paper's largest setting (C = 48, d = 320) that is 60 KiB -- the CPU
cache-contention concern of Section 4 (Figure 7) vanishes on TPU
(DESIGN.md section 2).

HBM traffic per database vector = d * 4 bytes + 4 (tag), identical to the
plain LeanVec kernel up to the tag byte -- the bandwidth win of the paper's
DR carries over; the extra one-hot FLOPs ride on otherwise-idle MXU cycles
in this bandwidth-bound regime. With a tag-sorted (cluster-contiguous)
database layout every tile is single-tag and the kernel degenerates to one
(TM, d) x (d, TN) matmul; the layout flag is plumbed through ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _gleanvec_ip_kernel(qv_ref, tags_ref, x_ref, out_ref, *, c: int):
    qv = qv_ref[...].astype(jnp.float32)      # (TM, C, d)
    tags = tags_ref[...]                      # (TN,)
    x = x_ref[...].astype(jnp.float32)        # (TN, d)
    tm = qv.shape[0]
    onehot = (tags[:, None]
              == jax.lax.broadcasted_iota(jnp.int32, (tags.shape[0], c), 1)
              ).astype(jnp.float32)           # (TN, C)

    def per_query(m, acc):
        q_sel = jax.lax.dot_general(
            onehot, qv[m], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (TN, d)
        s = jnp.sum(q_sel * x, axis=1)                   # (TN,)
        return jax.lax.dynamic_update_index_in_dim(acc, s, m, 0)

    out_ref[...] = jax.lax.fori_loop(
        0, tm, per_query, jnp.zeros_like(out_ref))


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def gleanvec_ip(q_views: jax.Array, tags: jax.Array, x_low: jax.Array,
                tm: int = 8, tn: int = 512, interpret: bool = False):
    """``q_views (M, C, d)``, ``tags (N,) int32``, ``x_low (N, d)`` ->
    scores ``(M, N) f32``."""
    m, c, d = q_views.shape
    n = x_low.shape[0]
    tm = min(tm, max(1, m))
    m_pad = (-m) % tm
    n_pad = (-n) % tn
    if m_pad:
        q_views = jnp.pad(q_views, ((0, m_pad), (0, 0), (0, 0)))
    if n_pad:
        x_low = jnp.pad(x_low, ((0, n_pad), (0, 0)))
        tags = jnp.pad(tags, (0, n_pad))
    grid = ((m + m_pad) // tm, (n + n_pad) // tn)

    out = pl.pallas_call(
        functools.partial(_gleanvec_ip_kernel, c=c),
        name="gleanvec_ip",
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, c, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((tn,), lambda i, j: (j,)),
            pl.BlockSpec((tn, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m + m_pad, n + n_pad), jnp.float32),
        interpret=interpret,
    )(q_views, tags, x_low)
    return out[:m, :n]
