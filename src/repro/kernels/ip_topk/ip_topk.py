"""Pallas TPU kernel: fused inner-product scan + running top-k.

This is the hot loop of the paper's Algorithm 1 main search on a flat index:
score every database vector against a query batch and keep the best k.
The kernel streams (TN, d) database tiles HBM -> VMEM once (the bandwidth
the paper's dimensionality reduction minimizes), computes the (TM, TN) score
tile on the MXU, and folds it into a running (TM, k) top-k held in VMEM
scratch across the sequential N grid dimension -- scores never round-trip
to HBM.

Top-k folding uses k iterations of (max, first-argmax, mask) on the VPU
(compare/select only: Mosaic has no in-kernel lane gather); k is small
(10..128) in every paper configuration. Integer codes are widened through
i32 on load, and the tile contraction runs at HIGHEST precision, so the
scores equal the f32 reference's on the TPU too.

VMEM budget per step (TM=128, TN=512, d=160, k=16, fp32):
  q tile 128*160*4 = 80 KiB, x tile 512*160*4 = 320 KiB,
  scores 128*512*4 = 256 KiB, scratch 2 * 128*16*4 = 16 KiB   << 16 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels._mosaic import NEG_INF, fold_topk, lanes, to_f32


def _ip_topk_kernel(q_ref, x_ref, vals_ref, ids_ref, *, k: int, tn: int,
                    n_total: int):
    nj = pl.program_id(1)

    @pl.when(nj == 0)
    def _init():
        vals_ref[...] = jnp.full_like(vals_ref, NEG_INF)
        ids_ref[...] = jnp.full_like(ids_ref, -1)

    q = to_f32(q_ref[...])                                 # (TM, d)
    x = to_f32(x_ref[...])                                 # (TN, d)
    scores = jax.lax.dot_general(
        q, x, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)                # (TM, TN)
    base = nj * tn
    col_ids = base + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(col_ids < n_total, scores, NEG_INF)
    # fold the tile into the running top-k: k rounds of max/mask over the
    # running (TM, k) and the tile's (TM, TN) candidates.
    vals_ref[...], ids_ref[...] = fold_topk(vals_ref[...], ids_ref[...],
                                            scores, col_ids, k)


@functools.partial(jax.jit,
                   static_argnames=("k", "tm", "tn", "interpret"))
def ip_topk(q: jax.Array, x: jax.Array, k: int, tm: int = 128, tn: int = 512,
            interpret: bool = False):
    """Fused MIPS top-k. ``q (M, d)``, ``x (N, d)`` -> (vals, ids) (M, k).

    M, N are padded up to tile multiples internally; d should be a multiple
    of 128 for MXU efficiency (any d is functionally correct).
    """
    m, d = q.shape
    n = x.shape[0]
    tm = min(tm, max(8, m))
    m_pad = (-m) % tm
    n_pad = (-n) % tn
    if m_pad:
        q = jnp.pad(q, ((0, m_pad), (0, 0)))
    if n_pad:
        x = jnp.pad(x, ((0, n_pad), (0, 0)))
    grid = ((m + m_pad) // tm, (n + n_pad) // tn)
    kp = lanes(k)                  # running top-k block, lane-aligned

    vals, ids = pl.pallas_call(
        functools.partial(_ip_topk_kernel, k=k, tn=tn, n_total=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tn, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tm, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((tm, kp), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(((m + m_pad), kp), jnp.float32),
            jax.ShapeDtypeStruct(((m + m_pad), kp), jnp.int32),
        ],
        interpret=interpret,
    )(q, x)
    return vals[:m, :k], ids[:m, :k]
