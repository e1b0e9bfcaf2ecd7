"""Pallas TPU kernel: int8 scalar-quantized inner products.

The paper applies scalar quantization on top of the reduced vectors Bx
(Section 3), compounding the bandwidth win: d * 1 byte per vector instead of
D * 4. Per-dimension scales fold into the query OUTSIDE the N loop
(<q, u*delta + lo> = <q*delta, u> + <q, lo>), so the kernel body is a pure
int8->f32 MXU matmul over streamed code tiles plus one broadcast add.
HBM traffic per database vector = d bytes.

VMEM per step (TM=128, TN=512, d=160): q 80 KiB + codes 80 KiB (u8)
+ scores 256 KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _sq_dot_kernel(qs_ref, qlo_ref, codes_ref, out_ref):
    qs = qs_ref[...].astype(jnp.float32)             # (TM, d) pre-scaled q
    u = codes_ref[...].astype(jnp.float32)           # (TN, d)
    qdotu = jax.lax.dot_general(
        qs, u, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # (TM, TN)
    out_ref[...] = qdotu + qlo_ref[...]              # (TM, 1) broadcast


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def sq_dot(q: jax.Array, codes: jax.Array, lo: jax.Array, delta: jax.Array,
           tm: int = 128, tn: int = 512, interpret: bool = False):
    """``q (M, d)``, ``codes (N, d) u8``, ``lo/delta (d,)`` -> (M, N) f32."""
    m, d = q.shape
    n = codes.shape[0]
    qf = q.astype(jnp.float32)
    q_scaled = qf * delta[None, :]
    q_lo = (qf @ lo)[:, None]                        # (M, 1)
    tm = min(tm, max(8, m))
    m_pad = (-m) % tm
    n_pad = (-n) % tn
    if m_pad:
        q_scaled = jnp.pad(q_scaled, ((0, m_pad), (0, 0)))
        q_lo = jnp.pad(q_lo, ((0, m_pad), (0, 0)))
    if n_pad:
        codes = jnp.pad(codes, ((0, n_pad), (0, 0)))
    grid = ((m + m_pad) // tm, (n + n_pad) // tn)

    out = pl.pallas_call(
        _sq_dot_kernel,
        name="sq_dot",
        grid=grid,
        in_specs=[
            pl.BlockSpec((tm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((tn, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m + m_pad, n + n_pad), jnp.float32),
        interpret=interpret,
    )(q_scaled, q_lo, codes)
    return out[:m, :n]
