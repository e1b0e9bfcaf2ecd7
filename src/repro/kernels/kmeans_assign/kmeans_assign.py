"""Pallas TPU kernel: spherical k-means assignment scan (paper Eq. 14/23).

Used during GleanVec learning (Algorithm 5, every EM iteration touches all n
database rows) and online when inserting vectors into a streaming index. The
centroid matrix stays resident in VMEM (C <= 100 in the paper; C x D fp32 at
C=64, D=960 is 240 KiB); database tiles stream through once:

    sims = x_tile @ centers^T   (MXU)
    tag  = argmax, val = max    (VPU)

HBM traffic = N*D*4 bytes read, N*8 written -- purely bandwidth-bound.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kmeans_assign_kernel(x_ref, c_ref, tags_ref, sims_ref):
    x = x_ref[...].astype(jnp.float32)         # (TN, D)
    cent = c_ref[...].astype(jnp.float32)      # (C, D)
    sims = jax.lax.dot_general(
        x, cent, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)    # (TN, C)
    tags_ref[...] = jnp.argmax(sims, axis=1).astype(jnp.int32)
    sims_ref[...] = jnp.max(sims, axis=1)


@functools.partial(jax.jit, static_argnames=("tn", "interpret"))
def kmeans_assign(x: jax.Array, centers: jax.Array, tn: int = 1024,
                  interpret: bool = False):
    """``x (N, D)``, ``centers (C, D)`` -> (tags (N,) i32, maxsim (N,) f32)."""
    n, d = x.shape
    c = centers.shape[0]
    n_pad = (-n) % tn
    if n_pad:
        x = jnp.pad(x, ((0, n_pad), (0, 0)))
    grid = ((n + n_pad) // tn,)

    tags, sims = pl.pallas_call(
        _kmeans_assign_kernel,
        name="kmeans_assign",
        grid=grid,
        in_specs=[
            pl.BlockSpec((tn, d), lambda i: (i, 0)),
            pl.BlockSpec((c, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tn,), lambda i: (i,)),
            pl.BlockSpec((tn,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n + n_pad,), jnp.int32),
            jax.ShapeDtypeStruct((n + n_pad,), jnp.float32),
        ],
        interpret=interpret,
    )(x, centers)
    return tags[:n], sims[:n]
