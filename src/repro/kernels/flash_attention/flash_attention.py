"""Pallas TPU kernel: FlashAttention-2-style fused attention.

Substrate for the assigned LM architectures (GQA for all five, sliding-window
for h2o-danube3). Online-softmax accumulation in VMEM scratch across the
sequential KV grid dimension; causal and sliding-window blocks that are fully
masked are skipped via the mask check degenerating to -inf (their
contribution underflows to zero weight).

Grid: (B * H, S/bq, S/bk), KV innermost. Scratch per (bq) q-block:
m (bq, 1), l (bq, 1), acc (bq, dh) fp32. VMEM per step (bq=bk=512, dh=128):
q/k/v tiles 3 * 512*128*4 = 768 KiB + acc 256 KiB << 16 MiB.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -3.4e38


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, causal: bool, window: Optional[int],
                  n_kv_blocks: int, scale: float):
    iq = pl.program_id(1)
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, dh)
    k = k_ref[0].astype(jnp.float32)                  # (bk, dh)
    v = v_ref[0].astype(jnp.float32)                  # (bk, dh)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                               # (bq, 1)
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked rows: exp(NEG_INF - NEG_INF) would be exp(0)=1
    safe_m = jnp.where(m_new <= NEG_INF, 0.0, m_new)
    p = jnp.exp(jnp.where(mask, s - safe_m, NEG_INF))
    alpha = jnp.where(m_prev <= NEG_INF, 0.0, jnp.exp(m_prev - safe_m))
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(jk == n_kv_blocks - 1)
    def _finalize():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, window: Optional[int] = None,
                    bq: int = 512, bk: int = 512, interpret: bool = False):
    """``q (B, H, S, dh)``, ``k/v (B, KV, S, dh)`` -> (B, H, S, dh).

    H % KV == 0 (GQA); S padded to tile multiples internally.
    """
    b, h, s_len, dh = q.shape
    kv = k.shape[1]
    group = h // kv
    bq = min(bq, s_len)
    bk = min(bk, s_len)
    pad = (-s_len) % max(bq, bk)
    if pad:
        # Padded keys sit at positions >= s_len; every real query has
        # q_pos < s_len, so the causal mask q_pos >= k_pos excludes them.
        # Non-causal padded attention would need an explicit kv-length mask.
        assert causal, "padding requires causal=True (pad S to a block multiple)"
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    s_pad = s_len + pad

    # fold padding into the window mask by treating it as causal+window on
    # the padded domain; for pure non-causal use an effective window.
    qr = q.reshape(b * h, s_pad, dh)
    kr = k.reshape(b * kv, s_pad, dh)
    vr = v.reshape(b * kv, s_pad, dh)
    n_kv_blocks = s_pad // bk
    grid = (b * h, s_pad // bq, n_kv_blocks)
    scale = 1.0 / float(dh) ** 0.5

    out = pl.pallas_call(
        functools.partial(_flash_kernel, bq=bq, bk=bk, causal=causal,
                          window=window, n_kv_blocks=n_kv_blocks,
                          scale=scale),
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, dh),
                         lambda bh, i, j, grp=group: (bh // grp, j, 0)),
            pl.BlockSpec((1, bk, dh),
                         lambda bh, i, j, grp=group: (bh // grp, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s_pad, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, s_pad, dh)[:, :, :s_len]
