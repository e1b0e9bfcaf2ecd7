"""Helpers shared by the fused top-k scans (``ip_topk``,
``gleanvec_sq_topk``, ``ivf_scan_topk``, ``graph_scan_beam_step``) so they
lower through Mosaic, the TPU's Pallas compiler.

Mosaic lowers neither an in-register lane gather (``take_along_axis``)
nor a lane concatenation of unaligned widths, and it cannot cast ``u8``
straight to ``f32``. These helpers express the same computations with
compare/select and lane reductions only, so the kernels compile for the
TPU and still compute exactly what their ``ref.py`` oracles do.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -3.4e38  # python scalar: safe to close over inside the kernel
_BIG = 2 ** 30
_INT_MIN = -(2 ** 31)
# Scalar-prefetch operands live in SMEM (1 MiB on v5e); the per-query
# schedules of one pallas_call stay under this many bytes.
SMEM_BUDGET = 512 * 1024


def to_f32(x: jax.Array) -> jax.Array:
    """Cast codes to f32 on load. ``u8``/``i8`` go through ``i32`` (Mosaic
    has no direct narrow-int -> f32 cast); every value is exact in both,
    so scores stay bit-identical to the reference."""
    if jnp.issubdtype(x.dtype, jnp.integer):
        x = x.astype(jnp.int32)
    return x.astype(jnp.float32)


def first_argmax(v: jax.Array, iota: jax.Array, vmax: jax.Array):
    """Per-row index of the FIRST entry equal to the row max ``vmax``
    (``jnp.argmax`` semantics), as a keepdims column."""
    return jnp.min(jnp.where(v == vmax, iota, _BIG), axis=1, keepdims=True)


def pick(x: jax.Array, iota: jax.Array, at: jax.Array, fill):
    """``x[row, at[row]]`` as a keepdims column (compare/select, no
    gather). ``fill`` must not exceed any value of ``x``."""
    return jnp.max(jnp.where(iota == at, x, fill), axis=1, keepdims=True)


def lanes(k: int) -> int:
    """Lane width of a running top-k block: ``k`` rounded up to a whole
    vreg row (128 lanes), so the fold can rotate it in place. Callers slice
    the first ``k`` lanes of the output."""
    return -(-k // 128) * 128


def fold_topk(run_v, run_i, new_v, new_i, k: int):
    """Merge a score tile into a running top-k held best-first in the
    first ``k`` of ``lanes(k)`` lanes: each round inserts every row's best
    remaining tile candidate that beats the row's k-th best, and the loop
    stops as soon as no row has one (at most ``k`` rounds). The result is
    ``top_k`` of the concatenation ``[run | new]`` with ties to the lower
    index -- running entries before tile entries, tile entries by column --
    exactly what ``argmax`` over the concatenated row would pick; slots no
    finite candidate fills keep their ids. Lanes past ``k`` are scratch."""
    iota_r = jax.lax.broadcasted_iota(jnp.int32, run_v.shape, 1)
    iota_n = jax.lax.broadcasted_iota(jnp.int32, new_v.shape, 1)
    head = iota_r < k

    def improving(run_v, new_v):
        best = jnp.max(new_v, axis=1, keepdims=True)
        return best, best > pick(run_v, iota_r, k - 1, -jnp.inf)

    def cond(carry):
        r, run_v, _, new_v = carry
        return (r < k) & jnp.any(improving(run_v, new_v)[1])

    def body(carry):
        r, run_v, run_i, new_v = carry
        best, ins = improving(run_v, new_v)
        at = first_argmax(new_v, iota_n, best)
        bid = pick(new_i, iota_n, at, _INT_MIN)
        # insertion slot: after every running entry >= best (ties keep the
        # running entry first); the tail shifts one lane to the right
        pos = jnp.sum((head & (run_v >= best)).astype(jnp.int32), axis=1,
                      keepdims=True)
        below = ins & (iota_r > pos)
        run_v = jnp.where(ins & (iota_r == pos), best,
                          jnp.where(below, pltpu.roll(run_v, 1, 1), run_v))
        run_i = jnp.where(ins & (iota_r == pos), bid,
                          jnp.where(below, pltpu.roll(run_i, 1, 1), run_i))
        new_v = jnp.where(ins & (iota_n == at), NEG_INF, new_v)
        return r + 1, run_v, run_i, new_v

    _, run_v, run_i, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), run_v, run_i, new_v))
    return run_v, run_i


def query_chunks(run, m: int, sched_len: int, smem_words: int, *args):
    """Call ``run(*rows)`` on consecutive query chunks of the per-query
    ``args`` (first axis ``m``) and concatenate its outputs, so that two
    ``(chunk, sched_len)`` int32 schedules plus ``smem_words`` of other
    scalar-prefetch operands fit :data:`SMEM_BUDGET`. One call when the
    whole batch fits."""
    per_query = 2 * 4 * max(1, sched_len)
    chunk = max(1, (SMEM_BUDGET - 4 * smem_words) // per_query)
    if chunk >= m:
        return run(*args)
    outs = [run(*(a[s:s + chunk] for a in args)) for s in range(0, m, chunk)]
    return [jnp.concatenate(parts, axis=0) for parts in zip(*outs)]
