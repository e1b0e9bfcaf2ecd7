"""Top-k utilities: blocked scans and (value, id) merge operations.

These bound the peak memory of brute-force scoring (the paper's Algorithm 1
main search over X_low) to one (m, block) tile at a time, mirroring the VMEM
tiling of the ``ip_topk`` Pallas kernel.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

__all__ = ["merge_topk", "blocked_topk", "NEG_INF"]

NEG_INF = jnp.float32(-3.4e38)


def merge_topk(val_a, id_a, val_b, id_b, k: int):
    """Merge two (batch, *) candidate sets into the joint top-k."""
    vals = jnp.concatenate([val_a, val_b], axis=-1)
    ids = jnp.concatenate([id_a, id_b], axis=-1)
    top_vals, sel = jax.lax.top_k(vals, k)
    return top_vals, jnp.take_along_axis(ids, sel, axis=-1)


@functools.partial(jax.jit, static_argnames=("score_block_fn", "n", "k",
                                             "block", "batch"))
def blocked_topk(score_block_fn: Callable, n: int, k: int, block: int,
                 batch: int):
    """Running top-k over ``n`` database items scored block-by-block.

    ``score_block_fn(start) -> (batch, block)`` scores for ids
    [start, start+block). Scores for ids >= n must already be -inf-masked by
    the caller (or n % block == 0).
    Returns (values, ids): (batch, k) each.

    The loop, its id bookkeeping and the merges are the profiler scope
    ``search.merge``; each block's scoring inside it is ``search.scan``.
    """
    n_blocks = -(-n // block)

    def body(carry, i):
        best_v, best_i = carry
        start = i * block
        with jax.named_scope("search.scan"):
            scores = score_block_fn(start)
        ids = start + jax.lax.broadcasted_iota(jnp.int32, (batch, block), 1)
        valid = ids < n
        scores = jnp.where(valid, scores, NEG_INF)
        best_v, best_i = merge_topk(best_v, best_i, scores, ids, k)
        return (best_v, best_i), None

    with jax.named_scope("search.merge"):
        init = (jnp.full((batch, k), NEG_INF),
                jnp.full((batch, k), -1, jnp.int32))
        (vals, ids), _ = jax.lax.scan(body, init, jnp.arange(n_blocks))
    return vals, ids
