"""Pure-jnp oracle for the fused inner-product + top-k scan kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ip_topk_ref(q: jax.Array, x: jax.Array, k: int):
    """Exact MIPS top-k: ``q (M, d)``, ``x (N, d)`` -> (vals, ids) (M, k)."""
    scores = jnp.dot(q.astype(jnp.float32), x.astype(jnp.float32).T,
                     precision=jax.lax.Precision.HIGHEST)
    vals, ids = jax.lax.top_k(scores, k)
    return vals, ids.astype(jnp.int32)
