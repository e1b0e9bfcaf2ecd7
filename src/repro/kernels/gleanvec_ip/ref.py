"""Pure-jnp oracle for the eager GleanVec inner-product kernel (Alg. 4)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def gleanvec_ip_ref(q_views: jax.Array, tags: jax.Array, x_low: jax.Array):
    """``q_views (M, C, d)``, ``tags (N,)``, ``x_low (N, d)`` -> scores (M, N).

    scores[m, n] = <q_views[m, tags[n]], x_low[n]>   (Eq. 16, eager).
    """
    q_sel = q_views[:, tags, :]                       # (M, N, d)
    return jnp.einsum("mnd,nd->mn", q_sel.astype(jnp.float32),
                      x_low.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
