"""Pure-jnp oracle for the spherical k-means assignment kernel (Eq. 14/23)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def kmeans_assign_ref(x: jax.Array, centers: jax.Array):
    """``x (N, D)``, ``centers (C, D)`` -> (tags (N,) i32, maxsim (N,) f32)."""
    sims = jnp.dot(x.astype(jnp.float32), centers.astype(jnp.float32).T,
                   precision=jax.lax.Precision.HIGHEST)
    return (jnp.argmax(sims, axis=1).astype(jnp.int32),
            jnp.max(sims, axis=1))
