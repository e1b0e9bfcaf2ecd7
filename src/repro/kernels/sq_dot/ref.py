"""Pure-jnp oracle for the int8 scalar-quantized dot kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def sq_dot_ref(q: jax.Array, codes: jax.Array, lo: jax.Array,
               delta: jax.Array):
    """``q (M, d)``, ``codes (N, d) u8``, ``lo/delta (d,)`` -> scores (M, N).

    scores[m, n] = <q_m, codes_n * delta + lo>
                 = <q_m * delta, codes_n> + <q_m, lo>.
    """
    qf = q.astype(jnp.float32)
    q_scaled = qf * delta[None, :]
    hi = jax.lax.Precision.HIGHEST
    return jnp.dot(q_scaled, codes.astype(jnp.float32).T, precision=hi) \
        + jnp.dot(qf, lo, precision=hi)[:, None]
