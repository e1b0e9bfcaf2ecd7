"""Spherical k-means (paper Appendix A) with k-means++ initialization.

Finds unit-norm centers mu_c maximizing sum_i max_c <x_i/||x_i||, mu_c>
via the EM-like iterations (23)-(24). Fully jittable (fixed iteration count),
einsum-based so it shards cleanly under pjit (assignments: one X @ mu^T per
iteration; center update: one one-hot matmul + psum).

Empty clusters are re-seeded to the currently worst-assigned points, matching
robust practice (the paper samples 1e5 points uniformly; C < 100).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.linalg import dot

__all__ = ["KMeansState", "normalize_rows", "kmeanspp_init", "fit", "assign"]


class KMeansState(NamedTuple):
    centers: jax.Array  # (C, D), unit rows
    inertia: jax.Array  # scalar: mean max-cosine objective (Eq. 22)


def normalize_rows(x: jax.Array, eps: float = 1e-12) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.maximum(jnp.sum(x * x, -1, keepdims=True),
                                         eps))


def assign(x_unit: jax.Array, centers: jax.Array) -> jax.Array:
    """Cluster tags via Eq. (14)/(23): argmax_c <x_i, mu_c>. (n,) int32."""
    sims = dot(x_unit, centers.T)
    return jnp.argmax(sims, axis=-1).astype(jnp.int32)


def kmeanspp_init(key: jax.Array, x_unit: jax.Array, c: int) -> jax.Array:
    """k-means++ seeding on the sphere (D^2 distance = 2 - 2 cos)."""
    n = x_unit.shape[0]
    k0, key = jax.random.split(key)
    first = x_unit[jax.random.randint(k0, (), 0, n)]

    def body(carry, key_i):
        centers, n_chosen, min_d2 = carry
        probs = min_d2 / jnp.maximum(jnp.sum(min_d2), 1e-12)
        idx = jax.random.choice(key_i, n, p=probs)
        new = x_unit[idx]
        centers = centers.at[n_chosen].set(new)
        d2 = 2.0 - 2.0 * dot(x_unit, new)
        return (centers, n_chosen + 1, jnp.minimum(min_d2, d2)), None

    centers0 = jnp.zeros((c, x_unit.shape[1]), x_unit.dtype).at[0].set(first)
    d2_0 = 2.0 - 2.0 * dot(x_unit, first)
    (centers, _, _), _ = jax.lax.scan(
        body, (centers0, 1, d2_0), jax.random.split(key, c - 1))
    return centers


@functools.partial(jax.jit, static_argnames=("c", "n_iters"))
def fit(key: jax.Array, x: jax.Array, c: int, n_iters: int = 25) -> KMeansState:
    """Run spherical k-means. ``x: (n, D)`` (not necessarily normalized)."""
    x_unit = normalize_rows(x.astype(jnp.float32))
    n = x_unit.shape[0]
    init_key, _ = jax.random.split(key)
    centers = kmeanspp_init(init_key, x_unit, c)

    def step(_, centers):
        sims = dot(x_unit, centers.T)                  # (n, C)
        tags = jnp.argmax(sims, axis=-1)
        onehot = jax.nn.one_hot(tags, c, dtype=jnp.float32)
        sums = dot(onehot.T, x_unit)                   # Eq. (24) numerator
        counts = jnp.sum(onehot, axis=0)
        # Empty clusters: re-seed at the globally worst-served points.
        worst = jnp.argsort(jnp.max(sims, axis=-1))[:c]
        reseed = x_unit[worst]
        norms = jnp.linalg.norm(sums, axis=-1, keepdims=True)
        new = jnp.where(counts[:, None] > 0,
                        sums / jnp.maximum(norms, 1e-12), reseed)
        return normalize_rows(new)

    centers = jax.lax.fori_loop(0, n_iters, step, centers)
    inertia = jnp.mean(jnp.max(dot(x_unit, centers.T), axis=-1))
    return KMeansState(centers=centers, inertia=inertia)
