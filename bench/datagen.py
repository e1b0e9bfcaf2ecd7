"""The benchmark's collection and queries, made on the device from a seed.

A copy of the statistical twin in ``repro.data.vectors`` (``make_mixture``
and ``make_dataset(ood=True)``), kept here so that a change to the program
cannot change the yardstick:

* database rows: a mixture of 8 anisotropic Gaussians, each of intrinsic
  dimension ``max(8, D // 6)`` with a geometric spectrum (decay 0.85),
  means spread 4.0;
* out-of-distribution queries: a rotated low-rank Gaussian (intrinsic
  dimension ``max(8, D // 8)``, decay 0.8) with a mean shift, mixed
  0.6 : 0.4 with a random database row (the query and its answers stay
  semantically linked).

Everything is drawn with ``jax.random`` on the device: the rows in chunks
inside one jitted call, so set-up never holds the collection on the host;
or, for a store kept in host memory (``make_host``), the same chunks one
call at a time, each copied into one host array.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
N_COMPONENTS = 8
SPREAD = 4.0


class Mixture(NamedTuple):
    means: jax.Array      # (C, D)
    bases: jax.Array      # (C, D, d_intr), columns scaled by the spectrum


class QueryLaw(NamedTuple):
    basis: jax.Array      # (D, d_intr)
    rot: jax.Array        # (D, D) orthonormal
    shift: jax.Array      # (D,)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (wider than 32 bits too)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _basis(key, dim: int, d_intr: int, decay: float) -> jax.Array:
    q, _ = jnp.linalg.qr(jax.random.normal(key, (dim, dim), jnp.float32))
    return q[:, :d_intr] * (decay ** jnp.arange(d_intr, dtype=jnp.float32))


def _mixture(key, dim: int) -> Mixture:
    d_intr = max(8, dim // 6)
    k_mean, k_basis = jax.random.split(key)
    means = jax.random.normal(k_mean, (N_COMPONENTS, dim)) * SPREAD
    bases = jax.vmap(lambda k: _basis(k, dim, d_intr, 0.85))(
        jax.random.split(k_basis, N_COMPONENTS))
    return Mixture(means, bases)


def _query_law(key, dim: int) -> QueryLaw:
    k_rot, k_basis, k_shift = jax.random.split(key, 3)
    rot, _ = jnp.linalg.qr(jax.random.normal(k_rot, (dim, dim)))
    return QueryLaw(basis=_basis(k_basis, dim, max(8, dim // 8), 0.8),
                    rot=rot,
                    shift=jax.random.normal(k_shift, (dim,)) * 2.0)


def _rows(key, mix: Mixture, rows: int) -> jax.Array:
    """``rows`` draws from the mixture: mean of the drawn component plus
    its low-rank Gaussian (one masked product per component)."""
    k_a, k_z = jax.random.split(key)
    a = jax.random.randint(k_a, (rows,), 0, N_COMPONENTS)
    z = jax.random.normal(k_z, (rows, mix.bases.shape[2]))
    out = mix.means[a]
    for c in range(N_COMPONENTS):
        part = jnp.matmul(z, mix.bases[c].T, precision=HIGHEST)
        out = out + jnp.where((a == c)[:, None], part, 0.0)
    return out


@functools.partial(jax.jit, static_argnames=("n", "dim", "chunk"))
def database(key, n: int, dim: int, chunk: int) -> jax.Array:
    """(n, dim) float32 rows, made ``chunk`` rows at a time on the device."""
    if n % chunk:
        raise ValueError(f"chunk {chunk} does not divide n={n}")
    mix = _mixture(jax.random.fold_in(key, 0), dim)
    k_rows = jax.random.fold_in(key, 1)

    def body(i, out):
        part = _rows(jax.random.fold_in(k_rows, i), mix, chunk)
        return jax.lax.dynamic_update_slice_in_dim(out, part, i * chunk, 0)

    return jax.lax.fori_loop(0, n // chunk, body,
                             jnp.zeros((n, dim), jnp.float32))


def _ood_draws(key, law: QueryLaw, x: jax.Array, count: int) -> jax.Array:
    k_z, k_anchor = jax.random.split(key)
    z = jax.random.normal(k_z, (count, law.basis.shape[1]))
    q = jnp.matmul(jnp.matmul(z, law.basis.T, precision=HIGHEST), law.rot,
                   precision=HIGHEST) + law.shift
    anchor = x[jax.random.randint(k_anchor, (count,), 0, x.shape[0])]
    return 0.6 * q + 0.4 * anchor


query_law = jax.jit(_query_law, static_argnames=("dim",))
ood_draws = jax.jit(_ood_draws, static_argnames=("count",))


class Collection(NamedTuple):
    x: jax.Array             # (n, D) float32 on the device (on the host
                             # from make_host)
    learn: jax.Array         # (n_learn, D) float32 on the device
    pool: np.ndarray         # (n_pool, D) float32 on the host
    law: QueryLaw


def make(collection_seed: int, seed: int, n: int, dim: int, n_learn: int,
         n_pool: int, chunk: int = 100_000) -> Collection:
    """The run's collection. The rows, the query law and the learning
    queries (for the fit) come from the configuration's
    ``collection_seed``, so every run of a cell serves the same collection
    and does the same work; the served pool (:func:`with_pool`) is drawn
    from the run's ``seed``."""
    key = seed_key(collection_seed)
    x = database(jax.random.fold_in(key, 11), n, dim, min(chunk, n))
    k_q = jax.random.fold_in(key, 12)
    law = query_law(jax.random.fold_in(k_q, 0), dim)
    learn = ood_draws(jax.random.fold_in(k_q, 1), law, x, n_learn)
    coll = Collection(x=x, learn=learn,
                      pool=np.zeros((0, dim), np.float32), law=law)
    return with_pool(coll, seed, n_pool)


def with_pool(coll: Collection, seed: int, n_pool: int,
              pool_chunk: int = 16_384) -> Collection:
    """``coll`` with a pool of ``n_pool`` served queries drawn from
    ``seed``, ``pool_chunk`` at a time on the device, copied to the
    host."""
    k_pool = jax.random.fold_in(seed_key(seed), 2)
    parts = [np.asarray(ood_draws(jax.random.fold_in(k_pool, i), coll.law,
                                  coll.x, pool_chunk))
             for i in range(-(-n_pool // pool_chunk))]
    if not parts:
        return coll
    return coll._replace(pool=np.concatenate(parts)[:n_pool])


# -- a store kept in host memory ------------------------------------------
#
# The same draws, for a configuration whose f32 rerank store lives in host
# memory (``"store": "host"``) and may not fit on the device: the device
# makes one chunk of rows at a time, with ``database``'s keys, and each
# chunk is copied into one host array; the anchors of the query draws are
# gathered from those host rows. Beside the device path, not through it.

mixture = jax.jit(_mixture, static_argnames=("dim",))
chunk_rows = jax.jit(_rows, static_argnames=("rows",))


@functools.partial(jax.jit, static_argnames=("count", "n"))
def anchor_ids(key, count: int, n: int) -> jax.Array:
    """The rows ``_ood_draws`` takes its anchors from, from its key."""
    return jax.random.randint(jax.random.split(key)[1], (count,), 0, n)


@jax.jit
def ood_mix(key, law: QueryLaw, anchor: jax.Array) -> jax.Array:
    """``_ood_draws`` with its anchor rows given."""
    k_z, _ = jax.random.split(key)
    z = jax.random.normal(k_z, (anchor.shape[0], law.basis.shape[1]))
    q = jnp.matmul(jnp.matmul(z, law.basis.T, precision=HIGHEST), law.rot,
                   precision=HIGHEST) + law.shift
    return 0.6 * q + 0.4 * anchor


def host_database(key, n: int, dim: int, chunk: int) -> np.ndarray:
    """``database(key, n, dim, chunk)`` in one read-only host array, made
    one chunk at a time: the device never holds more than one chunk."""
    if n % chunk:
        raise ValueError(f"chunk {chunk} does not divide n={n}")
    mix = mixture(jax.random.fold_in(key, 0), dim)
    k_rows = jax.random.fold_in(key, 1)
    x = np.empty((n, dim), np.float32)
    for i in range(n // chunk):
        x[i * chunk:(i + 1) * chunk] = np.asarray(
            chunk_rows(jax.random.fold_in(k_rows, i), mix, chunk))
    x.flags.writeable = False
    return x


def host_ood_draws(key, law: QueryLaw, x: np.ndarray,
                   count: int) -> jax.Array:
    """``ood_draws`` over host rows: the anchors are gathered on the
    host."""
    return ood_mix(key, law, x[np.asarray(anchor_ids(key, count,
                                                     x.shape[0]))])


def make_host(collection_seed: int, seed: int, n: int, dim: int,
              n_learn: int, n_pool: int, chunk: int = 100_000
              ) -> Collection:
    """:func:`make` with the rows in host memory (``x`` a read-only numpy
    array): the same rows, learning queries and pool."""
    key = seed_key(collection_seed)
    x = host_database(jax.random.fold_in(key, 11), n, dim, min(chunk, n))
    k_q = jax.random.fold_in(key, 12)
    law = query_law(jax.random.fold_in(k_q, 0), dim)
    learn = host_ood_draws(jax.random.fold_in(k_q, 1), law, x, n_learn)
    coll = Collection(x=x, learn=learn,
                      pool=np.zeros((0, dim), np.float32), law=law)
    return with_host_pool(coll, seed, n_pool)


def with_host_pool(coll: Collection, seed: int, n_pool: int,
                   pool_chunk: int = 16_384) -> Collection:
    """:func:`with_pool` over host rows."""
    k_pool = jax.random.fold_in(seed_key(seed), 2)
    parts = [np.asarray(host_ood_draws(jax.random.fold_in(k_pool, i),
                                       coll.law, coll.x, pool_chunk))
             for i in range(-(-n_pool // pool_chunk))]
    if not parts:
        return coll
    return coll._replace(pool=np.concatenate(parts)[:n_pool])
