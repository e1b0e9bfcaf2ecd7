"""GleanVec (paper Section 4, Algorithm 5): piecewise-linear query-aware DR.

Learning (Algorithm 5):
  1. spherical k-means on normalized database -> landmarks {mu_c};
  2. partition X by Eq. (19);
  3. per cluster, LeanVec-Sphering (Algorithm 2) -> (A_c, B_c).

Encoding: x_i -> (c_i, B_{c_i} x_i) stored contiguously (Eq. 14-15).
Query-side: lazy (Alg. 3) or eager (Alg. 4) selection of A_{c_i} q.

The per-cluster fits share the sphering matrix W (it depends on the queries
only), so learning computes one (D,D) eigh for W plus a batched (vmapped)
eigh over the C per-cluster sphered moments W K_X^c W.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import linalg, spherical_kmeans
from repro.core.leanvec_sphering import SpheringModel

__all__ = ["GleanVecModel", "fit", "fit_from_moments", "assign_tags",
           "encode_rows", "encode_database", "sort_by_tag", "inverse_permutation",
           "project_queries_eager", "inner_products_lazy",
           "inner_products_eager", "per_cluster_moments"]


class GleanVecModel(NamedTuple):
    """Learned GleanVec transform.

    ``centers``: (C, D) unit landmarks;  ``a``: (C, d, D);  ``b``: (C, d, D);
    ``w`` / ``w_pinv``: (D, D) shared sphering (query-side).
    """

    centers: jax.Array
    a: jax.Array
    b: jax.Array
    w: jax.Array
    w_pinv: jax.Array

    @property
    def n_clusters(self) -> int:
        return self.centers.shape[0]

    @property
    def dim(self) -> int:
        return self.a.shape[1]

    def truncate(self, d: int) -> "GleanVecModel":
        """Runtime target-d selection (Section 3.1 carries over per cluster)."""
        return GleanVecModel(self.centers, self.a[:, :d], self.b[:, :d],
                             self.w, self.w_pinv)


def per_cluster_moments(x: jax.Array, tags: jax.Array, c: int) -> jax.Array:
    """K_X^c = sum_{x in X_c} x x^T for each cluster: (C, D, D).

    One einsum per row slice (the one-hot contraction's (rows, C, D)
    temporary stays O(slice) at any n); shards over rows of ``x`` under
    pjit (psum on output).
    """
    x = jnp.asarray(x, jnp.float32)
    d_full = x.shape[1]

    def add(acc, xb, tb, fresh):
        onehot = jax.nn.one_hot(tb, c, dtype=jnp.float32) * fresh[:, None]
        return acc + jnp.einsum("nc,nd,ne->cde", onehot, xb, xb,
                                precision=linalg.F32)

    return linalg.fold_row_blocks(
        add, jnp.zeros((c, d_full, d_full), jnp.float32), [x, tags],
        linalg.block_rows(c * d_full * 4))


def fit_from_moments(centers: jax.Array, k_q: jax.Array,
                     k_x_per_cluster: jax.Array, d: int,
                     rel_eps: float = 1e-4) -> GleanVecModel:
    """Per-cluster LeanVec-Sphering given precomputed moments."""
    w, w_pinv = linalg.sphering_from_moment(k_q, rel_eps)

    def fit_one(k_x_c):
        m = linalg.dot(linalg.dot(w, k_x_c), w)
        m = 0.5 * (m + m.T)
        p = linalg.topk_eigvecs(m, d)
        return linalg.dot(p, w_pinv), linalg.dot(p, w)

    a, b = jax.vmap(fit_one)(k_x_per_cluster)
    return GleanVecModel(centers=centers, a=a, b=b, w=w, w_pinv=w_pinv)


@functools.partial(jax.jit, static_argnames=("c", "d", "kmeans_iters"))
def fit(key: jax.Array, queries: jax.Array, database: jax.Array, c: int,
        d: int, kmeans_iters: int = 25, rel_eps: float = 1e-4
        ) -> GleanVecModel:
    """Algorithm 5. ``queries: (m, D)``, ``database: (n, D)``."""
    km = spherical_kmeans.fit(key, database, c, kmeans_iters)
    x_unit = spherical_kmeans.normalize_rows(database.astype(jnp.float32))
    tags = spherical_kmeans.assign(x_unit, km.centers)
    k_q = linalg.second_moment(queries)
    k_x_c = per_cluster_moments(database, tags, c)
    return fit_from_moments(km.centers, k_q, k_x_c, d, rel_eps)


def assign_tags(model: GleanVecModel, database: jax.Array) -> jax.Array:
    """Eq. (19) cluster assignment under the model's fixed landmarks (the
    tag half of :func:`encode_database`; streaming inserts use it alone to
    route rank-1 moment updates). Row-sliced: no (n, D) temporary."""
    def tag(x):
        return spherical_kmeans.assign(
            spherical_kmeans.normalize_rows(x.astype(jnp.float32)),
            model.centers)

    database = jnp.asarray(database)
    return linalg.map_row_blocks(tag, [database],
                                 linalg.block_rows(database.shape[1] * 8))


def encode_rows(model: GleanVecModel, tags: jax.Array,
                database: jax.Array) -> jax.Array:
    """``x_low_i = B_{tags_i} x_i`` for a slice of rows: gather the (d, D)
    blocks then contract (f32 throughout)."""
    return jnp.einsum("ndk,nk->nd", model.b[tags],
                      database.astype(jnp.float32),
                      precision=linalg.F32)


def encode_database(model: GleanVecModel, database: jax.Array):
    """Eq. (14)-(15): tags ``c_i`` and reduced vectors ``x_i_low = B_{c_i} x_i``.

    Returns ``(tags: (n,) int32, x_low: (n, d))``. The pair is what a
    deployment stores contiguously per vector. Row-sliced, so the gathered
    (rows, d, D) projections never exceed one slice.
    """
    database = jnp.asarray(database, jnp.float32)
    tags = assign_tags(model, database)
    b = model.b
    x_low = linalg.map_row_blocks(
        lambda t, x: encode_rows(model, t, x), [tags, database],
        linalg.block_rows(b.shape[1] * b.shape[2] * 4))
    return tags, x_low


def project_queries_eager(model: GleanVecModel, queries: jax.Array):
    """Alg. 4 preprocess: all views q_c = A_c q. (m, C, d)."""
    return jnp.einsum("cdk,mk->mcd", model.a, queries.astype(jnp.float32),
                      precision=linalg.F32)


def inner_products_lazy(model: GleanVecModel, query: jax.Array,
                        tags: jax.Array, x_low: jax.Array) -> jax.Array:
    """Alg. 3: per-vector on-the-fly A_{c_i} q. query: (D,) -> (n,) scores."""
    a_sel = model.a[tags]                      # (n, d, D) gather
    q_proj = jnp.einsum("ndk,k->nd", a_sel, query.astype(jnp.float32),
                        precision=linalg.F32)
    return jnp.sum(q_proj * x_low, axis=-1)


def inner_products_eager(q_views: jax.Array, tags: jax.Array,
                         x_low: jax.Array) -> jax.Array:
    """Alg. 4: select precomputed view q_{c_i}. q_views: (C, d) for one query."""
    return jnp.sum(q_views[tags] * x_low, axis=-1)


def sort_by_tag(tags, x_low, x_full=None, block: int = 4096,
                slack_blocks: int = 0):
    """Cluster-contiguous layout for the sorted scorers / scans (see
    core.scorer.SortedGleanVecScorer): sorts rows by tag and pads each
    cluster to a ``block`` multiple, so every block of the sorted database
    carries exactly one tag. Works for any (n, d) row array -- f32 reduced
    vectors or u8 codes (pads with zeros of the input dtype).

    ``slack_blocks`` appends that many EXTRA all-padding blocks per
    cluster beyond the round-up -- free slots the streaming path's
    ``insert_rows`` can fill without changing the layout's shape (and
    hence without recompiling anything that closed over it).

    Returns (x_low_sorted, block_tags, perm, x_full_sorted) where
    ``perm[i_sorted] = original id`` (padding rows map to id -1 and are
    filled with zeros; sorted scorers additionally mask them to -inf).
    """
    import numpy as np
    tags_np = np.asarray(tags)
    x_low_np = np.asarray(x_low)
    n, d = x_low_np.shape
    order = np.argsort(tags_np, kind="stable")
    sorted_tags = tags_np[order]
    c = int(tags_np.max()) + 1 if n else 1
    rows, perm, blk_tags = [], [], []
    full_rows = None if x_full is None else []
    x_full_np = None if x_full is None else np.asarray(x_full)
    for ci in range(c):
        sel = order[sorted_tags == ci]
        pad = (-len(sel)) % block + slack_blocks * block
        rows.append(x_low_np[sel])
        perm.append(sel.astype(np.int64))
        if full_rows is not None:
            full_rows.append(x_full_np[sel])
        if pad:
            rows.append(np.zeros((pad, d), x_low_np.dtype))
            perm.append(np.full(pad, -1, np.int64))
            if full_rows is not None:
                full_rows.append(
                    np.zeros((pad, x_full_np.shape[1]), x_full_np.dtype))
        blk_tags.extend([ci] * ((len(sel) + pad) // block))
    x_low_sorted = jnp.asarray(np.concatenate(rows, axis=0))
    perm = jnp.asarray(np.concatenate(perm))
    block_tags = jnp.asarray(np.asarray(blk_tags, np.int32))
    x_full_sorted = (None if full_rows is None
                     else jnp.asarray(np.concatenate(full_rows, axis=0)))
    return x_low_sorted, block_tags, perm, x_full_sorted


def inverse_permutation(perm, n: int):
    """``inv[original_id] = sorted row`` for a ``sort_by_tag`` permutation.

    ``perm (n_sorted,)`` maps sorted rows to original ids (-1 = padding);
    every original id in [0, n) appears exactly once, so ``inv`` is total.
    """
    import numpy as np
    perm_np = np.asarray(perm)
    inv = np.full(n, -1, np.int32)
    valid = perm_np >= 0
    inv[perm_np[valid]] = np.nonzero(valid)[0].astype(np.int32)
    return jnp.asarray(inv)
