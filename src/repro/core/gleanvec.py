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

from repro.core import linalg, rerank_tier, spherical_kmeans
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
    route rank-1 moment updates). Row-sliced: no (n, D) temporary. A host
    store (``rerank_tier.is_host``) is streamed to the device one chunk at
    a time."""
    if rerank_tier.is_host(database):
        return jnp.concatenate(rerank_tier.map_host_chunks(
            lambda i, rows: _assign_tags_jit(model, rows), database))

    def tag(x):
        return spherical_kmeans.assign(
            spherical_kmeans.normalize_rows(x.astype(jnp.float32)),
            model.centers)

    database = jnp.asarray(database)
    return linalg.map_row_blocks(tag, [database],
                                 linalg.block_rows(database.shape[1] * 8))


# one compiled program for every chunk of a host store
_assign_tags_jit = jax.jit(assign_tags)


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
    (rows, d, D) projections never exceed one slice. A host store is
    streamed to the device one chunk at a time.
    """
    if rerank_tier.is_host(database):
        parts = rerank_tier.map_host_chunks(
            lambda i, rows: _encode_database_jit(model, rows), database)
        return tuple(jnp.concatenate(p) for p in zip(*parts))
    database = jnp.asarray(database, jnp.float32)
    tags = assign_tags(model, database)
    b = model.b
    x_low = linalg.map_row_blocks(
        lambda t, x: encode_rows(model, t, x), [tags, database],
        linalg.block_rows(b.shape[1] * b.shape[2] * 4))
    return tags, x_low


_encode_database_jit = jax.jit(encode_database)


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


def _tag_layout(tags, block: int = 4096, slack_blocks: int = 0):
    """The cluster-contiguous layout of rows with these ``tags`` (see
    :func:`sort_by_tag`), from the tags alone: ``perm`` (numpy int64,
    ``perm[i_sorted] = original id``, -1 for padding) and ``block_tags``
    (numpy int32, the one tag of each ``block`` of sorted rows)."""
    import numpy as np
    tags_np = np.asarray(tags)
    n = tags_np.shape[0]
    c = int(tags_np.max()) + 1 if n else 1
    counts = np.bincount(tags_np, minlength=c)
    padded = counts + (-counts) % block + slack_blocks * block
    order = np.argsort(tags_np, kind="stable")
    sorted_tags = tags_np[order]
    # cluster t's j-th row (in id order) lands on row j of its range
    rank = np.arange(n) - (np.cumsum(counts) - counts)[sorted_tags]
    perm = np.full(int(padded.sum()), -1, np.int64)
    perm[(np.cumsum(padded) - padded)[sorted_tags] + rank] = order
    block_tags = np.repeat(np.arange(c, dtype=np.int32), padded // block)
    return perm, block_tags


def _place_rows_host(rows, perm):
    import numpy as np
    rows = np.asarray(rows)
    if not rows.shape[0]:
        return jnp.zeros((len(perm),) + rows.shape[1:], rows.dtype)
    out = rows[np.maximum(perm, 0)]
    out[perm < 0] = 0
    return jnp.asarray(out)


@jax.jit
def _place_rows_device(rows, perm):
    picked = jnp.take(rows, jnp.maximum(perm, 0), axis=0)
    return jnp.where((perm >= 0)[:, None], picked,
                     jnp.zeros((), rows.dtype))


def sort_by_tag(tags, x_low, x_full=None, block: int = 4096,
                slack_blocks: int = 0, on_device: bool = False):
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
    The layout comes from the tags alone (:func:`_tag_layout`); the rows
    are reordered on the host, or with ``on_device`` in one gather on the
    device (rows that never cross to the host, as a host store's codes:
    the gather's temporaries take about three times the rows' bytes).
    """
    perm, block_tags = _tag_layout(tags, block, slack_blocks)
    if on_device:
        perm = jnp.asarray(perm)
        place = _place_rows_device
    else:
        place = _place_rows_host
    x_low_sorted = place(x_low, perm)
    x_full_sorted = None if x_full is None else place(x_full, perm)
    return x_low_sorted, jnp.asarray(block_tags), jnp.asarray(perm), \
        x_full_sorted


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
