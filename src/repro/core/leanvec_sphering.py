"""LeanVec-Sphering (paper Section 3, Algorithm 2).

Closed-form, hyperparameter-free, query-aware linear dimensionality reduction:

    Q = U S V^T            (SVD of the query matrix, D x m)
    W = U S U^T            (sphering matrix; W^2 = Q Q^T)
    P = top-d left singular vectors of W X
    A = P W^{-1}           (query projection,  f(q) = A q)
    B = P W                (database projection, g(x) = B x)

Everything is phrased in terms of the second-moment matrices
``K_Q = Q Q^T`` and ``K_X = X X^T`` so the same code serves the batch
(Algorithm 2), streaming (Section 3.2) and distributed (sharded-einsum + psum)
paths: the SVD of ``W X`` is replaced by the eigendecomposition of
``W K_X W`` (they share left singular vectors / eigenvectors).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import linalg

__all__ = ["SpheringModel", "fit", "fit_from_moments", "project_queries",
           "project_database", "full_rotation_model"]


class SpheringModel(NamedTuple):
    """Learned LeanVec-Sphering transform.

    ``a``: (d, D) query projection;  ``b``: (d, D) database projection;
    ``p``: (d, D) Stiefel factor;    ``w`` / ``w_pinv``: (D, D) sphering.

    When ``d == D`` this is the "flexible target dimensionality" model of
    Section 3.1: any row-prefix ``a[:d'], b[:d']`` is a valid reduced model and
    ``<a q, b x> == <q, x>`` exactly (Eq. 10), enabling runtime-tunable d and
    rerank-from-the-same-storage.
    """

    a: jax.Array
    b: jax.Array
    p: jax.Array
    w: jax.Array
    w_pinv: jax.Array

    @property
    def dim(self) -> int:
        return self.a.shape[0]

    def truncate(self, d: int) -> "SpheringModel":
        """Runtime selection of the target dimensionality (Section 3.1)."""
        return SpheringModel(self.a[:d], self.b[:d], self.p[:d], self.w,
                             self.w_pinv)


def fit_from_moments(k_q: jax.Array, k_x: jax.Array, d: int,
                     rel_eps: float = 1e-4) -> SpheringModel:
    """Algorithm 2 phrased on second moments (D x D inputs).

    ``k_q = sum_q q q^T``, ``k_x = sum_x x x^T``.
    """
    w, w_pinv = linalg.sphering_from_moment(k_q, rel_eps)
    # eig(W K_X W) shares eigenvectors with the left singular vectors of W X.
    m = linalg.dot(linalg.dot(w, k_x), w)
    m = 0.5 * (m + m.T)  # re-symmetrize for numerical stability
    p = linalg.topk_eigvecs(m, d)
    return SpheringModel(a=linalg.dot(p, w_pinv), b=linalg.dot(p, w), p=p,
                         w=w, w_pinv=w_pinv)


def fit(queries: jax.Array, database: jax.Array, d: int,
        rel_eps: float = 1e-4) -> SpheringModel:
    """Algorithm 2. ``queries: (m, D)``, ``database: (n, D)`` (row-major).

    REQUIREMENT (implicit in the paper, which uses 10k learning queries):
    m >~ D, else K_Q = QQ^T is rank-deficient and the pseudo-inverse W^+
    zeroes the null directions -- the query projection A = P W^+ then
    discards most of the space and recall drops BELOW plain SVD (measured
    on the laion twin at m=128, D=512). We warn rather than raise: a
    rank-deficient fit is still the paper's algorithm, just under-sampled.

    The data-touching part is two sharded einsums (lowering to matmul + psum
    under pjit); the rest is replicated O(D^3).
    """
    if queries.shape[0] < queries.shape[1]:
        import warnings
        warnings.warn(
            f"LeanVec-Sphering: {queries.shape[0]} learning queries for "
            f"D={queries.shape[1]} dims -- K_Q is rank-deficient and the "
            "sphering projection will discard directions; use m >= D "
            "queries (the paper uses 10k).", stacklevel=2)
    k_q = linalg.second_moment(queries)
    k_x = linalg.second_moment(database)
    return fit_from_moments(k_q, k_x, d, rel_eps)


def full_rotation_model(queries: jax.Array, database: jax.Array,
                        rel_eps: float = 1e-4) -> SpheringModel:
    """Section 3.1: fit with ``d = D`` so the stored vectors ``x' = P' W x``
    support every prefix dimensionality and exact reranking via Eq. (10)."""
    return fit(queries, database, d=queries.shape[1], rel_eps=rel_eps)


def project_queries(model: SpheringModel, queries: jax.Array) -> jax.Array:
    """f(q) = A q, batched: (m, D) -> (m, d)."""
    return linalg.dot(queries, model.a.T)


def project_database(model: SpheringModel, database: jax.Array) -> jax.Array:
    """g(x) = B x, batched: (n, D) -> (n, d)."""
    return linalg.dot(database, model.b.T)
