"""Linear-algebra primitives shared by the LeanVec/GleanVec family.

All functions are jit-safe and operate on second-moment (Gram) matrices where
possible so that the data-touching part is a single sharded einsum (psum under
GSPMD) and the O(D^3) part runs replicated on D x D matrices (D <= ~1024 for
every dataset in the paper).

Every f32 contraction of the fit, the encoders, the query preparation,
the scans and the rerank runs at :data:`F32` precision, named at each call
site. The TPU's default f32 matmul rounds its inputs to bf16 in one pass.
The GleanVec fit (sphering by K_Q^{-1/2}, whose small-eigenvalue
directions carry large weights) and the int8 scorers' query offsets
<A_c q, lo_c> (which cancel against the code products) both lose the
near-tie order that recall depends on: flat recall@10 fell to 0.37 at 7M
rows of T2I shape on a v5e. Emulating the rounding on the CPU at 1M rows
gives 0.90 for the fit alone and 0.82 for the query preparation alone
(1.0 in f32). XLA:CPU computes f32 matmuls in full either way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "F32",
    "dot",
    "second_moment",
    "cross_moment",
    "sphering_from_moment",
    "topk_eigvecs",
    "orthonormalize_rows",
    "polar",
    "safe_inv_sqrt_spectrum",
    "block_rows",
    "map_row_blocks",
    "fold_row_blocks",
]

# matmul precision of every f32 contraction (see the module docstring)
F32 = jax.lax.Precision.HIGHEST


def dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` at :data:`F32` precision."""
    return jnp.matmul(a, b, precision=F32)


def second_moment(x: jax.Array, dtype=jnp.float32) -> jax.Array:
    """K = sum_i x_i x_i^T  for row-major data ``x: (n, D)`` -> ``(D, D)``.

    Under pjit with ``x`` row-sharded this lowers to a local einsum + psum.
    """
    x = x.astype(dtype)
    return jnp.einsum("nd,ne->de", x, x, precision=F32)


def cross_moment(x: jax.Array, w: jax.Array, dtype=jnp.float32) -> jax.Array:
    """Weighted moment ``sum_i w_i x_i x_i^T`` with per-row weights ``w: (n,)``."""
    x = x.astype(dtype)
    return jnp.einsum("n,nd,ne->de", w.astype(dtype), x, x, precision=F32)


def safe_inv_sqrt_spectrum(s: jax.Array, rel_eps: float = 1e-4):
    """Pseudo-inverse-safe 1/s for an eigen/singular spectrum ``s >= 0``.

    Entries below ``rel_eps * max(s)`` are treated as zero (paper: "if not
    [invertible], we can use a pseudoinverse"). Default 1e-4: measured on an
    ill-conditioned query moment (cond(K_Q) ~ 7e10, low intrinsic query
    dim), 1e-6 lets W^-1 amplify noise directions (loss 0.51 -> 0.05 when
    clipped at 1e-4); 1e-2 starts discarding signal (loss 1.6).
    """
    cutoff = rel_eps * jnp.max(s)
    safe = jnp.where(s > cutoff, s, 1.0)
    return jnp.where(s > cutoff, 1.0 / safe, 0.0)


def sphering_from_moment(k_q: jax.Array, rel_eps: float = 1e-4):
    """Compute the sphering matrix ``W = U S U^T`` and its pseudo-inverse.

    ``k_q = Q Q^T = U S^2 U^T`` (eigendecomposition), so ``S = sqrt(eigvals)``.
    Returns ``(W, W_pinv)``, both ``(D, D)`` symmetric PSD.
    """
    evals, u = jnp.linalg.eigh(k_q.astype(jnp.float32))
    evals = jnp.maximum(evals, 0.0)
    s = jnp.sqrt(evals)
    w = dot(u * s[None, :], u.T)
    s_inv = safe_inv_sqrt_spectrum(s, rel_eps)
    w_pinv = dot(u * s_inv[None, :], u.T)
    return w, w_pinv


def topk_eigvecs(m: jax.Array, d: int) -> jax.Array:
    """Top-``d`` eigenvectors (largest eigenvalues) of symmetric ``m: (D, D)``.

    Returns ``P: (d, D)`` with orthonormal rows, sorted by decreasing
    eigenvalue. ``d`` may equal D (full rotation, used by the flexible-d
    storage scheme of Section 3.1).
    """
    evals, vecs = jnp.linalg.eigh(m.astype(jnp.float32))  # ascending
    order = jnp.argsort(-evals)
    return vecs[:, order[:d]].T


def orthonormalize_rows(a: jax.Array) -> jax.Array:
    """Project ``a: (d, D)`` onto the Stiefel manifold St(D, d) (row-orthonormal)
    via the polar decomposition: argmin_{U in St} ||U - a||_F."""
    u, _, vt = jnp.linalg.svd(a, full_matrices=False)
    return dot(u, vt)


def polar(a: jax.Array) -> jax.Array:
    """Polar factor of ``a`` (same shape); the LMO direction over the unit
    spectral-norm ball (convex hull of the Stiefel manifold)."""
    u, _, vt = jnp.linalg.svd(a, full_matrices=False)
    return dot(u, vt)


def block_rows(bytes_per_row: int, budget: int = 1 << 28) -> int:
    """Rows per slice so that a ``bytes_per_row`` temporary stays within
    ``budget`` bytes (256 MiB by default)."""
    return max(256, budget // max(1, bytes_per_row))


def _slices(xs, n: int, block: int, b):
    """Slice ``b`` of every array in ``xs``: rows ``[start, start+block)``
    with the last slice clamped to end at ``n``."""
    start = jnp.minimum(b * block, n - block)
    return start, [jax.lax.dynamic_slice_in_dim(x, start, block) for x in xs]


def map_row_blocks(fn, xs, block: int):
    """``fn(*slices)`` over consecutive ``block``-row slices of the arrays
    ``xs`` (one leading length ``n``), written back in place, so that
    ``fn``'s temporaries are O(block) whatever ``n`` is. The last slice is
    clamped to end at ``n``; the rows it shares with the previous one are
    recomputed identically. ``n <= block`` is one plain call."""
    n = xs[0].shape[0]
    if n <= block:
        return fn(*xs)
    shapes = jax.eval_shape(fn, *(x[:block] for x in xs))
    out = jax.tree.map(lambda s: jnp.zeros((n,) + s.shape[1:], s.dtype),
                       shapes)

    def body(b, out):
        start, sl = _slices(xs, n, block, b)
        return jax.tree.map(
            lambda o, r: jax.lax.dynamic_update_slice_in_dim(o, r, start, 0),
            out, fn(*sl))

    return jax.lax.fori_loop(0, -(-n // block), body, out)


def fold_row_blocks(fn, init, xs, block: int):
    """Accumulate ``carry = fn(carry, *slices, fresh)`` over consecutive
    ``block``-row slices of ``xs``. ``fresh (rows,) bool`` marks the rows
    no earlier slice covered (the clamped last slice overlaps its
    predecessor), so sums count every row once. ``n <= block`` is one
    call over all rows."""
    n = xs[0].shape[0]
    if n <= block:
        return fn(init, *xs, jnp.ones((n,), bool))

    def body(b, carry):
        start, sl = _slices(xs, n, block, b)
        fresh = start + jnp.arange(block) >= b * block
        return fn(carry, *sl, fresh)

    return jax.lax.fori_loop(0, -(-n // block), body, init)
