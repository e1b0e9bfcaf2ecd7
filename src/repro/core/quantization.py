"""Scalar quantization of reduced database vectors (paper Section 3: "we could
apply scalar quantization to the database vectors Bx ... as in LeanVec").

PER-DIMENSION affine int8: sphering-reduced vectors are strongly anisotropic
(leading principal dims carry most variance), so per-vector ranges (LVQ on
raw data) destroy the low-variance dims -- measured 10-recall@10 collapse
from 0.99 to 0.14 on the laion twin. Per-dimension scales keep every dim at
8-bit resolution AND fold into the query:

    <q, u * delta + lo> = <q * delta, u> + <q, lo>

so the fused kernel (kernels/sq_dot) is a pure int8 matmul with a
query-side pre-scale -- zero extra work per database byte.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.linalg import dot

__all__ = ["SQDatabase", "ClusteredSQDatabase", "quantize",
           "quantize_per_cluster", "cluster_ranges", "cluster_steps",
           "quantize_rows", "dequantize", "quantized_inner_products"]


class SQDatabase(NamedTuple):
    codes: jax.Array   # (n, d) uint8 codes
    lo: jax.Array      # (d,) per-dimension lower bound
    delta: jax.Array   # (d,) per-dimension step

    @property
    def bits(self) -> int:
        return 8


def quantize(x: jax.Array, bits: int = 8,
             valid: jax.Array = None) -> SQDatabase:
    """Per-dimension affine quantization to ``bits`` (<=8) levels.

    ``valid`` ((n,) bool, optional) restricts the (lo, hi) range fit to
    the marked rows -- streaming stores quantize fixed-capacity arrays
    whose dead/padding rows must not stretch the scales. Codes are still
    produced for every row (out-of-range rows clip)."""
    levels = (1 << bits) - 1
    if valid is None:
        lo = jnp.min(x, axis=0)
        hi = jnp.max(x, axis=0)
    else:
        v = valid[:, None]
        lo = jnp.min(jnp.where(v, x, jnp.inf), axis=0)
        hi = jnp.max(jnp.where(v, x, -jnp.inf), axis=0)
        lo = jnp.where(jnp.isfinite(lo), lo, 0.0)   # no valid rows at all
        hi = jnp.where(jnp.isfinite(hi), hi, 0.0)
    delta = jnp.maximum(hi - lo, 1e-12) / levels
    codes = jnp.clip(jnp.round((x - lo[None, :]) / delta[None, :]), 0,
                     levels).astype(jnp.uint8)
    return SQDatabase(codes=codes, lo=lo, delta=delta)


class ClusteredSQDatabase(NamedTuple):
    codes: jax.Array   # (n, d) uint8 codes
    lo: jax.Array      # (C, d) per-cluster per-dimension lower bound
    delta: jax.Array   # (C, d) per-cluster per-dimension step


def cluster_ranges(x: jax.Array, tags: jax.Array, n_clusters: int,
                   valid: jax.Array = None):
    """Raw per-cluster per-dimension (min, max) of ``x``; empty clusters
    hold the +-inf identities, so ranges of row slices combine with a plain
    ``minimum`` / ``maximum``. ``valid`` ((n,) bool, optional) excludes rows
    from the fit."""
    x = x.astype(jnp.float32)
    x_lo, x_hi = x, x
    if valid is not None:
        x_lo = jnp.where(valid[:, None], x, jnp.inf)
        x_hi = jnp.where(valid[:, None], x, -jnp.inf)
    return (jax.ops.segment_min(x_lo, tags, num_segments=n_clusters),
            jax.ops.segment_max(x_hi, tags, num_segments=n_clusters))


def cluster_steps(lo: jax.Array, hi: jax.Array, bits: int = 8):
    """(lo, delta) of :func:`cluster_ranges` output: an empty cluster (the
    +-inf sentinels) falls back to the zero range."""
    empty = ~jnp.isfinite(lo)
    lo = jnp.where(empty, 0.0, lo)
    hi = jnp.where(~jnp.isfinite(hi), 0.0, hi)
    return lo, jnp.maximum(hi - lo, 1e-12) / ((1 << bits) - 1)


def quantize_rows(x: jax.Array, tags: jax.Array, lo: jax.Array,
                  delta: jax.Array, bits: int = 8) -> jax.Array:
    """u8 codes of rows ``x`` under their clusters' (lo, delta)."""
    levels = (1 << bits) - 1
    return jnp.clip(jnp.round((x.astype(jnp.float32) - lo[tags])
                              / delta[tags]), 0, levels).astype(jnp.uint8)


def quantize_per_cluster(x: jax.Array, tags: jax.Array, n_clusters: int,
                         bits: int = 8,
                         valid: jax.Array = None) -> ClusteredSQDatabase:
    """Per-cluster per-dimension affine quantization (the GleanVec ∘ SQ
    composition): each cluster's B_c x vectors get their own (lo, delta)
    per dimension, so anisotropy WITHIN a cluster is preserved at full
    8-bit resolution and the scales still fold into the per-cluster query
    views A_c q.

    ``valid`` ((n,) bool, optional) excludes rows from the per-cluster
    range fit (dead / padding rows of streaming stores); their codes are
    still produced (clipped). A cluster with no valid rows falls into the
    existing empty-cluster guard."""
    lo, delta = cluster_steps(*cluster_ranges(x, tags, n_clusters, valid),
                              bits)
    return ClusteredSQDatabase(codes=quantize_rows(x, tags, lo, delta, bits),
                               lo=lo, delta=delta)


def dequantize(db: SQDatabase) -> jax.Array:
    return db.codes.astype(jnp.float32) * db.delta[None, :] + db.lo[None, :]


def quantized_inner_products(query: jax.Array, db: SQDatabase) -> jax.Array:
    """<q, dequant(x)> without materializing the dequantized matrix.

    ``query (d,)`` -> scores ``(n,)``.
    """
    q_scaled = query * db.delta
    return (dot(db.codes.astype(jnp.float32), q_scaled)
            + dot(query, db.lo))
