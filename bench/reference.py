"""The plain reference and the comparison that decides ``correct``.

The reference is exact top-k by inner product over the full-precision
rows, in plain ``jnp`` at ``precision="highest"``, computed on the device
in blocks of queries and rows. It imports nothing of the program. Over
rows kept in host memory (``exact_topk_host``, ``scores_of_host``) each
block of rows crosses to the device once, scored against every query.

The comparison reads, for every checked request, the reference's top-10
and the exact scores of the ten ids the served path returned:

* ``miss10``: the share of the reference's top-10 missing from the
  answers (1 - recall@10), over all checked requests;
* ``worst_gap``: the widest gap, over the checked requests, by which the
  lowest exact score among an answer's ids lies below the reference's
  10th best, as a share of the reference's best score. An answer with a
  missing (-1), out-of-range or repeated id reads infinity.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


@functools.partial(jax.jit, static_argnames=("k", "block", "precision"))
def _topk_rows(q, x, k: int, block: int, precision):
    """Exact top-``k`` of ``q @ x.T`` scanning ``x`` in row blocks; rows
    past the last whole block are scored in one tail block."""
    n = x.shape[0]
    whole = n // block

    def score(rows, start):
        s = jnp.matmul(q, rows.T, precision=precision)
        v, i = jax.lax.top_k(s, k)
        return v, i + start

    def merge(best, new):
        v = jnp.concatenate([best[0], new[0]], axis=1)
        i = jnp.concatenate([best[1], new[1]], axis=1)
        v, sel = jax.lax.top_k(v, k)
        return v, jnp.take_along_axis(i, sel, axis=1)

    def body(best, b):
        # the barrier keeps XLA from hoisting a precision conversion of
        # the whole store out of the loop (a second copy of x)
        rows = jax.lax.optimization_barrier(
            jax.lax.dynamic_slice_in_dim(x, b * block, block, axis=0))
        return merge(best, score(rows, b * block)), None

    init = (jnp.full((q.shape[0], k), -jnp.inf, jnp.float32),
            jnp.full((q.shape[0], k), -1, jnp.int32))
    best, _ = jax.lax.scan(body, init, jnp.arange(whole))
    if n % block:
        best = merge(best, score(x[whole * block:], whole * block))
    return best


def exact_topk(queries: np.ndarray, x: jax.Array, k: int = 10,
               query_block: int = 1024, row_block: int = 65536,
               precision=HIGHEST):
    """Exact top-``k`` (scores, ids) of every query, best first."""
    row_block = min(row_block, x.shape[0])
    vals, ids = [], []
    for s in range(0, len(queries), query_block):
        q = np.asarray(queries[s:s + query_block], np.float32)
        pad = query_block - len(q)          # one compiled shape per run
        q = np.pad(q, ((0, pad), (0, 0)))
        v, i = _topk_rows(jnp.asarray(q), x, k, row_block, precision)
        vals.append(np.asarray(v)[:len(q) - pad])
        ids.append(np.asarray(i)[:len(q) - pad])
    return np.concatenate(vals), np.concatenate(ids)


@jax.jit
def _scores_of(q, x, ids):
    rows = x[jnp.clip(ids, 0, x.shape[0] - 1)]
    return jnp.einsum("mkd,md->mk", rows, q, precision=HIGHEST)


def scores_of(queries: np.ndarray, x: jax.Array, ids: np.ndarray,
              block: int = 1024) -> np.ndarray:
    """Exact scores of given ids (clipped into range; the comparison
    rejects out-of-range ids itself)."""
    out = []
    for s in range(0, len(queries), block):
        q, i = queries[s:s + block], ids[s:s + block]
        pad = block - len(q)
        q = np.pad(np.asarray(q, np.float32), ((0, pad), (0, 0)))
        i = np.pad(np.asarray(i, np.int32), ((0, pad), (0, 0)))
        out.append(np.asarray(_scores_of(jnp.asarray(q), x,
                                         jnp.asarray(i)))[:len(q) - pad])
    return np.concatenate(out)


# -- a store kept in host memory ------------------------------------------

@functools.partial(jax.jit, static_argnames=("k", "precision"))
def fold_rows(best, q, rows, start, n, k: int, precision):
    """``best`` (values, ids) merged, as ``_topk_rows`` merges its blocks,
    with the top-``k`` of ``q @ rows.T``: the block of host rows that
    starts at row ``start``; its rows from ``n`` on are padding."""
    s = jnp.matmul(q, rows.T, precision=precision)
    ids = start + jnp.arange(rows.shape[0], dtype=jnp.int32)
    v, i = jax.lax.top_k(jnp.where(ids < n, s, -jnp.inf), k)
    v = jnp.concatenate([best[0], v], axis=1)
    i = jnp.concatenate([best[1], i + start], axis=1)
    v, sel = jax.lax.top_k(v, k)
    return v, jnp.take_along_axis(i, sel, axis=1)


def exact_topk_host(queries: np.ndarray, x: np.ndarray, k: int = 10,
                    query_block: int = 1024, row_block: int = 65536,
                    precision=HIGHEST):
    """:func:`exact_topk` over rows in host memory. Every query is scored
    at once (padded to whole ``query_block``s), and each block of
    ``row_block`` rows goes to the device once: the store crosses once."""
    n = x.shape[0]
    row_block = min(row_block, n)
    m = len(queries)
    q = np.asarray(queries, np.float32)
    q = jnp.asarray(np.pad(q, ((0, -m % query_block), (0, 0))))
    best = (jnp.full((q.shape[0], k), -jnp.inf, jnp.float32),
            jnp.full((q.shape[0], k), -1, jnp.int32))
    for start in range(0, n, row_block):
        rows = x[start:start + row_block]
        if len(rows) < row_block:           # one compiled shape per run
            rows = np.pad(rows, ((0, row_block - len(rows)), (0, 0)))
        best = fold_rows(best, q, rows, np.int32(start), np.int32(n), k,
                         precision)
    return np.asarray(best[0])[:m], np.asarray(best[1])[:m]


@jax.jit
def scores_of_rows(q, rows):
    """Exact scores of each query's given rows, ``(m, k, D)``."""
    return jnp.einsum("mkd,md->mk", rows, q, precision=HIGHEST)


def scores_of_host(queries: np.ndarray, x: np.ndarray, ids: np.ndarray,
                   block: int = 1024) -> np.ndarray:
    """:func:`scores_of` over rows in host memory: the answered rows are
    gathered on the host."""
    out = []
    for s in range(0, len(queries), block):
        q, i = queries[s:s + block], ids[s:s + block]
        pad = block - len(q)
        q = np.pad(np.asarray(q, np.float32), ((0, pad), (0, 0)))
        i = np.pad(np.asarray(i, np.int64), ((0, pad), (0, 0)))
        rows = x[np.clip(i, 0, x.shape[0] - 1)]
        out.append(np.asarray(scores_of_rows(jnp.asarray(q),
                                             rows))[:len(q) - pad])
    return np.concatenate(out)


class Comparison(NamedTuple):
    miss10: float
    worst_gap: float
    recall10: float
    checked: int


def compare(answers: np.ndarray, ans_scores: np.ndarray,
            ref_ids: np.ndarray, ref_scores: np.ndarray,
            n_rows: int) -> Comparison:
    """The numbers ``correct`` is decided on (see the module docstring).
    ``answers`` (m, 10) served ids; ``ans_scores`` their exact scores;
    ``ref_ids`` / ``ref_scores`` (m, 10) the reference's, best first."""
    answers = np.asarray(answers)
    m, k = ref_ids.shape
    hits = np.array([len(set(a.tolist()) & set(r.tolist()))
                     for a, r in zip(answers, ref_ids)], np.float64)
    recall = float(hits.sum() / (m * k)) if m else 0.0
    valid = np.array([a.shape[0] == k and len(set(a.tolist())) == k
                      and a.min() >= 0 and a.max() < n_rows
                      for a in answers], bool)
    scale = np.maximum(np.abs(ref_scores[:, 0]), 1e-30)
    gap = (ref_scores[:, k - 1] - ans_scores.min(axis=1)) / scale
    gap = np.where(valid, np.maximum(gap, 0.0), np.inf)
    return Comparison(miss10=1.0 - recall,
                      worst_gap=float(gap.max()) if m else np.inf,
                      recall10=recall, checked=int(m))
