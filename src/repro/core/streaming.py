"""Streaming vector search support (paper Section 3.2), bridged to the
whole scorer zoo and the state-passing serving engine.

Moment tracking (the paper's math)
----------------------------------
Maintains the D x D summary statistics

    K_Q(t) = sum_{q in Q_t} q q^T,   K_X(t) = sum_{x in X_t} x x^T

under vector insertions/removals (rank-1 updates, Eq. 11), refreshes the
projections every ``s`` updates by eigendecomposition (replacing the SVDs of
Algorithm 2), and re-projects stored database vectors with the transition
matrix  T = P_{t+1} W_{t+1} (P_t W_t)^{-1}  (Eq. 12) -- either eagerly over
the whole store or lazily on access (``pending`` mask). For the GleanVec
family the SAME machinery runs per cluster: ``k_x`` holds the (C, D, D)
per-cluster moments (the k-means landmarks stay fixed under streaming, so
inserts are tagged by the existing centers), ``refresh`` re-runs the
per-cluster fits through :func:`repro.core.gleanvec.fit_from_moments`, and
the transition matrix becomes a (C, d, d) stack applied per tag.

Serving bridge (the state-passing contract)
-------------------------------------------
:func:`build_streaming_artifacts` builds a FIXED-CAPACITY
:class:`~repro.core.search.SearchArtifacts`: row arrays pre-allocated to
``capacity`` with a ``live`` slot mask (row-aligned scorers) or free
padding slots inside each cluster's single-tag blocks (sorted scorers), so
that :func:`insert_rows` / :func:`remove_rows` and
:func:`refresh_artifacts` all preserve every leaf shape AND the pytree
treedef -- the invariants :meth:`repro.serve.engine.ServingEngine.swap`
checks before installing a new state with zero recompiles. The lifecycle
the ``--stream`` demo drives:

    observe_queries -> insert/insert_rows -> refresh -> refresh_artifacts
        -> refresh_state -> engine.swap

Functional style: every operation returns a new state (JAX arrays are
immutable); the launcher owns the loop.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gleanvec as gv
from repro.core import linalg
from repro.core import rerank_tier
from repro.core import scorer as sc
from repro.core.gleanvec import GleanVecModel
from repro.core.leanvec_sphering import SpheringModel, fit_from_moments
from repro.core.search import SearchArtifacts, ServingState

__all__ = ["StreamingState", "init", "init_gleanvec", "init_from_artifacts",
           "insert", "remove", "observe_queries", "needs_refresh",
           "refresh", "transition_matrix", "transition_condition",
           "reproject", "build_streaming_artifacts", "live_mask",
           "free_ids", "insert_rows", "remove_rows", "refresh_artifacts",
           "refresh_state"]


class StreamingState(NamedTuple):
    """Running moments + current model. ``k_x`` is (D, D) for the linear
    (LeanVec-Sphering) family and (C, D, D) -- one moment per cluster --
    for the GleanVec family; ``model`` is the matching
    :class:`SpheringModel` / :class:`GleanVecModel` and ``prev_bw`` the
    (d, D) or (C, d, D) database projection(s) at the last refresh (the
    denominator of Eq. 12)."""

    k_q: jax.Array            # (D, D) query second moment
    k_x: jax.Array            # (D, D) or (C, D, D) database second moment
    model: Union[SpheringModel, GleanVecModel]
    prev_bw: jax.Array        # (d, D) or (C, d, D) B = P W at last refresh
    updates_since: jax.Array  # scalar int32: updates since last refresh
    refresh_every: int        # s


def _per_cluster(state: StreamingState) -> bool:
    """GleanVec streaming tracks one K_X per cluster (static branch)."""
    return state.k_x.ndim == 3


def _assign(model, rows: jax.Array) -> jax.Array:
    return gv.assign_tags(model, rows)


def init(k_q: jax.Array, k_x: jax.Array, d: int,
         refresh_every: int = 1024) -> StreamingState:
    """Linear (LeanVec-Sphering) streaming state, model fit from moments."""
    model = fit_from_moments(k_q, k_x, d)
    return StreamingState(k_q=k_q, k_x=k_x, model=model, prev_bw=model.b,
                          updates_since=jnp.zeros((), jnp.int32),
                          refresh_every=refresh_every)


def init_gleanvec(model: GleanVecModel, k_q: jax.Array,
                  k_x_per_cluster: jax.Array,
                  refresh_every: int = 1024) -> StreamingState:
    """GleanVec streaming state around an ALREADY-FIT model (the landmarks
    and per-cluster projections serving right now): the first refresh's
    transition is measured against this model's B_c."""
    return StreamingState(k_q=k_q, k_x=k_x_per_cluster, model=model,
                          prev_bw=model.b,
                          updates_since=jnp.zeros((), jnp.int32),
                          refresh_every=refresh_every)


def init_from_artifacts(artifacts: SearchArtifacts, queries: jax.Array,
                        refresh_every: int = 1024) -> StreamingState:
    """Bootstrap the moments from a serving store: K_Q from the learning /
    observed queries, K_X from the store's LIVE full-precision rows
    (per-cluster for GleanVec models), model taken as-is so the first
    Eq. 12 transition is relative to what is currently serving."""
    model = artifacts.model
    if model is None:
        raise ValueError("mode 'full' stores raw vectors; there is no DR "
                         "model to stream (refresh is the identity)")
    k_q = linalg.second_moment(jnp.asarray(queries, jnp.float32))
    rows = artifacts.x_full[np.nonzero(live_mask(artifacts))[0]]
    if isinstance(model, GleanVecModel):
        tags = _assign(model, rows)
        k_x = gv.per_cluster_moments(rows, tags, model.n_clusters)
        return init_gleanvec(model, k_q, k_x, refresh_every)
    return StreamingState(k_q=k_q, k_x=linalg.second_moment(rows),
                          model=model, prev_bw=model.b,
                          updates_since=jnp.zeros((), jnp.int32),
                          refresh_every=refresh_every)


def insert(state: StreamingState, x: jax.Array) -> StreamingState:
    """X_t = X_{t-1} u {x}: rank-1 update of K_X (Eq. 11). ``x`` may be a
    single (D,) vector or a (b, D) batch; GleanVec states route each row's
    outer product to its cluster's moment."""
    x2d = jnp.atleast_2d(jnp.asarray(x, jnp.float32))
    if _per_cluster(state):
        tags = _assign(state.model, x2d)
        delta = gv.per_cluster_moments(x2d, tags, state.k_x.shape[0])
    else:
        delta = linalg.second_moment(x2d)
    return state._replace(k_x=state.k_x + delta,
                          updates_since=state.updates_since + x2d.shape[0])


def remove(state: StreamingState, x: jax.Array) -> StreamingState:
    """X_t = X_{t-1} \\ {x}: rank-1 downdate of K_X (Eq. 11)."""
    x2d = jnp.atleast_2d(jnp.asarray(x, jnp.float32))
    if _per_cluster(state):
        tags = _assign(state.model, x2d)
        delta = gv.per_cluster_moments(x2d, tags, state.k_x.shape[0])
    else:
        delta = linalg.second_moment(x2d)
    return state._replace(k_x=state.k_x - delta,
                          updates_since=state.updates_since + x2d.shape[0])


def observe_queries(state: StreamingState, q: jax.Array) -> StreamingState:
    """Fold a batch of observed queries into K_Q (Q_t evolves over time)."""
    return state._replace(k_q=state.k_q
                          + linalg.second_moment(jnp.asarray(q,
                                                             jnp.float32)))


def needs_refresh(state: StreamingState) -> jax.Array:
    return state.updates_since >= state.refresh_every


def refresh(state: StreamingState) -> StreamingState:
    """Recompute W, P (per cluster for GleanVec) from the current moments
    (s | t boundary); the outgoing model's B becomes ``prev_bw``."""
    d = state.model.dim
    if _per_cluster(state):
        new_model = gv.fit_from_moments(state.model.centers, state.k_q,
                                        state.k_x, d)
    else:
        new_model = fit_from_moments(state.k_q, state.k_x, d)
    return state._replace(model=new_model, prev_bw=state.model.b,
                          updates_since=jnp.zeros((), jnp.int32))


def transition_matrix(state: StreamingState) -> jax.Array:
    """T = P_{t'} W_{t'} (P_{t-1} W_{t-1})^+  (Eq. 12): (d, d), or the
    (C, d, d) per-cluster stack for GleanVec states.

    Valid exactly when d == D (full rotation storage, Section 3.1); for d < D
    it is the least-squares re-projection onto the new basis.
    """
    prev = state.prev_bw
    new = state.model.b
    if prev.ndim == 3:
        return jax.vmap(
            lambda nw, pv: linalg.dot(nw, jnp.linalg.pinv(pv)))(new, prev)
    return linalg.dot(new, jnp.linalg.pinv(prev))


def transition_condition(state: StreamingState) -> float:
    """Condition number of the Eq. 12 denominator B_prev = P_{t-1} W_{t-1}
    (max over clusters for GleanVec states): sigma_max / sigma_min of the
    (d, D) projection whose pseudo-inverse the transition solve applies.

    The ``pinv`` amplifies stored-vector noise by ~this factor, so a
    near-dead cluster (its moment collapsed onto a subspace -> a tiny
    trailing singular value) makes ``source="stored"`` reprojection
    garbage while ``source="full"`` re-encoding stays exact -- the
    escalation signal :class:`repro.serve.lifecycle.RefreshSupervisor`
    keys on. Returns ``inf`` for a singular solve and ``nan`` for
    non-finite inputs; callers should escalate unless the value is
    finite AND below their threshold.
    """
    prev = jnp.asarray(state.prev_bw, jnp.float32)
    if not bool(jnp.all(jnp.isfinite(prev))):
        return float("nan")
    s = jnp.linalg.svd(prev, compute_uv=False)       # (..., min(d, D))
    smax = jnp.max(s, axis=-1)
    smin = jnp.min(s, axis=-1)
    cond = jnp.where(smin > 0, smax / smin, jnp.inf)
    return float(jnp.max(cond))


def reproject(state: StreamingState, x_low: jax.Array,
              tags: Optional[jax.Array] = None,
              pending: Optional[jax.Array] = None) -> jax.Array:
    """Apply Eq. (12) to stored reduced vectors. GleanVec states need the
    rows' cluster ``tags`` (row i maps through T_{tags_i}); ``pending``
    selects lazy subsets -- unmarked rows keep their old projection."""
    t = transition_matrix(state)
    if t.ndim == 3:
        if tags is None:
            raise ValueError("per-cluster reprojection needs the rows' "
                             "cluster tags")
        new = jnp.einsum("nij,nj->ni", t[tags], x_low, precision=linalg.F32)
    else:
        new = linalg.dot(x_low, t.T)
    if pending is None:
        return new
    return jnp.where(pending[:, None], new, x_low)


# ---------------------------------------------------------------------------
# Serving bridge: fixed-capacity stores, row-level updates, state refresh.
# ---------------------------------------------------------------------------


_SORTED_MODES = ("gleanvec-sorted", "gleanvec-int8-sorted")


def build_streaming_artifacts(mode: str, database: jax.Array, model=None,
                              capacity: Optional[int] = None,
                              sort_block: int = 4096,
                              slack_blocks: int = 1,
                              host_rerank: bool = False) -> SearchArtifacts:
    """Fixed-capacity artifacts for any serving mode (see ``scorer.MODES``).

    Row-aligned modes pre-allocate ``capacity`` rows (the spare slots are
    filled with copies of row 0 so scale fits and tags stay sane, and
    masked dead via the scorer's ``live`` mask); sorted modes build the
    layout over the live rows with ``slack_blocks`` extra free blocks per
    cluster and a capacity-sized ``inv_perm``. Either way every later
    ``insert_rows`` / ``remove_rows`` / ``refresh_artifacts`` preserves
    leaf shapes and the treedef, so the serving engine swaps the result in
    without recompiling.

    ``host_rerank`` demotes the capacity-sized full-precision store to the
    host tier (:mod:`repro.core.rerank_tier`): the reduced serving
    representation keeps its device placement, while inserts/removes/
    refreshes update the host store through the same ``.at[ids].set`` /
    indexing surface -- a host-tier streamed store swaps with zero
    recompiles exactly like a device one.
    """
    X = jnp.asarray(database, jnp.float32)
    n0, _ = X.shape
    capacity = n0 if capacity is None else capacity
    if capacity < n0:
        raise ValueError(f"capacity {capacity} < initial rows {n0}")
    fill = jnp.broadcast_to(X[0], (capacity - n0, X.shape[1]))
    x_cap = jnp.concatenate([X, fill], axis=0)
    if mode in _SORTED_MODES:
        if mode == "gleanvec-sorted":
            scorer = sc.sorted_gleanvec_scorer(model, X, block=sort_block,
                                               slack_blocks=slack_blocks)
        else:
            scorer = sc.sorted_gleanvec_quantized_scorer(
                model, X, block=sort_block, slack_blocks=slack_blocks)
        pad = jnp.full((capacity - n0,), -1, scorer.inv_perm.dtype)
        scorer = scorer._replace(
            inv_perm=jnp.concatenate([scorer.inv_perm, pad]))
    else:
        scorer = sc.build_scorer(mode, x_cap, model, block=sort_block)
        live = jnp.arange(capacity) < n0
        scorer = scorer._replace(live=live)
    x_full = rerank_tier.demote(x_cap) if host_rerank else x_cap
    return SearchArtifacts(scorer=scorer, x_full=x_full, model=model)


def live_mask(artifacts: SearchArtifacts) -> np.ndarray:
    """(capacity,) bool over EXTERNAL ids: which slots hold a live vector."""
    s = artifacts.scorer
    if hasattr(s, "inv_perm"):
        return np.asarray(s.inv_perm) >= 0
    if getattr(s, "live", None) is not None:
        return np.asarray(s.live)
    return np.ones(s.n_rows, bool)


def free_ids(artifacts: SearchArtifacts, count: int) -> np.ndarray:
    """First ``count`` free external ids of a fixed-capacity store."""
    free = np.nonzero(~live_mask(artifacts))[0]
    if free.size < count:
        raise ValueError(f"store full: {free.size} free slots < {count}")
    return free[:count].astype(np.int32)


def insert_rows(artifacts: SearchArtifacts, rows: jax.Array,
                ids: Optional[jax.Array] = None):
    """Insert full-D ``rows`` into free slots of a fixed-capacity store
    (scorer representation + full-precision rerank store together).
    Returns ``(artifacts', ids)`` -- same treedef, same leaf shapes."""
    rows = jnp.atleast_2d(jnp.asarray(rows, jnp.float32))
    if ids is None:
        ids = free_ids(artifacts, rows.shape[0])
    ids = jnp.asarray(ids, jnp.int32)
    scorer = artifacts.scorer.insert_rows(ids, rows, artifacts.model)
    return (artifacts._replace(scorer=scorer,
                               x_full=artifacts.x_full.at[ids].set(rows)),
            ids)


def remove_rows(artifacts: SearchArtifacts,
                ids: jax.Array) -> SearchArtifacts:
    """Tombstone external ``ids``: they stop scoring / serving; their
    slots become insertable again."""
    return artifacts._replace(
        scorer=artifacts.scorer.remove_rows(jnp.asarray(ids, jnp.int32)))


def refresh_artifacts(artifacts: SearchArtifacts,
                      state: Optional[StreamingState],
                      source: str = "stored",
                      pending: Optional[jax.Array] = None
                      ) -> SearchArtifacts:
    """Re-encode the serving representation under ``state``'s refreshed
    model, emitting SAME-TREEDEF artifacts the engine can swap in.

    ``source="stored"`` is the paper's streaming path: the stored reduced
    vectors (dequantized first for the int8 families) map through the
    Eq. 12 transition matrix -- per cluster for GleanVec -- and the int8 /
    sorted representations are re-coded from the result with freshly
    fitted scales over the live rows; ``pending`` restricts the
    reprojection to the marked external ids (lazy refresh). With
    ``source="full"`` the representation re-encodes exactly from the
    full-precision ``x_full`` store instead (no Eq. 12 approximation; uses
    the rerank store the serving path already holds).

    ``state=None`` (or a model-free store, mode "full") returns the
    artifacts unchanged.
    """
    if state is None or artifacts.model is None:
        return artifacts
    if source not in ("stored", "full"):
        raise ValueError(f"unknown refresh source {source!r}")
    transition = transition_matrix(state) if source == "stored" else None
    x_full = artifacts.x_full if source == "full" else None
    scorer = artifacts.scorer.refresh(state.model, transition=transition,
                                      x_full=x_full, pending=pending)
    return artifacts._replace(scorer=scorer, model=state.model)


def refresh_state(serving: ServingState, state: Optional[StreamingState],
                  source: str = "stored",
                  pending: Optional[jax.Array] = None) -> ServingState:
    """Whole-state refresh: artifacts re-encoded AND the index's derived
    representations (IVF reduced-space centers) re-projected through the
    Index protocol's ``refreshed`` hook. The result has the same treedef
    and leaf avals as ``serving`` -- hand it to ``engine.swap``."""
    artifacts = refresh_artifacts(serving.artifacts, state, source=source,
                                  pending=pending)
    index = serving.index
    if hasattr(index, "refreshed"):
        index = index.refreshed(artifacts.scorer, artifacts.model)
    return serving._replace(artifacts=artifacts, index=index)
