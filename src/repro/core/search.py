"""Multi-step vector search (paper Algorithm 1), index-agnostic.

The main search runs in the compressed representation through any Index
protocol implementation (flat scan / IVF / graph / sharded placement from
``repro.index``, see :mod:`repro.index.protocol`) over the unified Scorer
protocol (:mod:`repro.core.scorer`); the postprocessing step re-ranks the
kappa candidates with full-precision inner products. With the flexible-d
storage of Section 3.1 (full rotation P'), the rerank uses the *same*
stored vectors (Eq. 10) -- no secondary database; the artifacts record the
query-side rotation explicitly (``rerank_a``) instead of inferring it from
model types, so no isinstance dispatch remains anywhere on the search path.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import linalg, rerank_tier
from repro.core import scorer as sc
from repro.index.topk import NEG_INF

__all__ = ["SearchArtifacts", "ServingState", "build_artifacts",
           "build_artifacts_sphering", "build_artifacts_gleanvec",
           "make_state", "state_search", "state_candidates",
           "multi_step_search", "rerank", "rerank_candidates", "host_tier",
           "demote_rerank_tier", "promote_rerank_tier"]


class SearchArtifacts(NamedTuple):
    """Everything the serving path needs, already reduced/encoded.

    ``scorer``: any Scorer-protocol implementation (main-search side);
    ``x_full``: (n, D) full-precision vectors for reranking (or the (n, D)
    rotated x' of Section 3.1 -- reranking is exact either way);
    ``rerank_a``: optional (D, D) query rotation for the rerank step (set
    when ``x_full`` stores rotated vectors, Eq. 10); ``model``: the learned
    DR model, kept for encode/refresh bookkeeping only -- the search path
    never inspects its type.
    """

    scorer: Any
    x_full: jax.Array
    rerank_a: Optional[jax.Array] = None
    model: Any = None

    @property
    def x_low(self):
        """Reduced database of float scorers (None for int8 scorers)."""
        return getattr(self.scorer, "x_low", None)

    @property
    def tags(self):
        """Cluster tags of GleanVec scorers (None for linear ones)."""
        return getattr(self.scorer, "tags", None)


def build_artifacts_sphering(model, database: jax.Array,
                             use_rotated_full: bool = True
                             ) -> SearchArtifacts:
    """Linear path. With ``use_rotated_full`` the full vectors are stored as
    x' = P'Wx (requires d == D model; Section 3.1) so the reduced view is a
    prefix of the stored vector and the rerank rotates queries by A'."""
    scorer = sc.linear_scorer(model, database)
    if use_rotated_full and model.dim == database.shape[1]:
        # x' = B'x; reduced view = prefix of x'; rerank query q' = A'q.
        return SearchArtifacts(scorer=scorer, x_full=scorer.x_low,
                               rerank_a=model.a, model=model)
    return SearchArtifacts(scorer=scorer, x_full=database, model=model)


def build_artifacts_gleanvec(model, database: jax.Array) -> SearchArtifacts:
    return SearchArtifacts(scorer=sc.gleanvec_scorer(model, database),
                           x_full=database, model=model)


def build_artifacts(mode: str, database: jax.Array,
                    model=None) -> SearchArtifacts:
    """Mode-string construction covering every scorer (see ``scorer.MODES``):
    full / sphering / gleanvec / sphering-int8 / gleanvec-int8 /
    gleanvec-sorted / gleanvec-int8-sorted.

    A host store (``rerank_tier.is_host``: a read-only numpy array or a
    ``HostStore``) stays where it is: the gleanvec scorers stream it to
    the device a chunk at a time, and ``x_full`` is a ``HostStore`` over
    the caller's array (float32 rows are not copied), so the artifacts
    are already demoted (:func:`demote_rerank_tier` returns them as they
    are)."""
    if isinstance(database, rerank_tier.HostStore):
        x_full = database
    elif rerank_tier.is_host(database):
        x_full = rerank_tier.HostStore(np.asarray(database, np.float32))
    else:
        x_full = jnp.asarray(database, jnp.float32)
    return SearchArtifacts(scorer=sc.build_scorer(mode, database, model),
                           x_full=x_full, model=model)


class ServingState(NamedTuple):
    """The complete runtime state of a serving search, as ONE pytree.

    This is the state-passing serving contract (Section 3.2): instead of
    closing a jitted function over the artifacts, the artifacts -- and the
    Index-protocol traversal mounted over them -- ride through the
    compiled ``state_search(queries, state)`` as a regular argument. jit
    specializes on the state's TREEDEF (scorer/index classes, static index
    config) and leaf avals only, so any weight update that preserves both
    (a streaming refresh, a row insert into pre-allocated capacity, a
    re-quantization) swaps in with ZERO recompiles.

    ``version`` is a data leaf (scalar int32), not treedef metadata, so
    bumping it never invalidates the compiled function; it exists so
    engines / logs can tell which state generation produced a result.
    """

    artifacts: SearchArtifacts
    index: Any                # Index-protocol pytree (FlatIndex & friends)
    version: jax.Array        # scalar int32 state generation counter


def make_state(artifacts: SearchArtifacts, index=None, block: int = 4096,
               version: int = 0) -> ServingState:
    """Mount ``artifacts`` behind ``index`` (None = flat blocked scan) as a
    :class:`ServingState`."""
    from repro.index.protocol import FlatIndex

    if index is None:
        index = FlatIndex(block=block)
    return ServingState(artifacts=artifacts, index=index,
                        version=jnp.asarray(version, jnp.int32))


def state_search(queries: jax.Array, state: ServingState, k: int,
                 kappa: int) -> jax.Array:
    """Algorithm 1 over a :class:`ServingState`: the single function every
    serving surface compiles. ``k`` / ``kappa`` are static; everything
    else -- scorer weights, index arrays, the full-precision store -- is a
    pytree argument, so refreshed states reuse the compiled executable."""
    return multi_step_search(queries, state.artifacts, state.index, k,
                             kappa)


def state_candidates(queries: jax.Array, state: ServingState,
                     kappa: int) -> jax.Array:
    """First stage of the two-level pipeline: the main (reduced-space)
    search only, returning (m, kappa) ORIGINAL-id candidates and never
    touching ``x_full``. Fully traceable even when the rerank tier lives
    on host (the store is aux data with zero leaves), so this is the
    function serving engines compile when ``host_tier(artifacts)`` is
    set -- the host gather + :func:`rerank_candidates` run outside."""
    scorer = state.artifacts.scorer
    with jax.named_scope("search.prepare"):
        qstate = state.index.prepare_queries(scorer, queries)
    _, candidates = state.index.candidates(qstate, scorer, kappa)
    return candidates


def host_tier(artifacts: SearchArtifacts):
    """The artifacts' host-resident rerank store, or None when ``x_full``
    is a regular device array (single-level hierarchy)."""
    return rerank_tier.host_store(artifacts.x_full)


def demote_rerank_tier(artifacts: SearchArtifacts,
                       shards: int = 0) -> SearchArtifacts:
    """Demote the (n, D) full-precision store to host memory (sharded when
    ``shards > 0``), keeping the reduced codes -- the fine-scan working
    set -- in device memory. See :mod:`repro.core.rerank_tier`."""
    return artifacts._replace(
        x_full=rerank_tier.demote(artifacts.x_full, shards=shards))


def promote_rerank_tier(artifacts: SearchArtifacts) -> SearchArtifacts:
    """Undo :func:`demote_rerank_tier` (materializes all n rows in HBM)."""
    if host_tier(artifacts) is None:
        return artifacts
    return artifacts._replace(x_full=rerank_tier.promote(artifacts.x_full))


def _rerank_math(q_full: jax.Array, cand_vecs: jax.Array,
                 candidates: jax.Array, k: int) -> jax.Array:
    """Tier-agnostic core of the rerank: exact top-k among the gathered
    candidate rows. -1 candidate slots score NEG_INF, and ``top_k``'s
    stable tie-break keeps real ids ahead of equal-scoring padding, so a
    row with fewer than k live candidates pads its tail with -1 (never an
    arbitrary id)."""
    with jax.named_scope("search.rerank"):
        scores = jnp.einsum("mkd,md->mk", cand_vecs, q_full,
                            precision=linalg.F32)
        scores = jnp.where(candidates >= 0, scores, NEG_INF)
        top = jax.lax.top_k(scores, k)[1]                # (m, k)
        return jnp.take_along_axis(candidates, top, axis=1)


# The small second-stage program of the two-level pipeline: reranks the
# kappa prefetched rows after they land on device. Compiles once per
# (m, kappa, D, k) shape family and is shared by every engine/retrieval
# surface (module-level cache).
rerank_candidates = jax.jit(_rerank_math, static_argnames=("k",))


def _rotate_queries(queries: jax.Array, artifacts: SearchArtifacts):
    return queries if artifacts.rerank_a is None \
        else linalg.dot(queries, artifacts.rerank_a.T)


def rerank(queries: jax.Array, artifacts: SearchArtifacts,
           candidates: jax.Array, k: int):
    """Postprocessing (Alg. 1 line 3): exact top-k among candidates.

    ``candidates``: (m, kappa) ids; -1 entries (padded / unfilled slots
    from graph or sharded searches) never win. When x_full stores the
    rotated x' (Section 3.1), queries are rotated by ``rerank_a`` (Eq. 10).

    Two placements of the full-precision store:

    * device array (default): the gather happens in HBM and the whole
      rerank is traceable -- it inlines into the one compiled
      ``state_search``.
    * host tier (:func:`demote_rerank_tier`): only the kappa candidate
      rows per query cross host->device (``store.take`` then
      ``device_put``), and the top-k runs in the small compiled
      :func:`rerank_candidates` program. This path is host-driven and
      CANNOT run under a trace -- jit ``state_candidates`` instead and
      rerank outside (what :class:`repro.serve.engine.ServingEngine`'s
      pipelined submit does).
    """
    store = host_tier(artifacts)
    if store is None:
        with jax.named_scope("search.rerank"):
            safe = jnp.where(candidates >= 0, candidates, 0)
            cand_vecs = artifacts.x_full[safe]           # (m, kappa, D)
            q_full = _rotate_queries(queries, artifacts)
        return _rerank_math(q_full, cand_vecs, candidates, k)
    if isinstance(candidates, jax.core.Tracer):
        raise TypeError(
            "rerank over a host-tier x_full cannot run inside jit: the "
            "host gather is not traceable. Compile state_candidates and "
            "rerank the gathered rows outside the trace (see "
            "repro.serve.engine.ServingEngine).")
    cand_ids = np.asarray(candidates)
    cand_vecs = jax.device_put(store.take(cand_ids))     # kappa rows only
    return rerank_candidates(_rotate_queries(queries, artifacts), cand_vecs,
                             jnp.asarray(cand_ids), k)


def multi_step_search(queries: jax.Array, artifacts: SearchArtifacts,
                      index_search, k: int, kappa: int):
    """Algorithm 1 over any index and any scorer.

    ``index_search`` is an Index-protocol object (``FlatIndex`` /
    ``IVFIndex`` / ``GraphIndex`` / ``ShardedIndex`` -- anything with
    ``prepare_queries`` + ``candidates``): the main search runs
    ``index.candidates(index.prepare_queries(scorer, queries), scorer,
    kappa)`` and the resulting ORIGINAL-id candidates are reranked in full
    precision. A legacy callable ``index_search(q_low, artifacts, kappa)
    -> (m, kappa) ids`` is still accepted, where ``q_low`` is the scorer's
    prepared query state.

    ``kappa >= k`` trades accuracy for rerank cost.
    """
    scorer = artifacts.scorer
    if hasattr(index_search, "candidates"):     # Index protocol
        with jax.named_scope("search.prepare"):
            qstate = index_search.prepare_queries(scorer, queries)
        _, candidates = index_search.candidates(qstate, scorer, kappa)
    else:                                       # legacy callable
        with jax.named_scope("search.prepare"):
            q_low = scorer.prepare_queries(queries)
        candidates = index_search(q_low, artifacts, kappa)
    return rerank(queries, artifacts, candidates, k)
