"""Batched vector-search serving engine (Algorithm 1 as a service), built
around the state-passing contract of :class:`repro.core.search.ServingState`.

The engine compiles ONE ``(queries, state) -> (ids, state)`` step and
carries the state through every call (the classic jax state-passing loop:
with donation the runtime aliases the state buffers input -> output, so the
pass-through is free). Because the artifacts are an argument rather than a
closure constant, ``swap(state)`` installs a refreshed scorer / index /
database with ZERO recompilations -- the swap is a treedef + aval check and
a pointer move, asserted by the compile counter the engine exposes
(``n_compiles``) and by the ``compile_counter`` test fixture.

Pulls requests from a host-side queue, pads to the compiled batch size,
executes the jitted multi-step search, and reports per-batch latency / QPS
plus swap latency. On CPU the numbers characterize the harness, on TPU the
system.
"""
from __future__ import annotations

import collections
import functools
import time
from dataclasses import dataclass
from typing import Deque, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import search as msearch

__all__ = ["ServeStats", "ServingEngine", "make_search_fn",
           "sanitize_queries"]


def sanitize_queries(queries: np.ndarray, dim: int
                     ) -> "tuple[np.ndarray, np.ndarray]":
    """The ONE input-hardening gate every serving surface shares
    (``ServingEngine.submit`` and the coalescing frontend's ``enqueue``).

    Validates shape/dtype -- a wrong-dimensionality or non-numeric batch
    raises a clear ``ValueError`` instead of surfacing as an XLA shape
    error from inside the compiled step -- and zeroes rows containing
    non-finite values so one poisoned row can never contaminate the rows
    sharing its padded batch. Returns ``(clean (n, dim) float32,
    bad_rows (n,) bool)``; callers report the flagged rows as all ``-1``
    ids and count them in ``ServeStats.n_sanitized``.
    """
    queries = np.asarray(queries)
    if queries.ndim != 2 or queries.shape[1] != dim:
        raise ValueError(
            f"queries must be a (n, {dim}) array; got shape "
            f"{queries.shape}")
    if not (np.issubdtype(queries.dtype, np.floating)
            or np.issubdtype(queries.dtype, np.integer)):
        raise ValueError(
            f"queries must be real-valued (float or int), got dtype "
            f"{queries.dtype}")
    queries = queries.astype(np.float32, copy=False)
    bad_rows = ~np.isfinite(queries).all(axis=1)
    if bad_rows.any():
        queries = np.where(bad_rows[:, None], np.float32(0), queries)
    return queries, bad_rows


def _engine_step(queries, state: msearch.ServingState, *, k: int,
                 kappa: int):
    """The one compiled serving step: search + state pass-through.

    Returning the (donated) state unchanged lets XLA alias its buffers
    input -> output, so carrying multi-GB artifacts through the call costs
    nothing and the caller's next step uses the same executable.
    """
    ids = msearch.state_search(queries, state, k, kappa)
    return ids, state


def _candidates_step(queries, state: msearch.ServingState, *, kappa: int):
    """First stage of the two-level serving pipeline (host rerank tier):
    the compiled reduced-space search only -- ``x_full`` is host-resident
    aux data and never enters the trace. The host gather of the kappa
    candidate rows, the prefetch ``device_put``, and the small compiled
    ``rerank_candidates`` program run outside, overlapped with the next
    batch's fine scan by ``ServingEngine.submit``."""
    cand = msearch.state_candidates(queries, state, kappa)
    return cand, state


class _Step:
    """A compiled ``(queries, state) -> (out, state)`` serving step. With
    donation the state round-trips through the program, where XLA aliases
    it input -> output; without, the program returns ``out`` alone and the
    state passes through on the host -- an echoed state that is not
    donated is a copy of every leaf, the rerank store included, on every
    call (and twice the state's device memory)."""

    def __init__(self, fn, donate: bool, name: str):
        self.donate = donate

        def step(queries, state):
            out = fn(queries, state)
            return out if donate else out[0]

        # the compiled module is ``jit_<name>``: a stable name for traces
        step.__name__ = step.__qualname__ = name
        self.jitted = jax.jit(step, donate_argnums=(1,) if donate else ())

    def __call__(self, queries, state):
        if self.donate:
            return self.jitted(queries, state)
        return self.jitted(queries, state), state

    def lower(self, queries, state):
        return self.jitted.lower(queries, state)

    def _cache_size(self) -> int:
        return self.jitted._cache_size()


def make_search_fn(artifacts, k: int, kappa: int, block: int = 4096,
                   index=None):
    """One-shot convenience: bind ``artifacts`` (+ optional Index-protocol
    ``index``) into a jit-able ``queries (B, D) -> ids (B, k)``.

    This is a thin wrapper over the state-passing path -- it builds a
    :class:`~repro.core.search.ServingState` and partially applies it. For
    anything long-lived (or refreshable) use :class:`ServingEngine`, which
    keeps the state an argument so it can be hot-swapped.
    """
    state = msearch.make_state(artifacts, index=index, block=block)

    def search_fn(queries):
        return msearch.state_search(queries, state, k, kappa)

    return search_fn


@dataclass
class ServeStats:
    """Serving counters. ``latencies_ms`` / ``swap_ms`` are RING BUFFERS
    (``deque(maxlen=window)``): a long-running engine sees millions of
    batches, and an unbounded list would both grow without limit and
    freeze the percentiles on ancient history -- the window keeps memory
    flat and the p50/p99 a moving view of the recent ``window`` batches.
    The scalar counters (``n_queries``/``n_batches``/``total_s``) remain
    lifetime totals."""

    n_queries: int = 0
    n_batches: int = 0
    n_sanitized: int = 0          # non-finite query rows zeroed out
    total_s: float = 0.0
    # Overload accounting (async frontend, :mod:`repro.serve.frontend`):
    # ``n_rejected`` counts requests refused AT ENQUEUE (bounded queue at
    # capacity, or a deadline the admission estimate says cannot be met);
    # ``n_shed`` counts requests the dispatcher dropped from the queue
    # because their deadline expired while waiting; ``n_deadline_miss``
    # counts requests that were served but completed past their deadline
    # (the SLO-miss tail the shed policy exists to bound). Rejection and
    # shedding are LOUD (a backpressure error to the client), never a
    # silent drop.
    n_rejected: int = 0
    n_shed: int = 0
    n_deadline_miss: int = 0
    # Host-tier traffic accounting (two-level rerank hierarchy only):
    # ``host_bytes`` is the measured host->device rerank-row traffic,
    # ``host_bytes_lb`` the m*kappa*D*4 lower bound per batch -- the bench
    # layer smoke-enforces measured <= 2x bound, pinning the tier's whole
    # point (per-query traffic scales with kappa, not n).
    host_bytes: int = 0
    host_bytes_lb: int = 0
    window: int = 8192
    latencies_ms: Optional[Deque[float]] = None
    swap_ms: Optional[Deque[float]] = None
    prefetch_ms: Optional[Deque[float]] = None    # host gather + H2D + rerank
    request_ms: Optional[Deque[float]] = None     # frontend enqueue->resolve

    def __post_init__(self):
        if self.latencies_ms is None:
            self.latencies_ms = collections.deque(maxlen=self.window)
        if self.swap_ms is None:
            self.swap_ms = collections.deque(maxlen=self.window)
        if self.prefetch_ms is None:
            self.prefetch_ms = collections.deque(maxlen=self.window)
        if self.request_ms is None:
            self.request_ms = collections.deque(maxlen=self.window)

    @property
    def qps(self) -> float:
        return self.n_queries / self.total_s if self.total_s else 0.0

    @property
    def host_bytes_ratio(self) -> float:
        """Measured host->device rerank traffic over the kappa-row lower
        bound (1.0 = every transferred byte is a candidate row)."""
        return self.host_bytes / self.host_bytes_lb \
            if self.host_bytes_lb else 0.0

    def percentile_ms(self, p: float) -> float:
        return float(np.percentile(np.asarray(self.latencies_ms,
                                              np.float64), p)) \
            if self.latencies_ms else 0.0

    def request_percentile_ms(self, p: float) -> float:
        """Percentile over per-REQUEST latency (enqueue -> resolved), the
        number an SLO is stated against -- queue wait included, unlike the
        per-batch compute window ``percentile_ms`` reads."""
        return float(np.percentile(np.asarray(self.request_ms,
                                              np.float64), p)) \
            if self.request_ms else 0.0

    @property
    def shed_rate(self) -> float:
        """Fraction of offered requests rejected or shed (0.0 when
        nothing was offered): the overload pressure-relief observable."""
        offered = self.n_queries + self.n_rejected + self.n_shed
        return (self.n_rejected + self.n_shed) / offered if offered else 0.0


class ServingEngine:
    """Serves ``state_search(queries (B, D), state) -> ids (B, k)`` at a
    fixed compiled batch size, with hot-swappable state.

    ``state`` is the versioned :class:`~repro.core.search.ServingState`
    pytree; ``swap`` installs a new state with the SAME treedef and leaf
    avals and refuses anything that would trigger a recompile; the engine
    bumps the state's version counter on every swap.

    ``donate=True`` additionally donates the state argument so XLA aliases
    its buffers input -> output (zero-copy carry of multi-GB artifacts on
    accelerators). Donation makes the engine the EXCLUSIVE owner of every
    leaf: outside references to the state passed in -- including arrays
    SHARED with it, like a StreamingState's model or the array the
    artifacts were built from -- die on the first call, so only enable it
    when the host loop reads state exclusively through ``engine.state``.
    It is off by default (and pointless on CPU, where jax does not
    implement donation and would warn on every call).
    """

    def __init__(self, state: msearch.ServingState, k: int, kappa: int,
                 batch_size: int, dim: int, donate: bool = False,
                 stats_window: int = 8192):
        if donate and jax.default_backend() == "cpu":
            donate = False      # not implemented on CPU; avoid the warning
        self.k = k
        self.kappa = kappa
        self.batch_size = batch_size
        self.dim = dim
        self.donate = donate
        self.stats = ServeStats(window=stats_window)
        self.state = state
        self.n_swaps = 0
        self._version0 = int(state.version)
        # Two serving shapes, picked by where the rerank tier lives:
        # device x_full -> ONE compiled step (search + rerank inline);
        # host x_full  -> compiled candidates step + host gather + the
        # shared compiled rerank_candidates, pipelined across batches.
        self._host = msearch.host_tier(state.artifacts)
        dummy = jnp.zeros((batch_size, dim), jnp.float32)
        if self._host is None:
            self._cand_fn = None
            self._fn = _Step(
                functools.partial(_engine_step, k=k, kappa=kappa), donate,
                "serve_step")
            # warmup/compile with a dummy batch
            ids, self.state = self._fn(dummy, self.state)
        else:
            self._fn = None
            self._cand_fn = _Step(
                functools.partial(_candidates_step, kappa=kappa), donate,
                "serve_candidates")
            # warmup compiles BOTH stages for this shape family
            cand, new_state = self._cand_fn(dummy, self.state)
            self.state = self._reattach(new_state)
            ids = msearch.rerank(dummy, self.state.artifacts,
                                 np.asarray(cand), k)
        jax.block_until_ready(ids)

    def _reattach(self, state: msearch.ServingState) -> msearch.ServingState:
        """Re-bind the LIVE host store to a state that round-tripped the
        compiled step: unflattening a jitted output reattaches the
        trace-time aux object, which after a content-refreshing swap would
        resurrect stale rows (aux equality is by shape/dtype only)."""
        if self._host is None:
            return state
        return state._replace(
            artifacts=state.artifacts._replace(x_full=self._host))

    @property
    def version(self) -> int:
        return int(self.state.version)

    @property
    def n_compiles(self) -> Optional[int]:
        """Executables compiled for the serving step (1 after warmup; still
        1 after any number of well-formed swaps). On the host-rerank path
        this counts the candidates stage -- the rerank stage is the
        module-level shared ``rerank_candidates`` cache."""
        fn = self._fn if self._fn is not None else self._cand_fn
        cache_size = getattr(fn, "_cache_size", None)
        return cache_size() if cache_size is not None else None

    def lower(self, batch: int):
        """The compiled serving step for ``batch`` queries, lowered against
        the installed state: its ``compile().as_text()`` is the executable
        a profiler trace's device operations name."""
        fn = self._fn if self._fn is not None else self._cand_fn
        return fn.lower(jnp.zeros((batch, self.dim), jnp.float32),
                        self.state)

    def search_with(self, queries, state: msearch.ServingState):
        """One full search against an arbitrary (treedef-compatible) state
        WITHOUT installing it or touching engine stats -- the lifecycle
        layer's canary hook. Runs whichever pipeline shape the engine
        serves, so a canary over a host-tier state exercises the candidate
        state's own host store. The argument transfer and the compiled
        call's dispatch are the profiler span ``serve.launch``."""
        with TraceAnnotation("serve.launch"):
            queries = jnp.asarray(queries, jnp.float32)
            if self._host is None:
                ids, _ = self._fn(queries, state)
                return ids
            cand, _ = self._cand_fn(queries, state)
        return msearch.rerank(queries, state.artifacts, np.asarray(cand),
                              self.k)

    def _check_swap_compatible(self, state: msearch.ServingState) -> None:
        """Raise ``ValueError`` unless ``state`` would reuse the compiled
        step (same treedef, same leaf shapes/dtypes). Pure check -- never
        mutates the engine; ``swap`` and the lifecycle layer's guarded
        swap both run it before touching anything."""
        old_def = jax.tree_util.tree_structure(self.state)
        new_def = jax.tree_util.tree_structure(state)
        if old_def != new_def:
            raise ValueError(
                "swap would recompile: state treedef changed\n"
                f"  installed: {old_def}\n  offered:   {new_def}")
        old_leaves = jax.tree_util.tree_leaves(self.state)
        new_leaves = jax.tree_util.tree_leaves(state)
        for i, (o, n) in enumerate(zip(old_leaves, new_leaves)):
            o_aval = (jnp.shape(o), jnp.result_type(o))
            n_aval = (jnp.shape(n), jnp.result_type(n))
            if o_aval != n_aval:
                raise ValueError(
                    f"swap would recompile: leaf {i} changed aval "
                    f"{o_aval} -> {n_aval}")

    def swap(self, state: msearch.ServingState) -> None:
        """Hot-swap the serving state: zero recompiles, by construction.

        The new state must match the installed one's treedef (same scorer /
        index classes, same static index config) and leaf shapes/dtypes --
        exactly the invariants ``streaming.refresh_state`` preserves. A
        mismatch raises BEFORE any engine field changes (``state`` /
        ``n_swaps`` are untouched on every rejection path) instead of
        silently recompiling. For semantic validation on top of the
        structural contract -- non-finite scans, canary batteries,
        rollback -- wrap the engine in
        :class:`repro.serve.lifecycle.GuardedEngine`.
        """
        self._check_swap_compatible(state)
        t0 = time.perf_counter()
        # host-side generation counter -> device scalar (a device_put, not
        # a compiled add: swaps never compile anything, not even once)
        self.n_swaps += 1
        self.state = state._replace(
            version=jnp.asarray(self._version0 + self.n_swaps, jnp.int32))
        if self._host is not None:
            # adopt the incoming store (contents may differ; treedef-equal
            # by construction) so _reattach serves the refreshed rows
            self._host = msearch.host_tier(self.state.artifacts)
        self.stats.swap_ms.append((time.perf_counter() - t0) * 1e3)

    def submit(self, queries: np.ndarray) -> np.ndarray:
        """Run all queries through fixed-size batches (pad the tail).

        Input hardening: an empty batch returns a ``(0, k)`` int32 array
        (nothing to concatenate); a wrong-dimensionality / non-numeric
        batch raises a clear ``ValueError`` instead of surfacing as an
        XLA shape error from inside the compiled step; rows containing
        non-finite values are zeroed before batching -- so one poisoned
        row can never contaminate the rows sharing its padded batch --
        and reported as all ``-1`` ids (counted in ``stats.n_sanitized``).
        """
        queries = np.asarray(queries)
        if queries.size == 0 and queries.ndim <= 2:
            return np.zeros((0, self.k), np.int32)
        queries, bad_rows = sanitize_queries(queries, self.dim)
        if bad_rows.any():
            self.stats.n_sanitized += int(bad_rows.sum())
        out = []
        n = queries.shape[0]
        if self._host is not None:
            return self._submit_pipelined(queries, bad_rows)
        for s in range(0, n, self.batch_size):
            chunk = queries[s:s + self.batch_size]
            pad = self.batch_size - chunk.shape[0]
            if pad:
                chunk = np.pad(chunk, ((0, pad), (0, 0)))
            t0 = time.perf_counter()
            with TraceAnnotation("serve.step", rows=self.batch_size,
                                 live=self.batch_size - pad):
                with TraceAnnotation("serve.launch"):
                    ids, self.state = self._fn(
                        jnp.asarray(chunk, jnp.float32), self.state)
                ids = jax.block_until_ready(ids)
            dt = time.perf_counter() - t0
            self.stats.n_batches += 1
            self.stats.n_queries += min(self.batch_size, n - s)
            self.stats.total_s += dt
            self.stats.latencies_ms.append(dt * 1e3)
            out.append(np.asarray(ids)[: self.batch_size - pad])
        result = np.concatenate(out, axis=0)
        if bad_rows.any():
            result[bad_rows] = -1      # sanitized rows: no fabricated hits
        return result

    def _submit_pipelined(self, queries: np.ndarray,
                          bad_rows: np.ndarray) -> np.ndarray:
        """Double-buffered two-level submit (host rerank tier).

        For each batch the compiled candidates step is DISPATCHED (jax's
        async dispatch returns immediately); while the device runs batch
        i+1's fine scan, the host drains batch i: block on its candidate
        ids, gather the kappa full-D rows from the host store, push them
        with a non-blocking ``device_put`` and fold the shared compiled
        ``rerank_candidates`` program over them. The host->device traffic
        is exactly the candidate rows -- batch*kappa*D*4 bytes, counted in
        ``stats.host_bytes`` against the matching lower bound -- never the
        (n, D) store.
        """
        out = []
        pending = None
        n = queries.shape[0]
        t_submit = time.perf_counter()
        for s in range(0, n, self.batch_size):
            chunk = queries[s:s + self.batch_size]
            pad = self.batch_size - chunk.shape[0]
            if pad:
                chunk = np.pad(chunk, ((0, pad), (0, 0)))
            t0 = time.perf_counter()
            q = jnp.asarray(chunk, jnp.float32)
            cand, new_state = self._cand_fn(q, self.state)   # async dispatch
            self.state = self._reattach(new_state)
            q_full = msearch._rotate_queries(q, self.state.artifacts)
            if pending is not None:
                out.append(self._finish(pending))   # overlaps batch s's scan
            pending = (cand, q_full, self.batch_size - pad,
                       min(self.batch_size, n - s), t0)
        out.append(self._finish(pending))
        # overlapping batches: QPS comes from the submit WALL time (per-
        # batch dispatch->finish windows overlap and would double-count)
        self.stats.total_s += time.perf_counter() - t_submit
        result = np.concatenate(out, axis=0)
        if bad_rows.any():
            result[bad_rows] = -1      # sanitized rows: no fabricated hits
        return result

    def _finish(self, pending) -> np.ndarray:
        """Drain one in-flight batch: host gather of its kappa candidate
        rows, prefetch to device, compiled rerank."""
        cand_dev, q_full, keep, n_live, t0 = pending
        cand = np.asarray(cand_dev)            # blocks on the fine scan
        tp = time.perf_counter()
        rows = self._host.take(cand)           # (batch, kappa, D) host gather
        rows_dev = jax.device_put(rows)        # non-blocking H2D prefetch
        ids = msearch.rerank_candidates(q_full, rows_dev,
                                        jnp.asarray(cand), self.k)
        ids = jax.block_until_ready(ids)
        now = time.perf_counter()
        self.stats.prefetch_ms.append((now - tp) * 1e3)
        self.stats.host_bytes += rows.nbytes
        self.stats.host_bytes_lb += (cand.shape[0] * self.kappa
                                     * rows.shape[-1] * rows.itemsize)
        self.stats.n_batches += 1
        self.stats.n_queries += n_live
        self.stats.latencies_ms.append((now - t0) * 1e3)
        return np.asarray(ids)[:keep]
