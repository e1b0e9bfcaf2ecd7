"""Overload-safe async serving frontend: bounded-queue request coalescer
with deadline admission, and a supervised background refresh worker.

``launch/serve.py``'s host loop is one-batch-in-one-batch-out: a single
slow client stalls everyone behind it, and ``--stream`` blocks serving
~100ms per refresh. This module is the concurrent frontend the
fault-tolerance substrate (PR 7) and the state-passing engine (PR 4/8)
were built to protect:

* :class:`ServingFrontend` -- many concurrent clients
  ``enqueue(query, deadline_ms)`` into a FIXED-CAPACITY admission queue;
  one dispatcher drains it into padded micro-batches drawn from a small
  STATIC set of bucket shapes (:func:`bucket_shapes`), so the one
  compiled ``state_search`` / ``state_candidates`` step is reused with
  zero recompiles after warmup -- the executable cache is bounded by
  ``len(buckets)`` forever (the ``BoundedCompileCache`` analysis rule).
  Results are sliced back per request; a request coalesced into a bucket
  is bit-identical to the same query sent through
  ``ServingEngine.submit`` alone. Input hardening is shared with
  ``submit`` (:func:`repro.serve.engine.sanitize_queries`): malformed
  requests raise at ``enqueue``, poisoned rows are zeroed, resolved as
  all ``-1`` ids, and never contaminate their bucket-mates.

* **Admission control / load shedding** -- the queue refuses work it
  cannot serve in time, LOUDLY. At enqueue: a full queue or a deadline
  the wait estimate (EWMA batch latency x queue depth in buckets) says
  cannot be met raises :class:`Rejected` (backpressure to the client,
  counted in ``ServeStats.n_rejected``). At dispatch: requests whose
  deadline expired while queued are shed -- their future fails with
  ``Rejected("shed")``, counted in ``n_shed`` -- so under sustained
  overload the tail is cut instead of every request's latency
  collapsing together.

* :class:`RefreshWorker` -- the Section 3.2 refresh loop as a
  BACKGROUND thread under :class:`~repro.serve.lifecycle.
  RefreshSupervisor` (retry/backoff, stored->full escalation,
  degrade -> recover), handing finished states to
  ``GuardedEngine.swap``. Serving never waits on a refresh: the
  dispatcher reads ``engine.state`` once per batch (an atomic reference
  read -- states are immutable pytrees, and a swap is a single
  reference assignment under the GIL), so a slow, stuck, or crashed
  worker leaves the stale-but-valid state serving and only
  ``staleness_s`` grows.

The deterministic core is :meth:`ServingFrontend.drain_once` with an
injectable ``clock`` -- tests drive admission, coalescing, and shedding
without threads or wall time; the dispatcher thread is a thin loop over
it.
"""
from __future__ import annotations

import collections
import math
import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import streaming
from repro.serve.engine import ServingEngine, sanitize_queries
from repro.serve.lifecycle import GuardedEngine, RefreshSupervisor

__all__ = ["MAX_BUCKETS", "Rejected", "bucket_shapes", "ServingFrontend",
           "RefreshWorker"]

# Contract ceiling on the static bucket set: every dispatched batch shape
# is one of len(buckets) <= MAX_BUCKETS shapes, so the compiled-step cache
# can never grow past it. Enforced here at construction and by the
# ``BoundedCompileCache`` rule in ``repro.analysis``.
MAX_BUCKETS = 12


class Rejected(RuntimeError):
    """Backpressure error: the frontend refused (or shed) a request.

    ``reason`` is a stable slug -- ``queue-full`` (admission queue at
    capacity), ``deadline`` (the wait estimate says the budget cannot be
    met), ``shed`` (deadline expired while queued), ``shutdown`` (the
    frontend is closing). Clients retry/route elsewhere; nothing is
    dropped silently."""

    def __init__(self, reason: str, detail: str = ""):
        self.reason = reason
        super().__init__(f"request rejected ({reason}): {detail}" if detail
                         else f"request rejected ({reason})")


def bucket_shapes(max_batch: int) -> Tuple[int, ...]:
    """The static micro-batch shape set: powers of two up to (and always
    including) ``max_batch``. Small by construction -- padding waste is
    bounded at 2x while the compiled executable count stays
    O(log max_batch), and the whole set is warmable up front."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    shapes = set()
    b = 1
    while b < max_batch:
        shapes.add(b)
        b *= 2
    shapes.add(max_batch)
    out = tuple(sorted(shapes))
    if len(out) > MAX_BUCKETS:
        raise ValueError(
            f"{len(out)} bucket shapes exceed MAX_BUCKETS={MAX_BUCKETS}; "
            f"the compile-cache bound is the frontend's contract")
    return out


@dataclass
class _Request:
    """One admitted client request (a single query vector)."""

    query: np.ndarray            # (1, dim) float32, already sanitized
    poisoned: bool               # non-finite row: resolve as all -1 ids
    deadline: float              # absolute clock time (math.inf = none)
    t_enqueue: float
    future: Future


class ServingFrontend:
    """Bounded-queue request coalescer over a :class:`ServingEngine`.

    ``engine`` may be a raw :class:`ServingEngine` or a
    :class:`~repro.serve.lifecycle.GuardedEngine` (unwrapped via its
    ``.engine``). The frontend dispatches through
    ``engine.search_with(queries, engine.state)`` -- the tier-dispatching
    entry that serves both the one-step device pipeline and the two-level
    host-rerank pipeline -- and never installs the pass-through state, so
    it composes with concurrent ``GuardedEngine.swap`` from a
    :class:`RefreshWorker` without locks on the hot path.

    ``capacity`` bounds the admission queue; ``default_deadline_ms`` is
    applied when ``enqueue`` is called without a deadline (None = no
    deadline); ``est_batch_ms``/``ewma_alpha`` seed and smooth the
    admission-time wait estimate; ``clock`` is injectable for
    deterministic tests. ``start=False`` skips the dispatcher thread --
    drive :meth:`drain_once` directly.
    """

    def __init__(self, engine, capacity: int = 256,
                 buckets: Optional[Sequence[int]] = None,
                 default_deadline_ms: Optional[float] = None,
                 est_batch_ms: float = 5.0, ewma_alpha: float = 0.2,
                 clock: Callable[[], float] = time.monotonic,
                 start: bool = True, warmup: bool = True):
        self.engine: ServingEngine = getattr(engine, "engine", engine)
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self.buckets = tuple(sorted(buckets)) if buckets is not None \
            else bucket_shapes(self.engine.batch_size)
        if len(self.buckets) > MAX_BUCKETS:
            raise ValueError(f"{len(self.buckets)} buckets exceed "
                             f"MAX_BUCKETS={MAX_BUCKETS}")
        self.max_bucket = self.buckets[-1]
        self.default_deadline_ms = default_deadline_ms
        self.stats = self.engine.stats
        self._ewma_s = est_batch_ms / 1e3
        self._ewma_alpha = float(ewma_alpha)
        self._clock = clock
        self._cv = threading.Condition(threading.Lock())
        self._queue: collections.deque = collections.deque()
        self._closed = False
        self.dispatched_shapes: set = set()
        self._thread: Optional[threading.Thread] = None
        if warmup:
            self.warmup()
        if start:
            self._thread = threading.Thread(target=self._dispatch_loop,
                                            name="frontend-dispatch",
                                            daemon=True)
            self._thread.start()

    # -- warmup / observability ------------------------------------------
    def warmup(self) -> None:
        """Compile every bucket shape up front (one executable each; the
        engine's own warmup already covers ``batch_size``, which is a
        bucket). After this, serving ANY admissible workload through the
        frontend compiles nothing -- compile_counter-asserted by the
        tests and the bursty-arrival bench."""
        dummy_state = self.engine.state
        for b in self.buckets:
            q = np.zeros((b, self.engine.dim), np.float32)
            jax.block_until_ready(self.engine.search_with(q, dummy_state))

    @property
    def queue_depth(self) -> int:
        with self._cv:
            return len(self._queue)

    def estimated_wait_s(self, depth: Optional[int] = None) -> float:
        """Admission-time service estimate: batches ahead of (and
        including) the candidate request, times the EWMA batch latency."""
        if depth is None:
            depth = self.queue_depth
        batches = depth // self.max_bucket + 1
        return batches * self._ewma_s

    # -- admission --------------------------------------------------------
    def enqueue(self, query: np.ndarray,
                deadline_ms: Optional[float] = None) -> Future:
        """Admit one query vector; returns a ``Future`` resolving to its
        (k,) int32 ids. Malformed input raises ``ValueError`` (shared
        hardening with ``submit``); an overloaded queue or an unmeetable
        deadline raises :class:`Rejected` -- backpressure, not a silent
        drop. Poisoned (non-finite) rows are admitted but sanitized:
        zeroed for batching, resolved as all ``-1`` ids."""
        q = np.asarray(query)
        if q.ndim == 1:
            q = q[None, :]
        if q.ndim != 2 or q.shape[0] != 1:
            raise ValueError(
                f"enqueue takes ONE query vector per request; got shape "
                f"{np.shape(query)} (use ServingEngine.submit for batches)")
        q, bad = sanitize_queries(q, self.engine.dim)
        if deadline_ms is None:
            deadline_ms = self.default_deadline_ms
        now = self._clock()
        deadline = math.inf if deadline_ms is None \
            else now + deadline_ms / 1e3
        with self._cv:
            if self._closed:
                raise Rejected("shutdown", "frontend is closed")
            if len(self._queue) >= self.capacity:
                self.stats.n_rejected += 1
                raise Rejected(
                    "queue-full",
                    f"admission queue at capacity {self.capacity}")
            est = self.estimated_wait_s(len(self._queue))
            if now + est > deadline:
                self.stats.n_rejected += 1
                raise Rejected(
                    "deadline",
                    f"predicted wait {est * 1e3:.1f}ms exceeds budget "
                    f"{deadline_ms:.1f}ms at depth {len(self._queue)}")
            if bad[0]:
                self.stats.n_sanitized += 1
            req = _Request(query=q, poisoned=bool(bad[0]),
                           deadline=deadline, t_enqueue=now,
                           future=Future())
            self._queue.append(req)
            self._cv.notify()
        return req.future

    # -- dispatch ---------------------------------------------------------
    def _pick_bucket(self, n: int) -> int:
        """Smallest declared bucket holding ``n`` requests. ``n`` never
        exceeds ``max_bucket`` (the dispatcher drains at most that many),
        so the result is always a member of the static set."""
        for b in self.buckets:
            if b >= n:
                return b
        return self.max_bucket

    def _take(self, timeout: Optional[float]
              ) -> Tuple[List[_Request], List[_Request]]:
        """Pop up to ``max_bucket`` requests, splitting off those whose
        deadline cannot survive one more batch window (shed)."""
        with self._cv:
            if not self._queue and timeout:
                self._cv.wait(timeout)
            batch: List[_Request] = []
            shed: List[_Request] = []
            horizon = self._clock() + self._ewma_s
            while self._queue and len(batch) < self.max_bucket:
                req = self._queue.popleft()
                (shed if req.deadline < horizon else batch).append(req)
        return batch, shed

    def drain_once(self, timeout: Optional[float] = None) -> int:
        """One dispatcher round: shed expired requests, coalesce the rest
        into one padded bucket, run the compiled step, slice results back
        per request. Returns the number of requests retired (served +
        shed). Deterministic -- the threaded dispatcher is a loop over
        this; tests call it directly.

        The round and its phases are profiler spans: ``serve.round``
        around ``serve.take``, ``serve.assemble``, ``serve.step`` (with
        the bucket's ``rows`` and its ``live`` requests) and
        ``serve.resolve``, so a trace shows where the host time between
        device steps goes."""
        with TraceAnnotation("serve.round"):
            return self._round(timeout)

    def _round(self, timeout: Optional[float]) -> int:
        with TraceAnnotation("serve.take"):
            batch, shed = self._take(timeout)
        for req in shed:
            self.stats.n_shed += 1
            req.future.set_exception(
                Rejected("shed", "deadline expired while queued"))
        if not batch:
            return len(shed)
        with TraceAnnotation("serve.assemble"):
            b = self._pick_bucket(len(batch))
            chunk = np.zeros((b, self.engine.dim), np.float32)
            for i, req in enumerate(batch):
                chunk[i] = req.query[0]
        t0 = self._clock()
        try:
            with TraceAnnotation("serve.step", rows=b, live=len(batch)):
                # one atomic reference read: a concurrent swap either
                # lands before (batch sees the fresh state) or after
                # (stale-but-valid) -- never a torn state, states being
                # immutable pytrees
                state = self.engine.state
                ids = jax.block_until_ready(
                    self.engine.search_with(chunk, state))
        except Exception as e:      # noqa: BLE001 -- fail THIS batch only
            for req in batch:
                req.future.set_exception(e)
            return len(batch) + len(shed)
        with TraceAnnotation("serve.resolve"):
            ids = np.asarray(ids)
            dt = self._clock() - t0
            a = self._ewma_alpha
            self._ewma_s = a * dt + (1 - a) * self._ewma_s
            self.dispatched_shapes.add(b)
            self.stats.n_batches += 1
            self.stats.n_queries += len(batch)
            self.stats.total_s += dt
            self.stats.latencies_ms.append(dt * 1e3)
            now = self._clock()
            for i, req in enumerate(batch):
                self.stats.request_ms.append((now - req.t_enqueue) * 1e3)
                if now > req.deadline:
                    self.stats.n_deadline_miss += 1
                out = np.full((self.engine.k,), -1, np.int32) \
                    if req.poisoned else ids[i].astype(np.int32, copy=True)
                req.future.set_result(out)
        return len(batch) + len(shed)

    def _dispatch_loop(self) -> None:
        while True:
            with self._cv:
                if self._closed and not self._queue:
                    return
            self.drain_once(timeout=0.02)

    # -- shutdown ---------------------------------------------------------
    def close(self, drain: bool = True, timeout: float = 10.0) -> None:
        """Stop admitting; either serve the backlog (``drain=True``) or
        fail it with ``Rejected("shutdown")``. Idempotent."""
        with self._cv:
            self._closed = True
            if not drain:
                while self._queue:
                    req = self._queue.popleft()
                    req.future.set_exception(
                        Rejected("shutdown", "frontend closed"))
            self._cv.notify_all()
        if self._thread is not None and self._thread.is_alive():
            self._thread.join(timeout)
        if drain:
            while self.queue_depth:     # un-threaded frontends drain here
                self.drain_once()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class RefreshWorker:
    """Supervised background refresh: ``observe -> refresh ->
    refresh_state -> GuardedEngine.swap`` on its OWN thread, so serving
    never blocks on a refresh.

    The worker owns the :class:`~repro.core.streaming.StreamingState`;
    traffic threads feed it via :meth:`observe` (bounded pending buffer)
    and kick cycles via :meth:`request_refresh` (or a periodic
    ``interval_s``). Each cycle runs under the
    :class:`~repro.serve.lifecycle.RefreshSupervisor` ladder -- retry
    with backoff, stored->full escalation on ill-conditioned Eq. 12
    transitions, graceful degradation -- and a degraded cycle
    auto-``recover``s the moments from the last-known-good store so the
    NEXT cycle swaps clean. A finished state is handed to
    ``GuardedEngine.swap``: a single reference assignment, double-
    buffered against the dispatcher's atomic state read and donation-
    safe (guarded engines are non-donating by construction).

    Failure is contained by design: a refresh that HANGS strands only
    this (daemon) thread -- ``stuck(timeout_s)`` flips true,
    ``staleness_s`` grows, and the engine keeps serving the stale-but-
    valid state; a crash outside the supervisor's net is recorded in
    ``crashed`` and the loop exits, again leaving serving untouched.
    """

    def __init__(self, supervisor: RefreshSupervisor,
                 stream: streaming.StreamingState, source: str = "stored",
                 refresh_fn=streaming.refresh, interval_s: float = 0.0,
                 pending_window: int = 64,
                 clock: Callable[[], float] = time.monotonic):
        self.supervisor = supervisor
        self.guarded: GuardedEngine = supervisor.guarded
        self.stream = stream
        self.source = source
        self.refresh_fn = refresh_fn
        self.interval_s = interval_s
        self._clock = clock
        self._pending: collections.deque = collections.deque(
            maxlen=pending_window)
        self._pending_lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self.n_cycles = 0
        self.crashed: Optional[BaseException] = None
        self.last_swap_t = clock()
        self._cycle_t0: Optional[float] = None
        self._thread = threading.Thread(target=self._loop,
                                        name="refresh-worker", daemon=True)

    # -- lifecycle --------------------------------------------------------
    def start(self) -> "RefreshWorker":
        self._thread.start()
        return self

    def stop(self, timeout: float = 5.0) -> bool:
        """Ask the worker to exit; returns False when the thread is still
        alive (e.g. stuck inside a hung refresh -- it is a daemon thread,
        so a stuck worker never pins the process)."""
        self._stop.set()
        self._wake.set()
        if self._thread.is_alive():
            self._thread.join(timeout)
        return not self._thread.is_alive()

    # -- traffic-side API -------------------------------------------------
    def observe(self, queries: np.ndarray) -> None:
        """Queue served queries for the next cycle's K_Q update (and the
        supervisor's recovery window). Bounded buffer: under overload old
        observations drop first -- observation is best-effort, serving
        state is not."""
        q = np.asarray(queries, np.float32)
        with self._pending_lock:
            self._pending.append(q)
        self.supervisor.note_queries(q)

    def request_refresh(self) -> None:
        """Kick one supervised refresh cycle (idempotent while pending)."""
        self._wake.set()

    # -- health observables -----------------------------------------------
    @property
    def degraded(self) -> bool:
        return self.supervisor.degraded

    @property
    def in_cycle_s(self) -> float:
        """Seconds the current cycle has been running (0 when idle)."""
        t0 = self._cycle_t0
        return self._clock() - t0 if t0 is not None else 0.0

    def stuck(self, timeout_s: float) -> bool:
        """True when the in-flight cycle has exceeded ``timeout_s`` --
        the watchdog signal a stuck refresh (hung I/O, a deadlocked
        solve) raises while serving continues on the stale state."""
        return self.in_cycle_s > timeout_s

    @property
    def staleness_s(self) -> float:
        """Seconds since the last successfully swapped refresh: the
        swap-staleness the bench reports. Grows without bound under a
        stuck/crashed worker -- by design, the alert condition."""
        return self._clock() - self.last_swap_t

    @property
    def healthy(self) -> bool:
        return self.crashed is None and self._thread.is_alive()

    # -- the supervised cycle ---------------------------------------------
    def run_cycle(self) -> Optional[object]:
        """One supervised refresh cycle, synchronously (the thread loop
        calls this; tests may too). Returns the ``RefreshReport`` (None
        when there was nothing to do)."""
        self._cycle_t0 = self._clock()
        try:
            with self._pending_lock:
                pending, n = list(self._pending), len(self._pending)
                self._pending.clear()
            stream = self.stream
            for q in pending:
                stream = streaming.observe_queries(stream, jnp.asarray(q))
            self.stream = stream    # observations survive a failed refresh
            stream, report = self.supervisor.refresh_and_swap(
                stream, source=self.source, refresh_fn=self.refresh_fn)
            self.stream = stream
            self.n_cycles += 1
            if report.outcome == "ok":
                self.last_swap_t = self._clock()
            elif report.outcome == "degraded":
                # close the degrade -> recover loop: rebuild the moments
                # from the last-known-good store + retained queries so the
                # NEXT cycle's refresh swaps clean
                try:
                    self.stream = self.supervisor.recover(stream)
                except ValueError:
                    pass            # no retained queries yet: stay degraded
            return report
        finally:
            self._cycle_t0 = None

    def _loop(self) -> None:
        while not self._stop.is_set():
            fired = self._wake.wait(
                self.interval_s if self.interval_s > 0 else None)
            if self._stop.is_set():
                return
            if fired:
                self._wake.clear()
            try:
                self.run_cycle()
            except BaseException as e:   # noqa: BLE001 -- watchdog record
                # outside the supervisor's net: record and stand down;
                # the engine keeps serving the stale-but-valid state
                self.crashed = e
                return
