"""The one traffic generator, and the host spans the benchmark records.

A traffic mix is a data file (``bench/traffic/<name>.json``) that this
module reads:

* ``{"loop": "closed", "clients": C, "queue": Q}``: C clients, each with
  one single-query request outstanding; each request's completion
  callback sends the client's next request at once (due time = the
  completion). No thread per client, and no sender thread.

Every request carries the next unused query of the run's pool: no query
repeats within a run. Requests enter through ``ServingFrontend.enqueue``,
the program's entry; ``Q`` is its admission-queue capacity.

:class:`Instrumented` wraps the frontend's dispatcher round, its queue
pop and the engine's ``search_with`` in host spans (``TraceAnnotation``,
so a traced run sees them on the trace's clock) and records, per batch,
its size and host time.
"""
from __future__ import annotations

import math
import threading
import time
from typing import List, NamedTuple, Optional

import jax
import numpy as np

WAIT_AFTER_CLOSE_S = 60.0
# a closed loop's pool over the most that full steps at the set-up's
# measured speed can answer
POOL_MARGIN = 2.0
# the least pool of a run: a set-up step slowed by other work on the host
# must not leave a short window without queries (the full-size cells'
# pools are larger; the pool's draws keep their order, so a floor adds
# queries at its end and changes none that is served)
POOL_FLOOR = 8192


class Batch(NamedTuple):
    """One dispatched batch: its host span and its requests (``queries``,
    the padded batch, is kept for traced runs only)."""
    index: int
    t0: float
    t1: float
    n_real: int
    queries: Optional[np.ndarray]


class Instrumented:
    """Host spans around the frontend's dispatcher round and the engine's
    step. ``keep_queries`` keeps each batch's padded queries (for the work
    counts of a traced run)."""

    def __init__(self, frontend, keep_queries: bool = False):
        self.batches: List[Batch] = []
        self._last_take = 0
        engine = frontend.engine
        take, search, drain = (frontend._take, engine.search_with,
                               frontend.drain_once)

        def timed_take(timeout):
            with jax.profiler.TraceAnnotation("bench.take"):
                batch, shed = take(timeout)
            self._last_take = len(batch)
            return batch, shed

        def timed_search(queries, state):
            i = len(self.batches)
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation(f"bench.batch.{i}"):
                ids = jax.block_until_ready(search(queries, state))
            self.batches.append(Batch(
                i, t0, time.perf_counter(), self._last_take,
                np.array(queries, copy=True) if keep_queries else None))
            return ids

        def timed_drain(timeout=None):
            with jax.profiler.TraceAnnotation("bench.drain"):
                return drain(timeout)

        frontend._take = timed_take
        engine.search_with = timed_search
        frontend.drain_once = timed_drain


class Window:
    """What one measured window did, request by request (times are
    ``perf_counter`` seconds)."""

    def __init__(self, n: int, k: int, t_start: float, seconds: float):
        self.t_start, self.seconds = t_start, seconds
        self.t_end = t_start + seconds
        self.n_sent = 0
        self.due = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.ok = np.zeros(n, bool)
        self.ids = np.full((n, k), -1, np.int32)
        self.errors: List[str] = []
        self.futures: List[object] = [None] * n
        self.pool_exhausted = False

    def trim(self):
        """Drop the unused tail of the per-request arrays."""
        n = self.n_sent
        for name in ("due", "done", "ok", "ids"):
            setattr(self, name, getattr(self, name)[:n])
        self.futures = self.futures[:n]

    @property
    def in_window(self) -> np.ndarray:
        """Requests due inside the window."""
        return self.due < self.t_end


def _send(fe, pool, win: Window, rid: int, due: float, on_done) -> None:
    """Enqueue request ``rid`` (due at ``due``); ``on_done(rid)`` runs once
    it has its answer, its error or its refusal."""
    from repro.serve.frontend import Rejected
    win.due[rid] = due
    try:
        fut = fe.enqueue(pool[rid])
    except Rejected as e:
        win.errors.append(f"request {rid}: {e}")
        win.done[rid] = time.perf_counter()
        on_done(rid)
        return
    win.futures[rid] = fut

    def finished(f):
        win.done[rid] = time.perf_counter()
        if f.exception() is None:
            win.ok[rid] = True
            win.ids[rid] = f.result()
        else:
            win.errors.append(f"request {rid}: {f.exception()!r}")
        on_done(rid)

    fut.add_done_callback(finished)


def _closed(fe, pool, win: Window, clients: int, queue_cap: int) -> None:
    """``clients`` clients, each sending its next request from the
    completion callback of its last (in the thread that answered it), due
    at that completion, until the window closes."""
    if clients > queue_cap:
        raise ValueError(f"{clients} clients overflow a queue of "
                         f"{queue_cap}: a refused client would resend "
                         "at once")
    lock = threading.Lock()
    idle = threading.Event()
    live = [1]      # requests in flight, and this thread while it sends

    def release():
        with lock:
            live[0] -= 1
            if live[0] == 0:
                idle.set()

    def take_rid(now: float) -> Optional[int]:
        with lock:
            if now >= win.t_end:
                return None
            if win.n_sent >= len(pool):
                win.pool_exhausted = True
                return None
            live[0] += 1
            win.n_sent += 1
            return win.n_sent - 1

    def on_done(rid):
        now = float(win.done[rid])      # the next request is due now
        try:
            nxt = take_rid(now)
            if nxt is not None:
                _send(fe, pool, win, nxt, now, on_done)
        except Exception as e:      # noqa: BLE001 -- the client stops
            win.errors.append(f"client after request {rid}: {e!r}")
        finally:
            release()

    for _ in range(clients):
        rid = take_rid(win.t_start)
        if rid is None:
            break
        _send(fe, pool, win, rid, win.t_start, on_done)
    release()
    idle.wait(win.seconds + WAIT_AFTER_CLOSE_S)


def capacity(traffic: dict, seconds: float, full_batch_s: float,
             max_batch: int) -> int:
    """Requests a window of this traffic can send at most: the size of
    the run's query pool. A closed loop is answered at most ``max_batch``
    requests a step, so its pool holds POOL_MARGIN times what steps of
    ``full_batch_s`` (the warmed step's time, measured in set-up) answer
    in the window, and at least POOL_FLOOR; a run that would need more
    queries fails rather than repeat one."""
    rate = max_batch / max(full_batch_s, 1e-4)
    return max(POOL_FLOOR, int(math.ceil(POOL_MARGIN * rate * seconds))
               + int(traffic["clients"]))


def drive(fe, pool: np.ndarray, traffic: dict, seconds: float, k: int,
          on_start=None) -> Window:
    """Offer the traffic to the frontend for ``seconds``, then wait (up to
    a minute) for every answer. ``on_start(window)`` runs as the window
    opens."""
    if traffic["loop"] != "closed":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    win = Window(len(pool), k, time.perf_counter(), seconds)
    if on_start is not None:
        on_start(win)
    _closed(fe, pool, win, int(traffic["clients"]), int(traffic["queue"]))
    deadline = time.perf_counter() + WAIT_AFTER_CLOSE_S
    for f in win.futures[:win.n_sent]:
        if f is not None:
            try:
                f.exception(timeout=max(0.0, deadline - time.perf_counter()))
            except TimeoutError:
                break
    win.trim()
    # each future's callback holds the frontend (``_send``'s ``on_done``):
    # let go of them, so that the frontend, its engine and the program's
    # state are freed once the caller lets go of its own references
    win.futures = []
    return win


def answered(win: Window) -> np.ndarray:
    """Indices of the requests that got an answer."""
    return np.flatnonzero(win.ok)


def batches_in(inst: Instrumented, win: Window) -> List[Batch]:
    """Batches dispatched inside the window."""
    return [b for b in inst.batches if win.t_start <= b.t0 < win.t_end]
