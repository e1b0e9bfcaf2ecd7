"""The traffic generator's closed loop (``bench/load.py``), against a
stand-in frontend that answers in batches from a thread of its own."""
import threading
import time
from concurrent.futures import Future

import numpy as np

import benchkit  # noqa: F401
import repro.serve.frontend  # noqa: F401  (imported before any window)
from bench import load

CLOSED = {"loop": "closed", "clients": 8, "queue": 16}


class _Frontend:
    """Answers up to ``batch`` queued requests every ``step_s``."""

    def __init__(self, batch=4, step_s=0.004):
        self.queue, self.lock = [], threading.Lock()
        self.most_queued = 0
        self.batch, self.step_s = batch, step_s
        self.stop = threading.Event()
        self.thread = threading.Thread(target=self._serve)
        self.thread.start()

    def enqueue(self, query):
        fut = Future()
        with self.lock:
            self.queue.append((query, fut))
            self.most_queued = max(self.most_queued, len(self.queue))
        return fut

    def _serve(self):
        while not self.stop.wait(self.step_s):
            with self.lock:
                batch, self.queue = (self.queue[:self.batch],
                                     self.queue[self.batch:])
            for q, fut in batch:
                fut.set_result(np.arange(10, dtype=np.int32) + int(q[0]))

    def close(self):
        self.stop.set()
        self.thread.join()


def _pool(n):
    return np.arange(n, dtype=np.float32)[:, None] * np.ones((1, 4),
                                                             np.float32)


def test_closed_loop_keeps_one_request_per_client():
    fe = _Frontend()
    try:
        win = load.drive(fe, _pool(4000), CLOSED, 0.3, k=10)
    finally:
        fe.close()
    assert fe.most_queued <= CLOSED["clients"]
    assert win.n_sent > 4 * CLOSED["clients"] and win.ok.all()
    # each query once, its answer its own
    np.testing.assert_array_equal(win.ids[:, 0], np.arange(win.n_sent))
    # the first requests are due at the start, each later one at the
    # completion of the request it follows, all inside the window
    assert np.all(win.due[:CLOSED["clients"]] == win.t_start)
    later = win.due[CLOSED["clients"]:]
    assert np.all(np.isin(later, win.done))
    assert np.all((later >= win.t_start) & (later < win.t_end))
    assert not win.pool_exhausted


def test_closed_loop_stops_at_the_end_of_its_pool():
    fe = _Frontend()
    try:
        win = load.drive(fe, _pool(40), CLOSED, 0.3, k=10)
    finally:
        fe.close()
    assert win.pool_exhausted and win.n_sent == 40 and win.ok.all()


def test_closed_pool_covers_twice_what_full_steps_answer():
    traffic = dict(CLOSED, clients=256, queue=512)
    # steps of 0.5 s answer at most 64 / 0.5 = 128 requests a second
    assert load.capacity(traffic, 51.0, 0.5, 64) == 2 * 128 * 51 + 256
    # a short window, or a slow set-up step, still gets the floor
    assert load.capacity(traffic, 10.0, 0.5, 64) == load.POOL_FLOOR
    t = time.perf_counter()
    assert load.capacity(traffic, 51.0, 0.3, 64) > 51 * 64 / 0.3
    assert time.perf_counter() - t < 1.0
