"""The reader of ``step_idle_ms`` (``bench/metrics/step_idle_ms.py``): the
host time of each traced batch in which the device ran nothing."""
import pytest

import benchkit
from bench import run
from bench import trace_reduce as tr

DEV, OPS, HOST = "/device:TPU:0", "XLA Ops", "/host:CPU"


def _read(events):
    return run.Spec(benchkit.REPO).reader("step_idle_ms")(
        run.Run(trace=tr.Reduced(events)))


def test_step_idle_is_each_batch_less_its_device_time():
    ev = tr.Event
    events = [
        ev(HOST, "t", tr.WINDOW, 0, 100_000_000),
        # batch 0: 10 ms, the device busy 4 ms of it (two overlapping ops)
        ev(HOST, "t", "bench.batch.0", 1_000_000, 10_000_000),
        ev(DEV, OPS, "%ivf_scan_topk.1 = custom-call()", 1_000_000,
           3_000_000),
        ev(DEV, OPS, "fusion.2", 3_000_000, 2_000_000),
        # batch 1: 20 ms, the device busy 6 ms of it
        ev(HOST, "t", "bench.batch.1", 20_000_000, 20_000_000),
        ev(DEV, OPS, "fusion.3", 30_000_000, 6_000_000),
        # an op outside every batch, and a batch cut by the window's end
        ev(DEV, OPS, "copy.4", 50_000_000, 5_000_000),
        ev(HOST, "t", "bench.batch.2", 95_000_000, 10_000_000),
    ]
    # batch 0 idles 6 ms, batch 1 14 ms; batch 2 is not wholly inside
    assert _read(events) == pytest.approx(10.0)


def test_step_idle_reads_nothing_without_a_trace_or_batches():
    reader = run.Spec(benchkit.REPO).reader("step_idle_ms")
    assert reader(run.Run(trace=None)) is None
    assert _read([tr.Event(HOST, "t", tr.WINDOW, 0, 1000)]) is None
