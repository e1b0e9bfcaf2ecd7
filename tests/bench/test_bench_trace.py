"""The trace-to-metrics reduction (``bench/trace_reduce.py``)."""
import re

import jax
import jax.numpy as jnp
import pytest

import benchkit  # noqa: F401
from bench import trace_reduce as tr

DEV, OPS, HOST = "/device:TPU:0", "XLA Ops", "/host:CPU"


def _events():
    ev = tr.Event
    return [
        ev(HOST, "t", tr.WINDOW, 0, 1000),
        ev(DEV, OPS, "fusion.1", 100, 200),
        ev(DEV, OPS, "range_scan_kernel", 200, 200),   # overlaps fusion.1
        ev(DEV, OPS, "fusion.2", 600, 100),
        ev(DEV, OPS, "copy", 950, 150),                # runs past the window
        ev(DEV, "XLA Modules", "jit_step", 100, 900),  # not an op line
        ev(HOST, "t", "bench.batch.0", 50, 400),
        ev(HOST, "t", "bench.batch.1", 550, 650),      # ends after the window
        ev(HOST, "t", "bench.take", 400, 200),
        ev(HOST, "t", "other.span", 0, 1000),          # not a bench span
    ]


def test_busy_idle_kernels_and_batches():
    r = tr.Reduced(_events())
    assert r.window_s == pytest.approx(1e-6)
    # union of [100, 400], [600, 700], [950, 1000]
    assert r.busy_s == pytest.approx(450e-9)
    assert r.idle_share == pytest.approx(0.55)
    assert r.kernel_s("range_scan") == pytest.approx(200e-9)
    assert r.batches() == {0: (50.0, 450.0)}
    assert r.busy_in(50, 450) == pytest.approx(300e-9)
    assert r.kernel_s("range_scan", 50, 250) == pytest.approx(50e-9)
    ops = dict((n, s) for n, s in r.device_ops())
    assert ops["fusion"] == pytest.approx(300e-9)      # grouped by name
    assert ops["copy"] == pytest.approx(50e-9)


def test_short_names_of_tpu_hlo_ops():
    assert tr.short_name("%copy.348 = f32[7000000,200]{1,0:T(8,128)} "
                         "copy(f32[7000000,200]{0,1:T(8,128)} %x)") \
        == "copy f32[7000000,200]"
    assert tr.short_name("%ivf_scan_topk.12 = (f32[8,1,128]{2,1,0}, "
                         "s32[8,1,128]{2,1,0}) custom-call(...)") \
        == "ivf_scan_topk f32[8,1,128]"
    assert tr.short_name("dot_general.1") == "dot_general"


def test_idle_gaps_are_named_by_the_host_span_they_fall_in():
    gaps = tr.Reduced(_events()).idle_gaps()
    assert [g[0] for g in gaps] == ["bench.batch", "bench.take",
                                    "bench.batch"]
    assert [g[1] for g in gaps] == pytest.approx([250e-9, 200e-9, 100e-9])


def test_a_trace_without_its_window_span_is_refused():
    with pytest.raises(ValueError, match="trace_window"):
        tr.Reduced([e for e in _events() if e.name != tr.WINDOW])


def test_recorded_cpu_trace(tmp_path):
    """The loader on a trace recorded here: the CPU runs XLA's ops on host
    threads, so the device selection points at those lines."""
    f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            for i in range(3):
                with jax.profiler.TraceAnnotation(f"bench.batch.{i}"):
                    f(x).block_until_ready()
    events = tr.load(tr.find_xplane(str(tmp_path)))
    r = tr.Reduced(events, device_plane=re.compile(r"^/host:CPU$"),
                   ops_line=re.compile(r"^tf_XLA"))
    assert sorted(r.batches()) == [0, 1, 2]
    assert 0 < r.busy_s <= r.window_s
    assert r.kernel_s("dot") > 0
    assert tr.Reduced(events).idle_share is None    # no TPU plane here


def test_roofline_readers_on_a_tpu_shaped_trace():
    """The roofline readers divide the least time of the traced batches'
    work by the device time inside their spans (kernel events found by
    their HLO names, as the TPU trace shows them)."""
    from bench import run, work
    ev = tr.Event
    events = [
        ev(HOST, "t", tr.WINDOW, 0, 10_000),
        ev(HOST, "t", "bench.batch.3", 1_000, 5_000),
        ev(DEV, OPS, "%ivf_scan_topk.12 = (f32[8,1,128]{2,1,0}) "
           "custom-call(s32[8,7296]{1,0})", 1_000, 3_000),
        ev(DEV, OPS, "%copy.348 = f32[7000000,200]{1,0} copy()", 4_000, 1_000),
    ]
    peaks = work.Peaks(hbm_bytes_per_s=1e9, int8_ops_per_s=1e12)
    fine = work.Work(bytes=1500.0, ops=10.0)          # 1.5 us at 1 GB/s
    step = fine + work.Work(bytes=500.0, ops=10.0)    # 2.0 us
    r = run.Run(trace=tr.Reduced(events), peaks=peaks,
                work={3: {"step": step, "fine": fine}})
    spec = run.Spec(benchkit.REPO)
    assert spec.reader("ivf_scan_roofline")(r) == pytest.approx(50.0)
    assert spec.reader("step_roofline")(r) == pytest.approx(50.0)
    assert spec.reader("idle_share")(r) == pytest.approx(60.0)
    flat = run.Run(trace=r.trace, peaks=peaks,
                   work={3: {"step": step, "fine": None}})
    assert spec.reader("ivf_scan_roofline")(flat) is None
