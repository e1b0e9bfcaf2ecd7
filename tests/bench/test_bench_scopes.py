"""The split of the traced steps by the program's scopes
(``bench/scopes.py``, ``bench/split.py``) and the ``host_round_ms``
reader."""
import re

import pytest

import benchkit
from bench import run, scopes, split, trace_reduce as tr

DEV, OPS, HOST = "/device:TPU:0", "XLA Ops", "/host:CPU"

HLO = """HloModule jit_serve_step, is_scheduled=true

%body (p: (s32[], f32[64,10])) -> (s32[], f32[64,10]) {
  %fusion.1 = f32[64,4096]{1,0} fusion(%p), kind=kLoop, calls=%f1, metadata={op_name="jit(serve_step)/search.merge/while/body/search.scan/jit(score)/dot_general" stack_frame_id=1}
  ROOT %sort.2 = f32[64,4196]{1,0} sort(%x), dimensions={1}, to_apply=%cmp, metadata={op_name="jit(serve_step)/search.merge/while/body/top_k"}
}

ENTRY %main (q: f32[64,32]) -> s32[64,10] {
  %fusion.3 = f32[64,4,16]{2,1,0} fusion(%q), kind=kOutput, calls=%f3, metadata={op_name="jit(serve_step)/search.prepare/einsum"}
  %while.4 = (s32[], f32[64,10]) while(%t), condition=%cond, body=%body, metadata={op_name="jit(serve_step)/search.merge/while"}
  %copy.5 = f32[1000,200]{1,0} copy(f32[1000,200]{0,1} %x_full), metadata={op_name="state.artifacts.x_full"}
  %fusion.6 = f32[6400,200]{1,0} fusion(%copy.5, %ids), kind=kCustom, calls=%f6, metadata={op_name="jit(serve_step)/search.rerank/gather"}
  ROOT %copy.7 = s32[64,10]{1,0} copy(%y)
}
"""


def op(name, start, dur):
    return tr.Event(DEV, OPS, f"%{name} = f32[64]{{0}} x()", start, dur)


def span(name, start, end, **stats):
    return scopes.Span(name, start, end, stats)


def _trace():
    ev = tr.Event
    events = [
        ev(HOST, "t", tr.WINDOW, 0, 10_000),
        ev(HOST, "t", "bench.drain", 0, 4_050),
        ev(HOST, "t", "other.span", 8_500, 1_500),      # not a host span
        op("fusion.3", 1_000, 100),                     # prepare
        op("while.4", 1_100, 1_500),                    # control flow
        op("fusion.1", 1_100, 400),                     # scan, in the loop
        op("sort.2", 1_500, 1_000),                     # merge, in the loop
        op("copy.5", 2_600, 200),                       # argument relayout
        op("fusion.6", 2_800, 200),                     # rerank
        op("copy.7", 3_000, 100),                       # no scope
        op("fusion.1", 5_100, 400),                     # the 8-row step
        op("copy.7", 7_000, 100),                       # between steps
        op("copy.7", 9_900, 100),
    ]
    spans = [
        span("serve.round", 0, 4_000),
        span("serve.take", 0, 700),
        span("serve.step", 800, 3_300, rows=64, live=64),
        span("serve.launch", 800, 900),
        span("serve.resolve", 3_300, 3_900),
        span("serve.round", 4_000, 7_000),
        span("serve.step", 5_000, 6_000, rows=8, live=5),
        span("serve.step", 9_950, 11_000, rows=64, live=64),  # past the end
    ]
    return events, spans


def test_instructions_map_to_their_innermost_scope():
    got = scopes.op_scopes(HLO)
    # the store's relayout is charged to the rerank that reads it
    assert got == {"fusion.1": "search.scan", "sort.2": "search.merge",
                   "fusion.3": "search.prepare", "while.4": "control",
                   "copy.5": "search.rerank", "fusion.6": "search.rerank",
                   "copy.7": ""}
    assert scopes.instruction("%fusion.1 = f32[64]{0} x()") == "fusion.1"
    assert scopes.innermost_scope("jit(s)/research.scan/x") is None


def test_split_of_the_traced_steps():
    events, spans = _trace()
    s = scopes.Split(events, spans, HLO, rows=64)
    assert s.steps == [(800, 3_300)]     # one 64-row step wholly inside
    assert s.offset_ns == 0
    assert s.rounds == [(0, 4_000), (4_000, 7_000)]
    assert s.scope_ms("search.prepare") == pytest.approx(100e-6)
    assert s.scope_ms("search.scan") == pytest.approx(400e-6)
    # united with the scan it overlaps, the loop's ops count once
    assert s.scope_ms("search.scan", "search.merge") \
        == pytest.approx(1_400e-6)
    assert s.scope_ms("search.probe") == 0.0
    assert s.scope_ms("search.rerank") == pytest.approx(400e-6)
    assert s.busy_ms() == pytest.approx(2_100e-6)
    assert s.coverage() == pytest.approx(1_900 / 2_100)
    by = s.by_scope()
    assert by[""] == [["copy f32[64]", pytest.approx(100e-9)]]
    # the relayout (copy.5) beside the gather (fusion.6)
    assert sorted(n for n, _ in by["search.rerank"]) \
        == ["copy f32[64]", "fusion f32[64]"]
    assert "control" not in by
    # each round less its step: 4000 - 2500 and 3000 - 1000
    assert s.host_round_ms() == pytest.approx((1_500 + 2_000) / 2 / 1e6)
    summary = s.summary()
    assert summary["probe_ms"] == pytest.approx(100e-6)
    assert summary["merge_ms"] == pytest.approx(1_000e-6)


def test_idle_gaps_are_named_by_the_program_spans():
    events, spans = _trace()
    gaps = dict((round(t * 1e9), name) for name, t in
                scopes.Split(events, spans, HLO, rows=64).idle_gaps())
    assert gaps == {1_000: "serve.take",     # inside take, round, drain
                    2_000: "serve.round",    # the next round covers half
                    1_500: "serve.round",
                    2_800: "no-span"}        # other.span names nothing


def test_the_device_timeline_is_moved_onto_the_host_one():
    """Device times that lag the host's by 5 us read as in step."""
    events, spans = _trace()
    late = [e._replace(start_ns=e.start_ns + 5_000) if e.plane == DEV
            else e for e in events]
    s = scopes.Split(late, spans, HLO, rows=64)
    assert s.offset_ns == -5_000
    assert s.scope_ms("search.scan", "search.merge") \
        == pytest.approx(1_400e-6)
    assert s.coverage() == pytest.approx(1_900 / 2_100)
    assert scopes.clock_offset([], [(0, 1)]) == 0.0


def test_no_steps_of_the_bucket_reads_nothing():
    events, spans = _trace()
    s = scopes.Split(events, spans, HLO, rows=32)
    assert s.scope_ms("search.scan") is None and s.coverage() is None


def test_host_round_reader():
    ev = tr.Event
    events = [ev(HOST, "t", tr.WINDOW, 0, 10_000),
              ev(HOST, "t", "bench.drain", 100, 4_000),
              ev(HOST, "t", "bench.batch.0", 1_000, 2_000),
              ev(HOST, "t", "bench.drain", 5_000, 2_000),
              ev(HOST, "t", "bench.drain", 9_000, 2_000),     # past the end
              ev(DEV, OPS, "fusion.1", 1_000, 1_500)]
    read = run.Spec(benchkit.REPO).reader("host_round_ms")
    assert read(run.Run(trace=tr.Reduced(events))) \
        == pytest.approx((2_000 + 2_000) / 2 / 1e6)
    assert read(run.Run(trace=None)) is None


def test_split_of_a_cpu_run(tmp_path):
    """The whole tool on a tiny flat cell: the CPU runs XLA's ops on host
    threads, which stand in for the device here."""
    root = benchkit.make_root(tmp_path, {"miss10": 0.1, "worst_gap": 0.05})
    out = split.split_cell(run.Spec(root), "tiny-flat", 2 ** 33 + 5, 1.5,
                           device_plane=re.compile(r"^/host:CPU$"),
                           ops_line=re.compile(r"^tf_XLA"))
    assert out["rows"] == benchkit.TINY["max_batch"] and out["steps"] > 0
    for phase in ("probe_ms", "scan_ms", "merge_ms", "rerank_ms"):
        assert out[phase] > 0, phase
    assert 0 < out["coverage"] <= 1
    assert out["host_round_ms"] > 0
    assert out["qps_traced"] > 0 and out["qps_untraced"] > 0
    assert any(g[0].startswith("serve.") for g in out["idle_gaps"])
