"""Profiler spans of the served path and named scopes of its compiled step.

The names are a contract: the benchmark's per-layer metrics read them.

* Host spans (``jax.profiler.TraceAnnotation``): each dispatcher round is
  ``serve.round`` around ``serve.take``, ``serve.assemble``,
  ``serve.step`` (with ``rows`` and ``live``) and ``serve.resolve``; the
  engine's ``serve.launch`` sits inside ``serve.step``. With the rerank
  store in host memory, ``serve.host_take`` (``rows``, ``bytes``) and
  ``serve.host_copy`` sit there too, and ``ServeStats`` counts the rows'
  bytes and each batch's ``host_take_ms``.
* Named scopes (``jax.named_scope``) of the compiled ``serve_step``: every
  compute instruction that comes from a traced operation carries one of
  ``search.prepare`` / ``probe`` / ``scan`` / ``merge`` / ``rerank``.
"""
import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.data import vectors
from repro.launch import serve
from repro.serve.engine import ServingEngine
from repro.serve.frontend import ServingFrontend

pytestmark = pytest.mark.tier1

D, BATCH = 32, 8
ROUND_PHASES = ("serve.take", "serve.assemble", "serve.step",
                "serve.resolve")
SCOPES = ("search.prepare", "search.probe", "search.scan", "search.merge",
          "search.rerank")
SERVED = {
    "flat-sorted": (["--mode", "gleanvec-int8-sorted"],
                    {"search.prepare", "search.scan", "search.merge",
                     "search.rerank"}),
    "flat-rows": (["--mode", "gleanvec-int8"],
                  {"search.prepare", "search.scan", "search.merge",
                   "search.rerank"}),
    "ivf-aligned": (["--mode", "gleanvec-int8-sorted", "--index", "ivf",
                     "--aligned", "--reduced-probe"],
                    {"search.prepare", "search.probe", "search.scan",
                     "search.rerank"}),
    "ivf": (["--mode", "gleanvec-int8", "--index", "ivf"],
            set(SCOPES)),
}


@pytest.fixture(scope="module")
def data():
    return vectors.make_dataset("tracing", n=4096, d=D, n_queries=64,
                                ood=True, seed=5)


def served_state(ds, extra):
    args = serve.parser().parse_args(
        ["--n", "4096", "--dim", str(D), "--d", "16", "--clusters", "4",
         "--lists", "8", "--nprobe", "2", *extra])
    return serve.build_state(args, ds.database, serve.fit_model(args, ds))


def host_spans(log_dir):
    """``serve.*`` events of the newest trace under ``log_dir``:
    (name, line, start_ns, end_ns, stats)."""
    path = max(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    return [(e.name, (p.name, ln.name), e.start_ns,
             e.start_ns + e.duration_ns, dict(e.stats))
            for p in ProfileData.from_file(str(path)).planes
            for ln in p.lines for e in ln.events
            if e.name.startswith("serve.")]


def inside(span, outers):
    _, line, s, e, _ = span
    return any(o[1] == line and o[2] <= s and e <= o[3] for o in outers)


def named(spans, name):
    return [s for s in spans if s[0] == name]


@pytest.fixture(scope="module")
def engine(data):
    return ServingEngine(served_state(data, SERVED["flat-sorted"][0]), k=10,
                         kappa=20, batch_size=BATCH, dim=D)


def test_dispatcher_round_spans_nest(data, engine, tmp_path):
    fe = ServingFrontend(engine, capacity=64, start=False)
    queries = np.asarray(data.queries_test)
    with jax.profiler.trace(str(tmp_path)):
        for n in (3, BATCH, 5):
            futures = [fe.enqueue(q) for q in queries[:n]]
            while fe.queue_depth:
                fe.drain_once()
            assert all(f.result().shape == (10,) for f in futures)
    spans = host_spans(tmp_path)
    rounds = named(spans, "serve.round")
    assert len(rounds) == 3
    for name in ROUND_PHASES:
        phase = named(spans, name)
        assert len(phase) == 3, name
        assert all(inside(s, rounds) for s in phase), name
    steps = named(spans, "serve.step")
    launches = named(spans, "serve.launch")
    assert len(launches) == 3
    assert all(inside(s, steps) for s in launches)
    assert sorted((s[4]["rows"], s[4]["live"]) for s in steps) \
        == [(4, 3), (8, 5), (8, 8)]


def test_submit_step_spans(data, engine, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        ids = engine.submit(np.asarray(data.queries_test[:BATCH + 3]))
    assert ids.shape == (BATCH + 3, 10)
    spans = host_spans(tmp_path)
    steps = named(spans, "serve.step")
    assert sorted((s[4]["rows"], s[4]["live"]) for s in steps) \
        == [(BATCH, 3), (BATCH, BATCH)]
    launches = named(spans, "serve.launch")
    assert len(launches) == 2 and all(inside(s, steps) for s in launches)


def test_host_tier_spans_and_counters_on_the_frontend_path(data, tmp_path):
    kappa = 20
    engine = ServingEngine(
        served_state(data, [*SERVED["ivf-aligned"][0], "--host-rerank"]),
        k=10, kappa=kappa, batch_size=BATCH, dim=D)
    fe = ServingFrontend(engine, capacity=64, start=False)
    s = engine.stats
    before = (s.host_bytes, s.host_bytes_lb, len(s.host_take_ms))
    queries = np.asarray(data.queries_test)
    with jax.profiler.trace(str(tmp_path)):
        for n in (3, BATCH):
            futures = [fe.enqueue(q) for q in queries[:n]]
            while fe.queue_depth:
                fe.drain_once()
            assert all(f.result().shape == (10,) for f in futures)
    spans = host_spans(tmp_path)
    steps = named(spans, "serve.step")
    takes = named(spans, "serve.host_take")
    copies = named(spans, "serve.host_copy")
    assert len(steps) == len(takes) == len(copies) == 2
    assert all(inside(t, steps) for t in takes + copies)
    rows = sorted(st[4]["rows"] * kappa for st in steps)       # 4 and 8
    assert sorted(t[4]["rows"] for t in takes) == rows
    assert sorted(t[4]["bytes"] for t in takes) == [r * D * 4 for r in rows]
    moved = sum(rows) * D * 4
    assert (s.host_bytes, s.host_bytes_lb, len(s.host_take_ms)) \
        == (before[0] + moved, before[1] + moved, before[2] + 2)
    assert all(ms > 0 for ms in list(s.host_take_ms)[-2:])


# -- the compiled step's scopes ---------------------------------------------

COMPUTATION = re.compile(r"^(ENTRY )?%(\S+) .*\{\s*$")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*)$")
OPCODE = re.compile(r"[\]})] ([a-z][a-z0-9-]*)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
CONTROL = ("while", "conditional", "call")
NOT_COMPUTE = ("parameter", "constant", "tuple", "get-tuple-element",
               "bitcast")


def executed_instructions(text):
    """(name, opcode, op_name) of every instruction of the computations the
    step runs as operations: the entry and, through control flow, the
    loop bodies, conditions and branches (not the bodies of fusions or of
    comparators, which run inside their caller)."""
    comps, entry, cur = {}, None, None
    for line in text.splitlines():
        m = COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(2), [])
            entry = m.group(2) if m.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur is not None and INSTRUCTION.match(line):
            name, rest = INSTRUCTION.match(line).groups()
            op = OPCODE.search(rest)
            cur.append((name, op.group(1) if op else "", rest))
    out, todo, seen = [], [entry], set()
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for name, op, rest in comps[comp]:
            meta = OP_NAME.search(rest)
            out.append((name, op, meta.group(1) if meta else None))
            if op in CONTROL:
                todo += re.findall(
                    r"(?:body|condition|to_apply|true_computation|"
                    r"false_computation)=%?([\w.\-]+)", rest)
                for group in re.findall(r"branch_computations=\{([^}]*)\}",
                                        rest):
                    todo += [c.strip().lstrip("%") for c in group.split(",")]
    return out


def innermost_scope(op_name):
    found = re.findall(r"(?:^|/)(search\.[a-z]+)(?=/|$)", op_name or "")
    return found[-1] if found else None


@pytest.mark.parametrize("name", list(SERVED))
def test_compiled_step_carries_scopes(data, name):
    extra, expected = SERVED[name]
    state = served_state(data, extra)
    eng = ServingEngine(state, k=10, kappa=20, batch_size=BATCH, dim=D)
    text = eng.lower(BATCH).compile().as_text()
    assert text.startswith("HloModule jit_serve_step")
    unscoped, scopes = [], set()
    for instr, op, op_name in executed_instructions(text):
        # only traced operations have a path (``jit(...)/...``); copies
        # XLA inserts carry none, and an argument's relayout carries the
        # argument's name
        if op in NOT_COMPUTE or op in CONTROL \
                or not (op_name or "").startswith("jit("):
            continue
        scope = innermost_scope(op_name)
        if scope is None:
            unscoped.append((instr, op, op_name))
        else:
            scopes.add(scope)
    assert unscoped == []
    assert scopes == expected


def test_innermost_scope():
    assert innermost_scope("jit(serve_step)/search.merge/while/body/"
                           "search.scan/jit(f)/dot_general") == "search.scan"
    assert innermost_scope("jit(serve_step)/search.rerank/gather") \
        == "search.rerank"
    assert innermost_scope("jit(serve_step)/research.scan/x") is None
    assert innermost_scope("state.artifacts.x_full") is None


def test_serve_cli_trace_dir(tmp_path, monkeypatch):
    # keep the CLI's compile cache out of the checkout
    monkeypatch.setattr(serve.runtime, "configure", lambda: "")
    serve.main(["--mode", "gleanvec-int8-sorted", "--n", "2048", "--dim",
                str(D), "--d", "16", "--clusters", "4", "--batch", "16",
                "--kappa", "20", "--trace-dir", str(tmp_path)])
    assert glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)
    assert named(host_spans(tmp_path), "serve.step")
