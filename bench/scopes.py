"""Split a traced slice's serving steps by the program's named scopes, and
name its idle gaps by the program's host spans.

The program names the phases of its compiled step with ``jax.named_scope``
(``search.prepare``, ``search.probe``, ``search.scan``, ``search.merge``,
``search.rerank``) and its dispatcher round with host spans (``serve.round``
around ``serve.take``, ``serve.assemble``, ``serve.step`` and
``serve.resolve``; ``serve.launch`` inside ``serve.step``).

A TPU trace names each device operation by its HLO instruction
(``%fusion.12 = f32[64,4096]{...} fusion(...)``) and carries nothing of the
scope it was traced under: that path is the instruction's ``op_name`` in
the compiled module. So the split reads the trace beside the text of the
executable that the traced steps ran (``compiled.as_text()``), and reads
only the steps of that executable's bucket (``serve.step``'s ``rows``).
The host's spans and the device's operations come on two timelines that a
TPU trace can set apart by a hundred milliseconds or more, so the split
first shifts the device's timeline by the amount that puts the most busy
time inside the host's step spans (:func:`clock_offset`).

Control flow (``while``, ``conditional``, ``call``) gets no scope: its
event spans the operations of its body, which have their own. An
instruction that no traced operation made (a copy XLA inserted, or an
argument's relayout, which carries the argument's name) is charged to the
one scope of the instructions that read it, where they share one.
"""
from __future__ import annotations

import copy
import re
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from bench import trace_reduce as tr

SCOPES = ("search.prepare", "search.probe", "search.scan", "search.merge",
          "search.rerank")
ROUND, STEP = "serve.round", "serve.step"
HOST_PREFIX = "serve."
CONTROL = ("while", "conditional", "call")

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*)$")
_OPCODE = re.compile(r"[\]})] ([a-z][a-z0-9-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"(?:^|/)(search\.[a-z]+)(?=/|$)")
_REF = re.compile(r"%([\w.\-]+)")


class Span(NamedTuple):
    """One host span of the program, with its arguments (``stats``)."""
    name: str
    start_ns: float
    end_ns: float
    stats: dict


def innermost_scope(op_name: Optional[str]) -> Optional[str]:
    """The last ``search.*`` component of an ``op_name`` path, or None."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else None


def _operands(rest: str, start: int) -> List[str]:
    """The instructions named in the operand list that opens at
    ``rest[start]``."""
    depth = 0
    for i in range(start, len(rest)):
        depth += {"(": 1, ")": -1}.get(rest[i], 0)
        if depth == 0:
            return _REF.findall(rest[start:i])
    return _REF.findall(rest[start:])


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """``{instruction: scope}`` for every instruction of a compiled module
    (names are unique in a module): its innermost ``search.*`` scope,
    ``""`` where it has none, ``"control"`` for control flow. An
    instruction no traced operation made takes the scope its readers
    share."""
    out: Dict[str, str] = {}
    readers: Dict[str, List[str]] = {}
    made_by_xla = []
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, rest = m.groups()
        op = _OPCODE.search(rest)
        meta = _OP_NAME.search(rest)
        path = meta.group(1) if meta else ""
        if op and op.group(1) in CONTROL:
            out[name] = "control"
        else:
            out[name] = innermost_scope(path) or ""
        if not path.startswith("jit("):
            made_by_xla.append(name)
        if op:
            for operand in _operands(rest, op.end() - 1):
                readers.setdefault(operand, []).append(name)
    changed = True
    while changed:
        changed = False
        for name in made_by_xla:
            shared = {out.get(r, "") for r in readers.get(name, ())}
            if not out[name] and len(shared) == 1 \
                    and shared <= set(SCOPES):
                out[name] = shared.pop()
                changed = True
    return out


def instruction(event_name: str) -> str:
    """The HLO instruction of a device op's event name."""
    return event_name.partition(" = ")[0].strip().lstrip("%")


def clock_offset(busy: Sequence[Tuple[float, float]],
                 steps: Sequence[Tuple[float, float]]) -> float:
    """The shift (ns) to add to the device's times that puts the most of
    its ``busy`` intervals inside the host's ``steps`` (each step's device
    work runs between its launch and its return), at a resolution of 10 us
    or finer; of the equal best shifts, the middle of the run nearest to
    no shift. 0 when either side is empty."""
    if not busy or not steps:
        return 0.0
    lo = min(min(s for s, _ in busy), min(s for s, _ in steps))
    hi = max(max(e for _, e in busy), max(e for _, e in steps))
    res_ns = max(1.0, min(10_000.0, (hi - lo) / 200_000))
    n = int((hi - lo) // res_ns) + 1

    def mask(intervals):
        m = np.zeros(n)
        for s, e in intervals:
            m[int((s - lo) // res_ns):int((e - lo) // res_ns)] = 1.0
        return m

    size = 2 * n
    # inside[d] = sum_t busy[t] * steps[t + d], every lag d at once
    inside = np.fft.irfft(np.conj(np.fft.rfft(mask(busy), size))
                          * np.fft.rfft(mask(steps), size), size)
    inside = np.concatenate([inside[n + 1:], inside[:n]])
    lags = np.arange(1 - n, n)
    best = np.flatnonzero(inside >= inside.max() - 0.5)
    runs = np.split(best, np.flatnonzero(np.diff(best) > 1) + 1)
    run = min(runs, key=lambda r: np.abs(lags[r]).min())
    return float(lags[run[len(run) // 2]] * res_ns)


def host_spans(path: str) -> List[Span]:
    """Every ``serve.*`` span of a trace file, with its arguments."""
    from jax.profiler import ProfileData
    return [Span(e.name, float(e.start_ns),
                 float(e.start_ns + e.duration_ns), dict(e.stats))
            for p in ProfileData.from_file(path).planes
            for ln in p.lines for e in ln.events
            if e.name.startswith(HOST_PREFIX)]


class Split:
    """The traced slice of ``events`` (:func:`bench.trace_reduce.load`),
    its program spans ``spans`` (:func:`host_spans`) and the text of the
    executable of the ``rows``-row bucket. ``device_plane`` and
    ``ops_line`` find the device operations, as in
    :class:`bench.trace_reduce.Reduced`."""

    def __init__(self, events: List[tr.Event], spans: List[Span],
                 hlo_text: str, rows: int, device_plane=tr.DEVICE_PLANE,
                 ops_line=tr.OPS_LINE):
        win = max((e for e in events if e.name == tr.WINDOW),
                  key=lambda e: e.dur_ns)
        lo, hi = win.start_ns, win.end_ns
        self.spans = [s for s in spans if s.end_ns > lo and s.start_ns < hi]
        inside = [s for s in self.spans if lo <= s.start_ns
                  and s.end_ns <= hi]
        self.steps = [(s.start_ns, s.end_ns) for s in inside
                      if s.name == STEP and s.stats.get("rows") == rows]
        self.rounds = [(s.start_ns, s.end_ns) for s in inside
                       if s.name == ROUND]
        on_device = [e for e in events if device_plane.match(e.plane)]
        self.offset_ns = clock_offset(
            tr._union((e.start_ns, e.end_ns) for e in on_device
                      if ops_line.match(e.line)), self.steps)
        self.trace = tr.Reduced(
            [e for e in events if not device_plane.match(e.plane)]
            + [e._replace(start_ns=e.start_ns + self.offset_ns)
               for e in on_device], device_plane, ops_line)
        self._scope = op_scopes(hlo_text)
        self._step_ops = [e for e in self.trace.ops
                          if instruction(e.name) in self._scope]

    def _scoped(self, scopes: Sequence[str], lo: float, hi: float) -> float:
        """Device seconds inside [lo, hi) of the ops of ``scopes`` (each
        plane's intervals united), averaged over planes."""
        planes = self.trace.planes
        total = 0.0
        for p in planes:
            ivs = [(e.start_ns, e.end_ns) for e in self._step_ops
                   if e.plane == p and self._scope[instruction(e.name)]
                   in scopes]
            total += tr._length(tr._clip(tr._union(ivs), lo, hi))
        return total / max(1, len(planes)) / 1e9

    def scope_ms(self, *scopes: str) -> Optional[float]:
        """Mean over the traced steps of the device time of ``scopes``."""
        if not self.steps:
            return None
        return 1e3 * sum(self._scoped(scopes, *s) for s in self.steps) \
            / len(self.steps)

    def busy_ms(self) -> Optional[float]:
        """Mean over the traced steps of the device's busy time."""
        if not self.steps:
            return None
        return 1e3 * sum(self.trace.busy_in(*s) for s in self.steps) \
            / len(self.steps)

    def coverage(self) -> Optional[float]:
        """Share of the steps' busy time that some scope's ops cover."""
        busy = self.busy_ms()
        return self.scope_ms(*SCOPES) / busy if busy else None

    def by_scope(self, top: int = 5) -> Dict[str, List[List]]:
        """Each scope's ``top`` operations inside the steps, by summed
        time: {scope: [[short name, seconds], ...]}, with the operations
        that carry no scope (control flow aside) under ``""``."""
        by: Dict[str, Dict[str, float]] = {}
        for e in self._step_ops:
            scope = self._scope[instruction(e.name)]
            t = sum(tr._length(tr._clip([(e.start_ns, e.end_ns)], *s))
                    for s in self.steps)
            if scope != "control" and t > 0:
                ops = by.setdefault(scope, {})
                name = tr.short_name(e.name)
                ops[name] = ops.get(name, 0.0) + t / 1e9
        n = max(1, len(self.trace.planes))
        return {scope: [[k, v / n] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]]
            for scope, ops in by.items()}

    def host_round_ms(self) -> Optional[float]:
        """Mean over the rounds wholly in the slice of ``serve.round`` less
        the ``serve.step`` inside it: the dispatcher's host time."""
        if not self.rounds:
            return None
        steps = [(s.start_ns, s.end_ns) for s in self.spans
                 if s.name == STEP]
        own = [(hi - lo) - tr._length(tr._clip(steps, lo, hi))
               for lo, hi in self.rounds]
        return sum(own) / len(own) / 1e6

    def idle_gaps(self, top: int = 10) -> List[List]:
        """The ``top`` longest idle stretches, each named by the innermost
        ``bench.*`` or ``serve.*`` span covering at least half of it."""
        named = copy.copy(self.trace)
        named.spans = self.trace.spans + [
            tr.Event("host", "program", s.name, s.start_ns,
                     s.end_ns - s.start_ns) for s in self.spans]
        return named.idle_gaps(top)

    def summary(self) -> Dict[str, object]:
        """Every number of the split, as one JSON-ready dict: the four
        phases (``probe_ms`` is query prep and the coarse probe together),
        prep alone, and what else the steps' busy time holds."""
        by = self.by_scope()
        unscoped = by.pop("", [])
        return dict(
            steps=len(self.steps), rounds=len(self.rounds),
            clock_offset_ms=self.offset_ns / 1e6,
            probe_ms=self.scope_ms("search.prepare", "search.probe"),
            prepare_ms=self.scope_ms("search.prepare"),
            scan_ms=self.scope_ms("search.scan"),
            merge_ms=self.scope_ms("search.merge"),
            rerank_ms=self.scope_ms("search.rerank"),
            busy_ms=self.busy_ms(), coverage=self.coverage(),
            host_round_ms=self.host_round_ms(), by_scope=by,
            unscoped=unscoped, idle_gaps=self.idle_gaps(),
            device_ops=self.trace.device_ops(10))
