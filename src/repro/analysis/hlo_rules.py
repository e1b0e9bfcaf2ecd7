"""HLO-layer rules: declarative checks over ``compiled.as_text()`` +
``normalize_cost(cost_analysis())`` for any jitted program.

These promote the perf story's load-bearing assertions into reusable
rules: the fused kernels' HBM wins exist precisely because certain
buffers NEVER materialize (the dense ``(m, n)`` / ``(m, nprobe*L)`` /
``(m, beam+expand*R)`` score matrices), serving steps never bounce
through the host, donated serving state actually aliases its outputs,
and traversal loops respect their trip ceilings.

Backend note: on CPU the Pallas kernels run in interpret mode, whose
emulation lowers ``pl.load`` to real HLO gathers -- so
:class:`NoGatherOnFusedPath` is a TPU/GPU contract and self-skips
elsewhere (raw-text subjects have no backend and always check, which is
what the fixture tests use). A program compiled ahead of time for a
described TPU topology reports that topology's platform, so the contract
is checked on TPU-compiled HLO from a CPU-only host too. :class:`NoDenseScoreMatrix` is
backend-independent: interpret mode preserves blocking, so the forbidden
shapes stay absent even on CPU (asserted since PR 5).
"""
from __future__ import annotations

import re
from typing import Optional, Sequence

from repro.analysis.registry import Rule, RuleResult
from repro.utils import hlo_analysis

__all__ = ["HLOProgram", "NoDenseScoreMatrix", "BufferPresent",
           "NoGatherOnFusedPath", "NoHostTransferInStep",
           "DonationCoverage", "WhileTripBudget", "donated_params"]

# input_output_alias={ {1}: (1, {}, may-alias), ... } -- the tuple's first
# field is the donated PARAMETER number (XLA prints the same syntax in
# both text dialects).
_ALIAS_ENTRY_RE = re.compile(r"\{[\d,\s]*\}:\s*\(\s*(\d+)\s*,")

_GATHER_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*\(?\s*(\w+)\[([\d,]*)\][^=]*?"
    r"\b(gather|dynamic-gather)\(", re.M)

_HOST_MARKERS = ("infeed(", "outfeed(", "send(", "recv(", "send-done(",
                 "recv-done(", "MoveToHost", "MoveToDevice")
_HOST_SPACE_RE = re.compile(r"\bS\(5\)")


def donated_params(hlo_text: str) -> frozenset:
    """Parameter numbers the module header marks as donation sources
    (``input_output_alias``). Empty when nothing is donated. The entries
    nest braces (``{1}: (1, {}, may-alias)``), so the block is taken by
    balanced-brace scan, not regex."""
    at = hlo_text.find("input_output_alias=")
    if at < 0:
        return frozenset()
    seg, depth = "", 0
    for ch in hlo_text[hlo_text.find("{", at):]:
        seg += ch
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
            if depth == 0:
                break
    return frozenset(int(e) for e in _ALIAS_ENTRY_RE.findall(seg))


def _platform(compiled) -> str:
    """The platform ``compiled`` was built for: its input devices' (an AOT
    compile for a described topology is not the host's backend), else
    the default backend."""
    import jax
    for sharding in jax.tree.leaves(getattr(compiled, "input_shardings",
                                            ())):
        return next(iter(sharding.device_set)).platform
    return jax.default_backend()


class HLOProgram:
    """One compiled program as the HLO rules see it: post-opt text, the
    normalized cost dict, parsed trip/byte stats, the defined-buffer
    shape set, and the backend it was compiled for (None for raw text)."""

    def __init__(self, hlo_text: str, cost: Optional[dict] = None,
                 backend: Optional[str] = None, label: str = ""):
        self.text = hlo_text
        self.cost = cost or {}
        self.backend = backend
        self.label = label
        self._shapes = None
        self._stats = None

    @classmethod
    def of(cls, subject, label: str = "") -> "HLOProgram":
        """Wrap a ``Compiled`` object / ``Lowered`` / raw HLO text."""
        if isinstance(subject, HLOProgram):
            return subject
        if isinstance(subject, str):
            return cls(subject, label=label)
        if hasattr(subject, "compile") and not hasattr(subject, "as_text"):
            subject = subject.compile()
        cost = {}
        try:
            cost = hlo_analysis.normalize_cost(subject.cost_analysis())
        except Exception:   # cost analysis is best-effort on some backends
            pass
        return cls(subject.as_text(), cost=cost,
                   backend=_platform(subject), label=label)

    @property
    def buffer_shapes(self):
        if self._shapes is None:
            self._shapes = hlo_analysis.buffer_shapes(self.text)
        return self._shapes

    @property
    def stats(self):
        if self._stats is None:
            self._stats = hlo_analysis.analyze_hlo(self.text)
        return self._stats

    @property
    def donated(self):
        return donated_params(self.text)


def _shape_key(dims: Sequence[int], dtype: str) -> str:
    return f"{dtype}[{','.join(str(int(d)) for d in dims)}]"


class _ShapeRule(Rule):
    family = "hlo"

    def __init__(self, *dims: int, dtypes: Sequence[str] = ("f32", "s32")):
        self.dims = tuple(int(d) for d in dims)
        self.keys = tuple(_shape_key(self.dims, dt) for dt in dtypes)

    def _present(self, program: HLOProgram):
        return sorted(k for k in self.keys if k in program.buffer_shapes)


class NoDenseScoreMatrix(_ShapeRule):
    """FORBIDDEN buffer shapes: the fused paths' HBM win is that no
    buffer of the dense score-matrix shape exists anywhere in the module
    (any dtype of interest -- scores f32, ids s32)."""

    name = "NoDenseScoreMatrix"
    contract = ("no fused-path module defines a dense score-matrix "
                "buffer of the forbidden (rows, cols) shape")

    def check(self, program: HLOProgram) -> RuleResult:
        hit = self._present(program)
        if hit:
            return self._fail(f"forbidden dense buffer(s) present: {hit}")
        return self._pass(f"none of {list(self.keys)} defined")


class BufferPresent(_ShapeRule):
    """The positive twin (gathered baselines DO materialize the dense
    matrix): at least one of the shapes must exist. Keeps the old
    ``assert shape in hlo`` tests honest about what they compare."""

    name = "BufferPresent"
    contract = ("the gathered baseline really materializes the dense "
                "buffer the fused path is measured against")

    def check(self, program: HLOProgram) -> RuleResult:
        hit = self._present(program)
        if hit:
            return self._pass(f"present: {hit}")
        return self._fail(f"expected one of {list(self.keys)}; "
                          "module defines none")


class NoGatherOnFusedPath(Rule):
    """No gather whose result exceeds ``max_bytes`` on a fused path: the
    scalar-prefetch schedule streams slabs instead of gathering rows.
    Skips on CPU-compiled programs (Pallas interpret emulation gathers)."""

    name = "NoGatherOnFusedPath"
    family = "hlo"
    contract = ("fused kernel paths stream slabs via the scalar-prefetch "
                "schedule; no large row-gather appears in the module")

    def __init__(self, max_bytes: int = 0,
                 backends: Sequence[str] = ("tpu", "gpu")):
        self.max_bytes = int(max_bytes)
        self.backends = tuple(backends)

    def check(self, program: HLOProgram) -> RuleResult:
        if program.backend is not None \
                and program.backend not in self.backends:
            return self._skip(
                f"backend {program.backend!r}: Pallas interpret mode "
                "emulates loads as gathers; contract holds on "
                f"{list(self.backends)} only")
        big = []
        for m in _GATHER_RE.finditer(program.text):
            dtype, dims = m.group(1), m.group(2)
            nbytes = hlo_analysis._shape_bytes(dtype, dims)
            if nbytes > self.max_bytes:
                big.append(f"{dtype}[{dims}]={nbytes}B")
        if big:
            return self._fail(
                f"gather result(s) over {self.max_bytes}B: {big}")
        return self._pass("no gather above budget")


class NoHostTransferInStep(Rule):
    """Serving steps (``state_search`` / ``state_candidates`` bodies)
    never move data host<->device: no infeed/outfeed/send/recv, no
    host-memory-space (``S(5)``) buffers, no MoveToHost/MoveToDevice
    custom calls. The host rerank tier runs OUTSIDE the compiled step."""

    name = "NoHostTransferInStep"
    family = "hlo"
    contract = ("compiled serving steps contain no host<->device "
                "transfer; the rerank tier's host gather stays outside")

    def check(self, program: HLOProgram) -> RuleResult:
        hits = []
        for i, ln in enumerate(program.text.splitlines()):
            s = ln.strip()
            if "=" not in s:
                continue
            if any(mk in s for mk in _HOST_MARKERS) \
                    or _HOST_SPACE_RE.search(s):
                hits.append(f"line {i + 1}: {s[:90]}")
        if hits:
            return self._fail("host transfer markers: " + "; ".join(hits))
        return self._pass("no host-transfer instruction")


class DonationCoverage(Rule):
    """Every parameter the caller donates is an ``input_output_alias``
    source in the compiled module -- i.e. donation actually took, and a
    swap does not silently double the state's memory footprint."""

    name = "DonationCoverage"
    family = "hlo"
    contract = ("donated ServingState leaves are input_output_alias "
                "sources in the compiled step (no double-buffered state)")

    def __init__(self, params: Sequence[int]):
        self.params = frozenset(int(p) for p in params)

    def check(self, program: HLOProgram) -> RuleResult:
        donated = program.donated
        missing = sorted(self.params - donated)
        if missing:
            return self._fail(
                f"parameters {missing} not aliased "
                f"(aliased: {sorted(donated)})")
        return self._pass(f"all {len(self.params)} donated params aliased")


class WhileTripBudget(Rule):
    """Every while loop's resolved trip count stays within budget --
    beam hops and blocked scans have static ceilings; a runaway trip
    count means a schedule/layout regression."""

    name = "WhileTripBudget"
    family = "hlo"
    contract = ("every while loop in the compiled step runs at most "
                "max_trips iterations (beam-hop / scan ceilings)")

    def __init__(self, max_trips: int):
        self.max_trips = int(max_trips)

    def check(self, program: HLOProgram) -> RuleResult:
        trips = program.stats["while_trips"]
        over = {b: t for b, t in trips.items() if t > self.max_trips}
        if over:
            return self._fail(
                f"loops over budget {self.max_trips}: {over}")
        return self._pass(f"{len(trips)} loop(s) within {self.max_trips}")
