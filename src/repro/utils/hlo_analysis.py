"""Post-optimization HLO text analysis: collective bytes, per-computation
FLOPs, and while-loop trip-count correction.

Why this exists: ``compiled.cost_analysis()`` counts every ``while`` body
(scan over layers, loss chunks, gradient-accumulation microbatches, ...)
exactly ONCE (verified empirically on jax 0.8 / XLA CPU), and exposes no
collective traffic at all. We therefore parse ``compiled.as_text()``:

  * every instruction is attributed to its enclosing computation;
  * operand shapes are resolved through a module-wide definition table
    (post-opt HLO lists operands as bare %names);
  * ``while`` trip counts come from the condition computation's ROOT
    ``compare(%iv, %constant), direction=LT`` pattern; failing that, the
    caller-provided default applies. Nested loops multiply.
  * collective bytes = sum of operand-buffer sizes of all-gather /
    all-reduce / reduce-scatter / all-to-all / collective-permute;
  * dot FLOPs = 2 * prod(result_shape) * contracting_size.

All byte sizes are per-device (the HLO is the post-SPMD module).

Dialect note: jax <= 0.4 / older XLA prints every name with a ``%`` sigil
and full computation signatures (``ENTRY %main.9 (p.1: f32[8]) -> f32[8]
{``); newer XLA (jax >= 0.6) drops the sigil and may print bare headers
(``ENTRY main.9 {``) and bare operand names (``add(p.1, c.2)``). Every
regex here treats the sigil and the signature as optional, and operand
extraction falls back to last-token parsing when no sigil is present --
``tests/fixtures/hlo/`` pins one fixture per dialect.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, FrozenSet, Optional, Tuple

import numpy as np

__all__ = ["analyze_hlo", "buffer_shapes", "normalize_cost", "HLOStats"]


def normalize_cost(cost) -> dict:
    """``Compiled.cost_analysis()`` as a plain properties dict (callers
    index by property name, ``cost["flops"]``); None becomes ``{}``."""
    return dict(cost or {})

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# computation header: '%name (sig) -> ... {' (0.4) or bare 'name {' (0.6+)
_COMP_START_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*[({]")
_WHILE_RE = re.compile(
    r"while\(.*?\).*?condition=%?([\w.\-]+).*?body=%?([\w.\-]+)")
_DOT_DNUMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
# '%name = (' tuple results keep the FIRST element shape for the def table
_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\(?\s*(\w+)\[([\d,]*)\]")
_SIG_RE = re.compile(r"%?([\w.\-]+):\s*(\w+)\[([\d,]*)\]")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_NAME_TAIL_RE = re.compile(r"([\w.\-]+)\s*$")

MAX_SANE_TRIPS = 1_000_000


class HLOStats(dict):
    """keys: collective_bytes, collective_by_kind, n_collectives,
    dot_flops, write_bytes, while_trips."""


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _operand_names(s: str):
    inner = s.split("(", 1)[1]
    depth, cur = 1, ""
    for ch in inner:
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                break
        cur += ch
    if "%" in cur:
        return re.findall(r"%([\w.\-]+)", cur)
    # sigil-less dialect: operands are 'f32[8]{0} name' or bare 'name';
    # split at depth-0 commas and keep each piece's trailing identifier
    names, depth, piece, pieces = [], 0, "", []
    for ch in cur:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == "," and depth == 0:
            pieces.append(piece)
            piece = ""
        else:
            piece += ch
    pieces.append(piece)
    for p in pieces:
        m = _NAME_TAIL_RE.search(p.strip())
        if m:
            names.append(m.group(1))
    return names


def _result_shapes(line: str):
    """(dtype, dims) pairs of the buffer(s) an instruction DEFINES --
    tuple results contribute every element; operand shapes are excluded."""
    rhs = line.split("=", 1)[1].lstrip()
    if rhs.startswith("("):
        depth, seg = 0, ""
        for ch in rhs:
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
            seg += ch
        return [(m.group(1), m.group(2)) for m in _SHAPE_RE.finditer(seg)]
    m = _SHAPE_RE.match(rhs)
    return [(m.group(1), m.group(2))] if m else []


def buffer_shapes(hlo_text: str) -> FrozenSet[str]:
    """Every buffer shape the module DEFINES, as normalized
    ``dtype[d0,d1]`` strings: instruction results (tuple elements
    included) plus computation parameters from either dialect's
    signatures. The NoDenseScoreMatrix-style rules check forbidden shapes
    against this set -- operand mentions alone never add a shape, so a
    shape is present iff some buffer of that shape actually exists."""
    shapes = set()
    for ln in hlo_text.splitlines():
        if not ln.strip() or ln.startswith("HloModule"):
            continue
        if _DEF_RE.match(ln):
            for dt, dims in _result_shapes(ln):
                shapes.add(f"{dt}[{dims}]")
        elif ln[0] not in " \t" and "(" in ln:
            # computation header: parameters are buffers too
            for ms in _SIG_RE.finditer(ln.split("->")[0]):
                shapes.add(f"{ms.group(2)}[{ms.group(3)}]")
    return frozenset(shapes)


def analyze_hlo(hlo_text: str,
                default_trips: Optional[Dict[str, int]] = None,
                fallback_trip: int = 1) -> HLOStats:
    lines = hlo_text.splitlines()
    default_trips = default_trips or {}

    # ---- computations ------------------------------------------------------
    comp_of_line: Dict[int, str] = {}
    current = None
    for i, ln in enumerate(lines):
        if not ln.strip() or ln.startswith("HloModule"):
            continue
        if ln and not ln[0].isspace():
            m = _COMP_START_RE.match(ln)
            if m and ln.rstrip().endswith("{"):
                current = m.group(1)
        if current is not None:
            comp_of_line[i] = current

    # ---- definition table --------------------------------------------------
    defs: Dict[str, Tuple[str, str]] = {}
    line_of_def: Dict[str, int] = {}
    for i, ln in enumerate(lines):
        m = _DEF_RE.match(ln)
        if m:
            defs[m.group(1)] = (m.group(2), m.group(3))
            line_of_def[m.group(1)] = i
        elif ln and not ln[0].isspace() and "(" in ln:
            for ms in _SIG_RE.finditer(ln):
                defs[ms.group(1)] = (ms.group(2), ms.group(3))

    # constants per computation: name -> int value (for trip resolution)
    const_val: Dict[str, int] = {}
    for i, ln in enumerate(lines):
        m = _DEF_RE.match(ln)
        if m and "constant(" in ln:
            mc = _CONST_RE.search(ln)
            if mc:
                const_val[m.group(1)] = int(mc.group(1))

    # ---- while edges & trip counts -----------------------------------------
    while_edges = []
    for i, ln in enumerate(lines):
        if "while(" in ln and "condition=" in ln:
            m = _WHILE_RE.search(ln)
            if m:
                while_edges.append(
                    (comp_of_line.get(i, "ENTRY"), m.group(2), m.group(1)))

    # ROOT instruction of each condition computation + per-comp s32 consts
    root_of_comp: Dict[str, str] = {}
    s32_consts_in_comp: Dict[str, list] = defaultdict(list)
    for i, ln in enumerate(lines):
        comp = comp_of_line.get(i)
        if comp is None:
            continue
        if "ROOT" in ln:
            root_of_comp[comp] = ln
        m = _DEF_RE.match(ln)
        if m and m.group(2) == "s32" and m.group(3) == "" \
                and "constant(" in ln:
            mc = _CONST_RE.search(ln)
            if mc:
                s32_consts_in_comp[comp].append(int(mc.group(1)))

    trips_of_body: Dict[str, int] = {}
    for _parent, body, cond in while_edges:
        trips = None
        root = root_of_comp.get(cond)
        if root is not None:
            # resolve the loop bound through the ROOT's constant operand
            for name in _operand_names(root):
                if name in const_val:
                    trips = const_val[name]
                    break
        if trips is None and s32_consts_in_comp.get(cond):
            # condition computations are tiny; their largest scalar s32
            # constant is the loop bound
            trips = max(s32_consts_in_comp[cond])
        if trips is None or trips <= 0 or trips > MAX_SANE_TRIPS:
            trips = fallback_trip   # conservative under-count
        trips_of_body[body] = trips

    # call edges (fusion/call/conditional computations inherit the caller's
    # multiplier with trips=1)
    call_edges = []
    call_re = re.compile(r"calls=%?([\w.\-]+)")
    for i, ln in enumerate(lines):
        if "calls=" in ln and "while(" not in ln:
            comp = comp_of_line.get(i)
            if comp is None:
                continue
            for mc in call_re.finditer(ln):
                call_edges.append((comp, mc.group(1)))

    # nesting multipliers (fixpoint over the small call/while graph)
    mult: Dict[str, float] = defaultdict(lambda: 1.0)
    for _ in range(16):
        changed = False
        for parent, body, _c in while_edges:
            m_new = mult[parent] * trips_of_body[body]
            if mult[body] != m_new:
                mult[body] = m_new
                changed = True
        for parent, callee in call_edges:
            m_new = max(mult[callee], mult[parent])
            if mult[callee] != m_new:
                mult[callee] = m_new
                changed = True
        if not changed:
            break

    # ---- accounting ---------------------------------------------------------
    coll_bytes = 0.0
    coll_by_kind: Dict[str, float] = defaultdict(float)
    n_coll = 0
    dot_flops = 0.0
    write_bytes = 0.0
    for i, ln in enumerate(lines):
        comp = comp_of_line.get(i)
        if comp is None:
            continue
        k = mult[comp]
        s = ln.strip()
        if "=" not in s:
            continue
        shapes = [(m.group(1), m.group(2)) for m in _SHAPE_RE.finditer(s)]
        if not shapes:
            continue
        res_bytes = _shape_bytes(*shapes[0])
        opcode_m = re.search(
            r"=\s*(?:\([^)]*\)\s*)?[\w\[\],{}:\s]*?(\w[\w\-]*)\(", s)
        op = opcode_m.group(1) if opcode_m else ""
        for kind in _COLLECTIVES:
            if op == kind or op.startswith(kind + "-"):
                operand_bytes = 0
                for name in _operand_names(s):
                    if name in defs:
                        operand_bytes += _shape_bytes(*defs[name])
                if operand_bytes == 0:
                    operand_bytes = res_bytes
                coll_bytes += k * operand_bytes
                coll_by_kind[kind] += k * operand_bytes
                n_coll += 1
                break
        if op == "dot":
            mdn = _DOT_DNUMS_RE.search(s)
            ops_ = _operand_names(s)
            if mdn and ops_ and ops_[0] in defs:
                lhs_dims = [int(x) for x in defs[ops_[0]][1].split(",") if x]
                cdims = [int(x) for x in mdn.group(1).split(",") if x]
                csize = int(np.prod([lhs_dims[c] for c in cdims])) \
                    if cdims else 1
                res_elems = res_bytes / max(
                    _DTYPE_BYTES.get(shapes[0][0], 4), 1)
                dot_flops += k * 2.0 * res_elems * csize
        if (op not in ("parameter", "constant", "tuple",
                       "get-tuple-element", "bitcast", "reshape",
                       # CPU-backend bf16 legalization artifacts -- absent
                       # in TPU modules (verified: f32 twins of every bf16
                       # loop carry); collectives are priced separately.
                       "convert", "copy", "copy-start", "copy-done",
                       "all-gather", "all-reduce", "reduce-scatter",
                       "all-to-all", "collective-permute")
                and not op.startswith("all-")
                and not comp.startswith("fused_computation")
                and not comp.startswith("wrapped_")):
            # fusion-internal results live in registers; only top-level
            # instruction results are HBM buffers
            if op == "dynamic-update-slice":
                # in-place: only the update slice is written
                ops_ = _operand_names(s)
                if len(ops_) >= 2 and ops_[1] in defs:
                    res_bytes = _shape_bytes(*defs[ops_[1]])
            elif op == "fusion" and "calls=" in s:
                # fusions whose root is a DUS also update in place: count
                # the update-slice size, not the whole (aliased) buffer
                mcall = re.search(r"calls=%?([\w.\-]+)", s)
                root = root_of_comp.get(mcall.group(1)) if mcall else None
                if root and "dynamic-update-slice(" in root:
                    r_ops = _operand_names(root)
                    if len(r_ops) >= 2 and r_ops[1] in defs:
                        res_bytes = min(res_bytes,
                                        _shape_bytes(*defs[r_ops[1]]))
            write_bytes += k * res_bytes

    return HLOStats(
        collective_bytes=coll_bytes,
        collective_by_kind=dict(coll_by_kind),
        n_collectives=n_coll,
        dot_flops=dot_flops,
        write_bytes=write_bytes,
        while_trips=dict(trips_of_body),
    )
