"""From the profiler's trace to the device's busy time, kernel times and
idle gaps.

The traced run wraps its traced slice in a host span
``bench.trace_window`` and each dispatched batch in ``bench.batch.<i>``
(:mod:`bench.load` writes them with ``jax.profiler.TraceAnnotation``),
so host spans and device operations are read on the trace's own clock.

* busy: the union of the intervals in which a device operation ran
  (events of the device planes' ``XLA Ops`` line), clipped to the window;
* idle share: 1 - busy / window;
* a kernel's time: the summed device time of its events, found by name;
* idle gaps: the stretches of the window with no device operation, each
  named by the host span that overlaps it most (``bench.*`` spans only).
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = re.compile(r"^XLA Ops$")
WINDOW = "bench.trace_window"
BATCH = re.compile(r"^bench\.batch\.(\d+)$")
HOST_PREFIX = "bench."


def short_name(name: str) -> str:
    """An op's event name without its numeric suffix and operands: the
    TPU trace names an op by its whole HLO line
    (``%copy.348 = f32[7000000,200]{1,0:T(8,128)} copy(...)``), which
    becomes ``copy f32[7000000,200]``."""
    head, _, rest = name.partition(" = ")
    base = re.sub(r"\.\d+$", "", head.strip().lstrip("%"))
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return f"{base} {shape.group(1)}" if shape else base


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` the profiler wrote under ``log_dir``."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def load(path: str) -> List[Event]:
    """Every event of every line of every plane of the trace file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    return [Event(p.name, ln.name, e.name, float(e.start_ns),
                  float(e.duration_ns))
            for p in data.planes for ln in p.lines for e in ln.events]


def _union(intervals: Iterable[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


class Reduced:
    """One traced slice, reduced. Times are in seconds."""

    def __init__(self, events: List[Event], device_plane=DEVICE_PLANE,
                 ops_line=OPS_LINE):
        windows = [e for e in events if e.name == WINDOW]
        if not windows:
            raise ValueError(f"trace has no {WINDOW!r} span")
        win = max(windows, key=lambda e: e.dur_ns)
        self._lo, self._hi = win.start_ns, win.end_ns
        self.ops = [e for e in events if device_plane.match(e.plane)
                    and ops_line.match(e.line) and e.end_ns > self._lo
                    and e.start_ns < self._hi]
        self.planes = sorted({e.plane for e in self.ops})
        self.spans = [e for e in events if e.name.startswith(HOST_PREFIX)
                      and e.name != WINDOW and e.end_ns > self._lo
                      and e.start_ns < self._hi]
        self._busy = {p: _clip(_union((e.start_ns, e.end_ns)
                                      for e in self.ops if e.plane == p),
                               self._lo, self._hi)
                      for p in self.planes}

    @property
    def window_s(self) -> float:
        return (self._hi - self._lo) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the device planes in the trace."""
        if not self.planes:
            return 0.0
        return sum(_length(b) for b in self._busy.values()) \
            / len(self.planes) / 1e9

    @property
    def idle_share(self) -> Optional[float]:
        if not self.planes or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def batches(self) -> Dict[int, Tuple[float, float]]:
        """``{batch index: (start_ns, end_ns)}`` of the batch spans that lie
        wholly inside the window."""
        out = {}
        for e in self.spans:
            m = BATCH.match(e.name)
            if m and e.start_ns >= self._lo and e.end_ns <= self._hi:
                out[int(m.group(1))] = (e.start_ns, e.end_ns)
        return out

    def busy_in(self, start_ns: float, end_ns: float) -> float:
        """Device-busy seconds inside one interval, averaged over planes."""
        if not self.planes:
            return 0.0
        return sum(_length(_clip(b, start_ns, end_ns))
                   for b in self._busy.values()) / len(self.planes) / 1e9

    def kernel_s(self, pattern: str, start_ns: Optional[float] = None,
                 end_ns: Optional[float] = None) -> float:
        """Summed device seconds of the ops whose name matches
        ``pattern`` (a regular expression), inside the interval when one
        is given, averaged over planes."""
        rx = re.compile(pattern)
        lo = self._lo if start_ns is None else start_ns
        hi = self._hi if end_ns is None else end_ns
        total = sum(_length(_clip([(e.start_ns, e.end_ns)], lo, hi))
                    for e in self.ops if rx.search(e.name))
        return total / max(1, len(self.planes)) / 1e9

    def device_ops(self, top: int = 10) -> List[List]:
        """The ``top`` device operations by summed time, grouped by
        :func:`short_name`: [name, seconds]."""
        by = collections.Counter()
        for e in self.ops:
            by[short_name(e.name)] += _length(_clip([(e.start_ns, e.end_ns)],
                                        self._lo, self._hi)) / 1e9
        return [[n, s / max(1, len(self.planes))]
                for n, s in by.most_common(top)]

    def idle_gaps(self, top: int = 10) -> List[List]:
        """The ``top`` longest idle stretches of the first device plane,
        each named by the host span it fell in (see :meth:`_span_at`):
        [span name, seconds]."""
        if not self.planes:
            return []
        busy = self._busy[self.planes[0]]
        gaps, t = [], self._lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self._hi:
            gaps.append((t, self._hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:top]:
            out.append([self._span_at(s, e), (e - s) / 1e9])
        return out

    def _span_at(self, s: float, e: float) -> str:
        """The innermost host span that covers at least half of [s, e),
        else the one that overlaps it most, else ``no-span``."""
        over = [(min(e, sp.end_ns) - max(s, sp.start_ns), sp)
                for sp in self.spans]
        over = [(ov, sp) for ov, sp in over if ov > 0]
        if not over:
            return "no-span"
        half = [sp for ov, sp in over if ov >= (e - s) / 2]
        sp = min(half, key=lambda x: x.dur_ns) if half \
            else max(over, key=lambda x: x[0])[1]
        return BATCH.sub("bench.batch", sp.name)
