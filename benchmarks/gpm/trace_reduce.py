"""Reduce a JAX profiler trace to device intervals and the harness's spans.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
From it this module takes, for each ``/device:TPU:<i>`` plane:

* the programs that ran: the events of its ``XLA Modules`` line, one per
  execution of a compiled program, named as jitted (``jit__einsum``,
  ``jit__pairjoin``, ...; the ``(<fingerprint>)`` suffix dropped);
* the operations inside them: the events of its ``XLA Ops`` line, each
  named by its HLO instruction (``while.14``, ``fusion.63``, ...) and
  tagged with the program it ran in.  Operations nest (a loop holds its
  body's fusions), so their times are unioned, never summed;

and from the host planes the harness's own spans,
``jax.profiler.TraceAnnotation``s whose names start with ``gpm.``
(``gpm.window``, ``gpm.plan_search``, ...).

Host and device events share the trace's clock, so an idle gap on a
device can be named by the innermost harness span around it.  The
per-layer metric readers under ``metrics/`` take their numbers from a
``Trace``; nothing here knows a kernel's name.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
FINGERPRINT = re.compile(r"\(\d+\)$")
SPAN_PREFIX = "gpm."


@dataclass(frozen=True)
class DeviceOp:
    """One operation, or one program execution, on a device."""
    device: int
    name: str
    module: str                         # the program it ran in
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    end_ns: float


@dataclass
class Trace:
    ops: List[DeviceOp] = field(default_factory=list)
    programs: List[DeviceOp] = field(default_factory=list)
    spans: List[Span] = field(default_factory=list)

    @property
    def devices(self) -> List[int]:
        return sorted({op.device for op in self.ops})

    def window(self) -> Tuple[float, float]:
        """The ``gpm.window`` span: the measured window on the trace's
        clock."""
        for s in self.spans:
            if s.name == SPAN_PREFIX + "window":
                return s.start_ns, s.end_ns
        raise ValueError("trace holds no gpm.window span")

    def window_ops(self) -> List[DeviceOp]:
        return self._in_window(self.ops)

    def _in_window(self, events) -> List[DeviceOp]:
        lo, hi = self.window()
        return [e for e in events if e.end_ns > lo and e.start_ns < hi]

    def busy_s(self) -> float:
        """Seconds in which some operation ran, per device, averaged over
        the devices that ran any: the union of op intervals clipped to
        the window."""
        lo, hi = self.window()
        devices = self.devices
        if not devices:
            return 0.0
        total = 0.0
        for d in devices:
            merged = union([(op.start_ns, op.end_ns) for op in self.ops
                            if op.device == d], lo, hi)
            total += sum(b - a for a, b in merged)
        return total / len(devices) / 1e9

    def window_s(self) -> float:
        lo, hi = self.window()
        return (hi - lo) / 1e9

    def program_seconds(self, pattern) -> float:
        """Device seconds, summed over devices, of the window's program
        executions whose name matches the regular expression
        ``pattern``."""
        return sum(p.dur_ns for p in self._in_window(self.programs)
                   if re.search(pattern, p.name)) / 1e9

    def top_ops(self, k: int = 10) -> List[list]:
        """The ``k`` (program/op name, seconds) pairs that took most device
        time in the window.  A loop's time includes its body's."""
        acc: dict = {}
        for op in self.window_ops():
            key = f"{op.module}/{op.name}" if op.module else op.name
            acc[key] = acc.get(key, 0.0) + op.dur_ns / 1e9
        return [[name, s] for name, s in
                sorted(acc.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> List[list]:
        """The ``k`` longest gaps in the window in which no op ran on the
        first device, each named by the innermost harness span that
        covers its middle."""
        lo, hi = self.window()
        devices = self.devices
        merged = union([(op.start_ns, op.end_ns) for op in self.ops
                        if devices and op.device == devices[0]], lo, hi)
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.phase_at((a + b) / 2), (b - a) / 1e9]
                for a, b in gaps[:k]]

    def phase_at(self, t_ns: float) -> str:
        inside = [s for s in self.spans if s.start_ns <= t_ns <= s.end_ns]
        if not inside:
            return "outside"
        s = min(inside, key=lambda s: s.end_ns - s.start_ns)
        return s.name[len(SPAN_PREFIX):]


def union(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Merge intervals after clipping them to [lo, hi]."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def find_xplane(log_dir) -> Path:
    files = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path) -> Trace:
    """Read one ``.xplane.pb`` file into a ``Trace``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    trace = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: list(line.events) for line in plane.lines}
            device = int(m.group(1))
            programs = sorted(
                (DeviceOp(device, FINGERPRINT.sub("", ev.name),
                          FINGERPRINT.sub("", ev.name), float(ev.start_ns),
                          float(ev.duration_ns))
                 for ev in lines.get(PROGRAMS_LINE, [])),
                key=lambda p: p.start_ns)
            trace.programs += programs
            trace.ops += _tag_programs(device, lines.get(OPS_LINE, []),
                                       programs)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        trace.spans.append(Span(
                            ev.name, float(ev.start_ns),
                            float(ev.start_ns + ev.duration_ns)))
    return trace


def _tag_programs(device: int, events, programs) -> List[DeviceOp]:
    """Device ops named by their HLO instruction (the text before
    `` = ``), each tagged with the program whose execution holds its
    start."""
    starts = [p.start_ns for p in programs]
    ops = []
    for ev in events:
        start = float(ev.start_ns)
        i = bisect.bisect_right(starts, start) - 1
        module = (programs[i].name if i >= 0 and start < programs[i].end_ns
                  else "")
        ops.append(DeviceOp(device, ev.name.split(" = ")[0].lstrip("%"),
                            module, start, float(ev.duration_ns)))
    return ops
