"""The device's idle time inside the window, booked to the program's spans.

Inside ``gpm.window`` each stretch in which no operation runs on the
first device is booked, instant by instant, to the innermost ``gpm.``
span open over it (the latest started; of two that start together the
shorter), and a stretch that crosses a span's edge is split there.  The
parts therefore sum to the window's idle time exactly.

The program's spans (``repro.obs.span``) are booked to the metric of
their layer by ``BOOKS``; the harness's own spans (``window``,
``graph_build``, ``plan_search``, ``execute``), any span not in
``BOOKS`` and no span at all are what the program's spans cannot name
(``unattributed_idle_s``).  A trace with no device operation gives
nothing: idle time is a device's.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

# metric -> the program spans (names after ``gpm.``) whose idle it reads
BOOKS = {
    "compile_host_s": ("compile", "apct", "candidates", "costing",
                       "verify"),
    "contract_host_s": ("contract", "adjacency"),
    "enumerate_host_s": ("enumerate",),
    "lowering_host_s": ("node", "combine", "expand", "join"),
    "guard_scan_s": ("guard_scan",),
    "transfer_s": ("upload", "readback"),
}
UNATTRIBUTED = "unattributed_idle_s"
PREFIX = "gpm."


def idle_stretches(trace) -> List[Tuple[float, float]]:
    """The window's stretches with no operation on the first device."""
    trace_reduce = bench.module("", "trace_reduce")  # noqa: F821
    lo, hi = trace.window()
    first = trace.devices[0]
    busy = trace_reduce.union([(op.start_ns, op.end_ns) for op in trace.ops
                               if op.device == first], lo, hi)
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def innermost_segments(spans, lo: float, hi: float):
    """[(start, end, span name or "")]: [lo, hi] cut at every span edge,
    each piece named by the innermost span open over it."""
    cuts = sorted({lo, hi} | {t for s in spans for t in (s.start_ns, s.end_ns)
                              if lo < t < hi})
    events = sorted(spans, key=lambda s: s.start_ns)
    open_, i, out = [], 0, []
    for a, b in zip(cuts, cuts[1:]):
        while i < len(events) and events[i].start_ns <= a:
            open_.append(events[i])
            i += 1
        open_ = [s for s in open_ if s.end_ns > a]
        inner = max(open_, key=lambda s: (s.start_ns, -s.end_ns),
                    default=None)
        out.append((a, b, inner.name[len(PREFIX):] if inner else ""))
    return out


def idle_seconds(trace) -> Dict[str, float]:
    """{span name ("" for none): idle seconds of the first device inside
    the window while that span was the innermost one open}."""
    lo, hi = trace.window()
    idle = idle_stretches(trace)
    segments = innermost_segments(trace.spans, lo, hi)
    out: Dict[str, float] = {}
    j = 0
    for a, b, name in segments:
        while j < len(idle) and idle[j][1] <= a:
            j += 1
        k = j
        while k < len(idle) and idle[k][0] < b:
            part = min(b, idle[k][1]) - max(a, idle[k][0])
            if part > 0:
                out[name] = out.get(name, 0.0) + part / 1e9
            k += 1
    return out


OWNER = {span: metric for metric, spans in BOOKS.items() for span in spans}


def booked(trace) -> Dict[str, float]:
    """Idle seconds in the window by metric of ``BOOKS``, plus
    ``unattributed_idle_s``."""
    out = dict.fromkeys(list(BOOKS) + [UNATTRIBUTED], 0.0)
    for name, seconds in idle_seconds(trace).items():
        out[OWNER.get(name, UNATTRIBUTED)] += seconds
    return out


def per_job(ctx, metric: str):
    """One metric of ``booked`` per window job.  None without device
    operations in the trace, and None where the program opened none of
    the spans of ``BOOKS`` (a program without them names nothing)."""
    trace = ctx.trace
    if not trace.ops or not ctx.jobs or not any(
            s.name[len(PREFIX):] in OWNER for s in trace.spans):
        return None
    return booked(trace)[metric] / len(ctx.jobs)
