"""Program spans on the profiler's clock, and the plan-execution tracer.

One span system.  ``span(name, **stats)`` opens a
``jax.profiler.TraceAnnotation`` named ``"gpm." + name``: with the
profiler running (``jax.profiler.start_trace`` / TensorBoard /
Perfetto) it lands in the same trace as the device's operations, on
the same clock, so any idle stretch of the device can be placed inside
the program step that held it.  With no profiler running a span costs
about a microsecond; ``stats`` become XPlane stats of the event and
leave its name clean.  Spans sit at the program's layer boundaries
(compile, node evaluation, Contract, joins, host<->device copies), never
in hot inner loops, which keep counters instead.

A stack of the open spans, per thread, labels what happens under them:
the JAX duration listener installed with the first span counts
``jax.traces`` (one per jaxpr trace) under the innermost open span,
which answers "which step re-traced".  ``upload`` and ``readback`` are
the host<->device copies of the mining path, each in its span and
counted in ``transfer.h2d_bytes`` / ``transfer.d2h_bytes`` by site; a
large f64 readback crosses as two exact f32 halves, counted again in
``transfer.split_bytes``.

``Tracer`` records one ``Span`` per evaluated plan node (plus one root
"execute" span per public read), nested exactly as the evaluation
recursion nests — a CutJoin span contains the Contract spans of the
factor tensors it had to materialise, a MobiusCombine span contains its
term evaluations, and a node served from the plan's value memo opens no
span at all.  Each span carries the node key, node class, cut size,
the kernel-vs-XLA route actually taken, the ``exact_block`` guard
outcome, factor shapes, and wall time from ``time.perf_counter``.  Its
node and guard-scan spans open the profiler spans ``gpm.node`` and
``gpm.guard_scan`` too, so the tree and the profiler trace name the
same steps; the program's other spans go to the profiler only.  While
one of its reads is open the tracer also keeps the counts of every
``obs.counter`` increment (``Tracer.counts``), so one job's transfer
bytes and re-traces are read off its tracer.

JAX dispatch is asynchronous, so a span that closed the instant the
kernel call returned would time the *enqueue*, not the work: callers
fence the evaluated value with ``fence`` (``jax.block_until_ready``)
before the span closes.

Exports: ``to_dict``/``to_json``/``save`` (the span tree, with per-span
self time, a root-coverage summary and the counts), which
``obs.drift`` and ``launch.mine --trace`` read.

Zero-dependency: stdlib only; jax and numpy are imported lazily, and
without jax a span is a null context.
"""
from __future__ import annotations

import functools
import json
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from contextlib import contextmanager
from typing import Dict, List, Optional

from repro.obs.metrics import REGISTRY

PREFIX = "gpm."
# the JAX monitoring event that marks one jaxpr trace
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
# Tracer span kinds -> the profiler span they open (the per-read
# "execute" roots open none: the harness owns that name)
PROFILE_KINDS = {"guard-scan": "guard_scan", "execute": None}

_local = threading.local()
_annotation = None                      # TraceAnnotation, resolved lazily


def _state():
    """This thread's open program spans and the Tracers with an open
    read, innermost last."""
    st = getattr(_local, "st", None)
    if st is None:
        st = _local.st = ([], [])
    return st


class _NullAnnotation:
    def __init__(self, name, **stats):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats):
        pass


def _resolve():
    """``jax.profiler.TraceAnnotation``, and the duration listener
    registered once; the null annotation where jax is missing."""
    global _annotation
    try:
        import jax
    except ImportError:
        _annotation = _NullAnnotation
        return _annotation
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _annotation = jax.profiler.TraceAnnotation
    return _annotation


def _on_duration(event: str, seconds: float, **_):
    if event != TRACE_EVENT:
        return
    spans = _state()[0]
    counter("jax.traces", span=spans[-1] if spans else "")


class span:
    """``with obs.span("contract", cut=2): ...`` — one program span,
    ``gpm.<name>`` in the profiler trace.  ``set(**stats)`` adds stats
    known only later (a route); as a decorator, ``@obs.span("compile")``
    opens a fresh span around every call."""
    __slots__ = ("name", "stats", "_ann")

    def __init__(self, name: str, **stats):
        self.name = name
        self.stats = stats
        self._ann = None

    def __enter__(self):
        ann = _annotation or _resolve()
        self._ann = ann(PREFIX + self.name, **self.stats)
        _state()[0].append(self.name)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        try:
            self._ann.__exit__(*exc)
        finally:
            _state()[0].pop()
        return False

    def set(self, **stats):
        self._ann.set_metadata(**stats)

    def __call__(self, fn):
        name, stats = self.name, self.stats

        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name, **stats):
                return fn(*args, **kwargs)
        return inner


def counter(name: str, value: float = 1, **labels) -> float:
    """Increment a registry counter, and the counts of the Tracer whose
    read is open on this thread."""
    total = REGISTRY.counter(name, value, **labels)
    tracers = _state()[1]
    if tracers:
        tracers[-1]._count(name, value, labels)
    return total


def note(key: str, items) -> None:
    """Extend the list attribute ``key`` of the innermost span of the
    Tracer whose read is open on this thread, for code that does not hold
    the Tracer (the sharded Contract's step descriptions); a no-op
    without one."""
    tracers = _state()[1]
    current = tracers[-1].current() if tracers else None
    if current is not None:
        current.attrs.setdefault(key, []).extend(items)


def upload(x, dtype=None, *, site: str, sharding=None):
    """``jnp.asarray(x, dtype)`` of a host array in a ``gpm.upload``
    span, its bytes counted as they cross (the converted copy's: an f64
    host array uploaded as f32 moves 4 bytes an element); with a
    ``sharding``, each device receives only its slice.  A jax Array
    (already on the device, or a tracer) passes through uncounted."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    if isinstance(x, jax.Array):
        return jnp.asarray(x, dtype)
    with span("upload", site=site):
        if sharding is None:
            out = jnp.asarray(x, dtype)
        else:
            out = jax.device_put(np.asarray(x, dtype), sharding)
    counter("transfer.h2d_bytes", out.nbytes, site=site)
    return out


def readback(x, *, site: str):
    """``np.asarray(x)`` of a device array in a ``gpm.readback`` span,
    its bytes counted.  The wait for the device's result comes first,
    outside the span, so the span holds the copy alone.  Host values
    pass through uncounted.

    A large f64 array on an accelerator (``_splits``) crosses as two
    native f32 halves, split on the device and added back exactly on
    the host (``_split_readback``).  The link moves the same bytes;
    ``transfer.split_bytes`` counts those that took the split (0 for a
    plain copy)."""
    import jax
    import numpy as np
    if not isinstance(x, jax.Array):
        return np.asarray(x)
    jax.block_until_ready(x)
    with span("readback", site=site):
        out = _split_readback(x) if _splits(x) else None
        split = out is not None
        if not split:
            out = np.asarray(x)
    counter("transfer.d2h_bytes", out.nbytes, site=site)
    counter("transfer.split_bytes", out.nbytes if split else 0, site=site)
    return out


# f64 device arrays of at least this many elements are read back split;
# below it the split program's dispatch costs more than the f64 copy
SPLIT_MIN_ELEMENTS = 1 << 16


def _off_host(x) -> bool:
    """Whether ``x`` lives on an accelerator.  On the CPU backend
    ``np.asarray`` is already a cheap copy."""
    return any(d.platform != "cpu" for d in x.devices())


def _splits(x) -> bool:
    import numpy as np
    return (x.dtype == np.float64 and x.size >= SPLIT_MIN_ELEMENTS
            and _off_host(x))


def _split_halves(x):
    """f32 ``(hi, lo)`` with ``hi + lo == x`` in f64 wherever ``ok``;
    every integer below 2**48 in magnitude splits so.  ``lo`` takes the
    sign of ``hi`` where it is zero, so ``-0.0`` rebuilds as ``-0.0``."""
    import jax.numpy as jnp
    hi = x.astype(jnp.float32)
    lo = (x - hi.astype(jnp.float64)).astype(jnp.float32)
    lo = jnp.where(lo == 0, jnp.copysign(lo, hi), lo)
    ok = jnp.all(hi.astype(jnp.float64) + lo.astype(jnp.float64) == x)
    return hi, lo, ok


@functools.lru_cache(maxsize=None)
def _split_program():
    import jax
    return jax.jit(_split_halves)


# numpy's ufuncs drop the GIL, so the rebuild runs in row blocks on a
# few threads
REBUILD_THREADS = 8


def _split_readback(x):
    """The f64 host copy of ``x`` rebuilt from its two f32 halves, or
    None where some element does not split exactly: the caller then
    makes the plain f64 copy, so nothing inexact reaches the host.

    On a TPU v5e an (8192, 8192) f64 array, which the chip emulates,
    reads back in ~2.5 s; its f32 halves take ~0.1 s each, and the
    rebuild ~0.6 s on one host thread or ~0.18 s on eight."""
    import jax
    import numpy as np
    with jax.enable_x64():
        hi, lo, ok = _split_program()(x)
    if not bool(np.asarray(ok)):
        return None
    hi.copy_to_host_async()
    lo.copy_to_host_async()
    hi, lo = np.asarray(hi), np.asarray(lo)
    out = np.empty(hi.shape, np.float64)
    parts = zip(*(np.array_split(a, REBUILD_THREADS) for a in (hi, lo, out)))
    with ThreadPoolExecutor(REBUILD_THREADS) as pool:
        list(pool.map(lambda p: np.add(p[0], p[1], out=p[2],
                                         dtype=np.float64), parts))
    return out


def fence(value):
    """Block until ``value`` is materialised on the host (no-op for
    host floats/ndarrays and when jax is absent); returns ``value``."""
    try:
        import jax
        jax.block_until_ready(value)
    except Exception:
        pass
    return value


class Span:
    """One timed node evaluation.  ``t0``/``t1`` are perf_counter
    seconds relative to the tracer's epoch; ``self_s`` (duration minus
    child durations) is the node's *own* work — the quantity the drift
    report pairs against its predicted cost."""
    __slots__ = ("name", "kind", "attrs", "t0", "t1", "children")

    def __init__(self, name: str, kind: str, attrs: dict, t0: float):
        self.name = name
        self.kind = kind
        self.attrs = attrs
        self.t0 = t0
        self.t1 = t0
        self.children: List[Span] = []

    @property
    def duration_s(self) -> float:
        return self.t1 - self.t0

    @property
    def self_s(self) -> float:
        return max(0.0, self.duration_s
                   - sum(c.duration_s for c in self.children))

    def to_dict(self) -> dict:
        return {"name": self.name, "kind": self.kind,
                "start_us": self.t0 * 1e6,
                "dur_us": self.duration_s * 1e6,
                "self_us": self.self_s * 1e6,
                "attrs": dict(self.attrs),
                "children": [c.to_dict() for c in self.children]}


class Tracer:
    """Collects span trees across one or more plan executions.  Attach
    with ``compiled_plan.tracer = tracer``; every subsequent public read
    (``count`` / ``local_counts`` / ``exists`` / ``domains``) opens a
    root span and nests node spans beneath it.  ``counts`` holds the
    ``obs.counter`` increments made while a read was open:
    {name: {"k=v,...": total}}, as ``obs.snapshot`` keys them."""

    def __init__(self, meta: Optional[dict] = None):
        self.roots: List[Span] = []
        self._stack: List[Span] = []
        self.counts: Dict[str, Dict[str, float]] = {}
        self.epoch = time.perf_counter()
        self.meta = dict(meta or {})
        if "backend" not in self.meta:
            try:
                import jax
                self.meta["backend"] = jax.default_backend()
            except Exception:
                self.meta["backend"] = "unknown"

    # -- recording ---------------------------------------------------------------
    @contextmanager
    def span(self, name: str, kind: str = "node", **attrs):
        s = Span(name, kind, attrs, time.perf_counter() - self.epoch)
        if self._stack:
            self._stack[-1].children.append(s)
        else:
            self.roots.append(s)
            _state()[1].append(self)
        self._stack.append(s)
        prof = PROFILE_KINDS.get(kind, "node")
        ps = None
        if prof is not None:
            stats = {"key": name, "cls": kind}
            if "cut_size" in attrs:
                stats["cut"] = attrs["cut_size"]
            ps = span(prof, **stats).__enter__()
        try:
            yield s
        except BaseException as e:
            s.attrs["error"] = type(e).__name__
            raise
        finally:
            s.t1 = time.perf_counter() - self.epoch
            if ps is not None:
                route = s.attrs.get("route")
                if route is not None:
                    ps.set(route=route)
                ps.__exit__(None, None, None)
            self._stack.pop()
            if not self._stack:
                _state()[1].pop()

    def annotate(self, **attrs):
        """Attach attributes to the innermost open span (no-op outside
        any span, so instrumented code paths also run untraced)."""
        if self._stack:
            self._stack[-1].attrs.update(attrs)

    def current(self) -> Optional[Span]:
        return self._stack[-1] if self._stack else None

    def _count(self, name: str, value: float, labels: dict):
        key = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
        series = self.counts.setdefault(name, {})
        series[key] = series.get(key, 0) + value

    def total(self, name: str) -> float:
        """One counter's increments during this tracer's reads, summed
        over its labels."""
        return sum(self.counts.get(name, {}).values())

    # -- analysis ----------------------------------------------------------------
    def walk(self):
        """Every span, depth-first, roots first."""
        stack = list(reversed(self.roots))
        while stack:
            s = stack.pop()
            yield s
            stack.extend(reversed(s.children))

    def coverage(self) -> Optional[float]:
        """Fraction of root-span ("execute") wall time covered by their
        immediate child node spans — how much of a measured end-to-end
        read the per-node accounting explains.  None without roots or
        with zero-duration roots."""
        execs = [r for r in self.roots if r.kind == "execute"] or self.roots
        total = sum(r.duration_s for r in execs)
        if total <= 0.0:
            return None
        inside = sum(c.duration_s for r in execs for c in r.children)
        return inside / total

    # -- export ------------------------------------------------------------------
    def to_dict(self) -> dict:
        cov = self.coverage()
        return {"meta": dict(self.meta),
                "coverage": cov,
                "counts": {k: dict(v) for k, v in self.counts.items()},
                "spans": [r.to_dict() for r in self.roots]}

    def to_json(self, indent: Optional[int] = 1) -> str:
        return json.dumps(self.to_dict(), indent=indent, sort_keys=True)

    def save(self, path: str) -> str:
        """Write the span-tree JSON to ``path``.  The timeline view is
        the profiler's trace, which holds the same spans beside the
        device's operations."""
        with open(path, "w") as fh:
            fh.write(self.to_json())
        return path
