"""Observability: program spans on the profiler's clock, unified
metrics, drift accounting.

Three pieces, all zero-dependency (stdlib; jax only behind a lazy
fence):

* ``trace`` — one span system.  ``span("contract")`` opens
  ``gpm.contract`` in the JAX profiler's trace, on the device
  operations' clock, at every layer boundary of the program (compile,
  node evaluation, Contract, joins, host<->device copies); ``upload`` /
  ``readback`` move tensors under their spans and count the bytes.
  ``Tracer``/``Span`` record per-node span trees over plan execution,
  exportable as JSON; attach with ``compiled_plan.tracer = Tracer()``
  (disabled, the default, costs one ``is None`` check per node eval).
  A tracer also keeps the counter increments of its own reads
  (``Tracer.counts``).
* ``metrics`` — the process-wide ``MetricsRegistry`` (labelled
  counters/gauges/histograms) behind module-level helpers, plus
  ``StatsView``, the dict-shaped facade that keeps every pre-existing
  ``.stats`` consumer working while mirroring increments into the
  registry.
* ``drift`` — pairs each node's APCT *predicted* cost with its traced
  measured self time and aggregates a calibration report (rank
  correlation + per-class ratio spread) per node class × cut size ×
  route — the measurement layer the ROADMAP autotune item builds on.

Typical use::

    from repro import obs
    tr = obs.Tracer()
    cp = compiler.compile(p, g)
    cp.tracer = tr
    jax.profiler.start_trace("prof")         # optional: the timeline
    cp.count(p)
    jax.profiler.stop_trace()                # gpm.* spans + device ops
    tr.save("out.json")
    tr.total("transfer.d2h_bytes")           # counts of this tracer's reads
    report = obs.drift.aggregate(obs.drift.pairs_from_trace(tr.to_dict()))

    obs.counter("my.events", kind="x")       # unified metrics
    print(obs.dump())
"""
from __future__ import annotations

from repro.obs import drift
from repro.obs.metrics import REGISTRY, MetricsRegistry, StatsView
from repro.obs.trace import (Span, Tracer, counter, fence, note, readback,
                             span, upload)

__all__ = ["Tracer", "Span", "fence", "note", "span", "upload", "readback",
           "MetricsRegistry", "StatsView", "REGISTRY", "drift", "counter",
           "gauge", "observe", "get", "snapshot", "dump", "reset"]


def gauge(name: str, value: float, **labels):
    REGISTRY.gauge(name, value, **labels)


def observe(name: str, value: float, **labels):
    REGISTRY.observe(name, value, **labels)


def get(name: str, default=0.0, **labels):
    return REGISTRY.get(name, default, **labels)


def snapshot() -> dict:
    return REGISTRY.snapshot()


def dump(indent=1) -> str:
    return REGISTRY.dump(indent)


def reset():
    REGISTRY.reset()
