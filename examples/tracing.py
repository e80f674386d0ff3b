"""Plan-execution tracing and the cost-model drift report.

Walks the observability layer end to end: attach a tracer to a compiled
plan, run it under the JAX profiler (whose trace holds the program's
``gpm.*`` spans beside the device's operations, on one clock), read the
span tree and the counts the tracer records (one span per IR node
evaluation, nested exactly as the evaluation recursion nests), and
aggregate the (predicted cost, measured time) pairs into the
calibration report that tells you where the APCT cost model drifts
from reality.

    PYTHONPATH=src python examples/tracing.py
"""
import sys
sys.path.insert(0, "src")

import os
import tempfile

import jax

from repro import compiler, obs
from repro.core.pattern import Pattern
from repro.graph.generators import erdos_renyi

graph = erdos_renyi(300, 8.0, seed=1)

# 5-clique minus one edge: its only cutting set has three vertices, so
# the compiler commits a |cut| = 3 decomposition join — the tri-join
# kernel tier, the most interesting thing to watch execute.
p = Pattern(5, [(u, v) for u in range(5) for v in range(u + 1, 5)
                if (u, v) != (3, 4)])

# --- 1. attach a tracer and execute ---------------------------------------
# Tracing is off by default (one is-None check per node eval); attaching
# a Tracer records a root "execute" span per public read with one node
# span per IR evaluation beneath it.  Values are fenced
# (jax.block_until_ready) before each span closes, so spans time the
# work, not the async enqueue.
# The same run under the JAX profiler writes a trace that TensorBoard's
# profile plugin and Perfetto open: the program's spans (gpm.compile,
# gpm.node, gpm.contract, gpm.join, gpm.upload, gpm.readback, ...) on
# the host timeline, the device's operations under them.
out_dir = tempfile.mkdtemp(prefix="k5me-")
tracer = obs.Tracer()
jax.profiler.start_trace(out_dir)
cp = compiler.compile(p, graph, cache=False)
cp.tracer = tracer
count = cp.count(p)
jax.profiler.stop_trace()
print(f"count = {count:,.0f} on {graph}; profiler trace under {out_dir}")

# --- 2. read the span tree ------------------------------------------------
# Each span carries the node key, node class, cut size, the route the
# node actually took (kernel vs xla-dense, einsum vs enumeration), the
# exact_block guard outcome, and factor shapes.
for span in tracer.walk():
    route = span.attrs.get("route", "")
    print(f"  {span.kind:16s} {span.name:28s} {route:12s} "
          f"{span.duration_s * 1e3:8.2f} ms (self {span.self_s * 1e3:.2f})")

# Coverage: how much of the end-to-end read the per-node spans explain.
print(f"node coverage of wall time: {tracer.coverage():.1%}")

# The counter increments made during the tracer's reads: bytes copied
# between host and device by site, and re-traces by the span they
# happened in.
for name in ("transfer.h2d_bytes", "transfer.d2h_bytes", "jax.traces"):
    print(f"  {name:24s} {tracer.total(name):,.0f}")

# --- 3. export ------------------------------------------------------------
# Span-tree JSON for tooling (obs.drift reads it); `mine.py --trace=FILE`
# does exactly this for full workloads.
print("wrote", tracer.save(os.path.join(out_dir, "k5me_trace.json")))

# --- 4. the drift report --------------------------------------------------
# Compilation stored each committed node's predicted APCT cost in
# plan.meta["node_costs"]; the trace measured each node's self time.
# The report groups pairs by node class x cut size x route: rank
# correlation says whether the model *orders* nodes correctly (all the
# plan picker needs), ratio spread says whether one per-class scale
# factor would calibrate absolute costs (the autotune on-ramp).
pairs = obs.drift.pairs_from_trace(tracer.to_dict())
report = obs.drift.aggregate(pairs)
print()
print(obs.drift.render(report))

# --- 5. the metrics registry ----------------------------------------------
# Counters accumulated process-wide while the plan ran: kernel-tier
# calls, exact_block guard outcomes, plan node evals/memo hits.  The
# .stats dicts on PlanCache / CompiledPlan / PatternQueryBatcher are
# live views over the same registry.
print("metrics registry:")
print(obs.dump(indent=2))
