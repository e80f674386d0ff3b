"""Program spans on the profiler's clock (``repro.obs.span``), the
counters at the same boundaries, and the benchmark's reading of them
(``benchmarks/gpm/idle_by_span.py``): a motif job's spans in a CPU
profiler trace, idle time split at span edges, transfer bytes against
the plan's shapes, re-traces under the span that caused them, and the
Tracer tree left as it was."""
import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compiler, obs
from repro.core.motifs import motif_patterns
from repro.graph.generators import erdos_renyi
from repro.graph.storage import Graph
from repro.obs.trace import _state

GPM = Path(__file__).resolve().parents[1] / "benchmarks" / "gpm"
HARNESS = ("window", "graph_build", "plan_search", "execute", "reference")
COMPILE_SPANS = {"compile", "apct", "candidates", "costing", "verify"}
EXECUTE_SPANS = {"node", "combine", "expand", "adjacency", "contract",
                 "enumerate", "guard_scan", "join", "upload", "readback"}
PATTERNS = motif_patterns(4)


def bench_module(name: str, subdir: str = ""):
    """A file of the benchmark, loaded as its harness loads it
    (``Bench.module``)."""
    key = "gpm_run_for_program_span_tests"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, GPM / "run.py")
        sys.modules[key] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[key])
    return sys.modules[key].Bench().module(subdir, name)


def _profiled_motif_job(tmp_path):
    """A motif-4 job as the harness runs it, under the CPU profiler: one
    plan on the kernel joins with its static certificate dropped (so
    every join scans its factors), one on the XLA joins (so the dense
    factors and mask are expanded)."""
    g = erdos_renyi(48, 5.0, seed=3)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0         # as the harness traces
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("gpm.window"):
            with jax.profiler.TraceAnnotation("gpm.plan_search"):
                kernel = compiler.compile(PATTERNS, g, cache=False)
                xla = compiler.compile(PATTERNS, g, cache=False,
                                       cutjoin_kernel=False)
            kernel._precert = {}
            with jax.profiler.TraceAnnotation("gpm.execute"):
                counts = [{p: cp.count(p) for p in PATTERNS}
                          for cp in (kernel, xla)]
    finally:
        jax.profiler.stop_trace()
    assert counts[0] == counts[1]
    reduce = bench_module("trace_reduce")
    return reduce.load(reduce.find_xplane(tmp_path))


def test_motif_job_spans_nest_inside_the_harness_spans(tmp_path):
    trace = _profiled_motif_job(tmp_path)
    harness = {s.name[4:]: s for s in trace.spans
               if s.name[4:] in HARNESS}
    program = [s for s in trace.spans if s.name[4:] not in HARNESS]
    names = {s.name[4:] for s in program}
    assert names == COMPILE_SPANS | EXECUTE_SPANS
    for s in program:
        outer = harness["plan_search" if s.name[4:] in COMPILE_SPANS
                        else "execute"]
        assert outer.start_ns <= s.start_ns <= s.end_ns <= outer.end_ns, \
            s.name
    # every span the program opens is booked to a metric of its layer,
    # so only the harness's own spans are left unattributed
    booked = bench_module("idle_by_span").OWNER
    assert names <= set(booked)
    # a CPU trace holds no TPU operation: the readers give nothing
    ctx = type("Ctx", (), {"trace": trace, "jobs": [object()]})()
    assert trace.ops == []
    assert bench_module("idle_by_span").per_job(ctx, "transfer_s") is None


def _synthetic_trace():
    reduce = bench_module("trace_reduce")
    s = 1e9                                 # ns per second
    ops = [reduce.DeviceOp(0, "fusion.1", "jit__einsum", 0.0, 10 * s),
           reduce.DeviceOp(0, "fusion.2", "jit__einsum", 60 * s, 40 * s)]
    spans = [reduce.Span("gpm.window", 0.0, 100 * s),
             reduce.Span("gpm.execute", 5 * s, 100 * s),
             reduce.Span("gpm.node", 15 * s, 70 * s),
             reduce.Span("gpm.contract", 20 * s, 40 * s),
             reduce.Span("gpm.readback", 40 * s, 70 * s)]
    return reduce.Trace(ops=ops, spans=spans)


def test_idle_by_span_splits_a_gap_at_span_edges():
    """One gap, 10 s to 60 s, crosses execute, node, contract and
    readback: each gets exactly the part it was innermost over, and
    the parts sum to (1 - busy share) of the window."""
    ibs = bench_module("idle_by_span")
    trace = _synthetic_trace()
    seconds = ibs.idle_seconds(trace)
    assert seconds == {"execute": 5.0, "node": 5.0, "contract": 20.0,
                       "readback": 20.0}
    assert sum(seconds.values()) == pytest.approx(
        (1 - trace.busy_s() / trace.window_s()) * trace.window_s())
    booked = ibs.booked(trace)
    assert booked["contract_host_s"] == 20.0
    assert booked["transfer_s"] == 20.0
    assert booked["lowering_host_s"] == 5.0
    assert booked["unattributed_idle_s"] == 5.0
    assert sum(booked.values()) == pytest.approx(50.0)
    ctx = type("Ctx", (), {"trace": trace, "jobs": [1, 2]})()
    assert ibs.per_job(ctx, "transfer_s") == 10.0
    # a program that opens none of the booked spans names nothing
    trace.spans = [s for s in trace.spans if s.name in ("gpm.window",
                                                        "gpm.execute")]
    assert ibs.per_job(ctx, "unattributed_idle_s") is None


def _expected_transfers(tracer, plan, n: int):
    """(h2d, d2h) bytes of a count read, from the plan's shapes and the
    routes the tracer recorded: the f64 adjacency up once; each free
    Contract's f64 tensor and each scalar down; kernel joins' factors
    up as f32 and XLA joins' factors plus mask up as f64, each join's
    scalar down."""
    h2d, d2h = 8 * n * n, 0
    for s in tracer.walk():
        route = s.attrs.get("route")
        if s.kind == "Contract":
            free = plan.nodes[s.name].free
            d2h += 8 * n ** len(free)
        elif route == "kernel":
            h2d += sum(4 * int(np.prod(shape))
                       for shape in s.attrs["factor_shapes"])
            d2h += 8
        elif route == "xla-dense":
            cut = s.attrs["cut_size"]
            h2d += 8 * n ** cut * (len(s.attrs["factor_shapes"]) + 1)
            d2h += 8
    return h2d, d2h


@pytest.mark.parametrize("kernel", [True, False])
def test_transfer_bytes_equal_the_plans_shapes(kernel):
    n = 256
    g = erdos_renyi(n, 6.0, seed=5)
    cp = compiler.compile(PATTERNS, g, cache=False, cutjoin_kernel=kernel)
    tracer = obs.Tracer()
    cp.tracer = tracer
    for p in PATTERNS:
        cp.count(p)
    routes = {s.attrs["route"] for s in tracer.walk() if s.kind == "CutJoin"}
    assert routes == {"kernel" if kernel else "xla-dense"}
    h2d, d2h = _expected_transfers(tracer, cp.plan, n)
    assert tracer.total("transfer.h2d_bytes") == h2d
    assert tracer.total("transfer.d2h_bytes") == d2h
    assert tracer.to_dict()["counts"]["transfer.h2d_bytes"]


_MESH_TRANSFERS = """
    from repro import compiler, obs
    from repro.core.counting import CountingEngine
    from repro.core.motifs import motif_patterns
    from repro.distributed import contract as C, meshes
    from repro.graph.generators import erdos_renyi

    mesh = meshes.data_mesh()
    assert meshes.num_shards(mesh) == 4
    g = erdos_renyi(96, 6.0, seed=5)
    pats = motif_patterns(4)
    rows = C.padded_rows(g.n, mesh)
    for kernel, route, site in ((True, "kernel-sharded", "kernel_result"),
                                (False, "xla-sharded", "xla_result")):
        cp = compiler.compile(pats, g, counter=CountingEngine(g, mesh=mesh),
                              cache=False, mesh=mesh, cutjoin_kernel=kernel)
        tracer = obs.Tracer()
        cp.tracer = tracer
        for p in pats:
            cp.count(p)
        spans = list(tracer.walk())
        joins = [s for s in spans if s.kind == "CutJoin"]
        assert {s.attrs["route"] for s in joins} == {route}
        scalars = [s for s in spans if s.kind == "Contract"
                   and not cp.plan.nodes[s.name].free]
        d2h = tracer.counts["transfer.d2h_bytes"]
        h2d = tracer.counts["transfer.h2d_bytes"]
        # every sharded join's scalar and every scalar Contract is read
        # back and counted; the free Contract tensors stay on the mesh
        assert d2h == {f"site={site}": 8.0 * len(joins),
                       "site=contract": 8.0 * len(scalars)}, d2h
        # the row-sharded adjacency goes up once, padded to the mesh
        assert h2d["site=adjacency"] == 8 * rows * rows, h2d
        assert set(h2d) <= {"site=adjacency", "site=kernel_factors",
                            "site=xla_factors"}, h2d
    print("OK")
"""


def test_mesh_transfers_are_counted_at_every_site():
    """The sharded routes count their host<->device copies as the
    one-chip path does: the sharded adjacency's upload, every join
    result and scalar Contract read back (four forced host devices)."""
    src = Path(__file__).resolve().parents[1] / "src"
    env = dict(os.environ, PYTHONPATH=str(src),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    run = subprocess.run([sys.executable, "-c", textwrap.dedent(
        _MESH_TRANSFERS)], capture_output=True, text=True, env=env,
        timeout=560)
    assert run.returncode == 0 and run.stdout.strip().endswith("OK"), \
        run.stderr[-4000:]


def test_counters_reach_the_registry_without_a_tracer():
    n = 256
    g = erdos_renyi(n, 6.0, seed=5)
    traced = compiler.compile(PATTERNS, g, cache=False)
    tracer = obs.Tracer()
    traced.tracer = tracer
    for p in PATTERNS:
        traced.count(p)
    before = {name: sum(obs.REGISTRY.series(name).values())
              for name in ("transfer.h2d_bytes", "transfer.d2h_bytes")}
    bare = compiler.compile(PATTERNS, Graph(n, g.edges), cache=False)
    assert bare.tracer is None
    for p in PATTERNS:
        bare.count(p)
    for name, value in before.items():
        grew = sum(obs.REGISTRY.series(name).values()) - value
        assert grew == tracer.total(name) > 0, name


def test_a_forced_retrace_is_counted_under_its_span():
    f = jax.jit(lambda x: jax.lax.add(x, x))    # one jaxpr, no inner jits
    small, large = jnp.ones(3), jnp.ones(5)
    with obs.span("outer_probe"):
        with obs.span("retrace_probe"):
            assert _state()[0][-2:] == ["outer_probe", "retrace_probe"]
            f(small)
            f(large)                        # a new shape: a second trace
            f(large)                        # cached: no trace
    assert obs.get("jax.traces", span="retrace_probe") == 2
    assert obs.get("jax.traces", span="outer_probe") == 0
    assert _state()[0] == []


def test_span_decorates_and_carries_late_stats():
    calls = []

    @obs.span("decorated_probe")
    def work(x):
        calls.append(_state()[0][-1])
        return x + 1

    assert work(1) == 2 and work(2) == 3
    assert calls == ["decorated_probe", "decorated_probe"]
    with obs.span("late_probe", cut=2) as s:
        s.set(route="kernel")               # known only after the span opens
    assert _state()[0] == []


def test_tracer_tree_keeps_node_and_guard_spans_only():
    """The SCALE-10 motif-4 plan on a GAP Urand graph (degree 16): 22
    node evaluations, every join certified before the run, so the tree
    holds node spans alone — the program's other spans go to the
    profiler, not the tree — and ``node_evals`` stays 22."""
    n, edges = bench_module("urand", "graphs").generate(
        {"SCALE": 10, "degree": 16}, 2147490001)
    cp = compiler.compile(PATTERNS, Graph(n, edges), cache=False)
    tracer = obs.Tracer()
    cp.tracer = tracer
    for p in PATTERNS:
        cp.count(p)
    kinds = [s.kind for s in tracer.walk() if s.kind != "execute"]
    assert len(kinds) == 22
    assert set(kinds) <= set(obs.drift.NODE_KINDS)
    assert all(s.name in cp.plan.nodes for s in tracer.walk()
               if s.kind != "execute")
