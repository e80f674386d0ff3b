"""Benchmark harness: one cell of ``BENCHMARK.json``, run once.

    python3 benchmarks/gpm/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

A cell names a graph deployment (``configs/<name>.json``) and a job mix
(``traffic/<name>.json``).  The run:

1. Set-up (``setup_s``, from process start): JAX on the TPU with its
   compilation cache in the checkout, the graph's edge list generated
   from ``--seed`` by the benchmark's own generator
   (``graphs/<generator>.py``), and one warm-up job of each kind in the
   mix, so that every program the window runs is compiled.  Without a
   TPU, or with fewer chips than the cell asks for, it exits 2 and prints
   no result.
2. The window: jobs back to back, one client.  Each job mines a fresh
   copy of the graph with its vertices permuted from (seed, job index),
   so no plan, count or memo of an earlier job is reused by content.  It
   is timed from the edge list handed to the program's ``Graph`` to
   exact answers on the host (``jobs/<kind>.py``).  The window ends when
   a job ends at or after ``--seconds``.
3. After the window: the device's peak memory is read, the program's
   state dropped, and every job's answers, warm-up included, compared
   with the plain reference (``reference/<kind>.py``), read back through
   the job's permutation.  A job that raised or differs has failed.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last the numbers compared beside their limits under ``checks``.
With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the metrics are the
per-layer ones, each read by ``metrics/<name>.py``.  Everything a cell,
a job mix or a metric needs is found by its name, so adding one is
adding files.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SRC = ROOT / "src"                      # the system under test
SEED_MOD = 1 << 64
# the compared numbers' limits: counts are exact, so any gap fails
LIMITS = {"max_gap": 0.0, "failed_jobs": 0}
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str):
    print(msg, flush=True)


class Bench:
    """The benchmark's files under ``root``, found by name."""

    def __init__(self, root: Path = HERE, spec: Optional[dict] = None):
        self.root = Path(root)
        self.spec = spec if spec is not None else json.loads(
            (self.root.parents[1] / "BENCHMARK.json").read_text())
        self._tag = hashlib.sha1(str(self.root).encode()).hexdigest()[:8]

    def module(self, subdir: str, name: str):
        """Import ``<subdir>/<name>.py`` as a module.  The module sees this
        ``Bench`` as its global ``bench``, so that a file that builds on
        another loads it the same way."""
        path = self.root / subdir / f"{name}.py"
        if not path.is_file():
            raise FileNotFoundError(f"no {subdir} file for {name!r}: {path}")
        key = f"gpm_{self._tag}_{subdir}_{name}".replace("-", "_")
        if key not in sys.modules:
            spec = importlib.util.spec_from_file_location(key, path)
            module = importlib.util.module_from_spec(spec)
            module.bench = self
            sys.modules[key] = module
            spec.loader.exec_module(module)
        return sys.modules[key]

    def data(self, subdir: str, name: str) -> dict:
        path = self.root / subdir / f"{name}.json"
        if not path.is_file():
            raise FileNotFoundError(f"no {subdir} file for {name!r}: {path}")
        return json.loads(path.read_text())

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.root / "peaks.json").read_text())["devices"]
        if device_kind not in table:
            raise KeyError(f"no peaks for device kind {device_kind!r} in "
                           f"peaks.json")
        return table[device_kind]


def permutation(seed: int, index: int, n: int) -> np.ndarray:
    """The vertex renaming of job ``index``: base vertex u is ``perm[u]``
    in the job's graph.  Warm-up jobs take the first indices."""
    rng = np.random.default_rng([seed % SEED_MOD, index])
    return rng.permutation(n)


@dataclass
class Job:
    index: int
    kind: str
    params: dict
    perm: np.ndarray
    seconds: float = 0.0
    answer: Any = None
    info: dict = field(default_factory=dict)
    tracer: Any = None
    error: Optional[str] = None


@dataclass
class Context:
    """What a per-layer metric reader sees: the window's jobs, the
    graph's vertex count, the reduced trace and the chip's peaks."""
    jobs: list
    n: int
    trace: Any
    peaks: dict


class CompileCounter:
    """Counts XLA compilations (persistent-cache hits included) while
    armed, through JAX's monitoring events."""

    def __init__(self):
        self.armed = False
        self.count = 0

    def __call__(self, event: str, duration: float, **_):
        if self.armed and event == COMPILE_EVENT:
            self.count += 1


def setup_compile_cache(directory: Path) -> str:
    """JAX's persistent compilation cache at ``directory``, a fixed path in
    the checkout, whatever the environment names: only the first run of a
    cell in a checkout compiles, and two checkouts share nothing.  Every
    program is cached, however quickly it compiled."""
    import jax
    directory.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(directory))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return str(directory)


def run_job(job: Job, module, n: int, edges: np.ndarray, trace: bool):
    from repro import obs
    job.tracer = obs.Tracer() if trace else None
    relabelled = job.perm[edges]
    t = time.perf_counter()
    try:
        job.answer, job.info = module.run(n, relabelled, job.params,
                                          job.tracer)
    except Exception:                       # a failed job, not a crash
        job.error = traceback.format_exc()
        print(f"job {job.index} ({job.kind}) raised:\n{job.error}",
              file=sys.stderr, flush=True)
    job.seconds = time.perf_counter() - t
    gc.collect()                            # drop the job's device state


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def applies(metric: dict, cell: dict) -> bool:
    return "workloads" not in metric or cell["name"] in metric["workloads"]


def run_cell(bench: Bench, cell: dict, *, seed: int, seconds: float,
             trace: bool, devices, t_start: float = T_START) -> dict:
    """One run of one cell on ``devices``; returns the result object."""
    import jax
    config = bench.data("configs", cell["config"])
    traffic = bench.data("traffic", cell["traffic"])
    n, edges = bench.module("graphs", config["generator"]).generate(
        config, seed % SEED_MOD)
    edges = np.asarray(edges, np.int64)
    mix = traffic["jobs"]
    modules = {spec["kind"]: bench.module("jobs", spec["kind"])
               for spec in mix}
    lo, hi = edges.min(axis=1), edges.max(axis=1)
    simple = len(np.unique((lo * n + hi)[lo != hi]))
    log(f"cell {cell['name']}: n={n}, {len(edges)} edges as generated, "
        f"{simple} distinct without self-loops (mean degree "
        f"{2 * simple / n:.2f}), jobs {[spec['kind'] for spec in mix]}")

    def make(index: int) -> Job:
        spec = mix[index % len(mix)]
        return Job(index, spec["kind"], spec, permutation(seed, index, n))

    compiles = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    warm = [make(i) for i in range(len(mix))]
    for job in warm:
        run_job(job, modules[job.kind], n, edges, trace=False)
        log(f"warm-up job {job.index} ({job.kind}): {job.seconds:.3f} s")
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="gpm-trace-") if trace else None
    if trace:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    jobs = []
    compiles.armed = True
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("gpm.window"):
        while True:
            job = make(len(mix) + len(jobs))
            run_job(job, modules[job.kind], n, edges, trace=trace)
            jobs.append(job)
            if time.perf_counter() - t0 >= seconds:
                break
    window_s = time.perf_counter() - t0
    compiles.armed = False
    if trace:
        jax.profiler.stop_trace()
    peak = peak_bytes(devices)
    for job in jobs:
        log(f"job {job.index} ({job.kind}): {job.seconds:.6f} s, plan "
            f"search {job.info.get('plan_search_s', float('nan')):.6f} s")
    log(f"window: {len(jobs)} jobs in {window_s:.6f} s; XLA compilations "
        f"inside the window: {compiles.count}")
    log(f"peak_bytes_in_use: {peak}")
    from repro import obs
    log("counters: " + json.dumps(
        {k: v for k, v in obs.snapshot().items()
         if k.split(".")[0] in ("kernel", "cutjoin", "plancache",
                                "analysis")}, sort_keys=True, default=str))

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    if trace:
        metrics, breakdown = traced_metrics(bench, cell, jobs, n, trace_dir,
                                            devices, device)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = {"job_s": window_s / len(jobs), "peak_hbm_gb": peak / 1e9,
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in bench.spec["end_to_end"] if applies(m, cell)}
    for job in warm + jobs:
        job.tracer = None
    gc.collect()

    gaps = compare(bench, warm + jobs, n, edges)
    bad = [g is None or g > LIMITS["max_gap"] for g in gaps]
    checks = {"max_gap": {"value": max((g for g in gaps if g is not None),
                                       default=0.0),
                          "limit": LIMITS["max_gap"]},
              "failed_jobs": {"value": sum(bad),
                              "limit": LIMITS["failed_jobs"]}}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": len(jobs), "failed": sum(bad[len(warm):]),
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return out


def compare(bench: Bench, jobs, n: int, edges: np.ndarray) -> list:
    """Each job's widest gap to the plain reference, read back through
    its permutation; None for a job that raised or whose answer has the
    wrong shape.  The reference runs once per distinct job, on the base
    graph."""
    import jax
    refs: dict = {}
    gaps = []
    for job in jobs:
        ref = bench.module("reference", job.kind)
        key = json.dumps(job.params, sort_keys=True)
        if key not in refs:
            with jax.profiler.TraceAnnotation("gpm.reference"):
                refs[key] = ref.counts(n, edges, job.params)
        gap = (None if job.error else
               float(ref.compare(job.answer, refs[key], job.perm)))
        if gap is not None and not np.isfinite(gap):
            gap = None
        if gap is None or gap > LIMITS["max_gap"]:
            print(f"job {job.index} ({job.kind}) differs from the "
                  f"reference: {'no answer' if gap is None else gap}",
                  file=sys.stderr, flush=True)
        gaps.append(gap)
    return gaps


def traced_metrics(bench: Bench, cell: dict, jobs, n: int, trace_dir: str,
                   devices, device: dict):
    """Per-layer metrics of a traced window and its breakdown; adds the
    device's busy and window seconds to ``device``."""
    trace_reduce = bench.module("", "trace_reduce")
    trace = trace_reduce.load(trace_reduce.find_xplane(trace_dir))
    log(f"trace: {len(trace.ops)} device ops, {len(trace.spans)} spans")
    ctx = Context(jobs=jobs, n=n, trace=trace,
                  peaks=bench.peaks(devices[0].device_kind))
    metrics = {}
    for m in bench.spec["per_layer"]:
        if applies(m, cell):
            value = bench.module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device["busy_s"] = trace.busy_s()
    device["window_s"] = trace.window_s()
    return metrics, {"device_ops": trace.top_ops(10),
                     "idle_gaps": trace.idle_gaps(10)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = Bench()
    cell = bench.cell(args.workload)
    sys.path.insert(0, str(SRC))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"run.py: {cell['name']} needs {cell['chips']} TPU chip(s), "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    devices = devices[:cell["chips"]]
    import repro  # noqa: F401  (the system under test: none, no result)
    log(f"device: {devices[0].device_kind} x{len(devices)}; compile cache: "
        f"{setup_compile_cache(bench.root / '.jax_cache')}")
    result = run_cell(bench, cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), devices=devices)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
