"""The four-chip cell: its files, a small copy of it run on four host
devices through the harness, and its per-layer readers on a synthetic
trace of four devices (and nothing without their programs, ops, steps
or counters)."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

import gpm_testlib as lib

CELL = "motif4-mesh4.graph500-s14"
READERS = ("sharded_contract_s", "sharded_step_roofline", "collective_s",
           "replicated_steps")


def test_mesh_cell_finds_its_files():
    spec = lib.spec()
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    entry = next(c for c in spec["configs"] if c["name"] == cell["config"])
    cfg = json.loads((lib.GPM / "configs" / f"{cell['config']}.json")
                     .read_text())
    assert entry["file"] == f"benchmarks/gpm/configs/{cell['config']}.json"
    assert cfg["reduced"] == entry["reduced"] == ["SCALE"]
    assert cfg["chips"] == cell["chips"] == 4
    assert cfg["SCALE"] == 14 and cfg["source_values"] == {"SCALE": 26}
    # a deployment of its own: the Graph500 generator as the MPI
    # reference code partitions it, not graph500-s13's one-node source
    s13_source = next(c["source"] for c in spec["configs"]
                      if c["name"] == "graph500-s13")
    assert entry["source"] != s13_source
    assert "A=0.57 B=C=0.19, edgefactor 16" in entry["source"]
    s13 = json.loads((lib.GPM / "configs" / "graph500-s13.json").read_text())
    assert set(cfg) == set(s13)
    traffic = json.loads((lib.GPM / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    assert traffic["jobs"] == [{"kind": "motif_mesh", "k": 4, "mesh": 4}]
    for job in traffic["jobs"]:
        assert (lib.GPM / "jobs" / f"{job['kind']}.py").is_file()
        assert (lib.GPM / "reference" / f"{job['kind']}.py").is_file()
    ends = {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        if m["name"] in READERS:
            reader = lib.module("metrics", m["name"])
            assert (reader.LAYER, reader.UNIT, reader.MOVES) == \
                (m["layer"], m["unit"], m["moves"])
            assert m["moves"] in ends and m["workloads"] == [CELL]


def test_mesh_reference_is_the_census_reference():
    mesh_ref = lib.module("reference", "motif_mesh")
    n, edges = lib.module("graphs", "kronecker").generate(
        lib.SMALL_CONFIGS["small-kron"], 5)
    assert mesh_ref.counts(n, edges, {"k": 4, "mesh": 4}) == \
        lib.module("reference", "motif").counts(n, edges, {"k": 4})


_SMALL_MESH_CELL = """
    import json, sys
    from pathlib import Path
    sys.path.insert(0, {tests!r})
    import jax
    import gpm_testlib as lib
    bench = lib.small_copy(Path({tmp!r}))
    cfg = dict(lib.SMALL_CONFIGS["small-kron"], name="small-kron-mesh",
               chips=4, reduced=[])
    (bench.root / "configs" / "small-kron-mesh.json").write_text(
        json.dumps(cfg))
    bench.spec["workloads"].append(
        {{"name": "mesh.small-kron-mesh", "config": "small-kron-mesh",
          "traffic": "motif4-mesh4", "chips": 4, "why": "CPU test cell"}})
    run = lib.harness()
    out = run.run_cell(bench, bench.cell("mesh.small-kron-mesh"),
                       seed=2**31 + 11, seconds=0.0, trace=False,
                       devices=jax.devices()[:4])
    print("RESULT " + json.dumps(out))
"""


def test_small_mesh_cell_runs_correct(tmp_path):
    """A small copy of the cell, through ``run_cell`` on four host
    devices: every job (warm-up included) equals the reference."""
    code = _SMALL_MESH_CELL.format(tests=str(lib.GPM / "tests"),
                                   tmp=str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=300, cwd=lib.ROOT)
    lines = [x for x in proc.stdout.splitlines() if x.startswith("RESULT ")]
    assert lines, proc.stdout + proc.stderr
    out = json.loads(lines[-1][len("RESULT "):])
    assert out["correct"] and out["failed"] == 0, out
    assert out["checks"]["max_gap"]["value"] == 0.0
    assert out["device"]["count"] == 4
    assert set(out["metrics"]) == {"job_s", "peak_hbm_gb", "setup_s"}


# -- the readers on a synthetic trace of four devices -------------------------

class _Tracer:
    def __init__(self, steps=(), counts=None):
        self.counts = counts if counts is not None else {}
        self._spans = [type("S", (), {"attrs": {"steps": list(steps)}})()]

    def walk(self):
        return iter(self._spans)


class _Job:
    def __init__(self, tracer):
        self.tracer = tracer


NARROW_STEP = {"form": "int8-scatter", "spec": "ab,ac->bc",
               "shapes": [[1024, 4096], [1024, 4096]], "dtype": "int8",
               "out_dtype": "int32", "count": 2}
VECTOR_STEP = {"form": "vector-psum", "spec": "ab,ab->b",
               "shapes": [[1024, 4096], [1024, 4096]], "dtype": "float64",
               "out_dtype": "float64", "count": 1}


def _trace(programs=True, collectives=True, spans=True):
    tr = lib.module("", "trace_reduce")
    trace = tr.Trace()
    if spans:
        trace.spans.append(tr.Span("gpm.window", 0.0, 10e9))
    ms = 1e6
    for d in range(4):
        if programs:           # device 3 is the slowest: 3 + 3 ms
            trace.programs += [
                tr.DeviceOp(d, "jit__contract_step", "jit__contract_step",
                            1e9, (2 + (d == 3)) * ms),
                tr.DeviceOp(d, "jit__contract_step", "jit__contract_step",
                            2e9, 3 * ms),
                tr.DeviceOp(d, "jit_local", "jit_local", 3e9, 50 * ms),
                # ends before the window opens: not counted
                tr.DeviceOp(d, "jit__contract_step", "jit__contract_step",
                            -1e9, 7 * ms)]
        trace.ops.append(tr.DeviceOp(d, "fusion.3", "jit__contract_step",
                                     1e9, 1 * ms))
        if collectives:        # overlapping halves union to 4 ms on device 2
            trace.ops += [
                tr.DeviceOp(d, "all-reduce.1", "jit__contract_step", 2e9,
                            (2 + 2 * (d == 2)) * ms),
                tr.DeviceOp(d, "all-gather-start.2", "jit__contract_step",
                            2e9 + 1 * ms, 1 * ms),
                tr.DeviceOp(d, "all-to-all.4", "jit__contract_step",
                            5e9, 0.5 * ms),
                tr.DeviceOp(d, "reduce-scatter.7", "jit_local", 20e9, ms)]
    return trace


def _ctx(trace, jobs):
    run = lib.harness()
    return run.Context(jobs=jobs, n=4096, trace=trace,
                       peaks={"flops_per_s": 197e12,
                              "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9})


def _read(name, ctx):
    return lib.module("metrics", name).read(ctx)


def test_readers_on_a_four_device_trace(monkeypatch):
    jobs = [_Job(_Tracer([NARROW_STEP, VECTOR_STEP],
                         {"contract.steps": {"form=int8-scatter": 2.0,
                                             "form=f64-psum": 1.0},
                          "contract.trim_gathers": {"": 1.0}})),
            _Job(_Tracer([NARROW_STEP],
                         {"contract.steps": {"form=int8-scatter": 1.0}}))]
    ctx = _ctx(_trace(), jobs)
    slowest = (3 + 3) / 1e3                     # device 3, in the window
    assert _read("sharded_contract_s", ctx) == pytest.approx(slowest / 2)
    # device 2: the all-reduce (4 ms) holds the all-gather's 1 ms, plus
    # the all-to-all's 0.5 ms; the reduce-scatter lies outside the window
    assert _read("collective_s", ctx) == pytest.approx(4.5e-3 / 2)
    assert _read("replicated_steps", ctx) == 1.0
    import jax
    monkeypatch.setattr(jax, "devices",
                        lambda: [type("D", (), {"device_kind":
                                                "TPU v5 lite"})()])
    rows, rp = 1024, 4096
    narrow = 2 * max(2.0 * rows * rp * rp / 393e12,
                     (2 * rows * rp + 4 * rp * rp) / 819e9)
    vector = max(2.0 * rows * rp / 197e12, (16 * rows * rp + 8 * rp) / 819e9)
    share = _read("sharded_step_roofline", ctx)
    assert share == pytest.approx(100 * (2 * narrow + vector) / slowest)
    assert 0 < share <= 100


@pytest.mark.parametrize("reader", READERS)
def test_readers_give_nothing_without_their_sources(reader):
    """No step programs, no collectives, no step descriptions and no
    ``contract.steps`` counter (a program without the sharded steps), or
    no device operation at all: each reader gives nothing."""
    jobs = [_Job(_Tracer([], {"transfer.d2h_bytes": {"site=x": 8.0}}))]
    bare = _trace(programs=False, collectives=False)
    assert _read(reader, _ctx(bare, jobs)) is None
    empty = _trace(programs=False, collectives=False)
    empty.ops.clear()
    assert _read(reader, _ctx(empty, jobs)) is None
