"""The benchmark's yardstick: generators, references, the control, the
schema of BENCHMARK.json, the files each cell names, and the trace
reduction on a trace recorded on a TPU v5e."""
from __future__ import annotations

import json
import re

import numpy as np
import pytest

import gpm_testlib as lib

GPM = lib.GPM
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TRACE = GPM / "tests" / "data" / "motif4-small.xplane.pb"


module = lib.module


def config(name):
    return json.loads((GPM / "configs" / f"{name}.json").read_text())


# -- generators ----------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2**31 + 12345])
def test_urand_hits_n_and_m(seed):
    cfg = config("gap-urand-s13")
    n, edges = module("graphs", "urand").generate(cfg, seed)
    assert n == 2 ** cfg["SCALE"] == 8192
    assert edges.shape == (cfg["degree"] * n, 2) == (131072, 2)
    assert edges.min() >= 0 and edges.max() < n
    degrees = np.bincount(edges.ravel(), minlength=n)
    assert degrees.max() < 3 * degrees.mean()         # no skew


@pytest.mark.parametrize("seed", [0, 2**31 + 12345])
def test_kronecker_hits_n_and_m(seed):
    cfg = config("graph500-s13")
    n, edges = module("graphs", "kronecker").generate(cfg, seed)
    assert n == 2 ** cfg["SCALE"] == 8192
    assert edges.shape == (cfg["edgefactor"] * n, 2) == (131072, 2)
    assert edges.min() >= 0 and edges.max() < n
    degrees = np.bincount(edges.ravel(), minlength=n)
    assert degrees.max() > 20 * degrees.mean()        # Kronecker skew


@pytest.mark.parametrize("generator", ["urand", "kronecker"])
def test_generators_follow_the_seed(generator):
    cfg = lib.SMALL_CONFIGS["small-urand" if generator == "urand"
                            else "small-kron"]
    gen = module("graphs", generator)
    a, b, c = gen.generate(cfg, 5), gen.generate(cfg, 5), gen.generate(cfg, 6)
    assert np.array_equal(a[1], b[1]) and not np.array_equal(a[1], c[1])


# -- references against brute force -------------------------------------------

def small_graphs():
    urand = module("graphs", "urand")
    kron = module("graphs", "kronecker")
    yield urand.generate({"SCALE": 4, "degree": 3}, 1)
    yield urand.generate({"SCALE": 4, "degree": 4}, 2)
    yield kron.generate({"SCALE": 4, "edgefactor": 5, "A": 0.57, "B": 0.19,
                         "C": 0.19}, 3)
    # repeats, self-loops and an isolated vertex
    yield 9, np.array([[0, 1], [1, 0], [1, 2], [2, 2], [2, 3], [3, 0],
                       [0, 2], [3, 4], [4, 5], [5, 6], [6, 3], [4, 6]])


@pytest.mark.parametrize("graph", list(range(4)))
def test_motif_reference_equals_brute_force(graph):
    n, edges = list(small_graphs())[graph]
    ref = module("reference", "motif").counts(n, edges, {"k": 4})
    assert ref == lib.brute_motifs(n, edges)


@pytest.mark.parametrize("graph", list(range(4)))
def test_chain_reference_equals_brute_force(graph):
    n, edges = list(small_graphs())[graph]
    ref = module("reference", "chain_local").counts(
        n, edges, {"k": 5, "top": 3})
    count, vec = lib.brute_chain5(n, edges)
    assert ref["count"] == count
    assert np.array_equal(ref["vertex"], vec)
    assert ref["top"] == sorted(vec.tolist(), reverse=True)[:3]


def test_reference_is_invariant_under_relabelling():
    motif, chain = module("reference", "motif"), module("reference",
                                                        "chain_local")
    n, edges = module("graphs", "kronecker").generate(
        lib.SMALL_CONFIGS["small-kron"], 11)
    perm = np.random.default_rng(3).permutation(n)
    assert motif.counts(n, edges, {"k": 4}) == \
        motif.counts(n, perm[edges], {"k": 4})
    base = chain.counts(n, edges, {"k": 5, "top": 10})
    moved = chain.counts(n, perm[edges], {"k": 5, "top": 10})
    assert moved["count"] == base["count"] and moved["top"] == base["top"]
    assert np.array_equal(moved["vertex"][perm], base["vertex"])
    top = [(float(moved["vertex"][w]), int(w))
           for w in np.argsort(-moved["vertex"], kind="stable")[:10]]
    answer = {"count": moved["count"], "vertex": moved["vertex"], "top": top}
    assert chain.compare(answer, base, perm) == 0.0
    answer["vertex"] = answer["vertex"].copy()
    answer["vertex"][perm[0]] += 1
    assert chain.compare(answer, base, perm) == 1.0


# -- the control: the reference one precision down must fail -------------------

@pytest.mark.parametrize("kind,params", [("motif", {"k": 4}),
                                         ("chain_local", {"k": 5, "top": 10})])
def test_float32_control_fails_the_comparison(kind, params):
    """Counts above 2**24 round in float32: the f32 reference put in the
    program's place differs from the f64 reference, so the limit 0 fails
    it, while the f64 reference meets it."""
    ref = module("reference", kind)
    n, edges = module("graphs", "urand").generate(
        lib.SMALL_CONFIGS["control-urand"], 4)
    exact = ref.counts(n, edges, params)
    low = ref.counts(n, edges, params, dtype=np.float32)
    if kind == "chain_local":
        order = np.lexsort((np.arange(n), -low["vertex"]))[:10]
        low["top"] = [(float(low["vertex"][w]), int(w)) for w in order]
        exact_answer = dict(exact, top=[
            (float(exact["vertex"][w]), int(w))
            for w in np.lexsort((np.arange(n), -exact["vertex"]))[:10]])
    else:
        exact_answer = exact
    identity = np.arange(n)
    assert ref.compare(exact_answer, exact, identity) == 0.0
    assert ref.compare(low, exact, identity) > 0.0


# -- BENCHMARK.json ------------------------------------------------------------

def test_benchmark_names_and_units_use_allowed_characters():
    spec = lib.spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in spec["configs"]]
             + [w["name"] for w in spec["workloads"]]
             + [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
             + [w[k] for w in spec["workloads"] for k in ("config", "traffic")]
             + [k for c in spec["configs"] for k in c["reduced"]])
    assert all(NAME.match(x) for x in names), names
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        own = [x["name"] for x in spec[group]]
        assert len(own) == len(set(own))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for text in ([w["why"] for w in spec["workloads"]]
                 + [m["layer"] for m in spec["per_layer"]]
                 + [c["source"] for c in spec["configs"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert 1 <= spec["run_seconds"] <= 51


def test_every_cell_finds_its_files():
    spec = lib.spec()
    configs = {c["name"]: c for c in spec["configs"]}
    for w in spec["workloads"]:
        cfg = config(w["config"])
        assert configs[w["config"]]["file"] == \
            f"benchmarks/gpm/configs/{w['config']}.json"
        assert cfg["reduced"] == configs[w["config"]]["reduced"]
        assert cfg["chips"] == w["chips"] == 1
        assert (GPM / "graphs" / f"{cfg['generator']}.py").is_file()
        traffic = json.loads(
            (GPM / "traffic" / f"{w['traffic']}.json").read_text())
        for job in traffic["jobs"]:
            assert (GPM / "jobs" / f"{job['kind']}.py").is_file()
            assert (GPM / "reference" / f"{job['kind']}.py").is_file()
    cells = {w["name"] for w in spec["workloads"]}
    ends = {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        reader = module("metrics", m["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES) == \
            (m["layer"], m["unit"], m["moves"])
        assert m["moves"] in ends and set(m.get("workloads", cells)) <= cells


def test_peaks_are_keyed_by_device_kind():
    peaks = json.loads((GPM / "peaks.json").read_text())
    assert peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9


# -- trace reduction -----------------------------------------------------------

def test_union_merges_and_clips():
    tr = module("", "trace_reduce")
    merged = tr.union([(5, 8), (0, 2), (1, 3), (7, 12), (20, 30)], 1, 11)
    assert merged == [(1, 3), (5, 11)]


def test_trace_reduce_on_a_recorded_chip_trace():
    """A traced 4-motif window on a 400-vertex graph, recorded on one
    TPU v5e: the ``.xplane.pb`` that a traced ``run_cell`` leaves in its
    profiler directory, copied before the directory is removed."""
    tr = module("", "trace_reduce")
    trace = tr.load(TRACE)
    lo, hi = trace.window()
    ops = trace.window_ops()
    assert trace.devices == [0] and ops
    assert all(op.module for op in trace.ops)   # every op inside a program
    busy = trace.busy_s()
    assert 0 < busy <= trace.window_s() == (hi - lo) / 1e9
    # busy time is the union: no more than the sum of op lengths
    total = sum(min(op.end_ns, hi) - max(op.start_ns, lo) for op in ops) / 1e9
    assert busy <= total + 1e-12
    gaps = trace.idle_gaps(10)
    assert gaps and all(g[1] > 0 for g in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    assert sum(g[1] for g in trace.idle_gaps(10 ** 6)) == \
        pytest.approx(trace.window_s() - busy, rel=1e-9, abs=1e-9)
    assert gaps[0][0] == "plan_search"          # the host compiles the plan
    joins = module("metrics", "join_kernel_s").kernel_seconds(trace)
    contract = trace.program_seconds(
        module("metrics", "contract_device_s").PROGRAMS)
    assert 0 < joins < busy and 0 < contract < busy
    assert trace.top_ops(3)[0][1] >= trace.top_ops(3)[-1][1]


def test_name_tables_match_the_raw_trace():
    """Kernel and Contract seconds by the name tables equal the sums
    read straight off the trace's program line."""
    from jax.profiler import ProfileData
    tr = module("", "trace_reduce")
    trace = tr.load(TRACE)
    lo, hi = trace.window()
    raw = {"einsum": 0.0, "pairjoin": 0.0, "calls": 0}
    for plane in ProfileData.from_file(str(TRACE)).planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for ev in line.events:
                if not lo < ev.start_ns < hi:
                    continue
                kind = ev.name.split("(")[0]
                if kind == "jit__einsum":
                    raw["einsum"] += ev.duration_ns / 1e9
                elif kind == "jit__pairjoin":
                    raw["pairjoin"] += ev.duration_ns / 1e9
                    raw["calls"] += 1
    assert raw["calls"] == 5                    # the census's five pair joins
    assert module("metrics", "join_kernel_s").kernel_seconds(trace) == \
        pytest.approx(raw["pairjoin"], rel=1e-12)
    assert trace.program_seconds(module(
        "metrics", "contract_device_s").PROGRAMS) == \
        pytest.approx(raw["einsum"], rel=1e-12)
