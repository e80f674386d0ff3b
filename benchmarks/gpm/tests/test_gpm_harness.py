"""The harness end to end on the CPU, on small cells of a copy of the
benchmark: correct runs, jobs on relabelled graphs, the result line's
keys, and a config, a job mix and a per-layer metric added as files."""
from __future__ import annotations

import json
import subprocess
import sys

import numpy as np
import pytest

import gpm_testlib as lib


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return lib.small_copy(tmp_path_factory.mktemp("gpm"))


@pytest.mark.parametrize("cell", ["motif4.small-urand",
                                  "chain5-local.small-urand",
                                  "motif4.small-kron"])
def test_small_cells_run_correct(bench, cell):
    out = lib.run_small(bench, cell, seed=2**31 + 7)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert out["correct"] and out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"job_s", "peak_hbm_gb", "setup_s"}
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 1
    assert out["checks"]["max_gap"] == {"value": 0.0, "limit": 0.0}


def test_relabelled_jobs_answer_alike():
    """One job module on two labellings of one graph: the same counts,
    and the per-vertex vector moved by the permutation."""
    run = lib.harness()
    bench = run.Bench()
    n, edges = bench.module("graphs", "urand").generate(
        lib.SMALL_CONFIGS["small-urand"], 3)
    perm = run.permutation(3, 1, n)
    motif = bench.module("jobs", "motif")
    a, _ = motif.run(n, edges, {"k": 4})
    b, _ = motif.run(n, perm[edges], {"k": 4})
    assert a == b
    chain = bench.module("jobs", "chain_local")
    a, _ = chain.run(n, edges, {"k": 5, "top": 5})
    b, _ = chain.run(n, perm[edges], {"k": 5, "top": 5})
    assert a["count"] == b["count"]
    assert np.array_equal(b["vertex"][perm], a["vertex"])
    assert [v for v, _ in a["top"]] == [v for v, _ in b["top"]]


def test_added_files_are_found_by_name(bench):
    """A new graph config, a new job mix and a new per-layer metric are
    files; the harness runs them with no edit to an existing file."""
    root = bench.root
    (root / "configs" / "small-new.json").write_text(json.dumps(
        {"name": "small-new", "generator": "urand", "SCALE": 5,
         "degree": 4, "chips": 1, "reduced": []}))
    (root / "traffic" / "mixed.json").write_text(json.dumps(
        {"name": "mixed",
         "jobs": [{"kind": "motif", "k": 4},
                  {"kind": "chain_local", "k": 5, "top": 3}]}))
    (root / "metrics" / "window_jobs.py").write_text(
        'LAYER = "harness"\nUNIT = "count"\nMOVES = "job_s"\n\n\n'
        'def read(ctx):\n    return float(len(ctx.jobs))\n')
    bench.spec["workloads"].append(
        {"name": "mixed.small-new", "config": "small-new",
         "traffic": "mixed", "chips": 1, "why": "added as files"})
    bench.spec["per_layer"].append(
        {"name": "window_jobs", "unit": "count", "better": "higher",
         "source": "host_clock", "layer": "harness", "moves": "job_s",
         "workloads": ["mixed.small-new"]})
    out = lib.run_small(bench, "mixed.small-new", trace=True, seconds=1e-3)
    assert out["correct"]
    # the metrics that list their cells do not list this one
    assert out["metrics"] == {"window_jobs": {"value": 1.0, "unit": "count"}}


def test_traced_run_reads_the_layers(bench):
    """A traced run gives the per-layer metrics of its cell; the device's
    readers find no TPU operation in a CPU trace and give nothing."""
    out = lib.run_small(bench, "motif4.small-urand", trace=True,
                        seconds=1e-3)
    assert out["correct"] and out["attempted"] >= 1
    assert set(out["metrics"]) == {"plan_search_s", "node_evals"}
    assert out["metrics"]["node_evals"]["value"] > 0
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, str(lib.GPM / "run.py"), "--workload",
         "motif4.graph500-s13", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=lib.ROOT,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 2
    assert "{" not in proc.stdout and "needs 1 TPU" in proc.stderr
