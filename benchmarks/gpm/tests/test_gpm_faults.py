"""A run with the timed path broken underneath must come out not
correct: an answer altered where the program produces it, in each job
kind, half of the graph's edges left out of the job, a per-vertex
answer served stale from an earlier job, a job that raises, and the
control (the reference in float32) put in the program's place."""
from __future__ import annotations

import json

import numpy as np
import pytest

import gpm_testlib as lib


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return lib.small_copy(tmp_path_factory.mktemp("gpm"))


def count_off_by_one(monkeypatch, bench):
    from repro.compiler import lowering
    count = lowering.CompiledPlan.count
    monkeypatch.setattr(lowering.CompiledPlan, "count",
                        lambda self, p: count(self, p) + 1.0)


def vertex_entry_altered(monkeypatch, bench):
    import repro.api
    plan_vertex_counts = repro.api.plan_vertex_counts

    def altered(cp, p):
        vec = plan_vertex_counts(cp, p)
        vec[len(vec) // 2] += 1.0
        return vec
    monkeypatch.setattr(repro.api, "plan_vertex_counts", altered)


def half_the_edges(monkeypatch, bench):
    import repro.graph.storage
    graph = repro.graph.storage.Graph
    monkeypatch.setattr(repro.graph.storage, "Graph",
                        lambda n, edges: graph(n, edges[:len(edges) // 2]))


def stale_vertex_vector(monkeypatch, bench):
    """Every job gets the first job's per-vertex vector back: right for
    the graph the first job mined, wrong under any other labelling."""
    import repro.api
    plan_vertex_counts = repro.api.plan_vertex_counts
    first = []

    def stale(cp, p):
        if not first:
            first.append(plan_vertex_counts(cp, p))
        return first[0].copy()
    monkeypatch.setattr(repro.api, "plan_vertex_counts", stale)


def compile_raises(monkeypatch, bench):
    import repro.compiler

    def broken(*args, **kwargs):
        raise RuntimeError("planted fault")
    monkeypatch.setattr(repro.compiler, "compile", broken)


def float32_control(monkeypatch, bench):
    """The control in the program's place: every job answers with the
    reference computed in float32 on the job's own relabelled graph."""
    control = bench.module("", "control")
    for kind in ("motif", "chain_local"):
        ref = bench.module("reference", kind)

        def f32_job(n, edges, params, tracer=None, kind=kind, ref=ref):
            low = ref.counts(n, edges, params, dtype=np.float32)
            return control.as_answer(kind, low, n), {"plan_search_s": 0.0}
        monkeypatch.setattr(bench.module("jobs", kind), "run", f32_job)


@pytest.mark.parametrize("cell", ["chain5-local.small-urand",
                                  "chain5-local.small-kron"])
def test_stale_answer_fails_on_a_relabelled_graph(bench, monkeypatch, cell):
    """The first job is the warm-up; every window job mines another
    labelling, so an answer reused from it fails."""
    lib.harness()
    stale_vertex_vector(monkeypatch, bench)
    out = lib.run_small(bench, cell, seed=13)
    assert out["correct"] is False
    assert out["failed"] == out["attempted"] >= 1


@pytest.mark.parametrize("cell,fault", [
    ("motif4.small-urand", count_off_by_one),
    ("chain5-local.small-urand", count_off_by_one),
    ("chain5-local.small-urand", vertex_entry_altered),
    ("motif4.small-urand", half_the_edges),
    ("chain5-local.small-kron", half_the_edges),
    ("motif4.small-kron", compile_raises),
    ("motif4.control-urand", float32_control),
    ("chain5-local.control-urand", float32_control),
])
def test_planted_fault_makes_the_run_not_correct(bench, monkeypatch, cell,
                                                 fault):
    lib.harness()
    fault(monkeypatch, bench)
    out = lib.run_small(bench, cell, seed=11)
    assert out["correct"] is False
    assert out["failed"] == out["attempted"] >= 1
    assert out["checks"]["failed_jobs"]["value"] >= 1
    json.dumps(out, allow_nan=False)            # the line stays strict JSON
    if fault is not compile_raises:
        assert out["checks"]["max_gap"]["value"] > out["checks"][
            "max_gap"]["limit"]

