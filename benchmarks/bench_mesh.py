"""Mesh-execution tier: data-parallel request fan-out and block-sharded
CutJoin factors (``repro.distributed.cutjoin``).

Two layers, mirroring the tier's design:

* layer 1 — serving throughput: a batch of independent pair-join
  requests served one dispatch at a time (the single-device serving
  loop: one ``cutjoin_reduce`` call per request, each paying full
  dispatch overhead) vs ``MeshExecutor.join_batch`` (one fused
  ``shard_map`` dispatch, requests spread over the ``data`` axis).  On
  the CI host the devices are XLA-forced host platform devices — the
  win measured here is fused-dispatch amortisation, the same mechanism
  that becomes true parallel speedup on a real multi-chip mesh.  The
  derived ``scaling=`` field on the batched row is the acceptance
  number (>= 3x at 8 devices);
* layer 2 — one big join: ``sharded_cutjoin`` (factors block-sharded
  over cut axis 0, f32 chunk partials reduced with ``psum``) vs the
  single-device kernel at n >= 512, counts asserted bit-for-bit equal;
* contract — the factor-*building* tier (``distributed/contract``): a
  free-hom cut tensor contracted from the row-sharded adjacency via
  collective einsums vs the single-device engine, bit-for-bit asserted,
  with the sharded engine's lazy dense adjacency asserted never built.

Run:  PYTHONPATH=src python -m benchmarks.bench_mesh [--smoke]
``--smoke`` runs the tiny CI configuration; either way the rows land in
``benchmarks/results/BENCH_mesh.json`` for the trend renderer.  The
module forces 8 host devices when ``XLA_FLAGS`` is unset, so it
measures the same mesh standalone as under the CI mesh leg.
"""
from __future__ import annotations

import argparse
import os

# must precede the first jax import: host platform device count is fixed
# at backend initialisation
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np

from benchmarks.common import emit, save_json, timeit
from repro.distributed import cutjoin as dcj
from repro.distributed import meshes
from repro.kernels import ops


def _request_stacks(batch: int, n: int, k: int = 2, seed: int = 0):
    """(B, k, n, n) integer factor stacks — one pair-join per request."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, 6, size=(batch, k, n, n)).astype(np.float64)


def bench_layer1(batch: int, n: int, repeat: int = 5):
    """Serving throughput: serial per-request kernel dispatch vs one
    fused mesh dispatch over the ``data`` axis."""
    import jax
    mesh = meshes.data_mesh()
    d = meshes.num_shards(mesh)
    stacks = _request_stacks(batch, n)

    # one guard certificate covering every request (min over the batch)
    block = min(b for b in (ops.cutjoin_exact_block(list(s))
                            for s in stacks) if b is not None)

    def serial():
        return np.asarray([ops.cutjoin_reduce(list(s), distinct=True,
                                              block=block)
                           for s in stacks])

    dt_s, got_s = timeit(serial, repeat=repeat, warmup=True)
    emit(f"mesh/serial/n={n}/B={batch}", dt_s / batch * 1e6)

    ex = dcj.MeshExecutor(mesh)
    dt_b, got_b = timeit(lambda: ex.join_batch(stacks),
                         repeat=repeat, warmup=True)
    scaling = dt_s / max(dt_b, 1e-12)
    emit(f"mesh/batched/n={n}/B={batch}/d={d}", dt_b / batch * 1e6,
         f"scaling={scaling:.1f}x")
    assert np.array_equal(got_s, got_b), "batched counts diverged"
    return scaling


def bench_layer2(n: int, cut: int, repeat: int = 3):
    """One big join, block-sharded over cut axis 0 vs single-device."""
    rng = np.random.default_rng(n + cut)
    mesh = meshes.data_mesh()
    d = meshes.num_shards(mesh)
    Ms = [rng.integers(0, 6, size=(n,) * cut).astype(np.float64)
          for _ in range(2)]
    block = ops.cutjoin_exact_block(Ms)
    assert block is not None

    dt_1, got_1 = timeit(lambda: ops.cutjoin_reduce(Ms, distinct=cut >= 2,
                                                    block=block),
                         repeat=repeat, warmup=True)
    emit(f"mesh/join-single/n={n}/cut={cut}", dt_1 * 1e6)

    dt_m, got_m = timeit(lambda: dcj.sharded_cutjoin(Ms, mesh=mesh,
                                                     distinct=cut >= 2,
                                                     block=block),
                         repeat=repeat, warmup=True)
    emit(f"mesh/join-sharded/n={n}/cut={cut}/d={d}", dt_m * 1e6,
         f"vs_single={dt_1 / max(dt_m, 1e-12):.2f}x")
    assert got_1 == got_m, (got_1, got_m)


def bench_layer2_tri(n: int, repeat: int = 2):
    """|cut| = 3 with axis-subset factors, sharded over axis 0."""
    rng = np.random.default_rng(n)
    mesh = meshes.data_mesh()
    d = meshes.num_shards(mesh)
    axes = [(0, 1), (1, 2), (0, 2)]
    Ms = [rng.integers(0, 5, size=(n, n)).astype(np.float64) for _ in axes]
    block = ops.cutjoin_exact_block(Ms)
    assert block is not None

    dt_1, got_1 = timeit(lambda: ops.cutjoin_reduce3(Ms, axes, n=n,
                                                     block=block),
                         repeat=repeat, warmup=True)
    emit(f"mesh/join3-single/n={n}", dt_1 * 1e6)

    dt_m, got_m = timeit(lambda: dcj.sharded_cutjoin3(Ms, axes, n=n,
                                                      mesh=mesh,
                                                      block=block),
                         repeat=repeat, warmup=True)
    emit(f"mesh/join3-sharded/n={n}/d={d}", dt_m * 1e6,
         f"vs_single={dt_1 / max(dt_m, 1e-12):.2f}x")
    assert got_1 == got_m, (got_1, got_m)


def bench_contract(n: int, repeat: int = 3):
    """The adjacency-sharded contract regime: a 4-cycle cut tensor
    (free = (0, 1)) contracted from the row-sharded adjacency via
    collective einsums vs the single-device dense-adjacency engine.
    Counts asserted bit-for-bit equal; the sharded engine's lazy dense
    adjacency asserted never built (no unsharded n x n anywhere)."""
    from repro.core.counting import CountingEngine
    from repro.core.pattern import cycle
    from repro.graph.generators import erdos_renyi

    mesh = meshes.data_mesh()
    d = meshes.num_shards(mesh)
    g = erdos_renyi(n, avg_degree=8.0, seed=7)
    p, free = cycle(4), (0, 1)

    single = CountingEngine(g)
    sharded = CountingEngine(g, mesh=mesh)

    def run_single():
        single.hom_free_memo.clear()
        return single.hom_free_tensor(p, free)

    def run_sharded():
        sharded.hom_free_memo.clear()
        return np.asarray(sharded.hom_free_tensor(p, free))

    dt_1, got_1 = timeit(run_single, repeat=repeat, warmup=True)
    emit(f"mesh/contract-single/n={n}", dt_1 * 1e6)
    dt_m, got_m = timeit(run_sharded, repeat=repeat, warmup=True)
    emit(f"mesh/contract-sharded/n={n}/d={d}", dt_m * 1e6,
         f"vs_single={dt_1 / max(dt_m, 1e-12):.2f}x")
    assert np.array_equal(np.asarray(got_1), got_m), \
        "sharded contraction diverged"
    assert sharded._A_dense is None, \
        "sharded engine materialised the dense adjacency"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI configuration")
    args = ap.parse_args(argv)
    import jax
    devs = jax.devices()
    if len(devs) < 2:
        raise SystemExit(
            f"bench_mesh measures a mesh and needs at least 2 devices; "
            f"the {devs[0].platform} backend has {len(devs)} (on the CPU, "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8)")

    if args.smoke:
        batch, bn, join_n, tri_n, con_n = 64, 64, 512, 160, 192
    else:
        batch, bn, join_n, tri_n, con_n = 128, 96, 1024, 256, 512

    scaling = bench_layer1(batch, bn)
    bench_layer2(join_n, cut=2)
    bench_layer2_tri(tri_n)
    bench_contract(con_n)
    path = save_json("mesh")
    if scaling < 3.0:
        print(f"WARNING: layer-1 scaling {scaling:.1f}x below the 3x "
              f"acceptance bar", flush=True)
    return path


if __name__ == "__main__":
    main()
