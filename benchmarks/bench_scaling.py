"""Fig 31: scalability.  The container has one physical core, so strong
scaling cannot be *measured* here; instead we (a) verify work-partitioned
execution (block-cyclic units) has low partitioning overhead — the
property that yields the paper's near-linear scaling when units run on
independent workers — and (b) run the sharded-einsum path on 8 forced
host CPU devices in a CPU-only subprocess to confirm multi-device
execution (it raises if the child fails)."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timeit
from repro.core import homomorphism as H
from repro.core.distributed import blockwise_hom_count
from repro.core.pattern import chain
from repro.graph import generators as gen

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run(scale: str = "small"):
    g = gen.erdos_renyi(2000, 10.0, seed=1)
    A = jnp.asarray(g.dense_adjacency(np.float64, pad=False))
    p = chain(5)
    t1, base = timeit(lambda: float(H.hom_count(p, A)))
    emit("scaling/blocks/1", t1 * 1e6, "")
    for nb in (2, 4, 8, 16):
        t, v = timeit(blockwise_hom_count, p, A, None, nb)
        assert abs(v - base) < 1e-6 * max(1.0, base)
        emit(f"scaling/blocks/{nb}", t * 1e6,
             f"overhead={t / t1:.2f}x")
    # sharded execution across forced host devices: a CPU-only child,
    # so it never contends with this process for an accelerator
    code = textwrap.dedent("""
        import jax, numpy as np, time
        from repro.graph.generators import erdos_renyi
        from repro.core.pattern import chain
        from repro.core.distributed import shard_adjacency, sharded_hom_count
        g = erdos_renyi(2000, 10.0, seed=1)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        A = shard_adjacency(g.dense_adjacency(np.float64, pad=False), mesh)
        t0 = time.perf_counter(); v = sharded_hom_count(chain(5), A, mesh)
        print(f"SHARDED_OK {time.perf_counter()-t0:.3f}")
    """)
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=560)
    if r.returncode != 0 or "SHARDED_OK" not in r.stdout:
        raise RuntimeError(f"8-device CPU child failed (rc "
                           f"{r.returncode}):\n{r.stderr[-2000:]}")
    emit("scaling/sharded_8dev_cpu", float(r.stdout.split()[-1]) * 1e6)


if __name__ == "__main__":
    run()
