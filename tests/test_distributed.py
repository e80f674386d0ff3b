"""Dry-run driver and production mesh shapes on forced host devices.

These tests spawn subprocesses with XLA_FLAGS so the main pytest process
keeps its single CPU device (per the task sheet).
"""
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=560)


def test_dryrun_driver_small_mesh():
    """The dry-run driver itself works end-to-end on a small forced mesh."""
    r = _run("""
        import sys
        sys.argv = ["dryrun"]
        from repro.launch.dryrun import build_cell, rules_for
        from repro.configs.registry import get_config
        from repro.configs.base import SHAPES
        from repro.distributed.meshes import sharding_ctx
        import jax
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh((2, 4), ("data", "model"))
        import dataclasses
        from repro.configs.base import reduced_config
        cfg = dataclasses.replace(reduced_config(get_config("qwen3-4b")),
                                  num_layers=4)
        shape = dataclasses.replace(SHAPES["train_4k"], seq=128, batch=8)
        rules = rules_for(cfg, shape)
        with sharding_ctx(mesh, rules):
            fn, args, in_sh, out_sh, donate = build_cell(
                cfg, shape, mesh, rules, microbatches=2)
            c = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                        donate_argnums=donate).lower(*args).compile()
        assert c.memory_analysis() is not None
        print("OK")
    """)
    assert "OK" in r.stdout, r.stdout + r.stderr


def test_production_mesh_shapes():
    r = _run("""
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        m2 = make_production_mesh(multi_pod=True)
        assert dict(m1.shape) == {"data": 16, "model": 16}
        assert dict(m2.shape) == {"pod": 2, "data": 16, "model": 16}
        print("OK")
    """, devices=512)
    assert "OK" in r.stdout, r.stdout + r.stderr
