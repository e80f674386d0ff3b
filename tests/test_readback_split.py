"""Large f64 readbacks as two exact f32 halves (``obs.readback``): the
split rebuilds every value bit for bit or falls back to the plain f64
copy, engages only on large f64 arrays off the host, and leaves a mining
job's counts and copied bytes as they were.  The split is forced here by
standing the CPU in for an accelerator (``trace._off_host``)."""
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

from repro import obs
from repro.obs import trace

ROOT = Path(__file__).resolve().parents[1]
GPM = ROOT / "benchmarks" / "gpm"
SIDE = 256                              # SIDE**2 == SPLIT_MIN_ELEMENTS


@pytest.fixture
def forced(monkeypatch):
    """Every device array counts as off the host, so the split engages
    on the CPU backend."""
    monkeypatch.setattr(trace, "_off_host", lambda x: True)


def _read(host: np.ndarray, site: str):
    """(the readback of ``host`` put on the device in f64, split bytes
    and copied bytes counted at ``site``)."""
    with jax.enable_x64():
        x = jnp.asarray(host, jnp.float64)
    out = obs.readback(x, site=site)
    return (out, obs.get("transfer.split_bytes", site=site),
            obs.get("transfer.d2h_bytes", site=site))


def _bits(a: np.ndarray) -> bytes:
    return np.ascontiguousarray(a, np.float64).tobytes()


def _exact_values(kind: str) -> np.ndarray:
    rng = np.random.default_rng(11)
    top = 2.0 ** 48 - 1
    if kind == "integers":
        v = np.floor(rng.random((SIDE, SIDE)) * top)
        v.flat[:4] = [top, 0.0, 1.0, 2.0 ** 24 + 1]
    elif kind == "negatives":
        v = -np.floor(rng.random((SIDE, SIDE)) * top)
        v.flat[:3] = [-top, -1.0, -(2.0 ** 47 + 3)]
    elif kind == "zeros":
        v = np.zeros((SIDE, SIDE))
        v[::2, ::3] = -0.0
    else:                                  # adjacency-like
        v = (rng.random((SIDE, SIDE)) < 0.05).astype(np.float64)
    return v


@pytest.mark.parametrize("kind", ["integers", "negatives", "zeros",
                                  "adjacency"])
def test_split_rebuilds_integers_bit_for_bit(forced, kind):
    host = _exact_values(kind)
    out, split, copied = _read(host, f"split_probe_{kind}")
    assert out.dtype == np.float64 and out.shape == host.shape
    assert _bits(out) == _bits(host)
    assert split == copied == host.nbytes


@pytest.mark.parametrize("value", [1.0 / 3.0, 2.0 ** 50 + 2.0 ** 25 + 1])
def test_an_inexact_value_takes_the_plain_copy(forced, value):
    host = np.floor(np.random.default_rng(3).random((SIDE, SIDE)) * 1e6)
    host[7, 9] = value
    with jax.enable_x64():
        assert trace._split_readback(jnp.asarray(host)) is None
    out, split, copied = _read(host, f"plain_probe_{value!r}")
    assert _bits(out) == _bits(host)
    assert split == 0 and copied == host.nbytes


@pytest.mark.parametrize("host", [
    np.arange(SIDE * SIDE - 1, dtype=np.float64),       # one short
    np.ones((4, 4)),
    np.float64(5.0),
    np.ones((SIDE, SIDE), np.float32),
    np.ones((SIDE, SIDE), np.int32),
    np.full((SIDE, SIDE), 2 ** 40, np.int64),
], ids=["f64-short", "f64-small", "f64-scalar", "f32", "i32", "i64"])
def test_small_or_non_f64_arrays_never_split(forced, host):
    with jax.enable_x64():
        x = jnp.asarray(host)
    assert not trace._splits(x)
    site = f"nosplit_probe_{host.dtype}_{host.size}"
    out = obs.readback(x, site=site)
    assert out.dtype == host.dtype and np.array_equal(out, host)
    assert obs.get("transfer.split_bytes", site=site) == 0
    assert obs.get("transfer.d2h_bytes", site=site) == host.nbytes


def test_host_arrays_do_not_split_unforced():
    with jax.enable_x64():
        x = jnp.zeros((SIDE, SIDE), jnp.float64)
    assert not trace._splits(x)         # the CPU backend copies plainly


_SHARDED = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import obs
    from repro.obs import trace

    trace._off_host = lambda x: True
    mesh = jax.make_mesh((4,), ("data",))
    rng = np.random.default_rng(5)
    host = np.floor(rng.random((512, 512)) * 2.0 ** 48)
    host[0, :8] = [-0.0, -1.0, 0.0, 1.0, 2.0 ** 48 - 1, -(2.0 ** 47), 3, 4]
    with jax.enable_x64():
        x = jax.device_put(jnp.asarray(host),
                           NamedSharding(mesh, P("data", None)))
    assert len(x.sharding.device_set) == 4 and trace._splits(x)
    out = obs.readback(x, site="sharded_probe")
    assert out.tobytes() == host.tobytes()
    assert obs.get("transfer.split_bytes", site="sharded_probe") \\
        == host.nbytes
    print("OK")
"""


def test_a_sharded_array_splits_and_rebuilds():
    """An f64 array row-sharded over four host devices splits on each
    shard and rebuilds whole on the host."""
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    run = subprocess.run([sys.executable, "-c", textwrap.dedent(_SHARDED)],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert run.returncode == 0 and run.stdout.strip().endswith("OK"), \
        run.stderr[-4000:]


def _bench_module(subdir: str, name: str):
    """A file of the benchmark, loaded as its harness loads it."""
    key = "gpm_run_for_readback_split_tests"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, GPM / "run.py")
        sys.modules[key] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[key])
    return sys.modules[key].Bench().module(subdir, name)


def _urand_motif_job(seed: int):
    """A 4-motif census job as the benchmark runs it, on a SCALE 10 GAP
    Urand graph: (answer, its tracer, n)."""
    n, edges = _bench_module("graphs", "urand").generate(
        {"SCALE": 10, "degree": 16}, seed)
    tracer = obs.Tracer()
    answer, _ = _bench_module("jobs", "motif").run(
        n, np.asarray(edges, np.int64), {"k": 4}, tracer)
    return answer, tracer, n


def test_a_forced_split_leaves_a_motif_jobs_counts_and_bytes(monkeypatch):
    seed = 2147490001
    plain, plain_tracer, n = _urand_motif_job(seed)
    monkeypatch.setattr(trace, "_off_host", lambda x: True)
    split, split_tracer, _ = _urand_motif_job(seed)
    assert split == plain
    assert split_tracer.total("transfer.d2h_bytes") == \
        plain_tracer.total("transfer.d2h_bytes")
    assert plain_tracer.total("transfer.split_bytes") == 0
    # the five free Contracts' (n, n) f64 tensors, and nothing else
    assert split_tracer.total("transfer.split_bytes") == 5 * n * n * 8
