"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.bitset import pack_bitsets

RNG = np.random.default_rng(0)


def _rand(shape, dtype):
    return jnp.asarray(RNG.normal(size=shape), dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("M,N,K", [(128, 128, 128), (256, 128, 384),
                                   (64, 96, 32), (200, 130, 70)])
def test_sddmm_matches_ref(M, N, K, dtype):
    lhs, rhs = _rand((M, K), dtype), _rand((N, K), dtype)
    mask = jnp.asarray(RNG.random((M, N)) < 0.3, jnp.float32)
    got = ops.sddmm(lhs, rhs, mask, bm=64, bn=64, bk=32, interpret=True)
    want = ref.sddmm_ref(lhs, rhs, mask)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("M,N,K", [(128, 128, 128), (96, 64, 160)])
def test_matreduce_matches_ref(M, N, K, dtype):
    lhs, rhs = _rand((M, K), dtype), _rand((N, K), dtype)
    mask = jnp.asarray(RNG.random((M, N)) < 0.5, jnp.float32)
    got = float(ops.masked_matmul_reduce(lhs, rhs, mask, bm=64, bn=64,
                                         bk=32, interpret=True))
    want = float(ref.matreduce_ref(lhs, rhs, mask))
    assert abs(got - want) < (abs(want) * 3e-2 + 1.0)


def test_triangle_count_kernel_matches_engine():
    from repro.core.counting import CountingEngine
    from repro.core.pattern import clique
    from repro.graph.generators import erdos_renyi
    g = erdos_renyi(150, 10.0, seed=4)
    adj = g.dense_adjacency(np.float32, pad=False)
    got = float(ops.triangle_count(adj, interpret=True))
    want = CountingEngine(g).edge_induced(clique(3))
    assert abs(got - want) < 1e-3


@pytest.mark.parametrize("n", [150, 200])
def test_triangle_count_pads_non_tile_multiple_n(n):
    """The wrapper zero-pads to the tile multiple, so any n works and
    padding is count-preserving."""
    from repro.core.counting import CountingEngine
    from repro.core.pattern import clique
    from repro.graph.generators import erdos_renyi
    g = erdos_renyi(n, 6.0, seed=4)
    assert g.n % 128 != 0
    adj = g.dense_adjacency(np.float32, pad=False)
    got = float(ops.triangle_count(adj, interpret=True))
    assert got == CountingEngine(g).edge_induced(clique(3))


@pytest.mark.parametrize("E,W", [(256, 4), (512, 16), (64, 7)])
def test_bitset_intersect_matches_ref(E, W):
    a = RNG.integers(0, 2**32, size=(E, W), dtype=np.uint32)
    b = RNG.integers(0, 2**32, size=(E, W), dtype=np.uint32)
    from repro.kernels.bitset import bitset_intersect
    blk = 64 if E % 64 == 0 else 1
    got = np.asarray(bitset_intersect(jnp.asarray(a), jnp.asarray(b),
                                      block=blk, interpret=True))
    want = ref.bitset_popcount_ref(a, b)
    np.testing.assert_array_equal(got, want)


def test_common_neighbors_counts_triangles():
    from repro.graph.generators import erdos_renyi
    g = erdos_renyi(100, 8.0, seed=6)
    adj = g.dense_adjacency(np.float32, pad=False) > 0.5
    cn = np.asarray(ops.common_neighbors(np.asarray(adj), g.edges,
                                         interpret=True))
    # sum over edges of common neighbours = 3 * #triangles
    from repro.core.counting import CountingEngine
    from repro.core.pattern import clique
    tri = CountingEngine(g).edge_induced(clique(3))
    assert cn.sum() == 3 * tri


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,D,causal", [
    (1, 128, 2, 64, True), (2, 256, 2, 64, True),
    (1, 128, 1, 128, False), (2, 64, 4, 32, True)])
def test_flash_attention_kernel_matches_ref(B, S, H, D, causal, dtype):
    q, k, v = (_rand((B, S, H, D), dtype) for _ in range(3))
    got = ops.flash_attention(q, k, v, causal=causal, bq=64, bk=64,
                              interpret=True)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, D)
    want = ref.flash_attention_ref(qf, kf, vf, causal=causal)
    want = want.reshape(B, H, S, D).transpose(0, 2, 1, 3)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_kernel_matches_model_layer():
    """Kernel result == the model's XLA flash path (layers.py)."""
    from repro.models.layers import flash_attention as xla_flash
    q, k, v = (_rand((2, 128, 2, 32), jnp.float32) for _ in range(3))
    got = ops.flash_attention(q, k, v, causal=True, bq=32, bk=32,
                              interpret=True)
    # layers.py works per-head already
    want = xla_flash(q, k, v, causal=True, block=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
