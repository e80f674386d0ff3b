"""Compile-only checks for a described TPU v5e chip (no chip needed).

Each main-path join kernel must lower to a Mosaic custom call at the
tiles the chip runs and at the padded shapes of a 7,115-vertex graph,
for a certified exactness chunk below the lane width (8) and at it
(128).  One f64 Contract einsum at that size must fit the chip's 16 GB.
The topology is described inside a fixture, never at import: only the
worker that runs this file loads the TPU compiler.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import matreduce as mr
from repro.kernels import ops

N = 7115                                  # SNAP Wiki-Vote's vertex count
HBM_BYTES = 16e9                          # one v5e chip
TILE = ops._tile(interpret=False)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:               # no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache, so keep it out of the cache altogether
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pair(sharding, block, *, vec=False, keep=False):
    """The pair kernel as ``prod_reduce[_keep]`` drives it: (3, n, n)
    factors, or three (n,) vectors laid out as 128-wide lane rows."""
    rows, cols = (-(-N // mr.LANE), mr.LANE) if vec else (N, N)
    tr, tc, c = mr._pair_tiles(rows, cols, block, TILE)
    stack = _sds(sharding, (3, mr._ceil_to(rows, tr), mr._ceil_to(cols, tc)))
    return mr._pairjoin.lower(stack, _sds(sharding, (2,), jnp.int32),
                              distinct=not vec, keep=keep, chunk=c, tr=tr,
                              tc=tc, interpret=False)


def _tri(sharding, block, *, keep=False):
    """The tri kernel over three pair factors spanning every axis pair."""
    bx, by, bz, c = mr._tri_tiles(N, block, TILE)
    tiles = (bx, by, bz)
    present = ((0, 1), (1, 2), (0, 2))
    stack = [_sds(sharding, tuple(1 if a is None else mr._ceil_to(N, tiles[a])
                                  for a in mr.tri_layout(ax)))
             for ax in present]
    return mr._trijoin.lower(*stack,
                             offsets=_sds(sharding, (3,), jnp.int32),
                             present=present, distinct=True, keep=keep,
                             chunk=c, bx=bx, by=by, bz=bz, interpret=False)


KERNELS = {
    "pair": _pair,
    "vec": functools.partial(_pair, vec=True),
    "pair-keep": functools.partial(_pair, keep=True),
    "tri": _tri,
    "tri-keep": functools.partial(_tri, keep=True),
}


@pytest.mark.parametrize("block", [8, 128])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_join_kernel_compiles_for_v5e(one_chip, kernel, block):
    with jax.enable_x64(True):
        compiled = KERNELS[kernel](one_chip, block).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_f64_contract_einsum_fits_one_chip(one_chip):
    """The widest two-operand Contract step at this n (an emulated f64
    matrix product) stays inside one chip's HBM."""
    a = _sds(one_chip, (N, N), jnp.float64)
    with jax.enable_x64(True):
        compiled = jax.jit(lambda x, y: jnp.einsum("ab,bc->ac", x, y)) \
            .lower(a, a).compile()
    m = compiled.memory_analysis()
    total = (m.temp_size_in_bytes + m.argument_size_in_bytes
             + m.output_size_in_bytes)
    assert 0 < total < HBM_BYTES, total
