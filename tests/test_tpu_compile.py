"""Compile-only checks for a described TPU v5e chip (no chip needed).

Each main-path join kernel must lower to a Mosaic custom call at the
tiles the chip runs and at the padded shapes of a 7,115-vertex graph,
for a certified exactness chunk below the lane width (8) and at it
(128).  One f64 Contract einsum at that size must fit the chip's 16 GB.
On the described v5e:2x2, every form of the sharded Contract step must
fit one chip at n = 16,384 (Graph500 SCALE 14), the adjacency
row-sharded over the four chips.  The topology is described inside a
fixture, never at import: only the worker that runs this file loads the
TPU compiler.
"""
import functools

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.distributed import contract as C
from repro.kernels import matreduce as mr
from repro.kernels import ops

N = 7115                                  # SNAP Wiki-Vote's vertex count
HBM_BYTES = 16e9                          # one v5e chip
HBM_USABLE = 15.75 * 2 ** 30              # what its compiler may allocate
TILE = ops._tile(interpret=False)
SCALE14 = 1 << 14                         # Graph500 SCALE 14's vertices
SCALE15 = 1 << 15


@pytest.fixture(scope="module")
def topo():
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
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.array(topo.devices), ("data",))


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


# the step programs of the sharded Contract that the 4-motif census runs
# at SCALE 14, each over two (n, n) f64 factors row-sharded on the
# eliminated vertex
SHARDED_STEPS = {
    # A(v, x) A(v, y): common-neighbour counts, one int8 plane a side
    "int8-scatter": lambda mesh: C._narrow_fn(mesh, (0, 1), (),
                                              (False, False), (1, 1)),
    # A(v, y) M(v, x) with M's entries up to 128**2: two planes of M
    "int8-scatter-2x1": lambda mesh: C._narrow_fn(mesh, (0, 1), (),
                                                  (False, False), (2, 1)),
    "vector-psum": lambda mesh: C._product_fn(mesh, ((0, 1), (0, 1)), (0, 0),
                                              (0, 1), False),
    "out-sharded-f64": lambda mesh: C._product_fn(mesh, ((0, 1), (0, 1)),
                                                  (0, 0), (0, 1), True),
}


def _row_sharded(mesh, n=SCALE14):
    return jax.ShapeDtypeStruct((n, n), jnp.float64,
                                sharding=NamedSharding(mesh, P("data", None)))


@pytest.mark.parametrize("form", sorted(SHARDED_STEPS))
def test_sharded_contract_step_fits_one_chip_at_scale14(four_chips, form):
    """Per chip: its factor rows, the int8 planes and int32 partials,
    and its row block of the output, inside one chip's HBM."""
    a = _row_sharded(four_chips)
    with jax.enable_x64(True):
        compiled = SHARDED_STEPS[form](four_chips).jitted.lower(a, a).compile()
    m = compiled.memory_analysis()
    total = (m.temp_size_in_bytes + m.argument_size_in_bytes
             + m.output_size_in_bytes)
    assert 0 < total < HBM_USABLE, total
    assert "f64[4096,16384]" in compiled.as_text()  # 4,096 rows a chip


def test_f64_psum_step_does_not_fit_at_scale14(four_chips):
    """The f64 ``psum`` form, kept only where the exactness bound refuses
    the int8 one: at SCALE 14 its emulated-f64 product and replicated
    (n, n) output need 24.00 GB a chip, more than the chip has."""
    a = _row_sharded(four_chips)
    fn = C._psum_fn(four_chips, "ab,ac->bc", (0, 0), (2, 2), 2)
    with jax.enable_x64(True), pytest.raises(Exception) as err:
        fn.jitted.lower(a, a).compile()
    used = re.search(r"Used ([0-9.]+)G of ([0-9.]+)G hbm", str(err.value))
    assert "RESOURCE_EXHAUSTED" in str(err.value) and used, str(err.value)
    assert float(used.group(1)) > float(used.group(2)) == 15.75


@pytest.mark.parametrize("digits, fits", [((1, 1), True), ((2, 1), False)])
def test_int8_scatter_step_at_scale15(four_chips, digits, fits):
    """The ``int8-scatter`` step still needs (n, n) int32 temporaries on
    every chip (the int32 reduce-scatter lowers as an all-reduce), so at
    n = 32,768 the one-plane step fits a chip and the step with two
    planes on one side does not."""
    a = _row_sharded(four_chips, SCALE15)
    fn = C._narrow_fn(four_chips, (0, 1), (), (False, False), digits)
    with jax.enable_x64(True):
        compiled = fn.jitted.lower(a, a).compile()
    m = compiled.memory_analysis()
    total = (m.temp_size_in_bytes + m.argument_size_in_bytes
             + m.output_size_in_bytes)
    assert (total < HBM_USABLE) == fits, total
