"""Mesh-execution tier for the decomposition join: Σ_{e_c} Π_i M_i(e_c)
sharded over a 1-D ``("data",)`` device mesh.

Two layers, mirroring the single-device kernel tier in ``kernels/ops.py``:

**Layer 1 — data-parallel plan execution** (``MeshExecutor``): the graph
(and its compiled plan) is replicated; concurrent requests fan out over
the mesh, one plan eval per device slot (``map``) or as one fused
``shard_map`` over a batch axis (``join_batch``).  Zero numerical
change — each request runs the exact single-device path.

**Layer 2 — block-sharded factors** (``sharded_cutjoin*``): the
CutJoin/LocalCount tile grid is distributed over cut axis 0.  Each
device holds its row-slice of every factor that *carries* axis 0
(axis-subset factors that miss it are replicated), runs the same Pallas
tile kernels on the slice — the injectivity mask stays globally correct
because the kernels take a per-grid-axis ``offsets`` vector
(``axis_index * rows_per_shard``) added to their tile iotas — and
reduces its f32 tile partials locally in f64.  Scalar joins finish with
a ``psum``; keep-axis locals either concatenate per-shard output slices
(the kept axis is the sharded axis) or ``psum`` per-shard partial
vectors (the kept axis is replicated).

**Exactness / bit-for-bitness.**  The sharded routes run only under the
same ``exact_block`` guard as the single-device kernels: every f32
chunk partial is then an exact integer, every per-device f64 partial
sum is an exact integer well below 2^53, and integer f64 addition is
associative — so ``psum`` order, shard count, and padding cannot change
the result, and the sharded count is bit-for-bit equal to the
single-device oracle.  The guard bound is *global* (max over the whole
factor), which dominates every shard's slice max, so a certificate for
the unsharded join certifies each shard's blocks too (see
``analysis.verify.precertify``).

Axis-0 padding to the shard x tile multiple is value-preserving for the
same reason it is in ``kernels/matreduce``: padded factor rows are
zero, the reduction is a sum, and every join has at least one factor
carrying axis 0 (``_tri_normalise`` injects a zero-padded ones-vector
on uncovered axes).

All ``shard_map`` call sites go through ``meshes.sharding_ctx`` — the
repo's ``mesh-guard`` lint rule enforces this — so logical-axis
``constrain`` calls made by factor producers resolve against the same
mesh the join executes on.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.distributed import meshes
from repro.kernels import matreduce as _mr
from repro.kernels.ops import _auto_interpret, _tile

_x64 = jax.enable_x64

# re-exported so GPM callers need only this module
data_mesh = meshes.data_mesh
num_shards = meshes.num_shards


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _pad_axis(x, axis: int, size: int):
    """Zero-pad one axis of ``x`` up to ``size``."""
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, pad)
    return jnp.pad(x, pads)


# -- layer 2: block-sharded joins ---------------------------------------------------

def _shard_offsets(rows: int, q: int, naxes: int):
    """Per-axis global offsets of this shard's slice: ``rows`` per shard
    along kernel axis ``q``, zero elsewhere."""
    start = jax.lax.axis_index("data") * rows
    return jnp.stack([start if a == q else jnp.int32(0)
                      for a in range(naxes)]).astype(jnp.int32)


@functools.lru_cache(maxsize=None)
def _pair_fn(mesh: Mesh, distinct: bool, keep: bool, chunk: int, tr: int,
             tc: int, rows: int, q: int, interpret: bool):
    """shard_map'd pair join over a (k, M, N) stack sharded on kernel
    axis ``q`` (0 = rows, 1 = the lane axis), ``rows`` per shard.  A
    scalar or a per-column partial vector ``psum``s; a kept column axis
    that is itself sharded (keep, q == 1) concatenates its output
    slices.  Cached per (mesh, statics) so serving plans trace once."""
    def local(stack):
        out = _mr._pairjoin(stack, _shard_offsets(rows, q, 2),
                            distinct=distinct, keep=keep, chunk=chunk,
                            tr=tr, tc=tc, interpret=interpret)
        return out if keep and q == 1 else jax.lax.psum(out, "data")

    spec = P(None, "data", None) if q == 0 else P(None, None, "data")
    jfn = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(spec,),
                                out_specs=P("data") if keep and q == 1
                                else P(), check_vma=False))

    def call(*args):
        with meshes.sharding_ctx(mesh):
            return jfn(*args)

    return call


def _sharded_pair(stack, *, mesh, distinct, keep, q, block, tile,
                  interpret):
    """Pad a (k, M, N) stack so kernel axis ``q`` splits into
    tile-aligned shards, then run the shard_map'd pair join."""
    d = num_shards(mesh)
    M, N = stack.shape[1], stack.shape[2]
    local = [M, N]
    local[q] = _ceil_to(local[q], d) // d
    tr, tc, c = _mr._pair_tiles(local[0], local[1], block or tile, tile)
    tiles = (tr, tc)
    size = [_ceil_to(M, tr), _ceil_to(N, tc)]
    size[q] = _ceil_to(local[q] * d, d * tiles[q])
    stack = _pad_axis(_pad_axis(stack, 1, size[0]), 2, size[1])
    fn = _pair_fn(mesh, distinct, keep, c, tr, tc, size[q] // d, q,
                  interpret)
    return fn(stack)


def sharded_cutjoin(factors, *, mesh: Mesh, distinct: bool = True,
                    block: Optional[int] = None, tile: Optional[int] = None,
                    interpret: Optional[bool] = None) -> float:
    """|cut| <= 2 decomposition join sharded over cut axis 0 — the mesh
    analogue of ``ops.cutjoin_reduce``.  ``block`` must come from the
    ``exact_block`` guard (``cutjoin_exact_block`` / a precertified
    chunk): the sharded route inherits the single-device exactness
    contract and is only bit-for-bit under it."""
    interpret = _auto_interpret(interpret)
    stack = _mr._pair_stack(factors)         # vectors: (k, n/128, 128)
    with _x64():
        return float(obs.readback(_sharded_pair(
            stack, mesh=mesh, distinct=distinct and np.ndim(factors[0]) == 2,
            keep=False, q=0, block=block, tile=_tile(interpret, tile),
            interpret=interpret), site="kernel_result"))


def sharded_cutjoin_keep(factors, *, keep: int = 0, mesh: Mesh,
                         distinct: bool = True,
                         block: Optional[int] = None,
                         tile: Optional[int] = None,
                         interpret: Optional[bool] = None) -> np.ndarray:
    """Keep-axis |cut| = 2 join sharded over original cut axis 0 — the
    mesh analogue of ``ops.cutjoin_reduce_keep``.  The kernel keeps its
    lane axis, so keep == 0 transposes: original axis 0 then rides the
    lanes and each shard owns a slice of the output; keep == 1 shards
    the reduced rows and ``psum``s per-shard partial vectors.  Same
    ``exact_block`` contract as the scalar routes."""
    interpret = _auto_interpret(interpret)
    assert keep in (0, 1)
    stack = _mr._pair_stack(factors)
    assert stack.shape[1] == stack.shape[2]
    n = stack.shape[1]
    if keep == 0:
        stack = jnp.swapaxes(stack, 1, 2)    # kept axis onto the lanes
    with _x64():
        out = _sharded_pair(stack, mesh=mesh, distinct=distinct, keep=True,
                            q=1 if keep == 0 else 0, block=block,
                            tile=_tile(interpret, tile), interpret=interpret)
        out = obs.readback(out, site="kernel_result")
        return np.asarray(out, np.float64)[:n]


@functools.lru_cache(maxsize=None)
def _tri_fn(mesh: Mesh, present: tuple, distinct: bool, keep: bool,
            chunk: int, tiles: tuple, rows: int, q: int, interpret: bool):
    """shard_map'd tri join: factors carrying kernel axis ``q`` arrive
    sliced along it (``rows`` per shard), the rest replicated.  A kept
    x axis that is itself sharded (keep, q == 0) concatenates its output
    slices; everything else ``psum``s."""
    def local(*stacked):
        bx, by, bz = tiles
        out = _mr._trijoin(*stacked, offsets=_shard_offsets(rows, q, 3),
                           present=present, distinct=distinct, keep=keep,
                           chunk=chunk, bx=bx, by=by, bz=bz,
                           interpret=interpret)
        return out if keep and q == 0 else jax.lax.psum(out, "data")

    in_specs = tuple(P(*[("data" if a == q else None)
                         for a in _mr.tri_layout(ax)]) for ax in present)
    jfn = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                                out_specs=P("data") if keep and q == 0
                                else P(), check_vma=False))

    def call(*args):
        with meshes.sharding_ctx(mesh):
            return jfn(*args)

    return call


def _sharded_tri(factors, axes, *, n, mesh, distinct, keep, q, block,
                 tile, interpret):
    """Normalise tri factors (compact layouts, tile padding, injected
    ones-vectors), extra-pad kernel axis ``q`` carriers to the shard x
    tile multiple so every shard's slice is tile-aligned, and run the
    shard_map'd tri join."""
    d = num_shards(mesh)
    bx, by, bz, c = _mr._tri_tiles(n, block or tile, tile)
    tiles = (bx, by, bz)
    stacked, present = _mr._tri_normalise(factors, axes, n, tiles)
    size = _ceil_to(_ceil_to(n, tiles[q]), d * tiles[q])
    stacked = [_pad_axis(F, _mr.tri_layout(ax).index(q), size)
               if q in ax else F for F, ax in zip(stacked, present)]
    fn = _tri_fn(mesh, present, distinct, keep, c, tiles, size // d, q,
                 interpret)
    return fn(*stacked)


def sharded_cutjoin3(factors, axes, *, n: int, mesh: Mesh,
                     distinct: bool = True, block: Optional[int] = None,
                     tile: Optional[int] = None,
                     interpret: Optional[bool] = None) -> float:
    """|cut| = 3 decomposition join sharded over cut axis 0 — the mesh
    analogue of ``ops.cutjoin_reduce3``.  Axis-subset factors are sliced
    only when they carry axis 0, else replicated to every device; the
    same ``exact_block`` contract as ``sharded_cutjoin`` applies."""
    interpret = _auto_interpret(interpret)
    with _x64():
        return float(obs.readback(
            _sharded_tri(factors, axes, n=n, mesh=mesh, distinct=distinct,
                         keep=False, q=0, block=block,
                         tile=_tile(interpret, tile), interpret=interpret),
            site="kernel_result"))


def sharded_cutjoin3_keep(factors, axes, *, keep: int, n: int,
                          mesh: Mesh, distinct: bool = True,
                          block: Optional[int] = None,
                          tile: Optional[int] = None,
                          interpret: Optional[bool] = None) -> np.ndarray:
    """Keep-axis |cut| = 3 join sharded over original cut axis 0 — the
    mesh analogue of ``ops.cutjoin_reduce3_keep``.  Factors are
    permuted host-side so the kept axis leads (exactly as the
    single-device wrapper does); the original cut axis 0 then sits at
    kernel position 0 (keep == 0: output slices, all-gather) or 1
    (keep != 0: partial vectors, psum)."""
    interpret = _auto_interpret(interpret)
    pfactors, paxes, perm = _mr.tri_permute(factors, axes, keep)
    with _x64():
        out = _sharded_tri(pfactors, paxes, n=n, mesh=mesh,
                           distinct=distinct, keep=True, q=perm.index(0),
                           block=block, tile=_tile(interpret, tile),
                           interpret=interpret)
        out = obs.readback(out, site="kernel_result")
        return np.asarray(out, np.float64)[:n]


def _distinct(block):
    """Π_{a<b} [x_a != x_b] over one shard's (rows, n, ..., n) block of
    the cut grid, from iotas: axis 0 starts at this shard's offset."""
    start = jax.lax.axis_index("data") * block.shape[0]
    idx = [jax.lax.broadcasted_iota(jnp.int32, block.shape, a)
           + (start if a == 0 else 0) for a in range(block.ndim)]
    keep = None
    for a in range(block.ndim):
        for b in range(a + 1, block.ndim):
            ne = idx[a] != idx[b]
            keep = ne if keep is None else keep & ne
    return keep


@functools.lru_cache(maxsize=None)
def _dense_fn(mesh: Mesh, nf: int, k: int, keep: Optional[int]):
    """shard_map'd dense f64 join (the ``xla-sharded`` and
    ``xla-sharded-keep`` routes): ``nf`` (n, ..., n) factors row-sliced
    on cut axis 0, their product masked to distinct cut tuples from
    iotas inside the shard, then Σ.  A scalar (``keep`` None) and a
    kept axis other than 0 ``psum`` per-shard partials; keep == 0 is
    the sharded axis, so each shard owns its output slice."""
    def local(*factors):
        prod = factors[0]
        for F in factors[1:]:
            prod = prod * F
        if k >= 2:
            prod = jnp.where(_distinct(prod), prod, 0.0)
        if keep is None:
            return jax.lax.psum(jnp.sum(prod), "data")
        vec = jnp.sum(prod, axis=tuple(a for a in range(k) if a != keep))
        return vec if keep == 0 else jax.lax.psum(vec, "data")

    spec = P(*(["data"] + [None] * (k - 1)))
    out_specs = P() if keep is None else P("data") if keep == 0 else P(None)
    jfn = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(spec,) * nf,
                                out_specs=out_specs, check_vma=False))

    def call(*args):
        with meshes.sharding_ctx(mesh):
            return jfn(*args)

    return call


def _dense_factors(Ms, k: int, mesh: Mesh):
    """Each (n,)*k factor on the mesh, row-sliced on cut axis 0 and
    zero-padded to the shard multiple: a host array goes straight to its
    slices (counted as ``obs.upload`` counts), a device array is
    resharded in the join's own program.  No stack is formed."""
    d = num_shards(mesh)
    rows = _ceil_to(np.shape(Ms[0])[0], d)
    sharding = NamedSharding(mesh, P(*(["data"] + [None] * (k - 1))))
    out = []
    for M in Ms:
        if isinstance(M, jax.Array):
            out.append(_pad_axis(jnp.asarray(M, jnp.float64), 0, rows))
            continue
        M = np.asarray(M, np.float64)
        if M.shape[0] < rows:
            M = np.pad(M, [(0, rows - M.shape[0])] + [(0, 0)] * (k - 1))
        out.append(obs.upload(M, sharding=sharding, site="xla_factors"))
    return out


def sharded_dense_join(Ms, k: int, *, mesh: Mesh) -> float:
    """The f64 dense join over factors expanded to the (n,)*k cut grid
    (as ``lowering._eval_cutjoin`` builds them), sharded over the first
    cut axis, summed over pairwise-distinct cut tuples only (the
    injectivity mask, built in each shard; |cut| = 1 needs none).  Pure XLA — no f32
    chunking, so no guard needed; f64 sums of integer counts are exact
    in any order, so this is bit-for-bit with the single-device
    ``_join_reduce``."""
    with _x64():
        fn = _dense_fn(mesh, len(Ms), k, None)
        return float(obs.readback(fn(*_dense_factors(Ms, k, mesh)),
                                  site="xla_result"))


def sharded_dense_join_keep(Ms, k: int, *, keep: int,
                            mesh: Mesh) -> np.ndarray:
    """The f64 dense keep-axis join (factors expanded to the cut grid,
    as ``lowering._eval_local`` builds them; pairwise-distinct cut
    tuples only, as in ``sharded_dense_join``) sharded over cut axis 0 — the mesh analogue
    of the ``_join_keep`` / ``_join_keep3`` XLA oracles, for keep-axis
    joins whose ``exact_block`` guard refused.  Pure XLA, f64 integer
    sums — bit-for-bit with the single-device oracle by the same
    argument as ``sharded_dense_join``."""
    assert 0 <= keep < k
    n = np.shape(Ms[0])[keep]
    with _x64():
        fn = _dense_fn(mesh, len(Ms), k, keep)
        out = obs.readback(fn(*_dense_factors(Ms, k, mesh)),
                           site="xla_result")
        return np.asarray(out, np.float64)[:n]


# -- layer 1: data-parallel plan execution ------------------------------------------

@functools.lru_cache(maxsize=None)
def _batch_pair_fn(mesh: Mesh, distinct: bool):
    """shard_map'd fused request batch: (B, k, n, n) f64 factor stacks
    sharded over the *batch* axis, each device evaluating its slice of
    requests as one dense masked join (product over factors, injectivity
    mask from iotas, per-request sum) — the same f64 arithmetic as the
    single-device ``_join_reduce`` dense route, so exact on integer
    counts with no block guard, in one XLA fusion per device."""
    def local(batch):                        # (per, k, n, n) on this shard
        prod = jnp.prod(batch, axis=1)       # (per, n, n)
        if distinct:
            rows = jax.lax.broadcasted_iota(jnp.int32, prod.shape[1:], 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, prod.shape[1:], 1)
            prod = jnp.where(rows != cols, prod, 0.0)
        return jnp.sum(prod, axis=(1, 2))

    jfn = jax.jit(jax.shard_map(local, mesh=mesh,
                            in_specs=(P("data", None, None, None),),
                            out_specs=P("data"), check_vma=False))

    def call(*args):
        with meshes.sharding_ctx(mesh):
            return jfn(*args)

    return call


class MeshExecutor:
    """Layer-1 data-parallel fan-out: the graph and compiled plans are
    replicated, concurrent requests spread over the ``data`` axis.

    ``map`` round-robins arbitrary per-request thunks over device slots
    via ``jax.default_device`` — zero numerical change, works for any
    plan eval (``PatternQueryBatcher`` requests, ``vertex_counts``,
    FSM-frontier probes).  ``join_batch`` is the fused fast path for
    homogeneous |cut| = 2 join batches: one ``shard_map`` dispatch
    evaluates ``ceil(B / d)`` joins per device instead of ``B``
    sequential kernel dispatches — on forced-host-device CI this is
    where the layer-1 throughput scaling comes from (per-dispatch
    overhead is amortised ~B-fold)."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        self.devices = list(mesh.devices.reshape(-1))

    def map(self, fn, items: Sequence):
        out = []
        for i, item in enumerate(items):
            dev = self.devices[i % len(self.devices)]
            with jax.default_device(dev):
                out.append(fn(item))
        obs.counter("mesh.map_requests", devices=len(self.devices),
                    value=len(items))
        return out

    def join_batch(self, stacks, *, distinct: bool = True) -> np.ndarray:
        """Fused scalar pair joins: ``stacks[r]`` is one request's
        (k, n, n) factor stack; returns the (B,) f64 counts.  Each
        device evaluates its request slice in f64 dense arithmetic
        (exact on integer counts — the same contract as the lowered
        dense route), so the result is bit-for-bit equal to ``B``
        serial guarded-kernel dispatches while paying for one."""
        d = num_shards(self.mesh)
        B = len(stacks)
        with _x64():
            # one host-side stack + one transfer — a per-request
            # jnp conversion loop costs more than the join itself
            big = jnp.asarray(np.asarray(stacks), jnp.float64)
            assert big.ndim == 4
            per = _ceil_to(B, d) // d
            big = _pad_axis(big, 0, per * d)
            fn = _batch_pair_fn(self.mesh, distinct)
            return np.asarray(fn(big), np.float64)[:B]
