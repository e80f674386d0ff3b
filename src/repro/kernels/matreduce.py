"""Fused masked-reduce Pallas kernels for counting-plan contractions.

``matreduce``    total = Σ_{i,j} mask[i,j] · (lhs @ rhsᵀ)[i,j] — the final
                 contraction step of a counting plan (e.g. triangle count
                 = Σ A ⊙ (A@A)); fusing the reduction keeps the (M,N)
                 product entirely in VMEM, never materialised to HBM.  The
                 scalar accumulates in SMEM.

``prod_reduce``  the k-factor masked product-reduce behind the compiler's
                 ``CutJoin`` op: Σ_{x,y} [x≠y] · Π_i F_i[x,y] over stacked
                 2-D factor tensors (|cut| = 2), or Σ_x Π_i F_i[x] for 1-D
                 factors (|cut| = 1, laid out as 128-wide lane rows, no
                 mask — a single cut vertex is always injective).  The
                 off-diagonal injectivity mask is derived *in-kernel* from
                 tile iotas, so no O(n²) mask is ever built.

``prod_reduce_keep``  the keep-axis variant behind ``LocalCount`` plans:
                 out[x] = Σ_{y≠x} Π_i F_i[x, y].  The same kernel; the
                 kept axis rides the lanes and partials are summed per
                 column.

``tri_reduce[_keep]``  the |cut| = 3 tier: Σ_{x≠y, y≠z, x≠z} Π_i F_i over a
                 3-D tile grid, where each factor spans a *subset* of the
                 cut axes and is stored at its own rank (``tri_layout``),
                 broadcast per tile in VMEM, never expanded in HBM; the
                 pairwise-distinct mask comes from three tile iotas.  The
                 keep variant permutes the factors so the kept axis leads
                 and sums partials per x.

**Tiles and exactness.**  Compiled for TPU the tiles are (8, 128)-aligned
with 128 lanes (``ops._tile``); interpreted they grow to 1024, where
per-step dispatch dominates.  The certified exactness chunk ``block``
(``exact_block``: a power of two from 8 up) never sets the tile: each
tile reduces its product over ``chunk`` sublane rows at a time into
lane-dense f32 partials, so every partial sums at most ``block`` cells
and stays an exact integer below 2^24.  Partials are reduced in f64 on
device; callers hold ``jax.enable_x64``.  Inputs are zero-padded to the
tile multiple, so any ``n`` works (padded entries are zero and the
reduction is a sum).

**Global index offsets.**  Every masked kernel takes a small int32
``offsets`` vector (one entry per cut axis, default zeros), prefetched
into SMEM and added to the tile iotas before the injectivity
comparison: a caller holding only a *slice* of the factor tensors — one
device's block of a cut axis under the mesh tier
(``distributed/cutjoin.py``) — passes its global start index so the
mask compares global cut vertices, not slice-local positions.  Offsets
may be traced values (``axis_index * rows`` inside ``shard_map``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs


def _pad_to(x, multiples):
    """Zero-pad every axis of ``x`` up to the matching tile multiple."""
    pads = [(0, (-s) % m) for s, m in zip(x.shape, multiples)]
    if any(p for _, p in pads):
        x = jnp.pad(x, pads)
    return x


# -- matreduce: Σ mask ⊙ (lhs @ rhsᵀ) ---------------------------------------------

def _matreduce_kernel(lhs_ref, rhs_ref, mask_ref, out_ref, acc_ref):
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    first = (i == 0) & (j == 0) & (k == 0)

    @pl.when(first)
    def _init():
        acc_ref[0, 0] = jnp.float32(0.0)

    prod = jax.lax.dot_general(lhs_ref[...], rhs_ref[...],
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)
    acc_ref[0, 0] += jnp.sum(prod * mask_ref[...].astype(jnp.float32))

    # write-out every step (sequential grid on TPU): last value wins
    out_ref[0, 0] = acc_ref[0, 0]


def matreduce(lhs, rhs, mask, *, bm: int = 128, bn: int = 128,
              bk: int = 128, interpret: bool = False):
    """Σ mask ⊙ (lhs @ rhsᵀ): lhs (M,K), rhs (N,K), mask (M,N) -> f32 scalar.

    Inputs are zero-padded to the tile multiple (count-preserving: padded
    mask entries are zero), so arbitrary shapes work.

    NOTE: with a K-grid the per-(i,j) product tile is partial, so the mask
    must be applied to partial products — valid because the mask is
    multiplicative and the reduction is a sum: Σ_k mask⊙P_k = mask⊙Σ_k P_k.
    """
    M, K = lhs.shape
    N = rhs.shape[0]
    assert rhs.shape[1] == K and mask.shape == (M, N)
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    lhs = _pad_to(lhs, (bm, bk))
    rhs = _pad_to(rhs, (bn, bk))
    mask = _pad_to(mask, (bm, bn))
    (M, K), N = lhs.shape, rhs.shape[0]
    out = pl.pallas_call(
        _matreduce_kernel,
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.float32),
        scratch_shapes=[pltpu.SMEM((1, 1), jnp.float32)],
        interpret=interpret,
    )(lhs, rhs, mask)
    return out[0, 0]


# -- join kernels: Σ over (injective) index tuples of Π_i F_i ----------------------

LANE, SUBLANE = 128, 8                 # the TPU's f32 vreg tile is (8, 128)
SLAB_PARTIALS = 1 << 24                # f32 partials one tri-join slab may hold


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _pow2_ceil(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def _chunk(block: int, tile: int, extent: int) -> int:
    """Cells per f32 partial: the certified ``block``, clamped to the
    tile and to the power-of-two cover of the axis it chunks (fewer
    cells per partial is always at least as exact), never below one
    sublane group so row tiles stay (8, 128)-aligned."""
    assert block >= SUBLANE and tile >= SUBLANE, (block, tile)
    return min(block, tile, max(SUBLANE, _pow2_ceil(extent)))


def _pair_tiles(M: int, N: int, block: int, tile: int):
    """(row tile, lane tile, chunk) for an (M, N) pair join: rows are a
    multiple of the chunk, lanes a multiple of 128."""
    c = _chunk(block, tile, M)
    return min(tile, _ceil_to(M, c)), min(tile, _ceil_to(N, LANE)), c


def _tri_tiles(n: int, block: int, tile: int):
    """(bx, by, bz, chunk) for an n-cube tri join: z on the lanes, y
    chunked on the sublanes, x a short leading axis — an (8, 128, 128)
    f32 tile is 512 KiB of VMEM per temporary at the TPU tile."""
    c = _chunk(block, tile, n)
    bx = min(max(SUBLANE, tile // 16), _ceil_to(n, SUBLANE))
    return bx, min(tile, _ceil_to(n, c)), min(tile, _ceil_to(n, LANE)), c


_I0 = np.int32(0)             # index-map zero: stays i32 when traced under x64


def _check_x64():
    # the partial reduce is f64; traced without x64 it would silently
    # run in f32 and lose exactness
    assert jax.config.jax_enable_x64, "join kernels reduce under enable_x64"


def _pairjoin_kernel(off_ref, stack_ref, out_ref, *, nf, masked, chunk):
    """One (tr, tc) tile of Π_i F_i[x, y], off-diagonal masked (tile
    iotas offset to global coordinates by the SMEM ``off_ref``), reduced
    to (tr / chunk, tc) per-column f32 partials of ``chunk`` cells each."""
    i, j = pl.program_id(0), pl.program_id(1)
    prod = stack_ref[0]
    for f in range(1, nf):
        prod = prod * stack_ref[f]
    tr, tc = prod.shape
    if masked:
        rows = jax.lax.broadcasted_iota(jnp.int32, (tr, tc), 0) \
            + (i * tr + off_ref[0])
        cols = jax.lax.broadcasted_iota(jnp.int32, (tr, tc), 1) \
            + (j * tc + off_ref[1])
        prod = jnp.where(rows == cols, jnp.float32(0.0), prod)
    out_ref[...] = prod.reshape(tr // chunk, chunk, tc).sum(axis=1)


@functools.partial(jax.jit, static_argnames=("distinct", "keep", "chunk",
                                             "tr", "tc", "interpret"))
def _pairjoin(stack, offsets, *, distinct, keep, chunk, tr, tc, interpret):
    """(k, M, N) tile-padded f32 stack -> the f64 sum of its (masked)
    factor product: a scalar, or with ``keep`` the (N,) per-column
    vector.  Per-tile partials come out lane-dense, (grid0, tr/chunk,
    N), and are reduced on device in f64."""
    _check_x64()
    k, M, N = stack.shape
    grid = (M // tr, N // tc)
    R = tr // chunk
    parts = pl.pallas_call(
        functools.partial(_pairjoin_kernel, nf=k, masked=distinct,
                          chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[pl.BlockSpec((k, tr, tc),
                                   lambda i, j, off: (_I0, i, j))],
            out_specs=pl.BlockSpec((None, R, tc),
                                   lambda i, j, off: (i, _I0, j))),
        out_shape=jax.ShapeDtypeStruct((grid[0], R, N), jnp.float32),
        interpret=interpret,
    )(offsets, stack)
    parts = parts.astype(jnp.float64)
    return parts.sum(axis=(0, 1)) if keep else parts.sum()


def _offsets_or_zero(offsets, naxes: int):
    """Normalise a per-axis global-offset vector (None -> zeros).  The
    entries may be traced (``shard_map`` passes ``axis_index``-derived
    starts), so everything downstream treats this as array data."""
    if offsets is None:
        return jnp.zeros((naxes,), jnp.int32)
    off = jnp.asarray(offsets, jnp.int32)
    assert off.shape == (naxes,), (off.shape, naxes)
    return off


def _pair_stack(factors):
    """f32 (k, M, N) stack; (n,) vectors (|cut| = 1) are laid out as
    (k, n/128, 128) lane rows so one kernel serves both cut sizes."""
    stack = jnp.stack([obs.upload(F, jnp.float32, site="kernel_factors")
                       for F in factors])
    if stack.ndim == 2:
        stack = _pad_to(stack, (1, LANE)).reshape(stack.shape[0], -1, LANE)
    assert stack.ndim == 3        # rectangular slices legal (sharded rows)
    return stack


def prod_reduce(factors, *, distinct: bool = True, block: int = 128,
                tile: int = 128, interpret: bool = False,
                offsets=None) -> float:
    """Σ over index tuples of Π_i F_i, factors all (n,) or all (n, n).

    ``distinct`` (2-D only) restricts the sum to off-diagonal cells —
    the |cut| = 2 injectivity constraint — via an in-kernel tile-index
    mask; nothing O(n²) is ever materialised besides the factor tensors
    the caller already holds.  Factors are cast to f32 and zero-padded to
    the tile multiple; every f32 partial sums at most ``block`` cells and
    the partials are reduced on device in f64 — exact for integer-valued
    factors while each partial stays below 2^24, which ``exact_block``
    certifies.  ``tile`` is the lane width (128 on TPU); ``offsets``
    gives the factors' global start index per cut axis (sliced callers
    only; vectors have no mask, so they ignore them).
    """
    stack = _pair_stack(factors)
    distinct = distinct and np.ndim(factors[0]) == 2
    tr, tc, c = _pair_tiles(stack.shape[1], stack.shape[2], block, tile)
    stack = _pad_to(stack, (1, tr, tc))
    with jax.enable_x64(True):
        return float(obs.readback(
            _pairjoin(stack, _offsets_or_zero(offsets, 2),
                      distinct=distinct, keep=False, chunk=c, tr=tr, tc=tc,
                      interpret=interpret), site="kernel_result"))


def prod_reduce_keep(factors, *, keep: int = 0, distinct: bool = True,
                     block: int = 128, tile: int = 128,
                     interpret: bool = False, offsets=None) -> np.ndarray:
    """Keep-axis masked product-reduce over (n, n) factors:

        keep=0:  out[x] = Σ_y [x≠y] · Π_i F_i[x, y]
        keep=1:  out[y] = Σ_x [x≠y] · Π_i F_i[x, y]

    The anchored partial-embedding read off a |cut| = 2 decomposition
    join: the ``prod_reduce`` kernel with its per-column partials summed
    per column, so the kept axis rides the lanes (keep=0 transposes the
    factors host-side).  Same padding, mask and exactness contract as
    ``prod_reduce``; ``offsets`` gives the factors' global start index
    per *original* cut axis, reordered alongside the axes.
    """
    stack = _pair_stack(factors)
    assert keep in (0, 1)
    off = _offsets_or_zero(offsets, 2)
    if keep == 0:
        stack = jnp.swapaxes(stack, 1, 2)    # kept axis onto the lanes
        off = off[::-1]
    n = stack.shape[2]
    tr, tc, c = _pair_tiles(stack.shape[1], n, block, tile)
    stack = _pad_to(stack, (1, tr, tc))
    with jax.enable_x64(True):
        out = _pairjoin(stack, off, distinct=distinct, keep=True, chunk=c,
                        tr=tr, tc=tc, interpret=interpret)
        out = obs.readback(out, site="kernel_result")
    return np.asarray(out, np.float64)[:n]


# -- tri_reduce: the |cut| = 3 tiled tri-join --------------------------------------

def tri_layout(axes) -> tuple:
    """The stored dims of a tri-join factor spanning cut ``axes``: the
    cut axis each HBM dim carries, None for a unit dim.  Factors keep
    their own rank (a pair factor stays (n, n) — a trailing unit dim
    would be padded to a full 128-lane tile in HBM); vectors are stored
    2-D with the unit dim placed so their block stays (8, 128)-legal."""
    ax = tuple(axes)
    return {(0,): (0, None), (1,): (1, None), (2,): (None, 2)}.get(ax, ax)


def _trijoin_kernel(meta_ref, *refs, present, masked, chunk):
    """One (bx, by, bz) tile of [x,y,z pairwise distinct] · Π_i F_i.
    Each factor block is viewed 3-D with unit dims on its absent axes
    and broadcast against the tile (never expanded in memory); the mask
    is three tile-iota comparisons offset to global coordinates by the
    SMEM ``meta_ref`` (per-axis offsets, then the slab's first x tile).
    The tile writes (bx, by / chunk, bz) lane-dense f32 partials of
    ``chunk`` cells each."""
    out_ref = refs[-1]
    bx, R, bz = out_ref.shape
    shape = (bx, R * chunk, bz)
    prod = None
    for ref, ax in zip(refs[:-1], present):
        f = ref[...].reshape(tuple(d if a in ax else 1
                                   for a, d in enumerate(shape)))
        prod = f if prod is None else prod * f
    prod = jnp.broadcast_to(prod, shape)
    if masked:
        i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        x = jax.lax.broadcasted_iota(jnp.int32, shape, 0) \
            + ((meta_ref[3] + i) * bx + meta_ref[0])
        y = jax.lax.broadcasted_iota(jnp.int32, shape, 1) \
            + (j * shape[1] + meta_ref[1])
        z = jax.lax.broadcasted_iota(jnp.int32, shape, 2) \
            + (k * bz + meta_ref[2])
        prod = jnp.where((x == y) | (x == z) | (y == z), jnp.float32(0.0),
                         prod)
    out_ref[...] = prod.reshape(bx, R, chunk, bz).sum(axis=2)


@functools.partial(jax.jit,
                   static_argnames=("present", "distinct", "keep", "chunk",
                                    "bx", "by", "bz", "interpret"))
def _trijoin(*stack, offsets, present, distinct, keep, chunk, bx, by, bz,
             interpret):
    """``stack``: one tile-padded f32 array per factor, stored as
    ``tri_layout(present[f])`` says.  Returns the f64 sum: a scalar, or
    with ``keep`` the per-x vector.  x runs in slabs (``lax.map``), each
    slab's partials reduced on device in f64 before the next, so the
    partial buffer stays near ``SLAB_PARTIALS`` whatever n is."""
    _check_x64()
    extent = [1, 1, 1]
    for s, ax in zip(stack, present):
        for d, a in zip(s.shape, tri_layout(ax)):
            if a is not None:
                extent[a] = max(extent[a], d)
    M, N, K = extent
    tiles = (bx, by, bz)
    R = by // chunk
    slab = bx
    while M % (2 * slab) == 0 and 2 * slab * N * K // chunk <= SLAB_PARTIALS:
        slab *= 2

    def spec(axes):
        dims = tri_layout(axes)
        return pl.BlockSpec(
            tuple(1 if a is None else tiles[a] for a in dims),
            lambda i, j, k, meta, dims=dims: tuple(
                _I0 if a is None else (meta[3] + i, j, k)[a] for a in dims))

    call = pl.pallas_call(
        functools.partial(_trijoin_kernel, present=present, masked=distinct,
                          chunk=chunk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(slab // bx, N // by, K // bz),
            in_specs=[spec(axes) for axes in present],
            out_specs=pl.BlockSpec((bx, None, R, bz),
                                   lambda i, j, k, meta: (i, j, _I0, k))),
        out_shape=jax.ShapeDtypeStruct((slab, N // by, R, K), jnp.float32),
        interpret=interpret)

    def one_slab(s):
        meta = jnp.concatenate([offsets, (s * (slab // bx))[None]])
        parts = call(meta.astype(jnp.int32), *stack).astype(jnp.float64)
        return parts.sum(axis=(1, 2, 3)) if keep else parts.sum()

    out = jax.lax.map(one_slab, jnp.arange(M // slab, dtype=jnp.int32))
    return out.reshape(-1) if keep else out.sum()


def _tri_normalise(factors, axes, n: int, tiles):
    """Cast each factor to f32, store it as ``tri_layout`` says (its own
    rank — axis-subset factors are broadcast per tile, never expanded),
    zero-pad each stored axis to its tile multiple, and inject a
    ones-vector on any axis no factor covers (zero-padded, so padding
    never contributes even on uncovered axes)."""
    covered = set()
    stacked, present = [], []
    for F, ax in zip(factors, axes):
        ax = tuple(ax)
        assert ax == tuple(sorted(set(ax))) and set(ax) <= {0, 1, 2}
        F = obs.upload(F, jnp.float32, site="kernel_factors")
        assert F.ndim == len(ax) and all(s == n for s in F.shape), \
            (F.shape, ax, n)
        covered |= set(ax)
        stacked.append(F)
        present.append(ax)
    for a in sorted({0, 1, 2} - covered):
        stacked.append(jnp.ones((n,), jnp.float32))
        present.append((a,))
    out = []
    for F, ax in zip(stacked, present):
        dims = tri_layout(ax)
        F = F.reshape(tuple(1 if a is None else n for a in dims))
        out.append(_pad_to(F, tuple(1 if a is None else tiles[a]
                                    for a in dims)))
    return out, tuple(present)


def tri_reduce(factors, axes, *, n: int, distinct: bool = True,
               block: int = 128, tile: int = 128, interpret: bool = False,
               offsets=None) -> float:
    """Σ over (pairwise-distinct) index triples of Π_i F_i, where factor
    i spans only the cut axes ``axes[i]`` (a sorted subset of (0, 1, 2))
    and broadcasts along the rest.

    The |cut| = 3 decomposition join.  The injectivity mask is derived
    in-kernel from tile indices — nothing O(n³) is materialised beyond
    whatever genuinely 3-D factors the caller already holds; axis-subset
    factors stay at their own size.  Every f32 partial sums at most
    ``block`` cells, so ``exact_block`` certifies the same chunk bound
    as the pair tier; partials are reduced on device in f64.
    ``offsets`` gives the factors' global start index per cut axis
    (sliced callers only)."""
    bx, by, bz, c = _tri_tiles(n, block, tile)
    stacked, present = _tri_normalise(factors, axes, n, (bx, by, bz))
    with jax.enable_x64(True):
        return float(obs.readback(
            _trijoin(*stacked, offsets=_offsets_or_zero(offsets, 3),
                     present=present, distinct=distinct, keep=False,
                     chunk=c, bx=bx, by=by, bz=bz, interpret=interpret),
            site="kernel_result"))


def tri_permute(factors, axes, keep: int):
    """Permute factors so cut axis ``keep`` leads (free for axis-subset
    factors — only their axis labels move).  Returns (factors, axes,
    perm), ``perm[i]`` the original axis now at position i."""
    assert keep in (0, 1, 2)
    perm = (keep,) + tuple(a for a in range(3) if a != keep)
    rank = {a: i for i, a in enumerate(perm)}
    pfactors, paxes = [], []
    for F, ax in zip(factors, axes):
        ax = tuple(ax)
        new = tuple(sorted(rank[a] for a in ax))
        order = tuple(ax.index(perm[a]) for a in new)
        pfactors.append(np.transpose(np.asarray(F), order)
                        if order != tuple(range(len(ax))) else F)
        paxes.append(new)
    return pfactors, paxes, perm


def tri_reduce_keep(factors, axes, *, keep: int, n: int,
                    distinct: bool = True, block: int = 128,
                    tile: int = 128, interpret: bool = False,
                    offsets=None) -> np.ndarray:
    """Keep-axis tri-join: out[w] = Σ over the other two (pairwise-
    distinct) axes of Π_i F_i — the anchored partial-embedding vector of
    a |cut| = 3 plan.  ``keep`` picks the surviving axis; factors are
    permuted host-side so it leads, then the same kernel runs with its
    partials reduced per x row.  ``offsets`` gives the factors' global
    start index per *original* cut axis, permuted alongside the axes."""
    pfactors, paxes, perm = tri_permute(factors, axes, keep)
    off = _offsets_or_zero(offsets, 3)[jnp.asarray(perm)]
    bx, by, bz, c = _tri_tiles(n, block, tile)
    stacked, present = _tri_normalise(pfactors, paxes, n, (bx, by, bz))
    with jax.enable_x64(True):
        out = _trijoin(*stacked, offsets=off, present=present,
                       distinct=distinct, keep=True, chunk=c, bx=bx, by=by,
                       bz=bz, interpret=interpret)
        out = obs.readback(out, site="kernel_result")
    return np.asarray(out, np.float64)[:n]


EXACT_LIMIT = float(1 << 24)                 # f32 exact-integer range


def exact_block(factors, max_block: int = 1024, min_block: int = 8,
                maxes=None):
    """Largest power-of-two chunk size whose f32 partial sums stay exact
    for integer-valued ``factors``.  Every kernel partial accumulates at
    most ``b`` cells (a column chunk of a pair tile, a y chunk of a tri
    tile), so every partial is an integer bounded by (Π_i max|F_i|) · b,
    and integers up to 2^24 are exactly representable in f32.  ``maxes``
    supplies precomputed per-factor max magnitudes (serving plans cache
    them — see ``CompiledPlan``) so repeated executions skip the
    full-tensor scan.  Returns None when even a ``min_block`` chunk
    cannot guarantee exactness — callers should take an f64 path
    instead."""
    maxprod = 1.0
    if maxes is None:
        maxes = [float(np.abs(np.asarray(F)).max()) for F in factors]
    for m in maxes:
        maxprod *= float(m)
    b = max_block
    while b >= min_block:
        if maxprod * b <= EXACT_LIMIT:
            return b
        b //= 2
    return None
