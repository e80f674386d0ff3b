"""Sharded hom contractions: bucket elimination with the adjacency
row-sharded over the 1-D ``("data",)`` device mesh.

``sharded_hom`` mirrors ``core.homomorphism.hom_count`` step for step —
same factor construction, same elimination order, same ``PlanTooWide``
cap — but the dense adjacency never exists as one n x n array anywhere:

* ``adjacency_blocks`` builds each device's row block directly from the
  graph's CSR via ``jax.make_array_from_callback`` — host-side peak is
  one (rows, Rp) block, device-side each shard holds only its rows;
* ``label_blocks`` shards the one-hot label indicators over the vertex
  (column) axis, so a labelled pattern's unary factors arrive already
  sliced;
* each elimination step runs under ``shard_map`` with the eliminated
  vertex's axis sharded in every involved factor (the adjacency is
  symmetric, so a factor carrying the vertex on its column axis is
  relabelled to serve it from the row-sharded buffer — no transpose, no
  gather).  Each device contracts its slice of the eliminated vertex,
  in one of four forms, each counted in ``contract.steps{form=}``:

  - ``int8-scatter`` — a step whose output is a vertex pair, (n, n).
    The factors on each side of the pair multiply into one (rows, Rp)
    integer block that is cut into base-128 digit planes of int8; each
    pair of planes is one int8 x int8 -> int32 product over the device's
    slice, a whole (Rp, Rp) int32 partial, reduce-scattered over
    ``"data"`` so that every device keeps its own row block of the
    output, widened to f64 and added up with its digit weight.  The
    output comes out row-sharded ``P("data", None)`` over its first
    axis, and nothing of size (n, n) is f64.  The int32 partials are
    not: XLA:TPU lowers the int32 reduce-scatter as an all-reduce of
    the whole partial and a slice, so a device holds (n, n) int32
    temporaries, and its memory still grows with n^2 (measured in
    ``tests/test_tpu_compile.py``: SCALE 14 fits a v5e chip, a two-plane
    step at SCALE 15 does not);
  - ``f64-psum`` — a step with two or more output axes that the narrow
    form does not take, because the magnitude bound refuses it or a
    factor or the output has three or more axes: the f64 einsum over
    the slice, ``psum``'d, so the output is replicated on every device;
  - ``vector-psum`` — a step whose output is one vertex axis or a
    scalar: the factors multiply elementwise in f64 over the slice and
    sum over the eliminated vertex, then ``psum`` (at most n f64 values
    cross the mesh);
  - ``out-sharded-f64`` — the final free-axis step, which sums nothing:
    the factors multiply elementwise in f64 with the *output* sharded
    over ``free[0]`` (cut axis 0), so the cut tensor a decomposition
    join consumes is born sliced along exactly the axis
    ``distributed/cutjoin`` shards.  An adjacency factor between two
    *later* free vertices is the one input that must replicate into the
    step; ``contract.finish_gathers`` counts those.

**Exactness.**  Every intermediate is a sum of products of 0/1
adjacency entries and non-negative integer unaries — a non-negative
integer.  Each factor carries a bound on its entries, computed from the
graph bound at call time (n and its largest degree) and never from
cached metadata: the adjacency and the unaries are 1; a step's output
is at most the product of its factors' bounds times its number of
non-zero terms, which is the largest degree when an adjacency factor is
involved (A(v, y) is non-zero for at most deg(y) values of v) and n
otherwise.  The narrow form runs only where that bound certifies it:

* each side's product is below 128^2, so it splits exactly into at most
  two int8 digit planes of 0..127;
* each plane product is at most 127 · 127 · (terms) < 2^31, so every
  int32 partial sum — each a sum of non-negative terms, over any subset
  of the eliminated vertex, on any device — is exact, and the
  reduce-scatter's sums too;
* the output bound is below 2^53, so the f64 sum of the weighted planes
  (each an integer times a power of two) is exact in any order.

Every other step is f64 over non-negative integers below 2^53 as
before, where integer addition is associative: psum order, shard count
and zero-padding cannot change any value, and the sharded route is
bit-for-bit equal to ``hom_count`` (the same argument as
``distributed/cutjoin``).  A pair step the bound refuses takes
``f64-psum`` and is counted, never narrowed.  At Graph500 SCALE 14 on a
v5e the f64-psum pair step needs 24.00 GB a chip and does not compile,
so a pair step the bound refuses there exhausts the chip; the census's
int8 steps need at most 2.7 GB of temporaries with one or two planes a
side (``tests/test_tpu_compile.py``).

**Padding.**  Vertex axes run over ``Rp = ceil(n / d) * d``.  Zero-
padding is value-preserving by induction: the adjacency blocks and
unary vectors are zero outside ``[0, n)``, every elimination output
axis is carried by some involved factor, so intermediates stay zero in
every padded region and padded assignments of the eliminated vertex
contribute nothing.  When d divides n there is no padding and the
returned free tensor keeps its ``P("data", ...)`` sharding end to end;
an indivisible n must trim ``Rp -> n``, and this jax version has no
uneven sharding, so the trim replicates the finished tensor
(``contract.trim_gathers`` counts it — the adjacency itself still
never materialises unsharded either way).

Every step is one jitted program named ``_contract_step``.  Callers
hold ``jax.enable_x64`` while calling (the engine does), so factors and
steps trace in f64.  All ``shard_map`` call sites go through
``meshes.sharding_ctx`` — the repo's ``mesh-guard`` lint rule — so
logical-axis ``constrain`` calls by surrounding code resolve against
the mesh the contraction executes on.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core import homomorphism as H
from repro.distributed import meshes

# step forms, the labels of ``contract.steps``
NARROW = "int8-scatter"
F64_PSUM = "f64-psum"
VECTOR = "vector-psum"
OUT_SHARDED = "out-sharded-f64"

DIGIT_BITS = 7                  # an int8 digit plane holds 0..127
MAX_DIGITS = 2                  # per side: products below 128**2
INT32_MAX = (1 << 31) - 1
F64_EXACT = 1 << 53


class Factor(NamedTuple):
    idx: tuple                  # pattern vertices, one per array axis
    array: object               # (Rp,)*len(idx), sharded or replicated
    adj: bool                   # the row-sharded adjacency itself
    bound: int                  # every entry lies in [0, bound]


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def padded_rows(n: int, mesh: Mesh) -> int:
    """Global vertex-axis extent of the sharded buffers: n rounded up to
    the shard multiple (== n exactly when the mesh divides n)."""
    return _ceil_to(max(n, 1), meshes.num_shards(mesh))


def adjacency_blocks(graph, mesh: Mesh, dtype=np.float64):
    """The (Rp, Rp) dense adjacency sharded ``P("data", None)``: each
    device's row block is built directly from CSR inside the
    ``make_array_from_callback`` shard callback, so no n x n array ever
    exists — not on the host, not on any device."""
    n = graph.n
    Rp = padded_rows(n, mesh)
    offs, nbrs = graph.csr
    sharding = NamedSharding(mesh, P("data", None))

    def block(index):
        rs = index[0]
        start = rs.start or 0
        stop = Rp if rs.stop is None else rs.stop
        out = np.zeros((stop - start, Rp), dtype)
        for r in range(start, min(stop, n)):
            out[r - start, nbrs[offs[r]:offs[r + 1]]] = 1
        return out

    return jax.make_array_from_callback((Rp, Rp), sharding, block)


def label_blocks(graph, mesh: Mesh, dtype=np.float64):
    """(num_labels, Rp) one-hot label indicators sharded
    ``P(None, "data")`` — row l is the label-l unary factor, already
    sliced along the vertex axis every elimination step shards."""
    assert graph.labels is not None
    n, L = graph.n, graph.num_labels
    Rp = padded_rows(n, mesh)
    labels = graph.labels
    sharding = NamedSharding(mesh, P(None, "data"))

    def block(index):
        cs = index[1]
        start = cs.start or 0
        stop = Rp if cs.stop is None else cs.stop
        out = np.zeros((L, stop - start), dtype)
        hi = min(stop, n)
        if hi > start:
            out[labels[start:hi], np.arange(hi - start)] = 1
        return out

    return jax.make_array_from_callback((L, Rp), sharding, block)


def _spec(rank: int, axis: Optional[int]) -> P:
    return P(*[("data" if i == axis else None) for i in range(rank)])


def _jit_step(body, mesh: Mesh, in_specs: tuple, out_specs: P):
    """``body`` under ``shard_map`` on ``mesh``, jitted as the program
    ``_contract_step`` (the name the benchmark's trace reader finds);
    the jitted function itself is ``.jitted``."""
    def _contract_step(*arrs):
        return body(*arrs)

    jfn = jax.jit(jax.shard_map(_contract_step, mesh=mesh,
                                in_specs=in_specs, out_specs=out_specs,
                                check_vma=False))

    def call(*args):
        with meshes.sharding_ctx(mesh):
            return jfn(*args)

    call.jitted = jfn                   # for compile-only checks
    return call


def _local_product(arrs, idx_sets: tuple, order: tuple):
    """Elementwise f64 product of one shard's factor slices, each
    broadcast to the axes ``order`` — no dot, so no emulated-f64
    matrix-product temporaries."""
    out = None
    for a, s in zip(arrs, idx_sets):
        a = jnp.transpose(a, [s.index(i) for i in order if i in s])
        extents = iter(a.shape)
        a = a.reshape([next(extents) if i in s else 1 for i in order])
        out = a if out is None else out * a
    return out


@functools.lru_cache(maxsize=None)
def _product_fn(mesh: Mesh, idx_sets: tuple, shard_axes: tuple,
                order: tuple, out_sharded: bool):
    """The ``vector-psum`` and ``out-sharded-f64`` steps.  ``order`` is
    the eliminated vertex then the output axes (``vector-psum``: summed
    over axis 0 and ``psum``'d), or the output axes alone
    (``out-sharded-f64``: sharded over ``order[0]``, nothing summed).
    Cached per (mesh, statics) so serving plans trace once."""
    def body(*arrs):
        prod = _local_product(arrs, idx_sets, order)
        if out_sharded:
            return prod
        return jax.lax.psum(jnp.sum(prod, axis=0), "data")

    in_specs = tuple(_spec(len(s), ax) for s, ax in zip(idx_sets, shard_axes))
    rank = len(order) - (0 if out_sharded else 1)
    out_specs = _spec(rank, 0 if out_sharded else None) if rank else P()
    return _jit_step(body, mesh, in_specs, out_specs)


@functools.lru_cache(maxsize=None)
def _psum_fn(mesh: Mesh, spec: str, shard_axes: tuple, ranks: tuple,
             out_rank: int):
    """The ``f64-psum`` step: the f64 einsum over each device's slice of
    the eliminated vertex, ``psum``'d — the output replicated."""
    def body(*arrs):
        return jax.lax.psum(jnp.einsum(spec, *arrs), "data")

    in_specs = tuple(_spec(r, ax) for r, ax in zip(ranks, shard_axes))
    return _jit_step(body, mesh, in_specs, _spec(out_rank, None))


def _digits(x, count: int):
    """Base-128 int8 digit planes of a block of integers below
    128**count, least significant first."""
    x = x.astype(jnp.int32)
    return [((x >> (DIGIT_BITS * i)) & 127).astype(jnp.int8)
            for i in range(count)]


@functools.lru_cache(maxsize=None)
def _narrow_fn(mesh: Mesh, sides: tuple, vectors: tuple, transposed: tuple,
               digits: tuple):
    """The ``int8-scatter`` step.  Every argument arrives sliced over the
    eliminated vertex; ``transposed[i]`` marks a pair factor whose
    eliminated vertex is its axis 1.  ``sides[i]`` (0 or 1) is the
    output axis a pair factor carries, ``vectors`` the side each vertex
    factor multiplies into, ``digits`` the planes of each side."""
    def body(*arrs):
        prods = [None, None]
        for a, side, t in zip(arrs, sides, transposed):
            a = a.T if t else a                  # (rows, Rp)
            prods[side] = a if prods[side] is None else prods[side] * a
        for a, side in zip(arrs[len(sides):], vectors):
            prods[side] = prods[side] * a[:, None]
        left, right = (_digits(p, c) for p, c in zip(prods, digits))
        out = None
        for i, lo in enumerate(left):
            for j, ro in enumerate(right):
                part = jax.lax.dot_general(
                    lo, ro, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)
                rows = jax.lax.psum_scatter(part, "data",
                                            scatter_dimension=0, tiled=True)
                term = rows.astype(jnp.float64) * float(
                    1 << (DIGIT_BITS * (i + j)))
                out = term if out is None else out + term
        return out

    in_specs = tuple(_spec(2, 1 if t else 0) for t in transposed) \
        + tuple(P("data") for _ in vectors)
    return _jit_step(body, mesh, in_specs, P("data", None))


def _digit_count(bound: int) -> int:
    return max(1, math.ceil(math.log2(bound + 1) / DIGIT_BITS))


def _narrow_plan(involved, out_idx: tuple, v: int, terms: int,
                 out_bound: int):
    """(sides, vectors, digits) of the ``int8-scatter`` form for one
    pair step, or None where its shape or bound refuses it."""
    if len(out_idx) != 2 or out_bound >= F64_EXACT:
        return None
    sides, vectors, bounds = [], [], [1, 1]
    for f in involved:
        if len(f.idx) == 1:
            continue
        if len(f.idx) != 2:
            return None
        other = f.idx[1 - f.idx.index(v)]
        side = out_idx.index(other)
        sides.append(side)
        bounds[side] *= f.bound
    for f in involved:
        if len(f.idx) == 1:                  # into the smaller side
            side = 0 if bounds[0] <= bounds[1] else 1
            vectors.append(side)
            bounds[side] *= f.bound
    digits = tuple(_digit_count(b) for b in bounds)
    if max(digits) > MAX_DIGITS:
        return None
    if min(bounds[0], 127) * min(bounds[1], 127) * terms > INT32_MAX:
        return None
    return tuple(sides), tuple(vectors), digits


def _record(steps, form: str, spec: str, shapes, dtype: str,
            out_dtype: str, count: int = 1):
    """Count one step and describe it for a reader of its operations and
    bytes: ``count`` products of the einsum ``spec`` over per-shard
    operand ``shapes``."""
    obs.counter("contract.steps", form=form)
    if steps is not None:
        steps.append({"form": form, "spec": spec,
                      "shapes": [list(s) for s in shapes], "dtype": dtype,
                      "out_dtype": out_dtype, "count": count})


def _local_shape(shape, axis: Optional[int], d: int) -> tuple:
    return tuple(x // d if i == axis else x for i, x in enumerate(shape))


def _check_width(out_idx: tuple, n: int, budget: int):
    out_elems = n ** len(out_idx)
    if out_elems > 4 * budget:
        raise H.PlanTooWide(f"intermediate of {out_elems:.2e} elements "
                            f"(indices {tuple(out_idx)}, n={n}) exceeds "
                            f"the cap")


def _eliminate(involved, out_idx: tuple, v: int, *, mesh, n: int,
               budget: int, terms: int, steps):
    """One elimination step of ``v`` over the factors that carry it,
    with ``v``'s axis device-sharded in each — the sharded analogue of
    ``homomorphism._contract`` (whose budget chunking the device split
    replaces).  Returns the output ``Factor``."""
    _check_width(out_idx, n, budget)
    d = meshes.num_shards(mesh)
    # A is symmetric: relabel (u, v) -> (v, u) so the eliminated vertex
    # is served from the row-sharded buffer as-is
    involved = [f._replace(idx=(f.idx[1], f.idx[0]))
                if f.adj and f.idx.index(v) == 1 else f for f in involved]
    idx_sets = tuple(tuple(f.idx) for f in involved)
    arrays = [f.array for f in involved]
    axes = tuple(s.index(v) for s in idx_sets)
    shapes = [_local_shape(a.shape, ax, d) for a, ax in zip(arrays, axes)]
    bound = math.prod(f.bound for f in involved) * terms
    spec = H._einsum_letters(idx_sets, out_idx)

    if len(out_idx) <= 1:
        order = (v,) + tuple(out_idx)
        _record(steps, VECTOR, spec, shapes, "float64", "float64")
        arr = _product_fn(mesh, idx_sets, axes, order, False)(*arrays)
        return Factor(tuple(out_idx), arr, False, bound)

    narrow = _narrow_plan(involved, tuple(out_idx), v, terms, bound)
    if narrow is None:
        _record(steps, F64_PSUM, spec, shapes, "float64", "float64")
        arr = _psum_fn(mesh, spec, axes, tuple(len(s) for s in idx_sets),
                       len(out_idx))(*arrays)
        return Factor(tuple(out_idx), arr, False, bound)

    sides, vectors, digits = narrow
    pairs = [f for f in involved if len(f.idx) == 2]
    vecs = [f for f in involved if len(f.idx) == 1]
    transposed = tuple(f.idx.index(v) == 1 for f in pairs)
    rows = padded_rows(n, mesh) // d
    Rp = rows * d
    _record(steps, NARROW, "ab,ac->bc", [(rows, Rp), (rows, Rp)], "int8",
            "int32", count=digits[0] * digits[1])
    arr = _narrow_fn(mesh, sides, tuple(vectors), transposed, digits)(
        *[f.array for f in pairs], *[f.array for f in vecs])
    return Factor(tuple(out_idx), arr, False, bound)


def _finish(factors, free: tuple, *, mesh, n: int, budget: int, steps):
    """The free-axis step: the remaining factors, all over free vertices
    only, multiplied with the output sharded over ``free[0]``."""
    _check_width(free, n, budget)
    d = meshes.num_shards(mesh)
    lead = free[0]
    idx_sets, arrays, axes = [], [], []
    gathers = 0
    for f in factors:
        s = f.idx
        if lead in s:
            if f.adj and s.index(lead) == 1:
                s = (s[1], s[0])
            axes.append(s.index(lead))
        else:
            axes.append(None)
            if f.adj:
                gathers += 1             # replicating a sharded A block
        idx_sets.append(tuple(s))
        arrays.append(f.array)
    if gathers:
        obs.counter("contract.finish_gathers", value=gathers)
    idx_sets = tuple(idx_sets)
    _record(steps, OUT_SHARDED, H._einsum_letters(idx_sets, free),
            [_local_shape(a.shape, ax, d) for a, ax in zip(arrays, axes)],
            "float64", "float64")
    return _product_fn(mesh, idx_sets, tuple(axes), tuple(free),
                       True)(*arrays)


def _trim(arr, n: int):
    """Rp -> n on every axis.  A no-op when the mesh divides n (the
    buffers were never padded and the sharding survives); otherwise the
    slice replicates — uneven shardings don't exist in this jax version
    — which the counter makes visible."""
    if not arr.ndim or arr.shape[0] == n:
        return arr
    obs.counter("contract.trim_gathers")
    return arr[(slice(0, n),) * arr.ndim]


def sharded_hom(p, blocks, *, mesh: Mesh, n: int, max_degree: int,
                order: Optional[tuple] = None, free: tuple = (),
                unary: Optional[dict] = None, budget: int = 1 << 27,
                steps: Optional[list] = None):
    """# homomorphisms of ``p`` into the graph whose row-sharded
    adjacency is ``blocks`` (from ``adjacency_blocks``), with ``free``
    pattern vertices kept as output axes — the collective mirror of
    ``homomorphism.hom_count``, bit-for-bit equal to it.

    ``unary`` maps pattern vertices to (Rp,) 0/1 factors
    (``label_blocks`` rows, or replicated vectors zero beyond ``n``).
    ``max_degree`` is the graph's largest degree, read off the graph on
    every call: the narrow steps' certificate rests on it.  ``steps``, when given, receives a
    description of every step run (its form, per-shard einsum and
    dtype).  Scalar counts return a 0-d f64 array; free counts return
    the (n,)*len(free) tensor sharded ``P("data", ...)`` over cut axis 0
    (replicated when the mesh does not divide n — see module
    docstring)."""
    free = tuple(free)
    Rp = blocks.shape[0]
    dtype = blocks.dtype
    degree = min(int(max_degree), n)

    def ones_vec():
        return jnp.where(jnp.arange(Rp) < n, jnp.ones((Rp,), dtype),
                         jnp.zeros((Rp,), dtype))

    if p.n == 1:
        vec = (unary or {}).get(0)
        if vec is None:
            vec = ones_vec()
        return _trim(vec, n) if free == (0,) else jnp.sum(vec)

    factors = [Factor((u, v), blocks, True, 1) for (u, v) in sorted(p.edges)]
    if unary:
        factors += [Factor((v,), vec, False, 1) for v, vec in unary.items()]
    covered = set()
    for f in factors:
        covered.update(f.idx)
    for v in range(p.n):                          # isolated vertices
        if v not in covered:
            factors.append(Factor((v,), ones_vec(), False, 1))

    order = order or H.greedy_plan(p, free)
    for v in order:
        if v in free:
            continue
        involved = [f for f in factors if v in f.idx]
        rest = [f for f in factors if v not in f.idx]
        out_idx = tuple(sorted({i for f in involved for i in f.idx} - {v}))
        terms = degree if any(f.adj for f in involved) else n
        factors = rest + [_eliminate(involved, out_idx, v, mesh=mesh, n=n,
                                     budget=budget, terms=terms,
                                     steps=steps)]

    if not free:
        total = jnp.asarray(1.0, dtype)
        for f in factors:
            a = f.array
            total = total * (a if a.ndim == 0 else jnp.sum(a))
        return total
    return _trim(_finish(factors, free, mesh=mesh, n=n, budget=budget,
                         steps=steps), n)
