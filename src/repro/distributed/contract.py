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
* each elimination step runs as a collective einsum under ``shard_map``:
  the eliminated vertex's axis is the sharded axis of every involved
  factor (the adjacency is symmetric, so a factor carrying the vertex
  on its column axis is relabelled to serve it from the row-sharded
  buffer — no transpose, no gather), each device contracts its slice,
  and a ``psum`` over ``"data"`` completes the sum — the intermediate
  comes out replicated;
* the final free-axis step shards its *output* over ``free[0]`` (cut
  axis 0): devices compute disjoint row blocks (``out_specs
  P("data", ...)``), so the cut tensor a decomposition join consumes is
  born sliced along exactly the axis ``distributed/cutjoin`` shards —
  the factor handoff needs no gather.  An adjacency factor between two
  *later* free vertices is the one input that must replicate into the
  step; ``contract.finish_gathers`` counts those so traces surface
  them.

**Exactness.**  Every intermediate is a sum of products of 0/1
adjacency entries and non-negative integer unaries — non-negative
integers, exact in f64 below 2^53, and f64 integer addition is
associative — so psum order, shard count, and zero-padding cannot
change any value: the sharded route is bit-for-bit equal to
``hom_count`` (the same argument as ``distributed/cutjoin``).

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

Callers hold ``jax.enable_x64`` while calling (the engine
does), so factors and steps trace in f64.  All ``shard_map`` call sites
go through ``meshes.sharding_ctx`` — the repo's ``mesh-guard`` lint
rule — so logical-axis ``constrain`` calls by surrounding code resolve
against the mesh the contraction executes on.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core import homomorphism as H
from repro.distributed import meshes


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


@functools.lru_cache(maxsize=None)
def _step_fn(mesh: Mesh, spec: str, shard_axes: tuple, ranks: tuple,
             out_rank: int, out_sharded: bool):
    """One shard_map'd contraction step, cached per (mesh, statics) so
    serving plans trace once.  ``shard_axes[i]`` is the axis of factor i
    carrying the sharded index (None = replicated into the step).
    Elimination steps (``out_sharded=False``) contract the sharded index
    locally and ``psum``; the free-output step (``out_sharded=True``)
    keeps it, each device emitting its disjoint output row block."""
    def local(*arrs):
        out = jnp.einsum(spec, *arrs)
        return out if out_sharded else jax.lax.psum(out, "data")

    in_specs = tuple(P(*[("data" if i == ax else None) for i in range(r)])
                     for r, ax in zip(ranks, shard_axes))
    out_specs = P(*(("data",) if out_sharded else (None,))
                  + (None,) * (out_rank - 1)) if out_rank else P()
    jfn = jax.jit(jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False))

    def call(*args):
        with meshes.sharding_ctx(mesh):
            return jfn(*args)

    return call


def _collective_contract(involved, out_idx, shard_index, *, mesh, n,
                         budget, out_sharded):
    """einsum the (indices, array, is_adjacency) factors down to
    ``out_idx`` with ``shard_index``'s axis device-sharded in every
    factor that carries it — the sharded analogue of
    ``homomorphism._contract`` (whose budget chunking the device split
    replaces)."""
    out_elems = n ** len(out_idx)
    if out_elems > 4 * budget:
        raise H.PlanTooWide(f"intermediate of {out_elems:.2e} elements "
                            f"(indices {tuple(out_idx)}, n={n}) exceeds "
                            f"the cap")
    idx_sets, arrays, shard_axes = [], [], []
    gathers = 0
    for s, a, is_adj in involved:
        if shard_index in s:
            if is_adj and s.index(shard_index) == 1:
                # A is symmetric: relabel (u, v) -> (v, u) so the sharded
                # index is served from the row-sharded buffer as-is
                s = (s[1], s[0])
            shard_axes.append(s.index(shard_index))
        else:
            shard_axes.append(None)
            if is_adj:
                gathers += 1             # replicating a sharded A block
        idx_sets.append(tuple(s))
        arrays.append(a)
    if gathers:
        obs.counter("contract.finish_gathers", value=gathers)
    spec = H._einsum_letters(idx_sets, tuple(out_idx))
    fn = _step_fn(mesh, spec, tuple(shard_axes),
                  tuple(len(s) for s in idx_sets), len(out_idx),
                  out_sharded)
    return fn(*arrays)


def _trim(arr, n: int):
    """Rp -> n on every axis.  A no-op when the mesh divides n (the
    buffers were never padded and the sharding survives); otherwise the
    slice replicates — uneven shardings don't exist in this jax version
    — which the counter makes visible."""
    if not arr.ndim or arr.shape[0] == n:
        return arr
    obs.counter("contract.trim_gathers")
    return arr[(slice(0, n),) * arr.ndim]


def sharded_hom(p, blocks, *, mesh: Mesh, n: int,
                order: Optional[tuple] = None, free: tuple = (),
                unary: Optional[dict] = None, budget: int = 1 << 27):
    """# homomorphisms of ``p`` into the graph whose row-sharded
    adjacency is ``blocks`` (from ``adjacency_blocks``), with ``free``
    pattern vertices kept as output axes — the collective mirror of
    ``homomorphism.hom_count``, bit-for-bit equal to it.

    ``unary`` maps pattern vertices to (Rp,) factors (``label_blocks``
    rows, or replicated vectors zero beyond ``n``).  Scalar counts
    return a 0-d f64 array; free counts return the (n,)*len(free)
    tensor sharded ``P("data", ...)`` over cut axis 0 (replicated when
    the mesh does not divide n — see module docstring)."""
    free = tuple(free)
    Rp = blocks.shape[0]
    dtype = blocks.dtype

    def ones_vec():
        return jnp.where(jnp.arange(Rp) < n, jnp.ones((Rp,), dtype),
                         jnp.zeros((Rp,), dtype))

    if p.n == 1:
        vec = (unary or {}).get(0)
        if vec is None:
            vec = ones_vec()
        return _trim(vec, n) if free == (0,) else jnp.sum(vec)

    factors = []                    # (index tuple, array, is_adjacency)
    for (u, v) in sorted(p.edges):
        factors.append(((u, v), blocks, True))
    if unary:
        for v, vec in unary.items():
            factors.append(((v,), vec, False))
    covered = set()
    for s, _, _ in factors:
        covered.update(s)
    for v in range(p.n):                          # isolated vertices
        if v not in covered:
            factors.append(((v,), ones_vec(), False))

    order = order or H.greedy_plan(p, free)
    for v in order:
        if v in free:
            continue
        involved = [f for f in factors if v in f[0]]
        rest = [f for f in factors if v not in f[0]]
        out_idx = tuple(sorted({i for s, _, _ in involved for i in s}
                               - {v}))
        arr = _collective_contract(involved, out_idx, v, mesh=mesh, n=n,
                                   budget=budget, out_sharded=False)
        factors = rest + [(out_idx, arr, False)]

    if not free:
        total = jnp.asarray(1.0, dtype)
        for _, a, _ in factors:
            total = total * (a if a.ndim == 0 else jnp.sum(a))
        return total
    arr = _collective_contract(factors, free, free[0], mesh=mesh, n=n,
                               budget=budget, out_sharded=True)
    return _trim(arr, n)
