"""Specialised clique counting: one exact device pass for K3 and K4, and
degeneracy-ordered enumeration for the rest.

Dense tensor contraction of K_k needs an N^(k-2) intermediate — exactly
the high-treewidth regime the paper's decomposition cannot help with
(cliques have no cutting set, §2.4 footnote).  The paper's observation is
that clique counting is cheap by *ordered enumeration*; we implement that
path on the host CSR (degeneracy order + out-neighbour intersections).

For K3 and K4 the same exact work fits the chip's int8 matrix unit.  For
an edge (u, a) let r = A[u] * A[a], the common-neighbour row; then
hom(K3) = 2 Σ_{u<a} Σ_b r_b and hom(K4) = 2 Σ_{u<a} rᵀ A r.
``clique_homs`` computes both in one pass over chunks of the edge list
(``_clique_pass``): no vertex order, CSR or recursion.

Also provides the pseudo-clique counter (K_k minus one edge,
vertex-induced) used by the PC application.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.graph.storage import Graph

# the largest n whose pass is exact in int32: a row's rᵀ A r is at most n²
PASS_MAX_N = math.isqrt(2 ** 31 - 1)
# edges per step of the pass: its transient is n² + 5 · CHUNK · n bytes
CHUNK = 1024


def degeneracy_order(g: Graph) -> np.ndarray:
    offs, nbrs = g.csr
    deg = np.diff(offs).astype(np.int64)
    removed = np.zeros(g.n, bool)
    order = np.empty(g.n, np.int64)
    # simple bucketed peeling
    for i in range(g.n):
        v = int(np.argmin(np.where(removed, np.iinfo(np.int64).max, deg)))
        order[i] = v
        removed[v] = True
        for w in nbrs[offs[v]:offs[v + 1]]:
            if not removed[w]:
                deg[w] -= 1
    return order


def _oriented_adj(g: Graph, order: np.ndarray) -> list:
    rank = np.empty(g.n, np.int64)
    rank[order] = np.arange(g.n)
    out = [None] * g.n
    offs, nbrs = g.csr
    for v in range(g.n):
        ns = nbrs[offs[v]:offs[v + 1]]
        fwd = ns[rank[ns] > rank[v]]
        out[v] = np.sort(fwd)
    return out


def clique_count(g: Graph, k: int) -> int:
    """Number of k-cliques (vertex subsets)."""
    if k == 1:
        return g.n
    if k == 2:
        return g.m
    adj = _oriented_adj(g, degeneracy_order(g))

    def rec(cands: np.ndarray, depth: int) -> int:
        if depth == k:
            return len(cands)
        total = 0
        for v in cands:
            nxt = np.intersect1d(cands, adj[v], assume_unique=True)
            if len(nxt) >= k - depth - 1:
                total += rec(nxt, depth + 1)
        return total

    total = 0
    for v in range(g.n):
        if len(adj[v]) >= k - 1:
            total += rec(adj[v], 2)
    return total


def clique_homs(g: Graph) -> tuple:
    """(hom(K3), hom(K4)) of ``g`` as exact ints, from one device pass.

    Each row the pass returns is at most n², exact in int32 for
    n <= ``PASS_MAX_N``; the host adds them in int64, exact since the
    sum is at most m · n² <= n⁴ / 2 < 2^63 there."""
    if g.n > PASS_MAX_N:
        raise ValueError(f"the int32 pass is exact only for n <= "
                         f"{PASS_MAX_N}, got {g.n}")
    if not g.m:
        return 0, 0
    steps = -(-g.m // CHUNK)
    # padding pairs index past the graph: their rows gather as zeros and
    # their scatter is dropped, so they count nothing
    edges = np.full((steps * CHUNK, 2), g.n, np.int32)
    edges[:g.m] = g.edges
    chunks = obs.upload(edges.reshape(steps, CHUNK, 2), site="clique_edges")
    tri, k4 = _clique_pass(chunks, n=g.n)
    tri = obs.readback(tri, site="clique_rows")
    k4 = obs.readback(k4, site="clique_rows")
    return (2 * int(tri.sum(dtype=np.int64)),
            2 * int(k4.sum(dtype=np.int64)))


@functools.partial(jax.jit, static_argnames=("n",))
def _clique_pass(chunks, *, n: int):
    """Per edge of each (CHUNK, 2) chunk of u < a pairs: Σ_b r_b and
    rᵀ A r for r = A[u] * A[a], int8 products accumulated in int32.  The
    int8 adjacency is scattered from the edges here, and lives only as
    long as the program."""
    u, a = chunks[..., 0].ravel(), chunks[..., 1].ravel()
    # one scatter of both directions: two in a row cost the chip a third
    # n² buffer at n = 16,384
    adj = jnp.zeros((n, n), jnp.int8).at[
        jnp.concatenate([u, a]), jnp.concatenate([a, u])].set(1, mode="drop")

    def rows(idx):
        return jnp.take(adj, idx, axis=0, mode="fill", fill_value=0)

    def step(chunk):
        r = rows(chunk[:, 0]) * rows(chunk[:, 1])
        p = jnp.dot(r, adj, preferred_element_type=jnp.int32)
        return (r.sum(1, dtype=jnp.int32),
                (p * r).sum(1, dtype=jnp.int32))

    return jax.lax.map(step, chunks)


def clique_minus_edge_count(g: Graph, k: int) -> int:
    """Vertex-induced count of K_k minus one edge: non-adjacent pairs
    (u,v) whose common neighbourhood contains a (k-2)-clique fully
    adjacent to both — i.e. cliques of size k-2 in the induced common
    neighbourhood."""
    assert k >= 3
    offs, nbrs = g.csr
    # candidate non-adjacent pairs with >= k-2 common neighbours: collect
    # from wedges
    pair_count: dict = {}
    for w in range(g.n):
        ns = nbrs[offs[w]:offs[w + 1]]
        if len(ns) < 2:
            continue
        for i in range(len(ns)):
            u = ns[i]
            for v in ns[i + 1:]:
                pair_count[(u, v)] = pair_count.get((u, v), 0) + 1
    total = 0
    for (u, v), c in pair_count.items():
        if c < k - 2 or g.has_edge(u, v):
            continue
        common = np.intersect1d(g.neighbors(u), g.neighbors(v),
                                assume_unique=True)
        sub = _induced(g, common)
        total += clique_count(sub, k - 2)
    return total


def pseudo_clique_count(g: Graph, k: int) -> int:
    """Vertex-induced pseudo-cliques with parameter 1 (paper's PC app):
    K_k plus K_k-minus-one-edge."""
    return clique_count(g, k) + clique_minus_edge_count(g, k)


def _induced(g: Graph, verts: np.ndarray) -> Graph:
    idx = {int(v): i for i, v in enumerate(verts)}
    edges = []
    vset = set(idx)
    for v in verts:
        for w in g.neighbors(int(v)):
            if int(w) in vset and int(w) > int(v):
                edges.append((idx[int(v)], idx[int(w)]))
    return Graph(len(verts), np.asarray(edges).reshape(-1, 2)
                 if edges else np.zeros((0, 2), np.int64))
