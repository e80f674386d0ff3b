"""Plain reference for the 4-motif census: sparse algebra on the host.

Imports nothing of the program.  For a simple undirected graph it gives
the six connected 4-vertex patterns' counts two ways:

* ``edge_induced``: copies of the pattern as a subgraph (not necessarily
  induced), from degrees, codegrees c(u, w) = |N(u) ∩ N(w)| and per-vertex
  triangles t(v):

  - 3-star          Σ_v C(d_v, 3)
  - 4-path          Σ_{uv ∈ E} (d_u - 1)(d_v - 1) - 3 T
  - tailed triangle Σ_v t(v) (d_v - 2)
  - 4-cycle         Σ_{u ≠ w} C(c(u, w), 2) / 4
  - diamond         Σ_{uv ∈ E} C(c(u, v), 2)
  - 4-clique        triangles inside each vertex's higher-ranked
                    neighbourhood, by a degree order

* ``vertex_induced``: the induced counts, by inverting the table of how
  many copies of each pattern each other pattern holds.

Every quantity is formed in ``dtype``: float64 is exact below 2**53, and
float32, which rounds above 2**24, is the control that must fail.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

NAMES = ("star3", "path4", "tailed_triangle", "cycle4", "diamond", "clique4")

# CONTAINS[i][j]: copies of pattern i (as a subgraph) inside pattern j
CONTAINS = (
    (1, 0, 1, 0, 2, 4),
    (0, 1, 2, 4, 6, 12),
    (0, 0, 1, 0, 4, 12),
    (0, 0, 0, 1, 1, 3),
    (0, 0, 0, 0, 1, 6),
    (0, 0, 0, 0, 0, 1),
)


def adjacency(n: int, edges: np.ndarray) -> sp.csr_matrix:
    """Symmetric 0/1 CSR adjacency of the simple graph the edge list
    names (self-loops and repeats dropped)."""
    e = np.asarray(edges, np.int64).reshape(-1, 2)
    u, v = e.min(axis=1), e.max(axis=1)
    keep = u != v
    key = np.unique(u[keep] * n + v[keep])
    u, v = key // n, key % n
    a = sp.coo_matrix((np.ones(2 * len(u), np.int64),
                       (np.concatenate([u, v]), np.concatenate([v, u]))),
                      shape=(n, n))
    return a.tocsr()


def structure(n: int, edges: np.ndarray) -> dict:
    """Integer building blocks shared by the census and the 5-chain."""
    a = adjacency(n, edges)
    deg = np.asarray(a.sum(axis=1)).ravel()
    a2 = (a @ a - sp.diags(deg, dtype=np.int64)).tocsr()       # codegrees off the diagonal
    a2.eliminate_zeros()
    on_edges = a2.multiply(a).tocsr()          # c(u, v) on the edges
    tri_v = np.asarray(on_edges.sum(axis=1)).ravel() // 2
    eu, ev = sp.triu(a, k=1).nonzero()
    return {"a": a, "deg": deg, "a2": a2, "on_edges": on_edges,
            "tri_v": tri_v, "eu": eu, "ev": ev}


def clique4(a: sp.csr_matrix, deg: np.ndarray) -> int:
    """4-cliques: each counted once, at its lowest vertex in the order
    (degree, id), as a triangle among that vertex's higher neighbours."""
    n = a.shape[0]
    rank = np.empty(n, np.int64)
    rank[np.lexsort((np.arange(n), deg))] = np.arange(n)
    coo = a.tocoo()
    up = rank[coo.row] < rank[coo.col]
    d = sp.csr_matrix((np.ones(int(up.sum()), np.int64),
                       (coo.row[up], coo.col[up])), shape=(n, n))
    total = 0
    for v in range(n):
        out = d.indices[d.indptr[v]:d.indptr[v + 1]]
        if len(out) < 3:
            continue
        sub = d[out][:, out]
        total += int((sub @ sub).multiply(sub).sum())
    return total


def counts(n: int, edges: np.ndarray, params: dict,
           dtype=np.float64) -> dict:
    if int(params["k"]) != 4:
        raise ValueError("the reference counts 4-vertex motifs only")
    s = structure(n, edges)
    f = np.dtype(dtype).type
    d = s["deg"].astype(dtype)
    tri_v = s["tri_v"].astype(dtype)
    c_all = s["a2"].data.astype(dtype)
    c_edge = np.asarray(s["on_edges"][s["eu"], s["ev"]]).ravel().astype(dtype)
    one, two, three = f(1), f(2), f(3)
    tri = np.sum(tri_v, dtype=dtype) / three
    edge = {
        "star3": np.sum(d * (d - one) * (d - two) / f(6), dtype=dtype),
        "path4": np.sum((d[s["eu"]] - one) * (d[s["ev"]] - one), dtype=dtype)
        - three * tri,
        "tailed_triangle": np.sum(tri_v * (d - two), dtype=dtype),
        "cycle4": np.sum(c_all * (c_all - one) / two, dtype=dtype) / f(4),
        "diamond": np.sum(c_edge * (c_edge - one) / two, dtype=dtype),
        "clique4": f(clique4(s["a"], s["deg"])),
    }
    return {"edge_induced": {k: float(v) for k, v in edge.items()},
            "vertex_induced": vertex_induced(edge, dtype)}


def vertex_induced(edge: dict, dtype=np.float64) -> dict:
    """Solve edge[i] = Σ_j CONTAINS[i][j] · induced[j] from the last
    pattern back (the table is upper triangular with a unit diagonal)."""
    ind = {}
    for i in reversed(range(len(NAMES))):
        acc = np.asarray(edge[NAMES[i]], dtype)
        for j in range(i + 1, len(NAMES)):
            acc = acc - np.asarray(CONTAINS[i][j], dtype) * \
                np.asarray(ind[NAMES[j]], dtype)
        ind[NAMES[i]] = acc
    return {k: float(v) for k, v in ind.items()}


def compare(out: dict, ref: dict, perm: np.ndarray) -> float:
    """Widest absolute gap between a job's counts and the reference's
    (global counts do not depend on the job's relabelling ``perm``)."""
    gap = 0.0
    for table in ("edge_induced", "vertex_induced"):
        for name in NAMES:
            gap = max(gap, abs(float(out[table][name]) - ref[table][name]))
    return gap
