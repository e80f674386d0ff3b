"""Plain reference for the 5-chain count and its per-vertex vector.

Imports nothing of the program.  A 5-chain is a simple path of four
edges, counted as a subgraph.  Its per-vertex vector gives, for each
vertex v, the number of 5-chains that contain v: end(v) + p1(v) +
mid(v), the directed chains that start at v, that have v second, and
the chains centred at v.  Each is an inclusion-exclusion over walks in
terms of the degrees d, codegrees c(v, y) = |N(v) ∩ N(y)| and per-vertex
triangles t:

    S     = A (d - 1)                   non-returning 2-walks from v
    Sq    = A (d - 1)^2
    Q(v)  = Σ_{y ≠ v} c(v, y)^2
    Z     = Q - S                       Σ_{y ≠ v} c(v, y)(c(v, y) - 1)
    E2(v) = Σ_{b ∈ N(v)} c(v, b)(d_b - 1)
    Y(v)  = Σ_{y ∈ N(v)} c(v, y)(d_y - 2)
    end   = A A S - d S - Sq - 2 (d - 2) t - 2 A t + 2 t - Q + S
    p1    = (d - 1)(A S - d (d - 1) - 2 t) - Y - Z
    mid   = (S^2 - Sq - Z - 2 E2 + 2 t) / 2

and the count is Σ_v mid(v).  Every quantity is formed in ``dtype``:
float64 is exact below 2**53, float32 the control that must fail.
"""
from __future__ import annotations

import numpy as np


# the shared sparse building blocks; ``bench`` is set by Bench.module
motif = bench.module("reference", "motif")  # noqa: F821


def counts(n: int, edges: np.ndarray, params: dict,
           dtype=np.float64) -> dict:
    if int(params["k"]) != 5:
        raise ValueError("the reference counts 5-chains only")
    s = motif.structure(n, edges)
    f = np.dtype(dtype).type
    a = s["a"].astype(dtype)
    on_edges = s["on_edges"].astype(dtype)
    d = s["deg"].astype(dtype)
    t = s["tri_v"].astype(dtype)
    c2 = s["a2"].multiply(s["a2"]).astype(dtype)
    one, two = f(1), f(2)
    S = a @ (d - one)
    Sq = a @ ((d - one) * (d - one))
    Q = np.asarray(c2.sum(axis=1, dtype=dtype)).ravel()
    Z = Q - S
    E2 = on_edges @ (d - one)
    Y = on_edges @ (d - two)
    AS = a @ S
    end = (a @ AS - d * S - Sq - two * (d - two) * t - two * (a @ t)
           + two * t - Q + S)
    p1 = (d - one) * (AS - d * (d - one) - two * t) - Y - Z
    mid = (S * S - Sq - Z - two * E2 + two * t) / two
    vec = (end + p1 + mid).astype(np.float64)
    return {"count": float(np.sum(mid, dtype=dtype)), "vertex": vec,
            "top": top_values(vec, int(params["top"]))}


def top_values(vec: np.ndarray, k: int) -> list:
    """The k largest entries, largest first."""
    return [float(x) for x in np.sort(vec)[::-1][:k]]


def compare(out: dict, ref: dict, perm: np.ndarray) -> float:
    """Widest absolute gap between a job's answers and the reference's.
    The job mined the graph with base vertex u renamed ``perm[u]``, so its
    vector is read back through ``perm``; each of its top vertices must
    hold, under its base name, the value the job reported, and the values
    must be the reference's largest."""
    vec = np.asarray(out["vertex"], np.float64)
    if vec.shape != ref["vertex"].shape:
        return float("inf")
    gap = max(abs(float(out["count"]) - ref["count"]),
              float(np.max(np.abs(vec[perm] - ref["vertex"]), initial=0.0)))
    inv = np.argsort(perm)
    if len(out["top"]) != len(ref["top"]):
        return float("inf")
    for (value, w), want in zip(out["top"], ref["top"]):
        gap = max(gap, abs(value - want),
                  abs(value - float(ref["vertex"][inv[w]])))
    return gap
