"""Graph500 Kronecker (R-MAT) edge generator.

The benchmark's own copy of the reference generator of the Graph500
specification (graph500.org, "Kronecker generator"): 2**SCALE vertices,
edgefactor * 2**SCALE edges, each drawn bit by bit from the initiator
[[A, B], [C, 1 - A - B - C]], then the vertex ids permuted at random and
the edge list shuffled.  Self-loops and repeated edges are kept here, as
the specification generates them; the graph takes them as undirected and
drops them.
"""
from __future__ import annotations

import numpy as np


def generate(params: dict, seed: int):
    """-> (n, (m, 2) int64 edge array, as generated)."""
    scale, edgefactor = int(params["SCALE"]), int(params["edgefactor"])
    a, b, c = float(params["A"]), float(params["B"]), float(params["C"])
    n, m = 1 << scale, edgefactor << scale
    rng = np.random.default_rng(seed)
    ab, c_norm, a_norm = a + b, c / (1.0 - (a + b)), a / (a + b)
    ij = np.zeros((2, m), np.int64)
    for bit in range(scale):
        ii = rng.random(m) > ab
        jj = rng.random(m) > np.where(ii, c_norm, a_norm)
        ij[0] += ii.astype(np.int64) << bit
        ij[1] += jj.astype(np.int64) << bit
    ij = rng.permutation(n)[ij]
    ij = ij[:, rng.permutation(m)]
    return n, ij.T.copy()
