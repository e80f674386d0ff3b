"""GAP Benchmark Suite uniform random graph (Urand) edge generator.

The benchmark's own copy of the suite's synthetic uniform generator
(Beamer, Asanović and Patterson, "The GAP Benchmark Suite",
arXiv:1508.03619; ``gapbs -u SCALE -k DEGREE``): 2**SCALE vertices and
DEGREE * 2**SCALE edges, both ends of every edge drawn uniformly and
independently, so that no vertex pair is likelier than another
(Erdős–Rényi).  Self-loops and repeated edges are kept here, as the
suite generates them; the graph takes them as undirected and drops
them, as the suite's builder does.
"""
from __future__ import annotations

import numpy as np


def generate(params: dict, seed: int):
    """-> (n, (m, 2) int64 edge array, as generated)."""
    scale, degree = int(params["SCALE"]), int(params["degree"])
    n, m = 1 << scale, degree << scale
    rng = np.random.default_rng(seed)
    return n, rng.integers(0, n, size=(m, 2), dtype=np.int64)
