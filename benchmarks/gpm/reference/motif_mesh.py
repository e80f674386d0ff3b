"""Plain reference for the 4-motif census run on a mesh: the counts do
not depend on the devices, so this is ``reference/motif.py``'s (sparse
algebra on the host, importing nothing of the program)."""
from __future__ import annotations

import numpy as np

motif = bench.module("reference", "motif")  # noqa: F821  (set by Bench.module)


def counts(n: int, edges: np.ndarray, params: dict,
           dtype=np.float64) -> dict:
    return motif.counts(n, edges, params, dtype=dtype)


def compare(out: dict, ref: dict, perm: np.ndarray) -> float:
    return motif.compare(out, ref, perm)
