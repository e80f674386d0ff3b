"""Helpers for the benchmark's own tests: the harness as a module, a copy
of the benchmark with small cells that run on the CPU, and brute-force
counters that share no code with the references."""
from __future__ import annotations

import importlib.util
import itertools
import json
import shutil
import sys
from pathlib import Path

import numpy as np

GPM = Path(__file__).resolve().parents[1]
ROOT = GPM.parents[1]

# connected 4-vertex patterns by the references' names
PATTERNS = {
    "star3": [(0, 1), (0, 2), (0, 3)],
    "path4": [(0, 1), (1, 2), (2, 3)],
    "tailed_triangle": [(0, 1), (1, 2), (0, 2), (2, 3)],
    "cycle4": [(0, 1), (1, 2), (2, 3), (0, 3)],
    "diamond": [(0, 1), (1, 2), (2, 3), (0, 3), (0, 2)],
    "clique4": [(0, 1), (0, 2), (0, 3), (1, 2), (1, 3), (2, 3)],
}
SMALL_CONFIGS = {
    "small-urand": {"generator": "urand", "SCALE": 6, "degree": 3},
    "small-kron": {"generator": "kronecker", "SCALE": 6, "edgefactor": 4,
                   "A": 0.57, "B": 0.19, "C": 0.19},
    # counts past 2**24, so that float32 rounds them
    "control-urand": {"generator": "urand", "SCALE": 11, "degree": 20},
}


def harness():
    """``run.py`` as a module, with the program importable."""
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    name = "gpm_harness_run"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, GPM / "run.py")
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


def module(subdir: str, name: str):
    """A file of the benchmark, loaded as the harness loads it."""
    return harness().Bench().module(subdir, name)


def spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def small_copy(tmp_path: Path):
    """A copy of the benchmark with CPU-sized cells ``<traffic>.<config>``
    for each small config and each traffic mix, and the CPU in its peak
    table, so the harness runs here; returns its ``Bench``."""
    run = harness()
    root = tmp_path / "benchmarks" / "gpm"
    shutil.copytree(GPM, root, ignore=shutil.ignore_patterns(
        "tests", ".jax_cache", "__pycache__"))
    bench_spec = spec()
    for name, cfg in SMALL_CONFIGS.items():
        (root / "configs" / f"{name}.json").write_text(json.dumps(
            dict(cfg, name=name, chips=1, reduced=[])))
        for traffic in ("motif4", "chain5-local"):
            cell = f"{traffic}.{name}"
            bench_spec["workloads"].append(
                {"name": cell, "config": name, "traffic": traffic,
                 "chips": 1, "why": "CPU test cell"})
            for metric in bench_spec["per_layer"]:
                metric.get("workloads", []).append(cell)
    peaks = json.loads((root / "peaks.json").read_text())
    peaks["devices"]["cpu"] = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                               "hbm_bytes": 1e10}
    (root / "peaks.json").write_text(json.dumps(peaks))
    return run.Bench(root, bench_spec)


def run_small(bench, cell: str, seed: int = 7, trace: bool = False,
              seconds: float = 0.0) -> dict:
    import jax
    run = harness()
    return run.run_cell(bench, bench.cell(cell), seed=seed, seconds=seconds,
                        trace=trace, devices=jax.devices()[:1])


def simple_adjacency(n: int, edges) -> list:
    adj = [set() for _ in range(n)]
    for u, v in np.asarray(edges).reshape(-1, 2):
        if u != v:
            adj[u].add(int(v))
            adj[v].add(int(u))
    return adj


def _maps(n: int, adj: list, pattern, k: int = 4):
    """Injective maps of a k-vertex pattern into the graph."""
    for image in itertools.permutations(range(n), k):
        if all(image[b] in adj[image[a]] for a, b in pattern):
            yield image


def automorphisms(pattern, k: int = 4) -> int:
    edges = {frozenset(e) for e in pattern}
    return sum(1 for p in itertools.permutations(range(k))
               if {frozenset((p[a], p[b])) for a, b in pattern} == edges)


def brute_motifs(n: int, edges) -> dict:
    """Both count tables of the 4-vertex census by enumeration."""
    adj = simple_adjacency(n, edges)
    edge_induced = {name: sum(1 for _ in _maps(n, adj, pat))
                    // automorphisms(pat)
                    for name, pat in PATTERNS.items()}
    induced = dict.fromkeys(PATTERNS, 0)
    for vs in itertools.combinations(range(n), 4):
        sub = {frozenset((i, j)) for i, j in itertools.combinations(range(4), 2)
               if vs[j] in adj[vs[i]]}
        for name, pat in PATTERNS.items():
            if len(pat) == len(sub) and any(
                    {frozenset((p[a], p[b])) for a, b in pat} == sub
                    for p in itertools.permutations(range(4))):
                induced[name] += 1
                break
    return {"edge_induced": edge_induced, "vertex_induced": induced}


def brute_chain5(n: int, edges):
    """5-chains by depth-first search: (count, per-vertex vector)."""
    adj = simple_adjacency(n, edges)
    vec = np.zeros(n)
    count = 0

    def walk(path):
        nonlocal count
        if len(path) == 5:
            if path[0] < path[-1]:
                count += 1
                vec[path] += 1
            return
        for y in adj[path[-1]]:
            if y not in path:
                walk(path + [y])

    for v in range(n):
        walk([v])
    return count, vec
