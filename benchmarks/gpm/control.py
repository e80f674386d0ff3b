"""The control of the comparison that decides ``correct``.

    python3 benchmarks/gpm/control.py --workload <cell> --seeds 1 2 3

The configurations state exact counts in float64.  The control is the
plain reference put in the program's place and computed one precision
down, in float32: on each seed's graph, at the cell's own size, it gives
the widest gap between the float32 answers and the float64 reference,
read exactly as a job's answers are.  A sound limit fails it.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np


def as_answer(kind: str, ref: dict, n: int) -> dict:
    """A reference result in the shape a job returns."""
    if kind != "chain_local":
        return ref
    order = np.lexsort((np.arange(n), -ref["vertex"]))[:len(ref["top"])]
    return dict(ref, top=[(float(ref["vertex"][w]), int(w)) for w in order])


def control_gaps(bench, cell: dict, seed: int) -> dict:
    """Widest gaps of the float64 and the float32 reference on the graph
    of ``seed`` (as the harness reduces it)."""
    config = bench.data("configs", cell["config"])
    n, edges = bench.module("graphs", config["generator"]).generate(
        config, seed)
    identity = np.arange(n)
    gaps = {}
    for params in bench.data("traffic", cell["traffic"])["jobs"]:
        ref = bench.module("reference", params["kind"])
        exact = ref.counts(n, edges, params)
        low = ref.counts(n, edges, params, dtype=np.float32)
        gaps[params["kind"]] = {
            "float64": ref.compare(as_answer(params["kind"], exact, n),
                                   exact, identity),
            "float32": ref.compare(as_answer(params["kind"], low, n), exact,
                                   identity)}
    return gaps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import run
    bench = run.Bench()
    cell = bench.cell(args.workload)
    for seed in args.seeds:
        print(json.dumps({"workload": cell["name"], "seed": seed,
                          "max_gap": control_gaps(bench, cell,
                                                  seed % run.SEED_MOD),
                          "limit": run.LIMITS["max_gap"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
