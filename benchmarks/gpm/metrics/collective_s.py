"""Device seconds per job of the collectives, on the slowest chip: the
union, within the window, of the intervals of each device's operations
named as an all-reduce, all-gather, reduce-scatter, collective-permute
or all-to-all (their async start and done halves included), the largest
of the devices taken, per job.  A trace without such operations gives
nothing."""
import re

LAYER = "mesh"
UNIT = "s/job"
MOVES = "job_s"

# the name table: HLO instructions that move data between chips
OPS = r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"


def per_device(trace) -> dict:
    """{device: seconds in which some collective ran, in the window}."""
    union = bench.module("", "trace_reduce").union  # noqa: F821
    lo, hi = trace.window()
    spans: dict = {}
    for op in trace.ops:
        if re.search(OPS, op.name):
            spans.setdefault(op.device, []).append((op.start_ns, op.end_ns))
    return {d: sum(b - a for a, b in union(iv, lo, hi)) / 1e9
            for d, iv in spans.items()}


def read(ctx):
    seconds = max(per_device(ctx.trace).values(), default=0.0)
    if seconds <= 0 or not ctx.jobs:
        return None
    return seconds / len(ctx.jobs)
