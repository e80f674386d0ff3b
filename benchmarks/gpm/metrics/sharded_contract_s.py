"""Device seconds per job of the sharded Contract's step programs
(``distributed/contract.py`` jits every step as ``_contract_step``), on
the slowest chip: each device's executions of those programs in the
window summed, the largest of the devices taken, per job.  A
one-device Contract, or a program that names its steps otherwise,
gives nothing."""
import re

LAYER = "Contract"
UNIT = "s/job"
MOVES = "job_s"

# the name table: programs of the sharded Contract steps
PROGRAMS = r"^jit__contract_step$"


def per_device(trace) -> dict:
    """{device: seconds of the step programs in the window}."""
    lo, hi = trace.window()
    out: dict = {}
    for p in trace.programs:
        if p.end_ns > lo and p.start_ns < hi and re.search(PROGRAMS, p.name):
            out[p.device] = out.get(p.device, 0.0) + p.dur_ns / 1e9
    return out


def slowest_seconds(trace) -> float:
    return max(per_device(trace).values(), default=0.0)


def read(ctx):
    seconds = slowest_seconds(ctx.trace)
    if seconds <= 0 or not ctx.jobs:
        return None
    return seconds / len(ctx.jobs)
