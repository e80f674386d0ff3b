"""Device seconds per job of the clique pass: the jitted program that
``core/cliques.py`` runs to count K3 and K4 of the whole graph in one
exact int8 pass over edge chunks, named ``_clique_pass``.  Each device's
executions in the window summed, the largest of the devices taken (on a
mesh the pass runs on one chip), per job.  A program that counts cliques
on the host only gives nothing."""
import re

LAYER = "Contract"
UNIT = "s/job"
MOVES = "job_s"

# the name table: the clique pass's program
PROGRAMS = r"^jit__clique_pass$"


def read(ctx):
    lo, hi = ctx.trace.window()
    per_device: dict = {}
    for p in ctx.trace.programs:
        if p.end_ns > lo and p.start_ns < hi and re.search(PROGRAMS, p.name):
            per_device[p.device] = per_device.get(p.device, 0.0) \
                + p.dur_ns / 1e9
    seconds = max(per_device.values(), default=0.0)
    if seconds <= 0 or not ctx.jobs:
        return None
    return seconds / len(ctx.jobs)
