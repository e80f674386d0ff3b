"""Gigabytes copied between host and device per job: the program's
``transfer.h2d_bytes`` and ``transfer.d2h_bytes`` counters, kept by the
``obs.Tracer`` attached to each job's plan for its own reads.  None
without device operations in the trace, or from a program whose tracer
keeps no counts."""
LAYER = "device"
UNIT = "GB/job"
MOVES = "job_s"

COUNTERS = ("transfer.h2d_bytes", "transfer.d2h_bytes")


def read(ctx):
    if not ctx.trace.ops or not ctx.jobs:
        return None
    total = 0.0
    for job in ctx.jobs:
        counts = getattr(job.tracer, "counts", None)
        if counts is None:
            return None
        total += sum(sum(counts.get(c, {}).values()) for c in COUNTERS)
    return total / 1e9 / len(ctx.jobs)
