"""Device idle seconds per job in the lowering's node-by-node interpreter:
the innermost open span is ``gpm.node`` (one node evaluation's own host
work), ``gpm.combine`` (a Moebius factor combined on the host),
``gpm.expand`` (the dense factors and mask of the XLA join fallback) or
``gpm.join`` (the join call outside its copies).  Booked instant by
instant by ``idle_by_span.py``."""
LAYER = "lowering"
UNIT = "s/job"
MOVES = "job_s"


def read(ctx):
    return bench.module(  # noqa: F821  (set by Bench.module)
        "", "idle_by_span").per_job(ctx, "lowering_host_s")
