"""Device idle seconds per job while complete patterns (cliques, the
plan's ``Intersect`` nodes) are counted by ordered enumeration on the
host CSR: the innermost open span is ``gpm.enumerate``.  Booked instant
by instant by ``idle_by_span.py``."""
LAYER = "Contract"
UNIT = "s/job"
MOVES = "job_s"


def read(ctx):
    return bench.module(  # noqa: F821  (set by Bench.module)
        "", "idle_by_span").per_job(ctx, "enumerate_host_s")
