"""Device idle seconds per job in the ``exact_block`` guard's factor
scan: the innermost open span is ``gpm.guard_scan``, opened for every
join that no static certificate covers.  Booked instant by instant by
``idle_by_span.py``."""
LAYER = "joins"
UNIT = "s/job"
MOVES = "job_s"


def read(ctx):
    return bench.module(  # noqa: F821  (set by Bench.module)
        "", "idle_by_span").per_job(ctx, "guard_scan_s")
