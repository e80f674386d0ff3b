"""Device idle seconds per job that the program's spans cannot name: the
innermost open span is one of the harness's (``gpm.window``,
``gpm.graph_build``, ``gpm.plan_search``, ``gpm.execute``), a span
``idle_by_span.BOOKS`` does not book, or none.  With the other idle
metrics it sums to the window's idle seconds per job."""
LAYER = "device"
UNIT = "s/job"
MOVES = "job_s"


def read(ctx):
    return bench.module(  # noqa: F821  (set by Bench.module)
        "", "idle_by_span").per_job(ctx, "unattributed_idle_s")
