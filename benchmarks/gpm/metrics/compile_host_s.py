"""Device idle seconds per job while the program compiles its plan: the
innermost open span is ``gpm.compile`` or one of its steps,
``gpm.apct`` (profiling the graph), ``gpm.candidates``,
``gpm.costing`` (selection and assembly) or ``gpm.verify``.  Booked
instant by instant by ``idle_by_span.py``."""
LAYER = "compile"
UNIT = "s/job"
MOVES = "job_s"


def read(ctx):
    return bench.module(  # noqa: F821  (set by Bench.module)
        "", "idle_by_span").per_job(ctx, "compile_host_s")
