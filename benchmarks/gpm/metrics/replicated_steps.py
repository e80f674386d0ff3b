"""Sharded Contract steps per job that leave an intermediate of two or
more vertex axes whole on every chip: the program's
``contract.steps{form=f64-psum}`` (a pair step the exactness bound kept
off the int8 form), ``contract.finish_gathers`` (an adjacency factor
replicated into the free-axis step) and ``contract.trim_gathers`` (a
result trimmed to n where the mesh does not divide it), kept by the
``obs.Tracer`` attached to each job's plan for its own reads.  None
without device operations in the trace, or from a program whose tracer
keeps no ``contract.steps`` (a program without the step forms)."""
LAYER = "Contract"
UNIT = "count/job"
MOVES = "job_s"

REPLICATING = ("contract.finish_gathers", "contract.trim_gathers")


def read(ctx):
    if not ctx.trace.ops or not ctx.jobs:
        return None
    total = 0.0
    for job in ctx.jobs:
        counts = getattr(job.tracer, "counts", None)
        if counts is None or "contract.steps" not in counts:
            return None
        total += counts["contract.steps"].get("form=f64-psum", 0.0)
        total += sum(sum(counts.get(c, {}).values()) for c in REPLICATING)
    return total / len(ctx.jobs)
