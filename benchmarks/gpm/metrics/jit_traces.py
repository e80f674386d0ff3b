"""JAX traces per job: the program's ``jax.traces`` counter (one per
jaxpr traced, counted by its ``jax.monitoring`` listener under the
innermost open program span), kept by the ``obs.Tracer`` attached to
each job's plan for its own reads.  Every shape is warmed up before the
window, so a trace inside it is a re-trace.  None without device
operations in the trace, or from a program whose tracer keeps no
counts."""
LAYER = "lowering"
UNIT = "count/job"
MOVES = "job_s"


def read(ctx):
    if not ctx.trace.ops or not ctx.jobs:
        return None
    total = 0.0
    for job in ctx.jobs:
        counts = getattr(job.tracer, "counts", None)
        if counts is None:
            return None
        total += sum(counts.get("jax.traces", {}).values())
    return total / len(ctx.jobs)
