"""Seconds per job in ``compiler.compile``: decomposition search,
costing and plan assembly, a plan-cache miss in every job because every
job mines a freshly relabelled graph.  Host clock, taken by the job
module around the call."""
LAYER = "compile"
UNIT = "s/job"
MOVES = "job_s"


def read(ctx):
    if not ctx.jobs:
        return None
    return sum(j.info["plan_search_s"] for j in ctx.jobs) / len(ctx.jobs)
