"""Plan nodes evaluated per job: the node spans (every span but the
per-read ``execute`` roots) of the ``obs.Tracer`` attached to each job's
plan.  Each is one host dispatch of the lowering's node-by-node
interpreter; a plan that fuses nodes lowers it."""
LAYER = "lowering"
UNIT = "count/job"
MOVES = "job_s"


def read(ctx):
    jobs = [j for j in ctx.jobs if j.tracer is not None]
    if not jobs:
        return None
    return sum(sum(1 for s in j.tracer.walk() if s.kind != "execute")
               for j in jobs) / len(jobs)
