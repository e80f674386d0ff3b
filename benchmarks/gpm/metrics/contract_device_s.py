"""Device seconds per job of the Contract tier: the jitted einsum
programs that ``core/homomorphism.py`` dispatches for every Contract
node, found in the trace by the program names below."""
LAYER = "Contract"
UNIT = "s/job"
MOVES = "job_s"

# the name table: programs that run Contract's einsums
PROGRAMS = r"^jit__einsum$"


def read(ctx):
    seconds = ctx.trace.program_seconds(PROGRAMS)
    if seconds <= 0 or not ctx.jobs:
        return None
    return seconds / len(ctx.jobs)
