"""Device seconds per job of the join kernels (``kernels/ops.py``,
``kernels/matreduce.py``): the jitted programs around the Pallas pair
join (|cut| <= 2) and tri join (|cut| = 3), scalar and keep-axis, found
in the trace by the program names below.  Each program holds its kernel
and the reduction of the kernel's partials; the padding and stacking of
the factors before it are programs of their own and are not counted."""
LAYER = "joins"
UNIT = "s/job"
MOVES = "job_s"

# the name table: programs of the join kernels
PROGRAMS = r"^jit__(pairjoin|trijoin)$"


def kernel_seconds(trace) -> float:
    return trace.program_seconds(PROGRAMS)


def read(ctx):
    seconds = kernel_seconds(ctx.trace)
    if seconds <= 0 or not ctx.jobs:
        return None
    return seconds / len(ctx.jobs)
