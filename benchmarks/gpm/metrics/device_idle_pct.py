"""Share of the measured window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, from the trace.  A
trace with no device operation gives nothing."""
LAYER = "device"
UNIT = "%"
MOVES = "job_s"


def read(ctx):
    window, busy = ctx.trace.window_s(), ctx.trace.busy_s()
    if window <= 0 or busy <= 0:
        return None
    return 100.0 * (1.0 - busy / window)
