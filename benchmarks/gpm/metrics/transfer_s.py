"""Device idle seconds per job while tensors are copied between host and
device: the innermost open span is ``gpm.upload`` (a host array to the
device, converted first where its dtype changes) or ``gpm.readback``
(a device result to the host, after the device has it).  Booked
instant by instant by ``idle_by_span.py``."""
LAYER = "device"
UNIT = "s/job"
MOVES = "job_s"


def read(ctx):
    return bench.module(  # noqa: F821  (set by Bench.module)
        "", "idle_by_span").per_job(ctx, "transfer_s")
