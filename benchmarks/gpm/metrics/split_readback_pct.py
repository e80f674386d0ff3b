"""Share of the bytes read back from the device that crossed as two
exact f32 halves rather than as emulated f64: the program's
``transfer.split_bytes`` over its ``transfer.d2h_bytes``, kept by the
``obs.Tracer`` attached to each job's plan for its own reads.  None
without device operations in the trace, or from a program whose tracer
keeps no counts or no ``transfer.split_bytes`` (a program without the
split, which counts no split bytes at all)."""
LAYER = "device"
UNIT = "%"
MOVES = "job_s"


def read(ctx):
    if not ctx.trace.ops or not ctx.jobs:
        return None
    split = copied = 0.0
    for job in ctx.jobs:
        counts = getattr(job.tracer, "counts", None)
        if counts is None or "transfer.split_bytes" not in counts:
            return None
        split += sum(counts["transfer.split_bytes"].values())
        copied += sum(counts.get("transfer.d2h_bytes", {}).values())
    return 100.0 * split / copied if copied else None
