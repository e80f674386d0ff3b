"""The join kernels' share of their roofline, in percent.

For every join that a window job ran on a kernel route, the least time
the chip could take is the larger of its required operations over the
peak rate and its required bytes over the peak bandwidth (``peaks.json``).
The share is the sum of those times over the kernels' device time
(``join_kernel_s``).  What a join requires follows from the graph's n,
its cut size and its factors, whatever kernel implements it: no tiles,
padding or masks.

* operations: k · n^|cut| for k factors, k - 1 products and one sum
  per assignment of the cut;
* bytes: each factor read once, as the f32 the join takes
  (4 bytes · its elements, from the factor shapes the node span holds).

The peak rate is the chip's published bf16 matrix peak, the highest it
has, so the share bounds the joins' elementwise f32 work from above.
"""
LAYER = "joins"
UNIT = "%"
MOVES = "job_s"

KERNEL_ROUTES = ("kernel", "kernel-keep")
FACTOR_BYTES = 4


def join_ops(n: int, factors: int, cut: int) -> float:
    return float(factors) * float(n) ** cut


def join_bytes(factor_shapes) -> float:
    total = 0.0
    for shape in factor_shapes:
        size = 1.0
        for d in shape:
            size *= d
        total += size
    return FACTOR_BYTES * total


def least_seconds(n: int, cut: int, factor_shapes, peaks: dict) -> float:
    return max(join_ops(n, len(factor_shapes), cut) / peaks["flops_per_s"],
               join_bytes(factor_shapes) / peaks["hbm_bytes_per_s"])


def read(ctx):
    kernel_s = bench.module(  # noqa: F821  (set by Bench.module)
        "metrics", "join_kernel_s").kernel_seconds(ctx.trace)
    if kernel_s <= 0:
        return None
    least = 0.0
    for job in ctx.jobs:
        for s in job.tracer.walk():
            if s.attrs.get("route") in KERNEL_ROUTES:
                least += least_seconds(ctx.n, s.attrs["cut_size"],
                                       s.attrs["factor_shapes"], ctx.peaks)
    return 100.0 * least / kernel_s
