"""The sharded Contract steps' share of their roofline, in percent.

Every step a window job ran leaves a description on the job's Contract
node span (``steps``, written by ``distributed/contract.py``): an
einsum ``spec`` over per-shard operand ``shapes``, the operands'
``dtype``, the output's ``out_dtype``, and ``count``, how many such
products the step makes (the int8 steps make one per pair of digit
planes).  Each chip runs the same per-shard step, so the least time a
chip could take for it is

    count · max(ops / peak of the operand dtype, bytes / peak bandwidth)

and the share is the sum over the steps over ``sharded_contract_s``'s
device seconds on the slowest chip.

* ops: an einsum of k operands over the extents E of all its indices
  makes (k - 1) · ΠE multiplies, and ΠE adds where it sums an index (a
  matrix product's 2 · M · N · K; a single operand that sums, ΠE);
* bytes: each operand read once and the output written once, at their
  dtypes;
* peaks: int8 from ``dtype_peaks.json``; every other dtype takes
  ``peaks.json``'s bf16 rate, the highest float rate the chip has, so
  the f64 steps, which the chip emulates, are bounded from above.
"""
import json
import math

import numpy as np

LAYER = "Contract"
UNIT = "%"
MOVES = "job_s"


def extents(spec: str, shapes) -> dict:
    lhs = spec.split("->")[0].split(",")
    out = {}
    for letters, shape in zip(lhs, shapes):
        out.update(zip(letters, shape))
    return out


def step_ops(spec: str, shapes) -> float:
    lhs, rhs = spec.split("->")
    operands = lhs.split(",")
    volume = float(math.prod(extents(spec, shapes).values()))
    summed = bool(set("".join(operands)) - set(rhs))
    return volume * (len(operands) - 1 + summed)


def step_bytes(spec: str, shapes, dtype: str, out_dtype: str) -> float:
    sizes = extents(spec, shapes)
    rhs = spec.split("->")[1]
    read = sum(float(math.prod(s)) for s in shapes)
    written = float(math.prod(sizes[c] for c in rhs))
    return (read * np.dtype(dtype).itemsize
            + written * np.dtype(out_dtype).itemsize)


def least_seconds(step: dict, peaks: dict, int8_peak: float) -> float:
    rate = int8_peak if step["dtype"] == "int8" else peaks["flops_per_s"]
    ops = step_ops(step["spec"], step["shapes"])
    nbytes = step_bytes(step["spec"], step["shapes"], step["dtype"],
                        step["out_dtype"])
    return step["count"] * max(ops / rate, nbytes / peaks["hbm_bytes_per_s"])


def int8_peak(device_kind: str) -> float:
    path = bench.root / "dtype_peaks.json"  # noqa: F821  (Bench.module)
    table = json.loads(path.read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no int8 peak for device kind {device_kind!r} in "
                       f"dtype_peaks.json")
    return table["devices"][device_kind]["int8"]


def read(ctx):
    import jax
    seconds = bench.module(  # noqa: F821  (set by Bench.module)
        "metrics", "sharded_contract_s").slowest_seconds(ctx.trace)
    if seconds <= 0 or any(j.tracer is None for j in ctx.jobs):
        return None
    peak = int8_peak(jax.devices()[0].device_kind)
    least = sum(least_seconds(step, ctx.peaks, peak)
                for job in ctx.jobs for s in job.tracer.walk()
                for step in s.attrs.get("steps", ()))
    return 100.0 * least / seconds if least else None
