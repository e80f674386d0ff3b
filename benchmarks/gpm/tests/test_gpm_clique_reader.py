"""``metrics/clique_device_s.py`` on synthetic traces: the clique pass's
device seconds per job, the slowest device's sum, and nothing from a
program that counts cliques on the host only."""
import pytest

import gpm_testlib as lib

MS = 1e6


def _trace(devices, pass_ms):
    tr = lib.module("", "trace_reduce")
    trace = tr.Trace()
    trace.spans.append(tr.Span("gpm.window", 0.0, 10e9))
    for d in range(devices):
        trace.ops.append(tr.DeviceOp(d, "fusion.1", "jit__einsum", 1e9, MS))
        trace.programs += [
            tr.DeviceOp(d, "jit__einsum", "jit__einsum", 1e9, 40 * MS),
            # ends before the window opens: not counted
            tr.DeviceOp(d, "jit__clique_pass", "jit__clique_pass",
                        -1e9, 9 * MS)]
        if d == 0 and pass_ms:
            trace.programs += [
                tr.DeviceOp(d, "jit__clique_pass", "jit__clique_pass",
                            2e9 + i * 1e9, pass_ms * MS) for i in range(2)]
    return trace


def _read(trace, jobs=2):
    run = lib.harness()
    ctx = run.Context(jobs=[object()] * jobs, n=8192, trace=trace,
                      peaks={"flops_per_s": 197e12,
                             "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9})
    return lib.module("metrics", "clique_device_s").read(ctx)


@pytest.mark.parametrize("devices", [1, 4])
def test_reads_the_pass_per_job_on_the_chip_that_ran_it(devices):
    """Two passes of 55 ms on one chip over two jobs: 55 ms a job, on
    one chip or the first of four; programs of other names, and a pass
    that ended before the window, count nothing."""
    assert _read(_trace(devices, 55)) == pytest.approx(0.055)


@pytest.mark.parametrize("devices", [1, 4])
def test_gives_nothing_without_the_pass(devices):
    """A program that counts cliques on the host runs no
    ``jit__clique_pass``: the reader gives nothing, and does not
    raise."""
    assert _read(_trace(devices, 0)) is None
