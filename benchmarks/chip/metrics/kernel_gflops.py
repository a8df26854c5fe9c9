"""kernel_gflops: the float operations the kernel executes in the traced
window, over the kernel's device time, in GFLOP/s per chip. The
operations come from the program's own counters
(``repro.core.tracing``: ``kernel_flops`` over ``steps``, the same for
every call of a run, since every call runs one plan), times the
window's steps; they count every row of each stripe, halo rows included,
at the compiled core's operations per site. This is the numerator of the
compute leg of the kernel's roofline: no VPU float32 peak is published,
so no share is taken. Absent where the program keeps no such counter."""

import importlib

import tracefile


def read(rec):
    trace = rec["trace"]
    if trace is None:
        return None
    try:
        tracing = importlib.import_module("repro.core.tracing")
    except ImportError:
        return None
    counters = tracing.snapshot()
    kernel_s = tracefile.device_s(trace, "kernel", rec["chips"])
    steps = tracefile.steps(trace)
    if (not kernel_s or not steps or not counters.get("kernel_flops")
            or not counters["steps"]):
        return None
    per_chip = (counters["kernel_flops"] / counters["steps"] * steps
                / rec["chips"])
    return per_chip / kernel_s / 1e9
