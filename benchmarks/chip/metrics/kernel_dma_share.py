"""kernel_dma_share: the bytes the kernel's DMAs are programmed to move
in the traced window, over the kernel's device time, as a share in %
of the chip's peak HBM bandwidth. The bytes come from the program's
own counters (``repro.core.tracing``: ``dma_bytes`` over ``steps``,
the same for every call of a run, since every call runs one plan),
times the window's steps. Unlike ``kernel_hbm_share`` it counts the
halo rows each stripe reads besides its own. Absent where the program
keeps no such counters."""

import importlib

import harness
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
    if not kernel_s or not steps or not counters["steps"]:
        return None
    per_chip = (counters["dma_bytes"] / counters["steps"] * steps
                / rec["chips"])
    bw = harness.peak(rec["root"], rec["device_kind"], "hbm_bytes_per_s")
    return 100.0 * per_chip / bw / kernel_s
