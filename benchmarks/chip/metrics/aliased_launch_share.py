"""aliased_launch_share: the share, in %, of the program's fused
launches that wrote into a buffer the one-chip launch loop recycles
(``input_output_aliases``) rather than into a new one, from the
program's own counters (``repro.core.tracing``: ``aliased_launches``
over ``launches``, the same for every call of a run, since every call
runs one plan). A loop of n launches that ping-pongs between two
buffers recycles n - 2 of them. Read, as ``kernel_dma_share`` is, only
where the trace shows the kernel on the device; absent where the
program keeps no such counter."""

import importlib

import tracefile


def read(rec):
    trace = rec["trace"]
    if trace is None or not tracefile.device_s(trace, "kernel",
                                               rec["chips"]):
        return None
    try:
        tracing = importlib.import_module("repro.core.tracing")
    except ImportError:
        return None
    counters = tracing.snapshot()
    if "aliased_launches" not in counters or not counters["launches"]:
        return None
    return 100.0 * counters["aliased_launches"] / counters["launches"]
