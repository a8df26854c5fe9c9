"""kernel_ms_per_step: device time of the stencil kernel (the Pallas
custom calls of the timed entry) per chip, over the steps of the traced
window."""

import tracefile


def read(rec):
    trace = rec["trace"]
    if trace is None:
        return None
    sec = tracefile.device_s(trace, "kernel", rec["chips"])
    steps = tracefile.steps(trace)
    return sec / steps * 1e3 if sec and steps else None
