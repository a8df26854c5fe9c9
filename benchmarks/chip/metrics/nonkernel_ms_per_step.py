"""nonkernel_ms_per_step: device time, per chip and step, of every op of
the program that is neither the stencil kernel nor a collective: the
launch glue (guard and halo concatenates, crops, loop copies). The
harness's own readback is left out."""

import tracefile


def read(rec):
    trace = rec["trace"]
    if trace is None:
        return None
    sec = tracefile.device_s(trace, "other", rec["chips"])
    steps = tracefile.steps(trace)
    return sec / steps * 1e3 if sec is not None and steps else None
