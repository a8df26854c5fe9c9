"""collective_ms_per_step: device time of the collectives (the halo
exchange's collective-permutes) per chip, over the steps of the traced
window. A cell with no collective reads nothing."""

import tracefile


def read(rec):
    trace = rec["trace"]
    if trace is None:
        return None
    sec = tracefile.device_s(trace, "collective", rec["chips"])
    steps = tracefile.steps(trace)
    return sec / steps * 1e3 if sec and steps else None
