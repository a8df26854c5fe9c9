"""device_idle_share: the share of the traced window, in %, in which no
op ran on a chip (1 - union of op intervals / window), averaged over the
chips."""

import tracefile


def read(rec):
    trace = rec["trace"]
    if trace is None or not trace["ops"]:
        return None
    window = tracefile.window_s(trace)
    if not window:
        return None
    return 100.0 * (1.0 - tracefile.busy_s(trace, rec["chips"]) / window)
