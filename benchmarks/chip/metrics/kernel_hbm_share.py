"""kernel_hbm_share: the stencil kernel's share, in %, of the HBM bound:
the least bytes a launch must move (its state read once and written
once) over the chip's peak HBM bandwidth, divided by the kernel's
measured device time. This is the memory leg of the roofline only; the
compute leg needs a VPU float32 peak, which is not published."""

import harness
import tracefile


def least_launch_bytes(words: int, sites: float, itemsize: int) -> float:
    """Bytes one launch moves at least over ``sites`` sites of a state
    of ``words`` words per site: read once and written once."""
    return words * sites * itemsize * 2


def read(rec):
    trace = rec["trace"]
    if trace is None:
        return None
    kernel_s = tracefile.device_s(trace, "kernel", rec["chips"])
    steps = tracefile.steps(trace)
    if not kernel_s or not steps:
        return None
    launches = steps / rec["plan"]["m"]
    need = launches * least_launch_bytes(
        rec["words"], rec["sites"] / rec["chips"], rec["itemsize"])
    bw = harness.peak(rec["root"], rec["device_kind"], "hbm_bytes_per_s")
    return 100.0 * need / bw / kernel_s
