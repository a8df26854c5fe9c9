"""model_step_ratio: the model's (``TPUModel``) predicted seconds per
step for the chosen plan over the seconds per step measured in the
traced window (wall time of the window over its steps). Above 1 the
model is slower than the chip, below 1 faster."""

import tracefile


def read(rec):
    trace = rec["trace"]
    if trace is None:
        return None
    steps = tracefile.steps(trace)
    window = tracefile.window_s(trace)
    if not steps or not window:
        return None
    return rec["plan"]["model_step_s"] / (window / steps)
