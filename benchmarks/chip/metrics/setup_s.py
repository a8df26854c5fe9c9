"""setup_s: seconds from process start to the first timed call: import,
device init, plan choice, state build, compile or cache load, warm-up."""


def read(rec):
    return rec["setup_s"]
