"""mlups: lattice-site updates completed in the window per second, in
millions: grid sites times the steps of every completed call, over the
window's seconds on the host clock."""


def read(rec):
    return (rec["calls"] * rec["steps_per_call"] * rec["sites"]
            / rec["window_s"] / 1e6)
