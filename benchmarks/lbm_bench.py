"""LBM throughput measured on CPU (the only real hardware here), across
the (n, m) structures: reference, SPD-compiled PE, temporal cascades, and
the Pallas temporal-blocking kernel (interpret mode), plus physics checks.

MLUPS = million lattice-site updates per second. CPU numbers validate
*relative* behavior (fused m-steps amortize memory traffic) — absolute
roofline numbers for the TPU target come from the DSE model.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.apps import lbm
from repro.compat import default_interpret, enable_compile_cache
from repro.kernels.lbm_stream.ops import lbm_run_blocked


def _time(f, *args, reps=3):
    f(*args)  # warm/compile
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def run(h: int = 128, w: int = 256, steps: int = 8) -> list[str]:
    out = []
    f0, attr, _ = lbm.taylor_green_init(h, w)
    one_tau = 1.0 / 0.8
    sites = h * w * steps

    rows = []

    t = _time(lambda f: lbm.ref_run(f, attr, one_tau, steps), f0)
    rows.append(("jnp reference (m=1)", t))

    for m in (1, 2, 4):
        sim = lbm.LBMSimulation(lbm.LBMProblem(h, w, mode="wrap"), m=m)
        t = _time(lambda f, s=sim: s.run(f, attr, steps), f0)
        rows.append((f"SPD-compiled cascade m={m}", t))

    for m in (2, 8):
        t = _time(
            lambda f, m=m: lbm_run_blocked(
                f, attr, one_tau, steps=steps, m=m, block_h=h // 4
            ),
            f0,
        )
        mode = "interpret" if default_interpret() else "compiled"
        rows.append((f"pallas temporal-block m={m} ({mode})", t))

    out.append("## LBM throughput (CPU), grid %dx%d, %d steps" % (h, w, steps))
    for name, t in rows:
        out.append(f"{name:42s} {t*1e3:9.2f} ms  {sites/t/1e6:8.1f} MLUPS")
        out.append(f"lbm/{name.replace(' ', '_')},{t*1e6:.0f},"
                   f"mlups={sites/t/1e6:.1f}")
    return out


if __name__ == "__main__":
    enable_compile_cache()
    print("\n".join(run()))
