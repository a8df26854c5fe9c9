"""2-D diffusion (Jacobi) — the second SPD application.

The LBM case study proves the stack end to end, but the paper's claim is
a *DSL*: any stream computation written in SPD should compile, sweep its
(n, m) design space, and execute. This five-point Jacobi diffusion core
is the smallest second witness of that claim (docs/pipeline.md §execute):

    u'[y, x] = u + alpha * (u[y-1] + u[y+1] + u[x-1] + u[x+1] - 4u)

One main-stream word in and out, four ``Stencil2D`` neighbor reads
(inferred halo = 1), diffusivity ``alpha`` as an ``Append_Reg`` register
— a very different (shallow, bandwidth-lean) workload shape from the
131-FLOP LBM pipeline, which is exactly what exercises the explorer's
models off the calibration point.

Ships the SPD source generator, the compiled core, a pure-``jnp``
reference (the oracle for the codegen'd Pallas kernel), and a
sinusoidal initial condition with its exact discrete decay factor for
physics validation.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import CompiledCore, Registry, parse_spd

#: Stencil taps of the five-point Laplacian: (dy, dx, port) per neighbor.
NEIGHBORS = ((1, 0, "un"), (-1, 0, "us"), (0, 1, "uw"), (0, -1, "ue"))


def diffusion_spd(width: int, mode: str = "wrap",
                  name: str = "Diff2D") -> str:
    """SPD source of one explicit diffusion (Jacobi) time step."""
    L = [
        f"Name {name};",
        "Main_In {mi::u};",
        "Main_Out {mo::u2};",
        "Append_Reg {rg::alpha};",
    ]
    for dy, dx, port in NEIGHBORS:
        L.append(
            f"HDL T{port}, 0, ({port}) = Stencil2D(u), "
            f"dy={dy}, dx={dx}, W={width}, mode={mode};"
        )
    L.append("EQU Nlap, lap = un + us + ue + uw - 4.0*u;")
    L.append("EQU Nnew, u2 = u + alpha*lap;")
    return "\n".join(L)


def compile_diffusion(width: int, mode: str = "wrap") -> CompiledCore:
    """Parse + compile the diffusion core into a fresh registry."""
    return Registry().compile(parse_spd(diffusion_spd(width, mode)))


# --------------------------------------------------------------------------
# Pure-jnp reference (the oracle)
# --------------------------------------------------------------------------


@jax.jit
def diffusion_ref_step(u, alpha):
    """One explicit five-point diffusion step, periodic boundaries."""
    lap = (
        jnp.roll(u, 1, axis=0) + jnp.roll(u, -1, axis=0)
        + jnp.roll(u, 1, axis=1) + jnp.roll(u, -1, axis=1)
        - 4.0 * u
    )
    return u + alpha * lap


@partial(jax.jit, static_argnames=("steps",))
def diffusion_ref_run(u, alpha, steps: int):
    def body(_, g):
        return diffusion_ref_step(g, alpha)

    return jax.lax.fori_loop(0, steps, body, u)


# --------------------------------------------------------------------------
# Initial condition + analytic reference
# --------------------------------------------------------------------------


def sine_init(h: int, w: int, amp: float = 1.0):
    """Lowest sinusoidal mode; returns ``(u0, decay_per_step(alpha))``.

    For u0 = amp·sin(ky·y)·sin(kx·x) the explicit five-point scheme
    decays the mode *exactly* by
    ``g(alpha) = 1 - alpha·(4 - 2cos(kx) - 2cos(ky))`` per step, so
    kernel physics can be validated against a closed form (the
    Taylor-Green analogue for this app).
    """
    y, x = jnp.meshgrid(
        jnp.arange(h, dtype=jnp.float32),
        jnp.arange(w, dtype=jnp.float32),
        indexing="ij",
    )
    ky, kx = 2 * math.pi / h, 2 * math.pi / w
    u0 = amp * jnp.sin(ky * y) * jnp.sin(kx * x)

    def decay_per_step(alpha: float) -> float:
        return 1.0 - alpha * (4.0 - 2.0 * math.cos(kx) - 2.0 * math.cos(ky))

    return u0, decay_per_step


class DiffusionSimulation:
    """Compiled-core driver mirroring :class:`repro.apps.lbm.LBMSimulation`.

    Holds the compiled SPD core and its problem size; hands the explorer
    a workload bound to this grid and frontier points to the codegen'd
    stream kernel (docs/pipeline.md §execute).
    """

    def __init__(self, height: int, width: int, alpha: float = 0.2):
        if not 0.0 < alpha <= 0.25:
            raise ValueError(f"explicit scheme needs 0 < alpha <= 0.25, "
                             f"got {alpha}")
        self.height, self.width, self.alpha = height, width, alpha
        self.core = compile_diffusion(width)
        self.kernel = self.core.stream_kernel()

    @property
    def hardware_report(self):
        return self.core.hardware_report

    def explorer(self, **kw):
        return self.core.explorer(
            elems=self.height * self.width, grid_w=self.width, **kw
        )

    def state(self, u) -> jnp.ndarray:
        return self.kernel.pack([u])

    def run(self, u, steps: int, *, m: int = 1, block_h: int | None = None,
            interpret: bool | None = None, d: int = 1):
        """Advance ``steps`` diffusion steps through the Pallas kernel.

        ``d > 1`` shards the grid across that many devices with halo
        exchange (docs/pipeline.md §distribute) — requires ``d``
        available devices and ``d | height``.
        """
        if block_h is None:
            from repro.core.legalize import blocking_plan

            block_h, m, _ = blocking_plan(
                self.height, 32, m, halo=self.kernel.halo, d=d,
            )
        kern = self.kernel if d == 1 else self.kernel.sharded(d)
        out = kern.run_blocked(
            self.state(u), (self.alpha,), steps=steps, m=m,
            block_h=block_h, interpret=interpret,
        )
        return out[0]
