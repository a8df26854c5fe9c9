"""Advection → reaction/diffusion — the 2-core stream-program application.

The LBM program (``repro.apps.lbm.lbm_program``) proves the program
layer on the paper's benchmark; this app is the second acceptance
workload (docs/pipeline.md §program, DESIGN.md §14): a genuine 2-core
chain whose stages are *both* stencil cores, so fusing them composes
halos (1 + 1 = 2 rows per temporal step) instead of merely chaining
pointwise work:

* ``Advect2D`` — first-order upwind advection with positive constant
  velocity ``(vx, vy)`` (``Append_Reg``), periodic boundaries:

      a = u - vx*(u - u[x-1]) - vy*(u - u[y-1])

* ``ReactDiffuse2D`` — explicit five-point diffusion plus a logistic
  reaction term (Fisher-KPP style), ``alpha``/``r`` as registers:

      u' = a + alpha*lap(a) + r*a*(1 - a)

``advdiff_spd`` is the hand-written monolithic single-core reference —
the same EQU formulae concatenated into one core, with the stage-2
stencils applied to the *computed* intermediate stream — which every
fusion partition of the program must reproduce bit for bit
(``tests/test_program.py``). A pure-``jnp`` oracle closes the loop.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import CompiledCore, Registry, parse_spd

#: Five-point Laplacian taps (dy, dx, port): Stencil2D(u), dy=a, dx=b
#: reads u[y-a, x-b] (the translation convention of repro.apps.lbm).
NEIGHBORS = ((1, 0, "n"), (-1, 0, "s"), (0, 1, "w"), (0, -1, "e"))


def advect_spd(width: int, mode: str = "wrap",
               name: str = "Advect2D") -> str:
    """Program stage 1: first-order upwind advection (halo 1)."""
    return "\n".join([
        f"Name {name};",
        "Main_In {mi::u};",
        "Main_Out {mo::a};",
        "Append_Reg {rg::vx,vy};",
        f"HDL Tux, 0, (uxm) = Stencil2D(u), dy=0, dx=1, "
        f"W={width}, mode={mode};",
        f"HDL Tuy, 0, (uym) = Stencil2D(u), dy=1, dx=0, "
        f"W={width}, mode={mode};",
        "EQU Nadv, a = u - vx*(u - uxm) - vy*(u - uym);",
    ])


def react_diffuse_spd(width: int, mode: str = "wrap",
                      name: str = "ReactDiffuse2D") -> str:
    """Program stage 2: five-point diffusion + logistic reaction (halo 1)."""
    L = [
        f"Name {name};",
        "Main_In {mi::a};",
        "Main_Out {mo::u2};",
        "Append_Reg {rg::alpha,r};",
    ]
    for dy, dx, port in NEIGHBORS:
        L.append(
            f"HDL T{port}, 0, (a{port}) = Stencil2D(a), "
            f"dy={dy}, dx={dx}, W={width}, mode={mode};"
        )
    L.append("EQU Nlap, lap = an + as + ae + aw - 4.0*a;")
    L.append("EQU Nnew, u2 = a + alpha*lap + r*a*(1.0 - a);")
    return "\n".join(L)


def advdiff_spd(width: int, mode: str = "wrap",
                name: str = "AdvDiff2D") -> str:
    """The monolithic single-core reference: both stages' formulae in one
    core, stage-2 stencils reading the computed intermediate ``a``
    (inferred halo 2 — the composed program halo)."""
    L = [
        f"Name {name};",
        "Main_In {mi::u};",
        "Main_Out {mo::u2};",
        "Append_Reg {rg::vx,vy,alpha,r};",
        f"HDL Tux, 0, (uxm) = Stencil2D(u), dy=0, dx=1, "
        f"W={width}, mode={mode};",
        f"HDL Tuy, 0, (uym) = Stencil2D(u), dy=1, dx=0, "
        f"W={width}, mode={mode};",
        "EQU Nadv, a = u - vx*(u - uxm) - vy*(u - uym);",
    ]
    for dy, dx, port in NEIGHBORS:
        L.append(
            f"HDL T{port}, 0, (a{port}) = Stencil2D(a), "
            f"dy={dy}, dx={dx}, W={width}, mode={mode};"
        )
    L.append("EQU Nlap, lap = an + as + ae + aw - 4.0*a;")
    L.append("EQU Nnew, u2 = a + alpha*lap + r*a*(1.0 - a);")
    return "\n".join(L)


def build_advdiff_registry(width: int, mode: str = "wrap") -> Registry:
    """Compile both stages + the monolithic reference into one registry."""
    reg = Registry()
    reg.compile(parse_spd(advect_spd(width, mode)))
    reg.compile(parse_spd(react_diffuse_spd(width, mode)))
    reg.compile(parse_spd(advdiff_spd(width, mode)))
    return reg


def advdiff_program(width: int, mode: str = "wrap"):
    """The app as a 2-core :class:`~repro.core.program.StreamProgram`:
    advect → react/diffuse, fusion partition left to the DSE."""
    from repro.core.program import StreamProgram

    return StreamProgram(
        build_advdiff_registry(width, mode),
        ["Advect2D", "ReactDiffuse2D"],
        width=width,
        name="AdvDiff_Program",
    )


# --------------------------------------------------------------------------
# Pure-jnp reference (the oracle)
# --------------------------------------------------------------------------


@jax.jit
def advdiff_ref_step(u, vx, vy, alpha, r):
    """One advect→react/diffuse step, periodic boundaries."""
    a = (
        u
        - vx * (u - jnp.roll(u, 1, axis=1))
        - vy * (u - jnp.roll(u, 1, axis=0))
    )
    lap = (
        jnp.roll(a, 1, axis=0) + jnp.roll(a, -1, axis=0)
        + jnp.roll(a, 1, axis=1) + jnp.roll(a, -1, axis=1)
        - 4.0 * a
    )
    return a + alpha * lap + r * a * (1.0 - a)


@partial(jax.jit, static_argnames=("steps",))
def advdiff_ref_run(u, vx, vy, alpha, r, steps: int):
    def body(_, g):
        return advdiff_ref_step(g, vx, vy, alpha, r)

    return jax.lax.fori_loop(0, steps, body, u)


def blob_init(h: int, w: int, amp: float = 0.8) -> jnp.ndarray:
    """A smooth periodic concentration blob in (0, amp]."""
    y, x = jnp.meshgrid(
        jnp.arange(h, dtype=jnp.float32),
        jnp.arange(w, dtype=jnp.float32),
        indexing="ij",
    )
    import math

    return amp * (
        0.5 + 0.25 * jnp.sin(2 * math.pi * y / h)
        + 0.25 * jnp.cos(2 * math.pi * x / w)
    )


# --------------------------------------------------------------------------
# Simulation driver
# --------------------------------------------------------------------------


class AdvectionDiffusionSimulation:
    """Driver mirroring :class:`repro.apps.lbm.LBMSimulation` for the
    2-core program: holds the compiled registry, hands the explorer a
    program-backed workload (``stages`` set, so the model prices fusion
    partitions cluster by cluster), and executes points through
    :func:`repro.core.program.program_run_factory`."""

    def __init__(self, height: int, width: int, *, vx: float = 0.2,
                 vy: float = 0.1, alpha: float = 0.15, r: float = 0.05):
        if not 0.0 < alpha <= 0.25:
            raise ValueError(f"explicit scheme needs 0 < alpha <= 0.25, "
                             f"got {alpha}")
        if not (0.0 <= vx <= 1.0 and 0.0 <= vy <= 1.0):
            raise ValueError("upwind scheme needs 0 <= vx, vy <= 1")
        self.height, self.width = height, width
        self.vx, self.vy, self.alpha, self.r = vx, vy, alpha, r
        self.program = advdiff_program(width)
        self.registry = self.program.registry

    @property
    def monolithic_core(self) -> CompiledCore:
        """The hand-written single-core AdvDiff2D reference."""
        return self.registry.lookup("AdvDiff2D")

    def regs(self) -> tuple:
        """Flat program register values (``vx, vy, alpha, r`` — also the
        monolithic core's register order)."""
        return (self.vx, self.vy, self.alpha, self.r)

    def state(self, u) -> jnp.ndarray:
        return self.program.monolithic_kernel().pack([u])

    def explorer(self, **kw):
        """DSE explorer over the program (fusion axis included via
        ``sweep_tpu(fusion_values=...)``)."""
        return self.program.explorer(
            self.height * self.width, grid_w=self.width, **kw
        )

    def run(self, u, steps: int, *, fusion: str = "", m: int = 1,
            block_h: int = 32, interpret: bool | None = None, d: int = 1):
        """Advance ``steps`` through the program under ``fusion``."""
        out = self.program.kernel(fusion).run_blocked(
            self.state(u), self.regs(), steps=steps, m=m,
            block_h=block_h, interpret=interpret, d=d,
        )
        return out[0]
