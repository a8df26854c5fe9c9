"""Public jit'd wrappers for the LBM temporal-blocking kernel, plus the
explorer hand-off: :func:`lbm_run_for_point` runs a ``DesignPoint``
straight from a ``repro.core.explorer`` sweep. Legalization of
model-chosen (block_h, m) plans is shared with the generic SPD codegen
path via :mod:`repro.core.legalize` (docs/pipeline.md §legalize); the
LBM kernel's per-step stencil reach is one row, so ``halo=1`` (the
default) applies."""

from __future__ import annotations

import functools

import jax

from repro.core.legalize import blocking_plan, resolve_run_plan

from .lbm_stream import lbm_multistep
from .ref import lbm_multistep_ref


def lbm_run_for_point(f, attr, one_tau, point, *, steps: int | None = None,
                      u_lid=0.0, interpret: bool | None = None):
    """Advance the lattice using a DSE design point's (block_h, m).

    See :func:`resolve_run_plan` for how the point is legalized — with
    the concrete stripe geometry (the grid width and the 9 distribution
    words + 1 attribute word resident per site), so the VMEM clamp
    applies exactly as it does on the generic codegen path.
    Returns ``(result, (block_h, m))``.
    """
    # The hand-written LBM kernel predates the streamed path and ignores
    # the resolved double_buffer protocol (it always uses the BlockSpec
    # pipeline); the generic codegen path is the streamed one.
    block_h, m, nsteps, _ = resolve_run_plan(
        f.shape[1], point, steps, width=f.shape[2], words=f.shape[0] + 1,
    )
    out = lbm_run_blocked(f, attr, one_tau, u_lid, steps=nsteps, m=m,
                          block_h=block_h, interpret=interpret)
    return out, (block_h, m)


@functools.partial(jax.jit, static_argnames=("steps", "m", "block_h", "interpret"))
def lbm_run_blocked(f, attr, one_tau, u_lid=0.0, *, steps: int, m: int = 4,
                    block_h: int = 32, interpret: bool | None = None):
    """Advance ``steps`` LBM time steps using m-fused kernel launches."""
    if steps % m:
        raise ValueError(f"steps={steps} must be a multiple of m={m}")

    def body(_, g):
        return lbm_multistep(
            g, attr, one_tau, u_lid, m=m, block_h=block_h, interpret=interpret
        )

    return jax.lax.fori_loop(0, steps // m, body, f)


__all__ = [
    "blocking_plan",
    "lbm_multistep",
    "lbm_multistep_ref",
    "lbm_run_blocked",
    "lbm_run_for_point",
    "resolve_run_plan",
]
