"""Pallas TPU launch scaffolding for codegen'd SPD stream kernels.

This is the generic form of the temporal-blocking structure hand-written
in ``repro.kernels.lbm_stream`` (DESIGN.md §2, docs/pipeline.md §codegen):

* the grid state is one stacked ``(P, H, W)`` f32 array — one channel per
  main-stream port of the SPD core;
* each grid program keeps a ``(P, block_h + 2·m·halo, W)``-row stripe
  VMEM-resident, assembled from its own block plus the two neighbor
  blocks (periodic in y via modular index maps);
* ``m`` fused applications of the core's dataflow function advance the
  stripe m time steps per HBM round-trip; after each application ``halo``
  edge rows per side go stale and are simply never read again (the
  temporal-blocking trapezoid);
* periodic x is handled inside the stripe function with in-register
  shifts (the full row width is resident), so no x-halo is needed;
* spatial parallelism is grid duplication: ``H / block_h`` programs run
  the same stripe function on disjoint row blocks.

The *stripe function* itself — ``step_fn((P, rows, W), regs) → (P, rows,
W)`` — is produced by :class:`repro.core.codegen.StreamKernel` from the
core's data-flow graph; this module only owns the ``pallas_call``
plumbing, exactly mirroring ``lbm_multistep`` so the two back ends stay
comparable line for line.

The batch axis (docs/pipeline.md §serve, DESIGN.md §13): state may
carry extra *leading* dimensions — ``(B, P, H, W)`` stacks B
independent simulations — and the launch generalizes mechanically: row
blocks are tiled on axis ``-2``, leading axes ride whole through every
BlockSpec, and the stripe function must handle the batched rank (the
codegen'd ``step_fn`` vmaps itself over leading axes). The batched
launch is bitwise identical per member to B separate launches.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import resolve_interpret


def _kernel(scal_ref, fc_ref, fu_ref, fd_ref, out_ref, *,
            step_fn: Callable, m: int, block_h: int, mh: int):
    regs = tuple(scal_ref[i] for i in range(scal_ref.shape[0]))
    if mh:
        # Assemble the (…, block_h + 2·mh, W) extended stripe from the
        # three VMEM-resident input stripes (the y-halo exchange). Rows
        # live on axis -2 so any leading (batch) axes ride through.
        f_ext = jnp.concatenate(
            [fu_ref[..., block_h - mh:, :], fc_ref[...],
             fd_ref[..., :mh, :]],
            axis=-2,
        )
    else:  # elementwise core: no neighbor rows needed
        f_ext = fc_ref[...]
    for _ in range(m):
        f_ext = step_fn(f_ext, regs)
    out_ref[...] = f_ext[..., mh:mh + block_h, :]


def spd_multistep(step_fn: Callable, state, scal, *, m: int, block_h: int,
                  halo: int, interpret: bool | None = None,
                  name: str | None = None):
    """Fused m-step launch of a codegen'd stripe function.

    Args:
      step_fn: ``((P, rows, W) stripe, regs tuple) -> (P, rows, W)`` — one
        application of the SPD core's dataflow over a row stripe, with y
        stencil reads sourced from within the stripe (edge rows go stale)
        and x stencil reads periodic in-register.
      state: (P, H, W) f32 stacked main-stream state; extra leading
        dimensions batch independent simulations — ``(B, P, H, W)``
        launches B members in one call (docs/pipeline.md §serve).
      scal: (R,) f32 Append_Reg scalar values (length >= 1; padded with a
        dummy when the core has no registers — SMEM refs need a shape).
      m: fused time steps per HBM round-trip (temporal parallelism).
      block_h: rows per grid program (spatial tile).
      halo: per-step stencil reach in rows (inferred by the codegen);
        the stripe carries ``m*halo`` extra rows per side.
      interpret: run under the Pallas interpreter; ``None`` decides by
        backend (``repro.compat.default_interpret``: CPU only).
      name: the kernel's name in the compiled program and device trace.
    """
    *lead, h, w = state.shape
    if h % block_h:
        raise ValueError(f"H={h} must be divisible by block_h={block_h}")
    mh = m * halo
    if mh > block_h:
        raise ValueError(
            f"m*halo={mh} must be <= block_h={block_h} (halo source)"
        )
    nblk = h // block_h
    nlead = len(lead)
    zeros = (0,) * nlead

    fspec = lambda off: pl.BlockSpec(
        (*lead, block_h, w),
        lambda i, off=off: zeros + ((i + off) % nblk, 0),
    )
    return pl.pallas_call(
        functools.partial(
            _kernel, step_fn=step_fn, m=m, block_h=block_h, mh=mh
        ),
        grid=(nblk,),
        in_specs=[
            # Append_Reg scalars live in SMEM (scalar memory) on TPU
            pl.BlockSpec(memory_space=pltpu.SMEM),
            fspec(0), fspec(-1), fspec(1),
        ],
        out_specs=pl.BlockSpec(
            (*lead, block_h, w), lambda i: zeros + (i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(state.shape, state.dtype),
        interpret=resolve_interpret(interpret),
        name=name,
    )(scal, state, state, state)
