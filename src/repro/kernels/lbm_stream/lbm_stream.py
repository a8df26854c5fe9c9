"""Pallas TPU kernel: fused m-step D2Q9 LBM with temporal blocking.

This is the TPU-native realization of the paper's *temporal parallelism*
(cascaded PEs): one HBM round-trip advances ``m`` time steps. Where the FPGA
cascades m physical pipelines with their own line buffers, the TPU kernel
keeps a (block_h + 2m)-row stripe of the lattice resident in VMEM, applies m
collide+stream+bounce steps entirely on-chip, and writes back only the
block_h center rows — arithmetic intensity scales with m while HBM traffic
stays constant (DESIGN.md §2).

Decomposition: 1-D over rows (y). Each grid program reads its own stripe
plus its two neighbors (periodic via modular index maps) — the y-halo — and
handles x wrap-around with in-register shifts, so the result is exactly
periodic, bit-matching the reference for fluid-only lattices and lattices
with bounce-back walls alike.

VMEM budget per program (f32): 10 fields x (3*block_h) x W x 4 B for the
three input stripes + ~10 x (block_h+2m) x W x 4 B working set. BlockSpec
shapes keep W the minor (lane) dimension, a multiple of 128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.apps.lbm import EX, EY, OPP, W as LATTICE_W
from repro.compat import resolve_interpret


def _shift_x(a, dx: int):
    """Periodic shift along the minor (x) axis: out[.., x] = a[.., x-dx]."""
    if dx == 0:
        return a
    if dx == 1:
        return jnp.concatenate([a[..., -1:], a[..., :-1]], axis=-1)
    if dx == -1:
        return jnp.concatenate([a[..., 1:], a[..., :1]], axis=-1)
    raise ValueError(dx)


def _shift_y(a, dy: int):
    """Non-periodic shift along rows (halo supplies the boundary)."""
    if dy == 0:
        return a
    pad = jnp.zeros_like(a[:, :abs(dy), :])
    if dy > 0:
        return jnp.concatenate([pad, a[:, :-dy, :]], axis=1)
    return jnp.concatenate([a[:, -dy:, :], pad], axis=1)


def _step(f, attr, one_tau, u_lid):
    """One collide->stream->bounce step on an extended (halo'd) stripe.

    Rows within `halo` of the stripe edge become invalid (they consumed
    y-neighbors that this step did not have); callers shrink the valid
    region by one row per step — the temporal-blocking trapezoid.
    """
    dtype = f.dtype
    fluid = attr < 0.5
    # --- collide (BGK), gated to fluid cells --------------------------------
    rho = jnp.sum(f, axis=0)
    inv_rho = 1.0 / rho
    ux = (f[1] + f[5] + f[8] - f[3] - f[6] - f[7]) * inv_rho
    uy = (f[2] + f[5] + f[6] - f[4] - f[7] - f[8]) * inv_rho
    usq = ux * ux + uy * uy
    post = []
    for i in range(9):
        cu = EX[i] * ux + EY[i] * uy if (EX[i] or EY[i]) else 0.0
        feq = (
            LATTICE_W[i].astype(dtype) if hasattr(LATTICE_W[i], "astype")
            else jnp.asarray(LATTICE_W[i], dtype)
        ) * rho * (1.0 + 3.0 * cu + 4.5 * cu * cu - 1.5 * usq)
        gi = f[i] - one_tau * (f[i] - feq)
        post.append(jnp.where(fluid, gi, f[i]))
    # --- stream (x periodic in-register, y via halo) ------------------------
    streamed = [
        _shift_x(_shift_y(post[i][None], int(EY[i]))[0], int(EX[i]))
        for i in range(9)
    ]
    # --- bounce-back with moving-wall correction ----------------------------
    solid = attr >= 0.5
    moving = attr >= 1.5
    out = []
    for i in range(9):
        refl = streamed[int(OPP[i])]
        corr = jnp.asarray(6.0 * float(LATTICE_W[i]) * float(EX[i]), dtype)
        bb = jnp.where(moving, refl + corr * u_lid, refl)
        out.append(jnp.where(solid, bb, streamed[i]))
    return jnp.stack(out)


def _kernel(scal_ref, fc_ref, fu_ref, fd_ref, ac_ref, au_ref, ad_ref,
            out_ref, *, m: int, block_h: int):
    one_tau = scal_ref[0]
    u_lid = scal_ref[1]
    # Assemble the (9, block_h + 2m, W) extended stripe from the three
    # VMEM-resident input stripes (the y-halo exchange).
    f_ext = jnp.concatenate(
        [fu_ref[:, block_h - m:, :], fc_ref[...], fd_ref[:, :m, :]], axis=1
    )
    a_ext = jnp.concatenate(
        [au_ref[block_h - m:, :], ac_ref[...], ad_ref[:m, :]], axis=0
    )
    # m fused steps; after each, one edge row per side goes stale. We keep
    # the full extent and simply never read the stale rows again: step k
    # needs rows valid to distance m-k, satisfied inductively.
    for _ in range(m):
        f_ext = _step(f_ext, a_ext, one_tau, u_lid)
    out_ref[...] = f_ext[:, m:m + block_h, :]


@functools.partial(
    jax.jit, static_argnames=("m", "block_h", "interpret")
)
def lbm_multistep(f, attr, one_tau, u_lid=0.0, *, m: int = 4,
                  block_h: int = 32, interpret: bool | None = None):
    """Fused m-step periodic LBM update.

    Args:
      f: (9, H, W) f32 distributions.
      attr: (H, W) f32 cell attributes (0 fluid / 1 wall / 2 moving lid).
      one_tau: 1/tau relaxation.
      u_lid: lid velocity for attr==2 cells.
      m: fused time steps per HBM round-trip (temporal parallelism).
      block_h: rows per grid program (spatial tile).
      interpret: run in Pallas interpret mode; ``None`` decides by backend
        (``repro.compat.default_interpret``: CPU only).
    """
    _, h, w = f.shape
    if h % block_h:
        raise ValueError(f"H={h} must be divisible by block_h={block_h}")
    if m > block_h:
        raise ValueError(f"m={m} must be <= block_h={block_h} (halo source)")
    nblk = h // block_h
    scal = jnp.asarray([one_tau, u_lid], jnp.float32)

    fspec = lambda off: pl.BlockSpec(
        (9, block_h, w), lambda i, off=off: (0, (i + off) % nblk, 0)
    )
    aspec = lambda off: pl.BlockSpec(
        (block_h, w), lambda i, off=off: ((i + off) % nblk, 0)
    )
    return pl.pallas_call(
        functools.partial(_kernel, m=m, block_h=block_h),
        grid=(nblk,),
        in_specs=[
            # physics scalars live in SMEM (scalar memory) on TPU
            pl.BlockSpec(memory_space=pltpu.SMEM),
            fspec(0), fspec(-1), fspec(1),
            aspec(0), aspec(-1), aspec(1),
        ],
        out_specs=pl.BlockSpec((9, block_h, w), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(f.shape, f.dtype),
        interpret=resolve_interpret(interpret),
    )(scal, f, f, f, attr, attr, attr)
