"""Pallas launch for one *shard* of a y-decomposed stream grid.

The single-device launch (:func:`repro.kernels.spd_stream.spd_multistep`)
sources every block's y-halo from its neighbor blocks with periodic
index maps — the whole grid is on one chip, so "the block above" always
exists locally. Under multi-device spatial parallelism
(docs/pipeline.md §distribute, DESIGN.md §8) each device holds only a
``(P, H/d, W)`` shard: the halo of the shard's edge blocks lives on a
*neighboring device* and is exchanged over the interconnect by
``repro.core.distribute`` before every fused launch.

This module owns the per-shard launch that consumes those exchanged
rows: :func:`spd_multistep_halo` takes an *extended* shard

    ``ext = [pad | up-halo | local rows | down-halo | pad]``

where the received ``m·halo`` neighbor rows are padded out to one full
``block_h`` guard block per side, so the interior kernel body — the
exact same ``_kernel`` as the single-device launch — assembles each
stripe from (previous block, own block, next block) with *non*-periodic
index maps: block 0's "previous block" is the up guard block, the last
block's "next block" is the down guard block. One code path, one
bit-for-bit stripe assembly, on- or off-device.

A 2-D device mesh (DESIGN.md §15) does not come through here: its
launch gathers each stripe from the shard and the exchanged guard
pieces in place
(:func:`repro.kernels.spd_stream.streaming.spd_multistep_gathered`).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import resolve_interpret

from .spd_stream import _kernel, spd_multistep


def spd_multistep_halo(step_fn: Callable, ext, scal, *, m: int, block_h: int,
                       halo: int, interpret: bool | None = None,
                       name: str | None = None):
    """Fused m-step launch over one halo-extended shard.

    Args:
      step_fn: the codegen'd stripe function, as in ``spd_multistep``.
      ext: ``(P, local_h + 2·block_h, W)`` f32 array — the shard's rows
        bracketed by one guard block per side whose inner ``m·halo`` rows
        hold the exchanged neighbor values (outer rows are padding and
        are never read, since ``m·halo <= block_h``).
      scal: (R,) f32 ``Append_Reg`` scalars (SMEM).
      m / block_h / halo: as in ``spd_multistep``; ``halo == 0`` cores
        need no exchanged rows and take the plain launch.
      interpret: run under the Pallas interpreter; ``None`` decides by
        backend (``repro.compat.default_interpret``: CPU only).
      name: the kernel's name in the compiled program and device trace.

    Returns the advanced ``(P, local_h, W)`` shard (guard blocks dropped).
    """
    mh = m * halo
    if mh == 0:
        # Elementwise core: no neighbor rows, no guard blocks expected.
        return spd_multistep(
            step_fn, ext, scal, m=m, block_h=block_h, halo=0,
            interpret=interpret, name=name,
        )
    p, rows, w = ext.shape
    local_h = rows - 2 * block_h
    if local_h < 1 or local_h % block_h:
        raise ValueError(
            f"extended shard of {rows} rows is not local_h + 2*block_h "
            f"with block_h={block_h} dividing local_h"
        )
    if mh > block_h:
        raise ValueError(
            f"m*halo={mh} must be <= block_h={block_h} (halo source)"
        )
    nblk = local_h // block_h

    # Non-periodic maps into the guard-extended array: grid program i
    # owns ext block i+1; its up/down neighbors are ext blocks i / i+2.
    fspec = lambda off: pl.BlockSpec(
        (p, block_h, w), lambda i, off=off: (0, i + 1 + off, 0)
    )
    return pl.pallas_call(
        functools.partial(
            _kernel, step_fn=step_fn, m=m, block_h=block_h, mh=mh
        ),
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            fspec(0), fspec(-1), fspec(1),
        ],
        out_specs=pl.BlockSpec((p, block_h, w), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((p, local_h, w), ext.dtype),
        interpret=resolve_interpret(interpret),
        name=name,
    )(scal, ext, ext, ext)
