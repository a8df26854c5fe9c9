"""Manually pipelined HBM↔VMEM streaming launch for SPD stream kernels.

The BlockSpec launch in :mod:`.spd_stream` describes stripes
*declaratively* and leaves the HBM↔VMEM movement to the Pallas grid
pipeliner. This module is the explicit form (DESIGN.md §12,
docs/pipeline.md §stream): the state stays in ``pl.ANY`` memory (HBM
on real TPUs), a single kernel program walks the row blocks with
``jax.lax.fori_loop``, and every ``(P, block_h + 2·mh, W)`` stripe
is staged through VMEM scratch buffers by explicit async copies
(``pltpu.make_async_copy`` + DMA semaphores) — ``emit_pipeline``-style
manual pipelining, written out so the buffer protocol is inspectable
and the ``double_buffer`` plan knob is *real*:

* ``double_buffer=True`` — ping/pong: two stripe buffers; while block
  ``i`` computes from one, block ``i+1``'s three-piece stripe DMA (up
  halo, center, down halo) already fills the other, and the finished
  block's output drains back to HBM asynchronously. Copy and compute
  overlap; VMEM holds two stripes (the legalizer's
  ``VMEM_DOUBLE_BUFFER`` accounting).
* ``double_buffer=False`` — one stripe buffer, sequential
  start→wait→compute per block. No overlap, but the stripe budget is
  the whole VMEM: this is the *streaming fallback* the legalizer drops
  to when a ping/pong pair of minimal stripes cannot fit.

Both variants stage block rows through VMEM instead of requiring the
grid to fit anywhere in particular, so grids whose full height
overflows VMEM stream at bandwidth. Stripe assembly (up-halo tail,
center block, down-halo head) is row-for-row identical to the
BlockSpec kernel's ``jnp.concatenate``, so streamed and declarative
launches — and the two ``nbuf`` variants — are bitwise identical.

Like the BlockSpec launch, state may carry extra leading dimensions —
``(B, P, H, W)`` batches B independent simulations into one walk
(docs/pipeline.md §serve, DESIGN.md §13): rows stay on axis ``-2``,
every stripe DMA moves all leading axes whole, and the VMEM scratch
stacks scale by B exactly as the legalizer's
``stripe_vmem_bytes(..., b=B)`` prices them. The width axis is opaque
the same way: under a column-sharded mesh (``dx > 1``, DESIGN.md §15)
``W`` arrives guard-column-extended to ``W/dx + 2·guard_cols(m·halo_x)``
and the legalizer prices the stripes at that width
(``stripe_vmem_bytes(..., halo_x=)``); the walk itself is unchanged.
Compiled for the TPU, the launch width must be a multiple of 128 lanes
and ``block_h`` a multiple of 8 rows (a ``ValueError`` says so before
the compiler does).

Halo rows are carried in whole sublane tiles: ``mh`` is ``m·halo``
rounded up to a multiple of 8 rows (capped at ``block_h``,
:func:`repro.core.legalize.halo_rows`), so with an 8-row-aligned
``block_h`` every DMA row offset and the output crop start on a tile
boundary, as the TPU compiler requires. The extra rows only widen the
trapezoid's stale margin; the centre block is computed from the same
values, so results are unchanged. The launch runs under the one VMEM
limit the legalizer prices against
(:data:`repro.core.legalize.VMEM_BYTES`).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import resolve_interpret
from repro.core.legalize import (
    LANES,
    SUBLANE_ROWS,
    VMEM_BYTES,
    halo_rows,
)


def _stream_kernel(scal_ref, state_ref, out_ref, buf, obuf, insem, outsem, *,
                   step_fn: Callable, m: int, block_h: int, mh: int,
                   nblk: int, nbuf: int, src_starts: Callable):
    """One-program streaming walk over ``nblk`` row blocks.

    ``buf``/``obuf`` are ``(nbuf, …)`` VMEM scratch stacks; ``insem`` /
    ``outsem`` the matching DMA semaphore stacks. ``src_starts(i)``
    maps a (traced) block index to the three source-row offsets of its
    stripe pieces in ``state_ref`` — periodic or guard-block-extended.
    Rows are addressed on axis ``-2``; any leading (batch) axes are
    copied whole per stripe piece.
    """
    regs = tuple(scal_ref[i] for i in range(scal_ref.shape[0]))
    # Full-slice prefix covering the leading axes (P, or B and P when
    # batched): state_ref is (…, H, W), buf slots are (…, rows, W).
    lead = (slice(None),) * (len(state_ref.shape) - 2)

    def rows(ref, start, size, slot=None):
        """``ref`` restricted to ``size`` rows from ``start`` on axis -2
        (optionally under a scratch-stack ``slot`` index)."""
        idx = lead + (pl.ds(start, size), slice(None))
        if slot is not None:
            idx = (slot,) + idx
        return ref.at[idx]

    def dma_in(slot, i):
        up, center, down = src_starts(i)
        copies = [
            pltpu.make_async_copy(
                rows(state_ref, center, block_h),
                rows(buf, mh, block_h, slot), insem.at[slot, 0]),
        ]
        if mh:
            copies.append(pltpu.make_async_copy(
                rows(state_ref, up, mh),
                rows(buf, 0, mh, slot), insem.at[slot, 1]))
            copies.append(pltpu.make_async_copy(
                rows(state_ref, down, mh),
                rows(buf, mh + block_h, mh, slot),
                insem.at[slot, 2]))
        return copies

    def dma_out(slot, blk):
        return pltpu.make_async_copy(
            obuf.at[slot], rows(out_ref, blk * block_h, block_h),
            outsem.at[slot])

    if nbuf > 1:
        # Prime the pipeline: block 0's stripe is in flight before the
        # block loop starts.
        for c in dma_in(0, 0):
            c.start()

    def body(i, carry):
        slot = jax.lax.rem(i, nbuf)
        if nbuf > 1:
            # Ping/pong: kick off block i+1's stripe DMA into the other
            # buffer before touching block i, so copy overlaps compute.
            nxt = jax.lax.rem(i + 1, nbuf)

            @pl.when(i + 1 < nblk)
            def _():
                for c in dma_in(nxt, i + 1):
                    c.start()
        else:
            # Single buffer: the one stripe buffer is only free once the
            # previous block fully finished, so start→wait→compute.
            for c in dma_in(slot, i):
                c.start()
        for c in dma_in(slot, i):
            c.wait()
        f_ext = buf[slot]
        for _ in range(m):
            f_ext = step_fn(f_ext, regs)

        # The output staging buffer for this slot still holds block
        # i - nbuf's rows until its drain DMA completes.
        @pl.when(i >= nbuf)
        def _():
            dma_out(slot, i - nbuf).wait()

        obuf[slot] = f_ext[..., mh:mh + block_h, :]
        dma_out(slot, i).start()
        return carry

    jax.lax.fori_loop(0, nblk, body, 0)

    # Drain: the last nbuf output copies are still in flight.
    def drain(i, carry):
        blk = nblk - nbuf + i
        slot = jax.lax.rem(jnp.maximum(blk, 0), nbuf)

        @pl.when(blk >= 0)
        def _():
            dma_out(slot, blk).wait()
        return carry

    jax.lax.fori_loop(0, nbuf, drain, 0)


def _into_dst(scal_ref, state_ref, _dst_ref, *refs, **kw):
    """:func:`_stream_kernel` with a destination input that it never
    reads: the output is that input's buffer (``input_output_aliases``),
    and every row of it is written."""
    _stream_kernel(scal_ref, state_ref, *refs, **kw)


def _streamed_call(step_fn, state, scal, *, m, block_h, mh, nblk, nbuf,
                   out_h, src_starts, interpret, name, dst=None):
    *lead, _, w = state.shape
    interpret = resolve_interpret(interpret)
    if not interpret and (w % LANES or block_h % SUBLANE_ROWS):
        raise ValueError(
            f"the TPU kernel stages whole ({SUBLANE_ROWS}, {LANES}) tiles: "
            f"width {w} must be a multiple of {LANES} and block_h "
            f"{block_h} a multiple of {SUBLANE_ROWS}"
        )
    rows = block_h + 2 * mh
    operands = (scal, state) if dst is None else (scal, state, dst)
    return pl.pallas_call(
        functools.partial(
            _stream_kernel if dst is None else _into_dst, step_fn=step_fn,
            m=m, block_h=block_h, mh=mh, nblk=nblk, nbuf=nbuf,
            src_starts=src_starts,
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            *[pl.BlockSpec(memory_space=pl.ANY)] * (len(operands) - 1),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((*lead, out_h, w), state.dtype),
        input_output_aliases={} if dst is None else {2: 0},
        scratch_shapes=[
            pltpu.VMEM((nbuf, *lead, rows, w), state.dtype),
            pltpu.VMEM((nbuf, *lead, block_h, w), state.dtype),
            pltpu.SemaphoreType.DMA((nbuf, 3 if mh else 1)),
            pltpu.SemaphoreType.DMA((nbuf,)),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_BYTES
        ),
        interpret=interpret,
        name=name,
    )(*operands)


def spd_multistep_streamed(step_fn: Callable, state, scal, *, m: int,
                           block_h: int, halo: int,
                           double_buffer: bool = True,
                           interpret: bool | None = None,
                           name: str | None = None, dst=None):
    """Streamed fused m-step launch, periodic in y.

    Drop-in for :func:`repro.kernels.spd_stream.spd_multistep` — same
    stripe function contract, same validation, bitwise-identical output
    — but with manual double-buffered DMA staging (docs/pipeline.md
    §stream). ``double_buffer`` picks the ping/pong (True) or
    single-buffer streaming-fallback (False) protocol. ``name`` names
    the kernel in the compiled program and the device trace.

    ``dst``, an array of ``state``'s shape and dtype that must not be
    ``state``, is the buffer the output is written into
    (``input_output_aliases``): the kernel never reads it, so a launch
    loop that hands each launch the buffer it last wrote needs no copy
    (:func:`repro.kernels.spd_stream.stream_run_blocked`). Without it
    the output is a new buffer.
    """
    *_, h, _ = state.shape
    if h % block_h:
        raise ValueError(f"H={h} must be divisible by block_h={block_h}")
    if m * halo > block_h:
        raise ValueError(
            f"m*halo={m * halo} must be <= block_h={block_h} (halo source)"
        )
    mh = halo_rows(m * halo, block_h)
    nblk = h // block_h
    nbuf = 2 if double_buffer else 1

    def src_starts(i):
        # Periodic y: block i's up halo is the tail of block i-1 (mod),
        # its down halo the head of block i+1 (mod).
        up = jnp.mod(i - 1, nblk) * block_h + (block_h - mh)
        down = jnp.mod(i + 1, nblk) * block_h
        return up, i * block_h, down

    return _streamed_call(
        step_fn, state, scal, m=m, block_h=block_h, mh=mh, nblk=nblk,
        nbuf=nbuf, out_h=h, src_starts=src_starts, interpret=interpret,
        name=name, dst=dst,
    )


def spd_multistep_halo_streamed(step_fn: Callable, ext, scal, *, m: int,
                                block_h: int, halo: int,
                                double_buffer: bool = True,
                                interpret: bool | None = None,
                                name: str | None = None):
    """Streamed fused m-step launch over one halo-extended shard.

    The streamed twin of
    :func:`repro.kernels.spd_stream.spd_multistep_halo`: ``ext`` is the
    ``(P, local_h + 2·block_h, W)`` guard-block-extended shard and the
    stripe source offsets are non-periodic — block i's center is ext
    block i+1, its halos come from ext blocks i / i+2 (docs/pipeline.md
    §stream).
    """
    if m * halo == 0:
        return spd_multistep_streamed(
            step_fn, ext, scal, m=m, block_h=block_h, halo=0,
            double_buffer=double_buffer, interpret=interpret, name=name,
        )
    *_, rows, _ = ext.shape
    local_h = rows - 2 * block_h
    if local_h < 1 or local_h % block_h:
        raise ValueError(
            f"extended shard of {rows} rows is not local_h + 2*block_h "
            f"with block_h={block_h} dividing local_h"
        )
    if m * halo > block_h:
        raise ValueError(
            f"m*halo={m * halo} must be <= block_h={block_h} (halo source)"
        )
    mh = halo_rows(m * halo, block_h)
    nblk = local_h // block_h

    def src_starts(i):
        center = (i + 1) * block_h
        return center - mh, center, (i + 2) * block_h

    return _streamed_call(
        step_fn, ext, scal, m=m, block_h=block_h, mh=mh, nblk=nblk,
        nbuf=2 if double_buffer else 1, out_h=local_h,
        src_starts=src_starts, interpret=interpret, name=name,
    )
