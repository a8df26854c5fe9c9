"""The streamed fused m-step launch of SPD stream kernels.

The grid state is one stacked ``(P, H, W)`` f32 array, one channel per
main-stream port of the SPD core. Each row block is advanced ``m`` time
steps per HBM round trip in a ``(P, block_h + 2·mh, W)`` stripe: the
block plus ``mh`` halo rows per side from its neighbour blocks
(periodic in y). After each application of the core ``halo`` edge rows
per side go stale and are never read again (the temporal-blocking
trapezoid); periodic x is handled inside the stripe function with
in-register shifts. The *stripe function* ``step_fn((P, rows, W),
regs)`` is produced by :class:`repro.core.codegen.StreamKernel` from
the core's data-flow graph; this module owns the ``pallas_call``.

The state stays in ``pl.ANY`` memory (HBM on real TPUs), a single
kernel program walks the row blocks with ``jax.lax.fori_loop``, and
every stripe is staged through VMEM scratch buffers by explicit async
copies (``pltpu.make_async_copy`` + DMA semaphores; DESIGN.md §12,
docs/pipeline.md §stream), so the ``double_buffer`` plan knob is
*real*:

* ``double_buffer=True`` — ping/pong: two stripe buffers; while block
  ``i`` computes from one, block ``i+1``'s stripe DMAs (up halo,
  center, down halo) already fill the other, and the finished block's
  output drains back to HBM asynchronously. Copy and compute overlap;
  VMEM holds two stripes (the legalizer's ``VMEM_DOUBLE_BUFFER``
  accounting).
* ``double_buffer=False`` — one stripe buffer, sequential
  start→wait→compute per block. No overlap, but the stripe budget is
  the whole VMEM: this is the *streaming fallback* the legalizer drops
  to when a ping/pong pair of minimal stripes cannot fit.

Both protocols assemble every stripe from the same rows, so they are
bitwise identical. State may carry extra leading dimensions —
``(B, P, H, W)`` batches B independent simulations into one walk
(docs/pipeline.md §serve, DESIGN.md §13): rows stay on axis ``-2``,
every stripe DMA moves all leading axes whole, and the VMEM scratch
stacks scale by B exactly as the legalizer's
``stripe_vmem_bytes(..., b=B)`` prices them. Each stripe is gathered
from a list of pieces (:class:`Piece`), each one DMA from one source
array into a window of the stripe buffer: on one chip the three pieces
above, from the state itself. On a device mesh (DESIGN.md §8, §15)
:func:`spd_multistep` also gathers the exchanged halo rows and, under a
sharded column axis, a stripe ``W/dx + 2·guard_cols(m·halo_x)`` lanes
wide from the exchanged guard columns, where they lie, and writes back
only the shard's own ``W/dx`` columns; the legalizer prices the
stripes at that width (``stripe_vmem_bytes(..., halo_x=)``). Compiled
for the TPU, every piece's width and the stripe's must be multiples of
128 lanes and ``block_h`` a multiple of 8 rows (a ``ValueError`` says
so before the compiler does).

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
from typing import Callable, NamedTuple

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


class Piece(NamedTuple):
    """One DMA of a stripe: ``size`` rows of source ref ``src`` from row
    ``start(i)`` into the stripe buffer at row ``row`` and lane ``col``.

    ``start`` and ``when`` take the (traced) block index; ``when`` says
    whether block ``i`` reads the piece at all (``None``: every block
    does). A source as wide as the stripe fills all its lanes.
    """

    src: int
    start: Callable
    size: int
    row: int
    col: int = 0
    when: Callable | None = None


def _stream_kernel(scal_ref, *refs, step_fn: Callable, m: int, block_h: int,
                   mh: int, nblk: int, nbuf: int, pieces: tuple,
                   nsrc: int, aliased: bool, interpret: bool):
    """One-program streaming walk over ``nblk`` row blocks.

    ``refs`` are the ``nsrc`` source refs, the aliased destination when
    ``aliased`` (never read: the output is its buffer), the output ref,
    the ``(nbuf, …)`` VMEM stripe and output stacks and their DMA
    semaphore stacks. Block ``i``'s stripe is gathered from the sources
    by the :class:`Piece` list ``pieces``, one semaphore a piece. Rows
    are addressed on axis ``-2``; any leading (batch) axes are copied
    whole per stripe piece. The output holds the centre rows of each
    stripe's first ``out_ref.shape[-1]`` lanes.
    """
    srcs = refs[:nsrc]
    out_ref, buf, obuf, insem, outsem = refs[nsrc + aliased:]
    regs = tuple(scal_ref[i] for i in range(scal_ref.shape[0]))
    # Full-slice prefix covering the leading axes (P, or B and P when
    # batched): out_ref is (…, H, W), buf slots are (…, rows, W).
    lead = (slice(None),) * (len(out_ref.shape) - 2)
    width = buf.shape[-1]
    out_w = out_ref.shape[-1]

    def rows(ref, start, size, slot=None, col=0, cols=None):
        """``ref`` restricted to ``size`` rows from ``start`` on axis -2
        (and to ``cols`` lanes from ``col``; optionally under a
        scratch-stack ``slot`` index)."""
        lanes = slice(None) if cols is None else pl.ds(col, cols)
        idx = lead + (pl.ds(start, size), lanes)
        if slot is not None:
            idx = (slot,) + idx
        return ref.at[idx]

    def dma_in(slot, i):
        """Block ``i``'s stripe DMAs as ``(when, copy)`` pairs; ``when``
        is ``True`` for a piece every block reads, and pieces that a
        block known at trace time does not read are left out."""
        copies = []
        for k, pc in enumerate(pieces):
            when = True if pc.when is None else pc.when(i)
            if when is False:
                continue
            src = srcs[pc.src]
            w = src.shape[-1]
            copies.append((when, pltpu.make_async_copy(
                rows(src, pc.start(i), pc.size),
                rows(buf, pc.row, pc.size, slot, pc.col,
                     None if w == width else w),
                insem.at[slot, k])))
        return copies

    def each(copies, op):
        for when, c in copies:
            if when is True:
                op(c)
            else:
                pl.when(when)(functools.partial(op, c))

    def start(c):
        c.start()

    def wait(c):
        c.wait()

    def dma_out(slot, blk):
        return pltpu.make_async_copy(
            obuf.at[slot], rows(out_ref, blk * block_h, block_h),
            outsem.at[slot])

    if nbuf > 1:
        # Prime the pipeline: block 0's stripe is in flight before the
        # block loop starts.
        each(dma_in(0, 0), start)

    def body(i, carry):
        slot = jax.lax.rem(i, nbuf)
        if nbuf > 1:
            # Ping/pong: kick off block i+1's stripe DMA into the other
            # buffer before touching block i, so copy overlaps compute.
            nxt = jax.lax.rem(i + 1, nbuf)

            @pl.when(i + 1 < nblk)
            def _():
                each(dma_in(nxt, i + 1), start)
        else:
            # Single buffer: the one stripe buffer is only free once the
            # previous block fully finished, so start→wait→compute.
            each(dma_in(slot, i), start)
        each(dma_in(slot, i), wait)
        f_ext = buf[slot]
        for _ in range(m):
            f_ext = step_fn(f_ext, regs)

        # The output staging buffer for this slot still holds block
        # i - nbuf's rows until its drain DMA completes.
        @pl.when(i >= nbuf)
        def _():
            dma_out(slot, i - nbuf).wait()

        centre = f_ext[..., mh:mh + block_h, :]
        obuf[slot] = centre if out_w == width else centre[..., :out_w]
        dma_out(slot, i).start()
        return carry

    # Interpreted, the block loop is an XLA while loop on the CPU, and
    # XLA removes one of a single trip: the stripe arithmetic is then
    # compiled among the ops around it, whose fusions contract its
    # mul-adds otherwise than a loop body's do, so a one-block launch
    # rounds differently from a many-block one. A bound XLA cannot
    # fold keeps every launch's arithmetic in a loop body. Compiled for
    # the TPU, Mosaic lowers the body once whatever the bound.
    trips = jax.lax.optimization_barrier(nblk) if interpret else nblk
    jax.lax.fori_loop(0, trips, body, 0)

    # Drain: the last nbuf output copies are still in flight.
    def drain(i, carry):
        blk = nblk - nbuf + i
        slot = jax.lax.rem(jnp.maximum(blk, 0), nbuf)

        @pl.when(blk >= 0)
        def _():
            dma_out(slot, blk).wait()
        return carry

    jax.lax.fori_loop(0, nbuf, drain, 0)


def _streamed_call(step_fn, srcs, scal, *, m, block_h, mh, nblk, nbuf,
                   out_h, pieces, interpret, name, dst=None):
    """Launch :func:`_stream_kernel` over the source arrays ``srcs``.

    The stripe is as wide as the pieces that fill it (``srcs[0]`` plus
    any pieces at lanes past it); the output is ``srcs[0]``'s leading
    axes by ``out_h`` rows by ``srcs[0]``'s width, written into ``dst``
    when one is given.
    """
    state = srcs[0]
    *lead, _, w = state.shape
    width = max(pc.col + srcs[pc.src].shape[-1] for pc in pieces)
    interpret = resolve_interpret(interpret)
    widths = sorted({width, *(src.shape[-1] for src in srcs)})
    if not interpret and (any(v % LANES for v in widths)
                          or block_h % SUBLANE_ROWS):
        raise ValueError(
            f"the TPU kernel stages whole ({SUBLANE_ROWS}, {LANES}) tiles: "
            f"widths {widths} must be multiples of {LANES} and "
            f"block_h {block_h} a multiple of {SUBLANE_ROWS}"
        )
    rows = block_h + 2 * mh
    operands = (scal, *srcs) if dst is None else (scal, *srcs, dst)
    return pl.pallas_call(
        functools.partial(
            _stream_kernel, step_fn=step_fn, m=m, block_h=block_h, mh=mh,
            nblk=nblk, nbuf=nbuf, pieces=tuple(pieces), nsrc=len(srcs),
            aliased=dst is not None, interpret=bool(interpret),
        ),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            *[pl.BlockSpec(memory_space=pl.ANY)] * (len(operands) - 1),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((*lead, out_h, w), state.dtype),
        input_output_aliases={} if dst is None else {len(srcs) + 1: 0},
        scratch_shapes=[
            pltpu.VMEM((nbuf, *lead, rows, width), state.dtype),
            pltpu.VMEM((nbuf, *lead, block_h, w), state.dtype),
            pltpu.SemaphoreType.DMA((nbuf, len(pieces))),
            pltpu.SemaphoreType.DMA((nbuf,)),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_BYTES
        ),
        interpret=interpret,
        name=name,
    )(*operands)


def _periodic_pieces(src, *, block_h, mh, nblk, col=0, edges=False):
    """Centre, up-halo and down-halo pieces of source ``src``, periodic
    in y: block i's up halo is the tail of block i-1 (mod), its down
    halo the head of block i+1 (mod). With ``edges``, the first block
    reads no up halo and the last no down halo from ``src``."""
    pieces = [Piece(src, lambda i: i * block_h, block_h, mh, col)]
    if mh:
        pieces += [
            Piece(src, lambda i: jnp.mod(i - 1, nblk) * block_h
                  + (block_h - mh), mh, 0, col,
                  (lambda i: i > 0) if edges else None),
            Piece(src, lambda i: jnp.mod(i + 1, nblk) * block_h, mh,
                  mh + block_h, col,
                  (lambda i: i < nblk - 1) if edges else None),
        ]
    return pieces


def spd_multistep(step_fn: Callable, state, scal, *, m: int, block_h: int,
                  halo: int, guards=None, edges=None,
                  double_buffer: bool = True, interpret: bool | None = None,
                  name: str | None = None, dst=None):
    """Streamed fused m-step launch: advance ``state`` ``m`` steps, its
    stripes gathered from the state and any exchanged pieces in place.

    ``state`` is the ``(…, H, W)`` grid (on a mesh, the shard's own
    rows), read as it is, and ``step_fn`` the stripe function
    (docs/pipeline.md §stream). Without ``guards`` and ``edges`` the
    launch is periodic in y and x within ``state``, as on one chip.
    ``double_buffer`` picks the ping/pong (True) or single-buffer
    streaming-fallback (False) protocol; ``name`` names the kernel in
    the compiled program and the device trace.

    On a device mesh (docs/pipeline.md §distribute, DESIGN.md §8, §15)
    the stripe is laid out ``[shard W | guards]`` and advanced by the
    same *periodic* stripe body: ``guards``, for a sharded column axis,
    is a ``(…, H, G)`` array whose first columns are the right
    neighbour's first columns and whose last columns are the left
    neighbour's last ones, so shard column 0 wraps onto its true left
    neighbour and column ``W - 1`` runs into its true right one; the
    two guards meet in the middle of ``G``, where the stale columns
    stay. ``edges``, for a sharded row axis, is the pair of
    ``(…, mh, W + G)`` halo-row pieces that block 0 reads above it and
    the last block reads below it, in the stripe's layout (``mh`` =
    :func:`~repro.core.legalize.halo_rows`). The output is
    ``state``'s own ``(…, H, W)``.

    ``dst``, an array of ``state``'s shape and dtype that must not be
    ``state``, is the buffer the output is written into
    (``input_output_aliases``): the kernel never reads it, so a launch
    loop that hands each launch the buffer it last wrote needs no copy
    (:func:`repro.kernels.spd_stream.stream_run_blocked`). Without it
    the output is a new buffer.
    """
    *_, h, w = state.shape
    if h % block_h:
        raise ValueError(f"H={h} must be divisible by block_h={block_h}")
    if m * halo > block_h:
        raise ValueError(
            f"m*halo={m * halo} must be <= block_h={block_h} (halo source)"
        )
    mh = halo_rows(m * halo, block_h)
    nblk = h // block_h
    edged = edges is not None and mh > 0
    srcs = [state] if guards is None else [state, guards]
    pieces = [
        pc for k in range(len(srcs))
        for pc in _periodic_pieces(k, block_h=block_h, mh=mh, nblk=nblk,
                                   col=w if k else 0, edges=edged)
    ]
    if edged:
        # Block 0's up halo and the last block's down halo are the
        # exchanged rows; every other block's stay in the shard.
        pieces += [
            Piece(len(srcs), lambda i: 0, mh, 0, when=lambda i: i == 0),
            Piece(len(srcs) + 1, lambda i: 0, mh, mh + block_h,
                  when=lambda i: i == nblk - 1),
        ]
        srcs += list(edges)
    return _streamed_call(
        step_fn, tuple(srcs), scal, m=m, block_h=block_h, mh=mh,
        nblk=nblk, nbuf=2 if double_buffer else 1, out_h=h, pieces=pieces,
        interpret=interpret, name=name, dst=dst,
    )
