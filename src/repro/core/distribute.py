"""Multi-device spatial parallelism for stream kernels: the device mesh.

The paper's spatial parallelism duplicates pipelines until one chip's
resources (or its memory link) give out. This module is the
production-scale continuation (DESIGN.md §8, §15, docs/pipeline.md
§distribute): duplicate across *chips*. A codegen'd
:class:`~repro.core.codegen.StreamKernel`'s ``(P, H, W)`` grid is
decomposed across a 2-D device mesh ``(dy, dx)``: rows split into ``dy``
equal shards on the row axis and columns into ``dx`` equal shards on
the column axis, ``d = dy·dx`` devices in total. Every device runs the
same temporal-blocking Pallas launch on its own ``(H/dy, W/dx)`` shard
under ``shard_map``, and before each fused m-step launch the boundary
data is exchanged with the mesh neighbors via ``lax.ppermute`` (both
axes are rings, which is what makes the global periodic boundary come
out right: shard 0's up-neighbor is shard dy-1, and column shard 0's
left-neighbor is column shard dx-1).

Halo-exchange protocol, per fused launch, for every mesh shape:

1. each shard sends its bottom ``m·halo`` rows to the next row shard and
   its top ``m·halo`` rows to the previous one, and — when ``dx > 1`` —
   its rightmost ``m·halo_x`` columns to the next column shard and its
   leftmost to the previous one (``ppermute`` collectives issued
   together, all depending only on the current shard — on TPU these ride
   the ICI links the DSE model's ``t_collective`` term prices, row and
   column volumes separately). A mesh with one row shard (``dy == 1``)
   sends no rows, since its halo rows wrap within the shard, and one
   with one column shard (``dx == 1``) sends no columns, since its
   stripes are full rows that wrap in x in registers, as on one chip;
2. nothing shard-sized is assembled. The two received column pieces are
   padded into one guard array of ``2·guard_cols(m·halo_x)`` columns,
   ``[right | zeros | left]``; a small second hop column-permutes the
   edges of the received row pieces to fetch the four
   ``(m·halo, m·halo_x)`` corner blocks from the diagonal neighbors,
   which join the row pieces in the same layout; and
   :func:`repro.kernels.spd_stream.spd_multistep` DMAs each stripe from
   the shard, the guard array and the row pieces where they lie, into a
   VMEM stripe laid out ``[shard | right guard | left guard]``. The
   kernel's periodic stripe body then wraps each shard edge onto the
   guard columns that hold its true neighbors; the stale columns stay
   where the two guards meet, and the launch writes back only the
   shard's own columns, into one of two buffers the loop ping-pongs as
   on one chip.

Because the launches reuse the single-device kernel arithmetic and the
exchange delivers exactly the rows and columns the periodic index maps
and periodic in-register x shifts would have read, the sharded run is
**bit-identical** to the single-device kernel for any legal mesh — the
correctness contract asserted in ``tests/test_distribute.py``,
``tests/test_mesh.py`` and ``tests/test_mesh_launch.py``.

Plans come from the shared legalizer (docs/pipeline.md §legalize) with
per-shard accounting: ``blocking_plan(..., d=d, dx=dx)`` requires
``dy | H`` and ``dx | W`` and tiles the *shard* geometry — the
per-stripe width term drops to ``W/dx`` (plus the guard columns), which
is what lets wide grids legalize larger ``block_h``/``m`` under column
sharding. Off-TPU, the mesh devices are available under
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` with the kernels
in interpret mode — how CI runs the distribution and mesh suites.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.parallel.sharding import stream_grid_pspec

from . import tracing
from .legalize import (
    guard_cols,
    halo_rows,
    launch_dma_bytes,
    launch_flops,
    mesh_shape,
    resolve_run_plan,
    shard_height,
    shard_width,
    stripe_cols,
)

#: Name of the row device axis.
DEVICE_AXIS = "d"

#: Name of the column device axis of the 2-D mesh (DESIGN.md §15).
DEVICE_AXIS_X = "dx"

__all__ = [
    "DEVICE_AXIS",
    "DEVICE_AXIS_X",
    "ShardedStreamKernel",
    "device_axis_values",
    "device_mesh",
    "mesh_axis_values",
]


def device_axis_values(max_d: int) -> tuple[int, ...]:
    """Powers of two up to ``max_d`` — the default sweep of the d axis."""
    if max_d < 1:
        raise ValueError(f"max_d must be >= 1, got {max_d}")
    vals = []
    v = 1
    while v <= max_d:
        vals.append(v)
        v *= 2
    return tuple(vals)


def mesh_axis_values(max_d: int) -> tuple[tuple[int, int], ...]:
    """Every power-of-two mesh shape ``(dy, dx)`` with ``dy·dx <= max_d``.

    The mesh-shape enumeration of the device count's factorizations
    (DESIGN.md §15): the searched lattice of spatial decompositions, the
    2-D generalization of :func:`device_axis_values`. ``(d, 1)`` shapes
    shard rows only.
    """
    return tuple(
        (dy, dx)
        for dy in device_axis_values(max_d)
        for dx in device_axis_values(max_d)
        if dy * dx <= max_d
    )


def device_mesh(dy: int, dx: int,
                devices: Sequence | None = None) -> Mesh:
    """A two-axis ``(dy, dx)`` device mesh (DESIGN.md §15).

    Rows shard over :data:`DEVICE_AXIS`, columns over
    :data:`DEVICE_AXIS_X`; both axes are rings for ``lax.ppermute``, so
    the grid's periodic boundary closes across chips in y *and* x.
    Raises when the platform has fewer than ``dy·dx`` devices.
    """
    if dy < 1 or dx < 1:
        raise ValueError(f"mesh axes must be >= 1, got (dy={dy}, dx={dx})")
    d = dy * dx
    devs = list(devices) if devices is not None else list(jax.devices())
    if len(devs) < d:
        raise ValueError(
            f"need {d} devices for a ({dy}, {dx}) mesh, have {len(devs)} "
            f"(off-TPU: XLA_FLAGS=--xla_force_host_platform_device_count={d})"
        )
    return Mesh(
        np.array(devs[:d]).reshape(dy, dx), (DEVICE_AXIS, DEVICE_AXIS_X)
    )


class ShardedStreamKernel:
    """A codegen'd stream kernel decomposed across a ``(dy, dx)`` mesh.

    Obtained via :meth:`repro.core.codegen.StreamKernel.sharded`. The
    public surface mirrors the single-device kernel —
    :meth:`run_blocked` / :meth:`run_for_point` — so the explorer times
    single- and multi-device frontier points through one code path
    (docs/pipeline.md §execute); ``d == 1`` simply delegates to the
    wrapped kernel (no mesh, no exchange). ``d`` is the *total* device
    count and ``dx`` its column factor (``dy = d / dx``, DESIGN.md §15);
    every mesh with ``d > 1`` runs the same launch loop.
    """

    def __init__(self, kernel, d: int, devices: Sequence | None = None,
                 dx: int = 1):
        self.kernel = kernel
        self.d = int(d)
        self.dy, self.dx = mesh_shape(self.d, dx)
        self.halo = kernel.halo
        self.halo_x = int(getattr(kernel, "halo_x", kernel.halo))
        self.mesh = (None if self.d == 1
                     else device_mesh(self.dy, self.dx, devices))
        self._jitted: dict = {}

    # ---- the shard-mapped launch loop --------------------------------------

    def _fn(self, steps: int, m: int, block_h: int, double_buffer: bool,
            interpret: bool):
        """Build (and cache) the jitted shard_map'd run for one plan."""
        key = (steps, m, block_h, double_buffer, interpret)
        cached = self._jitted.get(key)
        if cached is not None:
            return cached
        spec = stream_grid_pspec(DEVICE_AXIS, axis_x=DEVICE_AXIS_X)
        fn = jax.jit(jax.shard_map(
            self._local_run(steps, m, block_h, double_buffer, interpret),
            mesh=self.mesh, in_specs=(spec, P(None)), out_specs=spec,
            check_vma=False,
        ))
        self._jitted[key] = fn
        return fn

    def _local_run(self, steps, m, block_h, double_buffer, interpret):
        """The per-shard loop (DESIGN.md §8, §15): each launch gathers
        its stripes from the shard and the exchanged halo pieces in
        place and writes only the shard's own columns, and the launches
        ping-pong between two buffers the kernel writes, as on one chip
        (:func:`~repro.kernels.spd_stream.stream_run_blocked`)."""
        from repro.kernels.spd_stream import spd_multistep, stream_run_blocked

        dy, dx, halo = self.dy, self.dx, self.halo
        step_fn = self.kernel._step_fn
        name = self.kernel.name
        mh = m * halo
        # A full-width shard (dx == 1) wraps x in its registers, as on
        # one chip: it takes no column guards.
        mhx = m * self.halo_x if dx > 1 else 0
        # Guard columns per side: the mhx exchanged columns plus zero
        # padding up to whole half-lane tiles, so the two guards make
        # whole 128-lane tiles. The zero columns only widen the stale
        # margin where the right guard meets the left one.
        gx = guard_cols(mhx)
        # The halo rows the stripe carries (whole 8-row tiles): the
        # exchanged mh rows next to the shard, zero rows beyond them.
        rows = halo_rows(mh, block_h)
        # Row-ring permutes run over DEVICE_AXIS (per mesh column);
        # column-ring permutes over DEVICE_AXIS_X (per mesh row). A
        # size-1 row axis needs no row exchange: the launch wraps its
        # halo rows within the shard, as on one chip.
        perm_dn = [(i, (i + 1) % dy) for i in range(dy)]
        perm_up = [(i, (i - 1) % dy) for i in range(dy)]
        perm_r = [(j, (j + 1) % dx) for j in range(dx)]  # right cols -> next
        perm_l = [(j, (j - 1) % dx) for j in range(dx)]  # left cols -> prev

        def guard(left, right):
            """``[right | zeros | left]``: the stripe's guard columns,
            right after the shard's own (the stripe body wraps in x)."""
            with jax.named_scope(tracing.ASSEMBLE):
                pad = jnp.zeros(left.shape[:-1] + (2 * (gx - mhx),),
                                left.dtype)
                return jnp.concatenate([right, pad, left], axis=-1)

        def exchange_x(piece):
            """The column guard of ``piece``'s rows via the dx ring."""
            w = piece.shape[-1]

            def ship(cols, perm):
                # Sent flat: a (…, rows, mhx) operand takes a column-major
                # layout for the collective, which XLA would otherwise
                # push back onto the whole shard in the launch loop (a
                # shard-sized copy a launch); flat, it stays row-major.
                shape = cols.shape
                flat = cols.reshape(shape[:-2] + (shape[-2] * shape[-1],))
                return jax.lax.ppermute(
                    flat, DEVICE_AXIS_X, perm).reshape(shape)

            with jax.named_scope(tracing.EXCHANGE):
                left = ship(piece[..., w - mhx:], perm_r)
                right = ship(piece[..., :mhx], perm_l)
            return guard(left, right)

        def edge(got, corner, top):
            """One exchanged halo-row piece in the stripe's layout: the
            received rows with their corner guard, padded with zero
            rows away from the shard to whole tiles."""
            with jax.named_scope(tracing.ASSEMBLE):
                piece = got if corner is None else jnp.concatenate(
                    [got, corner], axis=-1)
                pad = jnp.zeros(piece.shape[:-2] + (rows - mh,)
                                + piece.shape[-1:], piece.dtype)
                return jnp.concatenate(
                    [pad, piece] if top else [piece, pad], axis=-2)

        def launch(cur, scal, *, m, block_h, interpret, dst=None):
            lh = cur.shape[-2]
            # All first-hop collectives depend only on `cur` and are
            # issued together: the column exchange and, on a sharded
            # row axis, the row exchange (guard rows at local width).
            guards = exchange_x(cur) if mhx else None
            edges = None
            if mh and dy > 1:
                with jax.named_scope(tracing.EXCHANGE):
                    up = jax.lax.ppermute(
                        cur[..., lh - mh:, :], DEVICE_AXIS, perm_dn
                    )
                    dn = jax.lax.ppermute(
                        cur[..., :mh, :], DEVICE_AXIS, perm_up
                    )
                # Corner second hop (DESIGN.md §15): column-permute the
                # received row guards' edges, which fetches the diagonal
                # neighbors' (mh, mhx) corner blocks — the same values a
                # width-extended row exchange would have shipped, but
                # only (mh × mhx) elements per link.
                edges = (
                    edge(up, exchange_x(up) if mhx else None, top=True),
                    edge(dn, exchange_x(dn) if mhx else None, top=False),
                )
            with jax.named_scope(tracing.LAUNCH):
                return spd_multistep(
                    step_fn, cur, scal, m=m, block_h=block_h, halo=halo,
                    guards=guards, edges=edges,
                    double_buffer=double_buffer, interpret=interpret,
                    name=name, dst=dst,
                )

        # Named for the compiled module (jit_spd_run_sharded).
        def spd_run_sharded(local, scal):
            return stream_run_blocked(
                launch, local, scal, steps=steps, m=m, block_h=block_h,
                interpret=interpret,
            )

        return spd_run_sharded

    # ---- launches (mirroring StreamKernel) ---------------------------------

    def run_blocked(self, state, regs: Sequence = (), *, steps: int,
                    m: int, block_h: int, double_buffer: bool = True,
                    interpret: bool | None = None):
        """Advance ``steps`` time steps, halo-exchanging every m steps.

        ``double_buffer`` selects the per-shard streamed launch's buffer
        protocol (docs/pipeline.md §stream). ``state`` is only read.
        """
        if self.d == 1:
            return self.kernel.run_blocked(
                state, regs, steps=steps, m=m, block_h=block_h,
                double_buffer=double_buffer, interpret=interpret,
            )
        p, h, w = state.shape
        local_h = shard_height(h, self.dy)
        local_w = shard_width(w, self.dx)
        if local_h % block_h:
            raise ValueError(
                f"shard height {local_h} (h={h} over d={self.dy}) must be "
                f"divisible by block_h={block_h}"
            )
        if m * self.halo > block_h:
            raise ValueError(
                f"m*halo={m * self.halo} must be <= block_h={block_h} "
                "(halo source)"
            )
        if self.dx > 1 and m * self.halo_x > local_w:
            raise ValueError(
                f"m*halo_x={m * self.halo_x} must be <= the shard width "
                f"{local_w} (w={w} over dx={self.dx}; the column guard is "
                "sourced from one neighbor shard per side)"
            )
        if steps % m:
            raise ValueError(f"steps={steps} must be a multiple of m={m}")
        with jax.profiler.TraceAnnotation(tracing.RUN):
            fn = self._fn(steps, m, block_h, bool(double_buffer),
                          interpret)
            out = fn(state, self.kernel._scal(regs))
            launches = steps // m
            # Every shard's launch gathers and computes its stripes at
            # the guarded width, and the loop ping-pongs two
            # kernel-written buffers, as on one chip.
            width = stripe_cols(local_w, m, self.halo_x if self.dx > 1 else 0)
            shard_bytes = launch_dma_bytes(
                local_h, width, p, block_h=block_h, m=m, halo=self.halo,
                itemsize=state.dtype.itemsize)
            shard_flops = launch_flops(
                local_h, width, 1, block_h=block_h, m=m, halo=self.halo,
                flops=self.kernel.compiled.flops)
            tracing.count(launches=launches, steps=steps,
                          dma_bytes=launches * self.d * shard_bytes,
                          kernel_flops=launches * self.d * shard_flops,
                          aliased_launches=max(0, launches - 2))
        return out

    def run_for_point(self, state, regs: Sequence = (), *, point,
                      steps: int | None = None,
                      interpret: bool | None = None):
        """Advance the grid using a DSE design point's (block_h, m).

        The point is legalized *per shard* with the shared
        :func:`repro.core.legalize.resolve_run_plan` (``d``/``dx`` =
        this kernel's mesh shape, DESIGN.md §15). Returns
        ``(result, (block_h, m, double_buffer))``.
        """
        p, h, w = state.shape
        block_h, m, nsteps, double_buffer = resolve_run_plan(
            h, point, steps, halo=self.halo, width=w, words=p, d=self.d,
            dx=self.dx, halo_x=self.halo_x, interpret=interpret,
        )
        out = self.run_blocked(
            state, regs, steps=nsteps, m=m, block_h=block_h,
            double_buffer=double_buffer, interpret=interpret,
        )
        return out, (block_h, m, double_buffer)
