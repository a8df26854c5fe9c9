"""SPD core → Pallas TPU stream-kernel codegen.

``repro.core.compiler`` lowers an SPD core to a per-point JAX dataflow
function; this module lowers the same :class:`CompiledCore` one level
further, into an *executable temporal-blocking Pallas kernel* with the
structure of the hand-written ``repro.kernels.lbm_stream`` — the missing
bottom of the paper's flow, where the generated datapath actually runs
(docs/pipeline.md §codegen, DESIGN.md §7). Three pieces:

1. **Stencil-offset inference** (:func:`stencil_summary`) — an abstract
   interpretation of the core's DFG that tracks, for every main output
   port, the set of (dy, dx) grid offsets of the main inputs it reads.
   ``Stencil2D`` nodes add their offset; EQU/elementwise nodes union
   their operands; sub-core calls compose offsets additively along the
   dataflow path. The per-step y-halo is ``max |dy|`` over all reads
   (docs/pipeline.md §codegen).
2. **Stripe lowering** (:meth:`StreamKernel._step_fn`) — re-evaluates the
   DFG over ``(rows, W)`` row stripes instead of whole grids: y stencil
   reads become non-periodic in-stripe shifts (the halo rows supply the
   neighbor values; ``halo`` edge rows go stale per application — the
   temporal-blocking trapezoid), x stencil reads become periodic
   in-register shifts (the full row width is VMEM-resident). Under a
   column-sharded 2-D device mesh the same periodic body runs over a
   stripe laid out ``[shard | right guard | left guard]``: the x wrap
   lands each shard edge on the guard columns that came off-device,
   and the stale columns stay where the two guards meet (DESIGN.md
   §15).
3. **Launch + legalization** — the stripe function is handed to
   :func:`repro.kernels.spd_stream.spd_multistep` for the
   ``(block_h + 2·m·halo)``-row Pallas launch; explorer-chosen
   (block_h, m) plans are legalized by the shared
   :mod:`repro.core.legalize` (docs/pipeline.md §legalize) with this
   kernel's inferred halo.

Correctness contract (asserted in ``tests/test_codegen.py``): in
interpret mode the kernel bit-matches m repeated applications of the
compiler's reference JAX function (:meth:`StreamKernel.reference`), for
any legal (m, block_h) decomposition.

Supported cores: no branch streams, ``|main_in| == |main_out|`` (outputs
feed inputs across fused steps, the same chaining contract as
``temporal_cascade``), stream state expressed as ``Stencil2D`` nodes with
``mode=wrap`` (periodic grids; 1-D ``Delay``/``StreamForward``/
``StreamBackward`` state has no 2-D stripe equivalent and is rejected).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp


from . import tracing
from .compiler import CompiledCore, eval_expr
from .dfg import SPDError
from .legalize import launch_dma_bytes, launch_flops, resolve_run_plan
from .library import LibraryModule

#: 1-D stream-state modules with no 2-D stripe lowering.
_STREAM_1D = ("Delay", "StreamForward", "StreamBackward")


class CodegenError(SPDError):
    """The core cannot be lowered to a stream kernel (with the reason)."""


# --------------------------------------------------------------------------
# Stencil-offset inference
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class StencilSummary:
    """What a core's outputs read from the streamed grid.

    ``port_reads`` maps each output port to the set of
    ``(input_port, dy, dx)`` triples it (transitively) consumes:
    "this output reads that input at grid offset (y−dy, x−dx)".
    ``offsets`` is the union of all (dy, dx); ``halo_y``/``halo_x`` are
    the per-step stencil reach (``max |dy|`` / ``max |dx|``);
    ``modes`` collects the boundary modes of every Stencil2D crossed.
    """

    port_reads: Mapping[str, frozenset]
    offsets: frozenset
    halo_y: int
    halo_x: int
    modes: frozenset

    def halo(self) -> int:
        """Rows of halo one application of the core consumes per side."""
        return self.halo_y


def _normalize_incoming(incoming, n: int) -> tuple:
    """Canonical per-input ``(dy, dx)`` extents tuple for memo keys.

    ``None`` (the single-core case: inputs arrive straight off the grid)
    normalizes to all-zero extents — the same key as an explicit
    all-zero request, so both spellings share one memo entry.
    """
    if incoming is None:
        return ((0, 0),) * n
    ext = tuple((int(dy), int(dx)) for dy, dx in incoming)
    if len(ext) != n:
        raise CodegenError(
            f"incoming extents cover {len(ext)} inputs, core has {n}"
        )
    return ext


def _core_reads(compiled: CompiledCore, incoming=None) -> dict[str, set]:
    """Per-output ``(input_index, dy, dx)`` read sets of one core.

    Abstract interpretation over the toposorted DFG: every variable
    carries the set of (core-input index, dy, dx) it transitively reads.
    Indices are positions in ``core.input_ports()`` (main + brch + regs);
    register/param inputs are scalars and carry the empty set.

    ``incoming`` is the per-main-input ``(dy, dx)`` extent the producer
    edge applies before this core sees the stream (docs/pipeline.md
    §program): input ``i`` seeds at ``(i, dy_i, dx_i)`` instead of
    ``(i, 0, 0)``, so a program stage's summary composes its upstream
    edge reach.

    Memoized per (compiled core, incoming extents): sub-cores are shared
    across call sites (and cascades repeat the same PE m times), so
    without the cache the walk would re-derive every callee's read set
    at every call site — and fusion clusters reuse one sub-core at
    *different* incoming extents, so the memo must key on the pair, not
    the core alone, or the second use would read the first use's stale
    offsets.
    """
    core = compiled.core
    key = _normalize_incoming(
        incoming,
        len(core.main_input_ports()) + len(core.brch_input_ports()),
    )
    memo = getattr(compiled, "_stencil_reads_memo", None)
    if memo is None:
        memo = {}
        compiled._stencil_reads_memo = memo
    cached = memo.get(key)
    if cached is not None:
        return cached
    alias = core.alias_map()
    main = set(core.main_input_ports()) | set(core.brch_input_ports())
    env: dict[str, set] = {}
    stream_idx = 0
    for i, p in enumerate(core.input_ports()):
        if p in main:
            dy, dx = key[stream_idx]
            stream_idx += 1
            env[p] = {(i, dy, dx)}
        else:
            env[p] = set()
    for p in core.params:
        env[p] = set()

    for node in core.toposort():
        ins = [env[alias.get(v, v)] for v in node.inputs]
        merged = set().union(*ins) if ins else set()
        if node.kind == "equ":
            env[node.outputs[0]] = merged
            continue
        mod = compiled.registry.lookup(node.module)
        if isinstance(mod, LibraryModule):
            if mod.name in _STREAM_1D:
                raise CodegenError(
                    f"core {core.name}: node {node.name} uses 1-D stream "
                    f"module {mod.name}; express grid state as Stencil2D "
                    "for stream codegen"
                )
            if mod.name == "Stencil2D":
                p = mod.resolve_params(node, core.params)
                dy, dx = int(p.get("dy", 0)), int(p.get("dx", 0))
                env[node.outputs[0]] = {
                    (i, oy + dy, ox + dx) for (i, oy, ox) in ins[0]
                }
            else:
                # Library modules other than the stencil buffer are
                # pointwise over the stream (mux, comparator, fixed-
                # function units): offsets pass through unchanged.
                for o in node.outputs:
                    env[o] = merged
        else:
            # Sub-core call: compose the callee's per-output read sets
            # with this call site's argument offsets (additive).
            sub = _core_reads(mod)
            sub_outs = mod.core.output_ports()
            if len(sub_outs) != len(node.outputs):
                raise CodegenError(
                    f"node {node.name}: module {node.module} has "
                    f"{len(sub_outs)} outputs, node declares "
                    f"{len(node.outputs)}"
                )
            for o_port, o_var in zip(sub_outs, node.outputs):
                acc: set = set()
                for (i, dy, dx) in sub[o_port]:
                    acc.update(
                        (j, oy + dy, ox + dx) for (j, oy, ox) in ins[i]
                    )
                env[o_var] = acc

    reads = {p: env[alias.get(p, p)] for p in core.output_ports()}
    memo[key] = reads
    return reads


def _stencil_modes(compiled: CompiledCore) -> set:
    """Boundary modes of every Stencil2D reachable from ``compiled``."""
    core = compiled.core
    modes: set = set()
    for node in core.nodes:
        if node.kind != "hdl":
            continue
        mod = compiled.registry.lookup(node.module)
        if isinstance(mod, LibraryModule):
            if mod.name == "Stencil2D":
                p = mod.resolve_params(node, core.params)
                if int(p.get("dy", 0)) or int(p.get("dx", 0)):
                    modes.add(str(p.get("mode", "zero")))
        else:
            modes |= _stencil_modes(mod)
    return modes


def stencil_summary(compiled: CompiledCore,
                    incoming=None) -> StencilSummary:
    """Infer the stencil footprint of a compiled core's DFG.

    Walks the graph once (recursing into sub-cores, memoized per
    (core, incoming extents)) and returns which input ports each output
    reads at which grid offsets, plus the halo the temporal-blocking
    kernel must carry per fused step. Cached on the compiled core:
    ``stream_halo``, ``stream_kernel()`` and direct callers all share
    one walk. ``incoming`` composes producer-edge ``(dy, dx)`` extents
    into the footprint (docs/pipeline.md §program) — a program stage's
    effective halo is its own reach *through* the edge feeding it.
    """
    core = compiled.core
    key = _normalize_incoming(
        incoming,
        len(core.main_input_ports()) + len(core.brch_input_ports()),
    )
    memo = getattr(compiled, "_stencil_summary_memo", None)
    if memo is None:
        memo = {}
        compiled._stencil_summary_memo = memo
    cached = memo.get(key)
    if cached is not None:
        return cached
    names = core.input_ports()
    reads = {
        port: frozenset((names[i], dy, dx) for (i, dy, dx) in triples)
        for port, triples in _core_reads(compiled, key).items()
    }
    offsets = frozenset(
        (dy, dx) for triples in reads.values() for (_, dy, dx) in triples
    )
    summary = StencilSummary(
        port_reads=reads,
        offsets=offsets,
        halo_y=max((abs(dy) for dy, _ in offsets), default=0),
        halo_x=max((abs(dx) for _, dx in offsets), default=0),
        modes=frozenset(_stencil_modes(compiled)),
    )
    memo[key] = summary
    return summary


# --------------------------------------------------------------------------
# Stripe-mode DFG evaluation
# --------------------------------------------------------------------------


def _stripe_shift(x, dy: int, dx: int):
    """``out[y, x] = in[y-dy, x-dx]`` on a (rows, W) stripe.

    y is shifted non-periodically with zero fill — the stripe's halo rows
    hold the true neighbor values, and rows that consume the zero fill
    are exactly the rows the trapezoid retires; x is shifted
    periodically in-register (the full row width is resident).
    """
    if dy:
        pad = jnp.zeros((abs(dy),) + x.shape[1:], x.dtype)
        x = (
            jnp.concatenate([pad, x[:-dy]], axis=0)
            if dy > 0
            else jnp.concatenate([x[-dy:], pad], axis=0)
        )
    dx %= x.shape[1]  # periodic: offsets beyond one row width wrap
    if dx:
        # With dx normalized into [1, W), this one concatenate is the
        # periodic shift out[:, x] = in[:, (x - dx) mod W].
        x = jnp.concatenate([x[:, -dx:], x[:, :-dx]], axis=1)
    return x


def _eval_stripe(compiled: CompiledCore, env: dict) -> list:
    """Evaluate a core's DFG over (rows, W) stripe arrays.

    Structurally identical to :meth:`CompiledCore.apply` (same casts,
    same ``eval_expr``, same node order) so the kernel's arithmetic
    bit-matches the compiler's reference function — only ``Stencil2D``
    is re-lowered to :func:`_stripe_shift` semantics, and sub-core calls
    recurse through this evaluator instead of ``apply``.
    """
    core = compiled.core
    alias = core.alias_map()
    for node in core.toposort():
        ins = [env[alias.get(v, v)] for v in node.inputs]
        if node.kind == "equ":
            local = dict(env)
            local.update({
                v: jnp.asarray(env[alias.get(v, v)], jnp.float32)
                for v in node.inputs
            })
            env[node.outputs[0]] = eval_expr(node.expr, local)
            continue
        mod = compiled.registry.lookup(node.module)
        if isinstance(mod, LibraryModule):
            if mod.name in _STREAM_1D:
                raise CodegenError(
                    f"core {core.name}: node {node.name} uses 1-D stream "
                    f"module {mod.name}; not lowerable to a 2-D stripe"
                )
            if mod.name == "Stencil2D":
                p = mod.resolve_params(node, core.params)
                outs = [
                    _stripe_shift(
                        jnp.asarray(ins[0], jnp.float32),
                        int(p.get("dy", 0)), int(p.get("dx", 0)),
                    )
                ]
            else:
                outs = mod.apply(ins, mod.resolve_params(node, core.params))
        else:
            sub_env: dict = dict(zip(mod.core.input_ports(), ins))
            sub_env.update({
                k: jnp.float32(v) for k, v in mod.core.params.items()
            })
            outs = _eval_stripe(mod, sub_env)
        if len(outs) != len(node.outputs):
            raise CodegenError(
                f"node {node.name}: module {node.module} returned "
                f"{len(outs)} outputs, node declares {len(node.outputs)}"
            )
        for name, val in zip(node.outputs, outs):
            env[name] = val
    out = []
    for p in core.output_ports():
        src = alias.get(p, p)
        if src not in env:
            raise CodegenError(
                f"core {core.name}: output port {p!r} undriven"
            )
        out.append(env[src])
    return out


# --------------------------------------------------------------------------
# The codegen'd kernel
# --------------------------------------------------------------------------


class StreamKernel:
    """A compiled SPD core lowered to a temporal-blocking Pallas kernel.

    Obtained via :meth:`CompiledCore.stream_kernel`. The grid state is a
    stacked ``(P, H, W)`` f32 array with one channel per main-stream port
    (in ``main_in`` order); ``Append_Reg`` values are passed as a scalar
    tuple. One fused launch (:meth:`__call__`) advances ``m`` time steps
    per HBM round-trip; :meth:`run_for_point` legalizes and runs a DSE
    design point straight from an explorer sweep
    (docs/pipeline.md §execute).
    """

    def __init__(self, compiled: CompiledCore):
        core = compiled.core
        if core.brch_input_ports() or core.brch_output_ports():
            raise CodegenError(
                f"core {core.name}: branch streams are not lowerable to a "
                "stream kernel (no per-element side channel on the grid)"
            )
        if len(core.main_input_ports()) != len(core.main_output_ports()):
            raise CodegenError(
                f"core {core.name}: |main_in| != |main_out| "
                f"({len(core.main_input_ports())} != "
                f"{len(core.main_output_ports())}); fused steps chain "
                "outputs back into inputs"
            )
        self.compiled = compiled
        self.summary = stencil_summary(compiled)
        bad = self.summary.modes - {"wrap"}
        if bad:
            raise CodegenError(
                f"core {core.name}: Stencil2D mode(s) {sorted(bad)} not "
                "supported; the stream kernel's y-halo is periodic "
                "(mode=wrap). Express walls via stream attributes."
            )
        self.halo = self.summary.halo()
        self.halo_x = self.summary.halo_x
        self._ports = core.main_input_ports()
        self._regs = list(core.regs)
        self._params = dict(core.params)
        #: The kernel's name in the compiled program and device trace.
        self.name = tracing.kernel_name(core.name)
        from repro.kernels.spd_stream import spd_multistep, stream_run_blocked

        # The jitted entries are named functions, not partials, so their
        # XLA modules read jit_spd_… in the compiled program and trace.
        def spd_launch(state, scal, *, m, block_h, double_buffer=True,
                       interpret=None, dst=None):
            with jax.named_scope(tracing.LAUNCH):
                return spd_multistep(
                    self._step_fn, state, scal, m=m, block_h=block_h,
                    halo=self.halo, double_buffer=double_buffer,
                    interpret=interpret, name=self.name, dst=dst,
                )

        def spd_run_blocked(state, scal, *, steps, m, block_h,
                            double_buffer, interpret):
            return stream_run_blocked(
                functools.partial(self._streamed,
                                  double_buffer=double_buffer),
                state, scal, steps=steps, m=m, block_h=block_h,
                interpret=interpret,
            )

        # Manually pipelined launch (docs/pipeline.md §stream), with
        # double_buffer a real plan knob.
        self._streamed = jax.jit(
            spd_launch,
            static_argnames=("m", "block_h", "double_buffer", "interpret"),
        )
        self._sharded: dict[tuple[int, int], object] = {}
        # jit'd so the steps//m launch loop compiles once per plan shape
        # and is reused across calls (an eager lax.fori_loop over a fresh
        # closure would re-lower the whole loop on every invocation —
        # which is also what makes fused vs. pipelined program walls in
        # benchmarks/dse_sweep.py §2h an apples-to-apples comparison).
        self._run_blocked = jax.jit(
            spd_run_blocked,
            static_argnames=("steps", "m", "block_h", "double_buffer",
                             "interpret"),
        )
        # jit'd so XLA applies the same mul-add contractions as inside the
        # kernel: this is what makes the bit-match contract hold exactly.
        self._reference = jax.jit(self._reference_impl, static_argnames=("m",))

    # ---- the lowered stripe function --------------------------------------

    def _step_fn(self, f_ext, regs):
        """One application of the core over an extended (halo'd) stripe.

        A rank-3 stripe is ``(P, rows, W)``; higher ranks carry batch
        axes in front (``(B, P, rows, W)``, docs/pipeline.md §serve) and
        are handled by vmapping this same body over each leading axis,
        so batched and unbatched launches share one lowering.
        """
        if f_ext.ndim > 3:
            return jax.vmap(lambda s: self._step_fn(s, regs))(f_ext)
        env: dict = {p: f_ext[i] for i, p in enumerate(self._ports)}
        env.update(dict(zip(self._regs, regs)))
        env.update({k: jnp.float32(v) for k, v in self._params.items()})
        outs = _eval_stripe(self.compiled, env)
        n = len(self._ports)
        return jnp.stack([jnp.asarray(o, f_ext.dtype) for o in outs[:n]])

    # ---- launches ----------------------------------------------------------

    def _scal(self, regs: Sequence) -> jnp.ndarray:
        if len(regs) != len(self._regs):
            raise CodegenError(
                f"core {self.compiled.core.name}: expected "
                f"{len(self._regs)} register values {self._regs}, "
                f"got {len(regs)}"
            )
        # SMEM refs need a non-empty shape; pad reg-less cores with one 0.
        vals = list(regs) if regs else [0.0]
        return jnp.asarray(vals, jnp.float32)

    def launch_dma_bytes(self, state, *, m: int, block_h: int) -> int:
        """Bytes one streamed fused launch over ``state`` moves by DMA
        (:func:`repro.core.legalize.launch_dma_bytes`)."""
        *lead, h, w = state.shape
        return launch_dma_bytes(h, w, math.prod(lead), block_h=block_h,
                                m=m, halo=self.halo,
                                itemsize=state.dtype.itemsize)

    def launch_flops(self, state, *, m: int, block_h: int) -> int:
        """Float operations one streamed fused launch over ``state``
        executes (:func:`repro.core.legalize.launch_flops`)."""
        *batch, _, h, w = state.shape
        return launch_flops(h, w, math.prod(batch), block_h=block_h, m=m,
                            halo=self.halo, flops=self.compiled.flops)

    def __call__(self, state, regs: Sequence = (), *, m: int = 1,
                 block_h: int = 32, double_buffer: bool = True,
                 interpret: bool | None = None):
        """One fused launch: advance ``state`` by ``m`` time steps.

        ``double_buffer`` selects the streamed launch's buffer protocol
        (ping/pong vs single-buffer, docs/pipeline.md §stream); both are
        bitwise identical to :meth:`reference`.
        """
        with jax.profiler.TraceAnnotation(tracing.RUN):
            out = self._streamed(
                state, self._scal(regs), m=m, block_h=block_h,
                double_buffer=double_buffer, interpret=interpret,
            )
            tracing.count(launches=1, steps=m,
                          dma_bytes=self.launch_dma_bytes(
                              state, m=m, block_h=block_h),
                          kernel_flops=self.launch_flops(
                              state, m=m, block_h=block_h))
        return out

    def run_blocked(self, state, regs: Sequence = (), *, steps: int,
                    m: int, block_h: int, double_buffer: bool = True,
                    interpret: bool | None = None):
        """Advance ``steps`` time steps using m-fused kernel launches."""
        steps, m, block_h = int(steps), int(m), int(block_h)
        with jax.profiler.TraceAnnotation(tracing.RUN):
            out = self._run_blocked(
                state, self._scal(regs), steps=steps, m=m, block_h=block_h,
                double_buffer=bool(double_buffer), interpret=interpret,
            )
            launches = steps // m
            tracing.count(launches=launches, steps=steps,
                          dma_bytes=launches * self.launch_dma_bytes(
                              state, m=m, block_h=block_h),
                          kernel_flops=launches * self.launch_flops(
                              state, m=m, block_h=block_h),
                          aliased_launches=max(0, launches - 2))
        return out

    def sharded(self, d: int, devices: Sequence | None = None,
                dx: int = 1):
        """Decompose this kernel across ``d`` devices.

        Returns a :class:`repro.core.distribute.ShardedStreamKernel`
        running this kernel's stripe function per shard with halo
        exchange between fused launches (docs/pipeline.md §distribute).
        ``dx`` factors ``d`` into a ``(dy, dx)`` 2-D mesh
        (DESIGN.md §15): rows shard over ``dy = d / dx`` with the ring
        exchange, columns over ``dx`` with the column-halo exchange.
        ``d == 1`` is the identity wrapper (delegates straight back).
        Default-device wrappers are cached per ``(d, dx)`` so repeat
        callers (e.g. an app driver looping ``run(..., d=2)``) reuse the
        shard_map jit cache instead of recompiling every call.
        """
        from .distribute import ShardedStreamKernel

        if devices is not None:
            return ShardedStreamKernel(self, d, devices, dx=dx)
        if (d, dx) not in self._sharded:
            self._sharded[(d, dx)] = ShardedStreamKernel(self, d, dx=dx)
        return self._sharded[(d, dx)]

    def run_for_point(self, state, regs: Sequence = (), *, point,
                      steps: int | None = None,
                      interpret: bool | None = None):
        """Advance the grid using a DSE design point's (block_h, m).

        The point is legalized with the shared
        :func:`repro.core.legalize.resolve_run_plan`, using this kernel's
        inferred halo and the state's concrete width for the VMEM clamp
        (with the double-buffered→single-buffered streaming fallback).
        Returns ``(result, (block_h, m, double_buffer))``. ``state`` may
        carry batch axes in front of ``(P, H, W)``; the VMEM clamp then
        prices the full ``b``-wide stripe (docs/pipeline.md §serve).
        """
        *lead, h, w = state.shape
        p = lead[-1] if lead else 1
        b = 1
        for n in lead[:-1]:
            b *= int(n)
        block_h, m, nsteps, double_buffer = resolve_run_plan(
            h, point, steps, halo=self.halo, width=w, words=p, b=b,
            dx=1,  # this is the single-device launch path
            interpret=interpret,
        )
        out = self.run_blocked(
            state, regs, steps=nsteps, m=m, block_h=block_h,
            double_buffer=double_buffer, interpret=interpret,
        )
        return out, (block_h, m, double_buffer)

    # ---- the compiler's reference function --------------------------------

    def reference(self, state, regs: Sequence = (), *, m: int = 1):
        """m repeated applications of the compiled core's JAX function.

        This is the semantics the kernel must reproduce bit-for-bit in
        interpret mode: :meth:`CompiledCore.apply` on the full grid
        (``Stencil2D`` fully periodic), outputs chained into inputs.
        """
        return self._reference(state, tuple(regs), m=m)

    def _reference_impl(self, state, regs, *, m: int):
        outs = [state[i] for i in range(len(self._ports))]
        for _ in range(m):
            outs = self.compiled.apply(list(outs) + list(regs))
        return jnp.stack(
            [jnp.asarray(o, state.dtype) for o in outs[:len(self._ports)]]
        )

    def pack(self, arrays: Sequence) -> jnp.ndarray:
        """Stack per-port (H, W) grids into the kernel's (P, H, W) state."""
        if len(arrays) != len(self._ports):
            raise CodegenError(
                f"expected {len(self._ports)} main-stream fields "
                f"{self._ports}, got {len(arrays)}"
            )
        return jnp.stack([jnp.asarray(a, jnp.float32) for a in arrays])

    def pack_batch(self, states: Sequence) -> jnp.ndarray:
        """Stack ``b`` packed (P, H, W) states into a (B, P, H, W) batch.

        The batch axis groups independent simulations into one launch
        (docs/pipeline.md §serve); members must share grid geometry.
        """
        if not states:
            raise CodegenError("pack_batch needs at least one state")
        arrs = [jnp.asarray(s, jnp.float32) for s in states]
        if any(a.shape != arrs[0].shape for a in arrs):
            raise CodegenError(
                "pack_batch members must share one (P, H, W) geometry; "
                f"got {[a.shape for a in arrs]}"
            )
        return jnp.stack(arrs)


__all__ = [
    "CodegenError",
    "StencilSummary",
    "StreamKernel",
    "stencil_summary",
]
