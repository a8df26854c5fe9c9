"""Streaming program graphs: multi-core fusion/pipelining as one layer.

The paper's DSL is hierarchical — full applications are chains of
stream cores, and the DSE picks the parallelism mix for the whole
structure. This module is that layer (docs/pipeline.md §program,
DESIGN.md §14): a :class:`StreamProgram` takes a DAG of compiled SPD
cores (producer→consumer edges with per-edge stencil extents) and
lowers each *fusion cluster* of a partition to one ``pallas_call``:

* **fused** — a cluster's member stages are chained inside a single
  stripe body, by synthesizing an SPD wrapper core that calls the
  member cores in sequence (the same sub-core chaining idiom as
  ``apps.lbm.pe_spd``) with edge extents realized as ``Stencil2D``
  nodes; the wrapper compiles through the ordinary
  :class:`~repro.core.codegen.StreamKernel` path, so stencil-offset
  inference composes the member halos automatically and the launch is
  the standard ``m``-blocked temporal-blocking kernel.
* **pipelined** — clusters on either side of a *cut* edge run as
  chained launches: one jitted ``fori_loop`` advances the program a
  step at a time, each step running every cluster's kernel back to
  back, so intermediate fields stay on device between launches (no
  host round-trip — asserted under ``jax.transfer_guard`` in
  ``tests/test_program.py``).

The fusion partition (``"3"`` fully fused, ``"1+2"``, ``"1+1+1"`` fully
pipelined — :func:`repro.core.legalize.parse_fusion`) is a first-class
plan dimension: legalized by
:func:`~repro.core.legalize.program_blocking_plan` (cluster stripes are
the *sum* of member-stage stripes at the *composed* halo), priced by
``TPUModel.evaluate(..., fusion=)`` (one HBM pass when fused, one per
cluster per step when pipelined), and searched through the
``repro.core.search`` strategies next to ``(n, m, d, block_h,
double_buffer, b)``.

Supported graphs: linear chains (every stage has exactly one producer
and one consumer edge). A general DAG is validated down to this shape —
diamond/fan-out programs raise :class:`ProgramError`; the partition
algebra below is defined on chains and the acceptance apps (uLBM's
collide+stream → boundary → moments, advection → react/diffuse) are
chains.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp


from . import tracing
from .codegen import CodegenError, StreamKernel, stencil_summary
from .compiler import CompiledCore, Registry
from .dfg import SPDError
from .legalize import parse_fusion, resolve_run_plan
from .spd import parse_spd


class ProgramError(SPDError):
    """The core DAG cannot be lowered as a stream program (with why)."""


def fusion_partitions(nstages: int) -> tuple[str, ...]:
    """All fusion partition specs of an ``nstages``-stage chain.

    The 2^(n-1) ordered compositions of ``nstages``, as canonical
    ``"+"``-joined specs — ``fusion_partitions(3)`` is ``('3', '2+1',
    '1+2', '1+1+1')`` (fully fused first, fully pipelined last). This
    is the fusion axis the sweep lattice enumerates (docs/pipeline.md
    §program).
    """

    def _comps(n):
        if n == 0:
            yield ()
            return
        for first in range(n, 0, -1):
            for rest in _comps(n - first):
                yield (first,) + rest

    return tuple(
        "+".join(str(s) for s in comp) for comp in _comps(int(nstages))
    )


@dataclass(frozen=True)
class ProgramStage:
    """One stage of a stream program: a compiled core plus the
    ``(dy, dx)`` stencil extent of its incoming producer edge (``(0, 0)``
    for the source stage — there is no edge feeding it)."""

    compiled: CompiledCore
    extent: tuple[int, int] = (0, 0)

    @property
    def name(self) -> str:
        return self.compiled.core.name


class StreamProgram:
    """A producer→consumer DAG of SPD cores, lowerable per fusion
    partition (docs/pipeline.md §program, DESIGN.md §14).

    ``stages`` are compiled cores (or registry names) sharing one
    registry; ``edges`` are ``(producer, consumer)`` or ``(producer,
    consumer, (dy, dx))`` tuples over stage indices or names, validated
    to form the linear chain ``0 → 1 → … → n-1`` (``None`` means the
    chain with zero extents). Every stage must be stream-lowerable on
    its own (``|main_in| == |main_out|``, no branch streams) and all
    stages must agree on the main port count ``P`` — cluster launches
    chain ``(P, H, W)`` states stage to stage exactly as fused steps
    chain them within one core.

    ``Append_Reg`` scalars concatenate in stage order into one flat
    program register tuple; cluster kernels slice their members' span.
    """

    def __init__(self, registry: Registry, stages: Sequence,
                 edges: Sequence | None = None, *, width: int = 0,
                 name: str = "program"):
        self.registry = registry
        self.name = str(name)
        self.width = int(width)
        resolved = []
        for s in stages:
            if isinstance(s, str):
                s = registry.lookup(s)
            if not isinstance(s, CompiledCore):
                raise ProgramError(
                    f"program stage {s!r} is not a compiled SPD core"
                )
            resolved.append(s)
        if not resolved:
            raise ProgramError("a stream program needs >= 1 stage")
        names = [c.core.name for c in resolved]
        if len(set(names)) != len(names):
            raise ProgramError(f"duplicate stage cores: {names}")
        extents = self._chain_extents(names, edges)
        self.stages: tuple[ProgramStage, ...] = tuple(
            ProgramStage(c, e) for c, e in zip(resolved, extents)
        )
        ports = None
        for st in self.stages:
            core = st.compiled.core
            if core.brch_input_ports() or core.brch_output_ports():
                raise ProgramError(
                    f"stage {core.name}: branch streams are not "
                    "lowerable in a stream program"
                )
            if len(core.main_input_ports()) != len(core.main_output_ports()):
                raise ProgramError(
                    f"stage {core.name}: |main_in| != |main_out| "
                    f"({len(core.main_input_ports())} != "
                    f"{len(core.main_output_ports())}); program edges "
                    "chain outputs into the consumer's inputs"
                )
            if ports is None:
                ports = len(core.main_input_ports())
            elif len(core.main_input_ports()) != ports:
                raise ProgramError(
                    f"stage {core.name} has {len(core.main_input_ports())} "
                    f"main ports, chain carries {ports}; all stages of a "
                    "program share one (P, H, W) stream shape"
                )
            if st.extent != (0, 0) and not self.width:
                raise ProgramError(
                    f"edge into stage {core.name} has extent {st.extent}; "
                    "non-zero edge extents need the program's grid "
                    "width (StreamProgram(..., width=W)) to synthesize "
                    "their Stencil2D nodes"
                )
        self.P = ports
        self._cluster_kernels: dict[tuple[int, int], StreamKernel] = {}
        self._program_kernels: dict[str, "ProgramKernel"] = {}

    @staticmethod
    def _chain_extents(names, edges):
        """Validate the edge set as the linear chain; per-stage extents."""
        n = len(names)
        if edges is None:
            return [(0, 0)] * n
        index = {nm: i for i, nm in enumerate(names)}
        extents = [(0, 0)] * n
        seen = set()
        for e in edges:
            if len(e) == 2:
                prod, cons = e
                ext = (0, 0)
            else:
                prod, cons, ext = e
            prod = index[prod] if isinstance(prod, str) else int(prod)
            cons = index[cons] if isinstance(cons, str) else int(cons)
            if cons != prod + 1 or not (0 <= prod < n - 1):
                raise ProgramError(
                    f"edge {prod}->{cons} is not a chain edge; stream "
                    "programs support linear chains (stage i feeds "
                    "stage i+1) — diamond/fan-out DAGs are not lowerable"
                )
            if (prod, cons) in seen:
                raise ProgramError(f"duplicate edge {prod}->{cons}")
            seen.add((prod, cons))
            dy, dx = ext
            extents[cons] = (int(dy), int(dx))
        if len(seen) != n - 1:
            missing = [
                (i, i + 1) for i in range(n - 1) if (i, i + 1) not in seen
            ]
            raise ProgramError(
                f"program edges leave the chain disconnected: missing "
                f"{missing}"
            )
        return extents

    # ---- per-stage geometry (the legalizer/model contract) ----------------

    @property
    def nstages(self) -> int:
        return len(self.stages)

    def stage_halo(self, k: int) -> int:
        """Per-step stencil reach of stage ``k`` *through* its incoming
        edge: the stage's own inferred halo composed with the producer
        edge's extent (satellite memoization keys on this pair — see
        :func:`repro.core.codegen.stencil_summary`)."""
        st = self.stages[k]
        return stencil_summary(
            st.compiled, incoming=(st.extent,) * self.P
        ).halo()

    def stage_geometry(self) -> tuple[tuple[int, int], ...]:
        """``(words, halo)`` per stage, in chain order — the ``stages``
        argument of :func:`repro.core.legalize.program_blocking_plan`:
        every stage stripes the full ``P``-channel state, and a fused
        cluster's composed halo is the sum of its members' entries."""
        return tuple(
            (self.P, self.stage_halo(k)) for k in range(self.nstages)
        )

    # ---- cluster synthesis -------------------------------------------------

    def _cluster_spd(self, lo: int, hi: int) -> str:
        """SPD text of the wrapper core fusing stages [lo, hi).

        The member cores are chained as sub-core calls (the ``pe_spd``
        idiom); each stage's incoming-edge extent — including the *cut*
        edge feeding the cluster when ``lo > 0`` — becomes a per-port
        ``Stencil2D`` node ahead of the stage call, so every program
        edge is applied exactly once across any partition.
        """
        xin = [f"x{j}" for j in range(self.P)]
        yout = [f"y{j}" for j in range(self.P)]
        lines = [
            f"Name {self.name}_f{lo}_{hi};",
            f"Main_In {{mi::{','.join(xin)}}};",
            f"Main_Out {{mo::{','.join(yout)}}};",
        ]
        regs = [
            f"s{k}_{r}"
            for k in range(lo, hi)
            for r in self.stages[k].compiled.core.regs
        ]
        if regs:
            lines.append(f"Append_Reg {{rg::{','.join(regs)}}};")
        cur = xin
        for k in range(lo, hi):
            dy, dx = self.stages[k].extent if k > 0 else (0, 0)
            if (dy, dx) != (0, 0):
                nxt = [f"e{k}_{j}" for j in range(self.P)]
                for j in range(self.P):
                    lines.append(
                        f"HDL E{k}_{j}, 0, ({nxt[j]}) = "
                        f"Stencil2D({cur[j]}), dy={dy}, dx={dx}, "
                        f"W={self.width}, mode=wrap;"
                    )
                cur = nxt
            outs = yout if k == hi - 1 else [
                f"t{k}_{j}" for j in range(self.P)
            ]
            args = cur + [
                f"s{k}_{r}" for r in self.stages[k].compiled.core.regs
            ]
            lines.append(
                f"HDL S{k}, 0, ({','.join(outs)}) = "
                f"{self.stages[k].name}({','.join(args)});"
            )
            cur = outs
        return "\n".join(lines) + "\n"

    def cluster_kernel(self, lo: int, hi: int) -> StreamKernel:
        """The :class:`StreamKernel` of the fused span [lo, hi), cached
        per span so partitions sharing a cluster share one kernel (and
        one jit cache)."""
        if not (0 <= lo < hi <= self.nstages):
            raise ProgramError(f"bad cluster span [{lo}, {hi})")
        key = (lo, hi)
        if key not in self._cluster_kernels:
            compiled = self.registry.compile(
                parse_spd(self._cluster_spd(lo, hi))
            )
            self._cluster_kernels[key] = StreamKernel(compiled)
        return self._cluster_kernels[key]

    def monolithic_kernel(self) -> StreamKernel:
        """The fully fused single-core kernel — the program's reference
        semantics (one stripe body chaining every stage)."""
        return self.cluster_kernel(0, self.nstages)

    def kernel(self, fusion: str = "") -> "ProgramKernel":
        """The program lowered under a fusion partition, cached per
        canonical spec (``""`` means fully fused)."""
        sizes = parse_fusion(fusion, self.nstages)
        spec = "+".join(str(s) for s in sizes)
        if spec not in self._program_kernels:
            self._program_kernels[spec] = ProgramKernel(self, spec)
        return self._program_kernels[spec]

    # ---- registers ---------------------------------------------------------

    def reg_names(self) -> tuple[str, ...]:
        """Flat program register names, stage order (``s{k}_{reg}``)."""
        return tuple(
            f"s{k}_{r}"
            for k, st in enumerate(self.stages)
            for r in st.compiled.core.regs
        )

    def reg_slice(self, lo: int, hi: int) -> slice:
        """Span of the flat register tuple owned by stages [lo, hi)."""
        counts = [len(st.compiled.core.regs) for st in self.stages]
        return slice(sum(counts[:lo]), sum(counts[:hi]))

    # ---- DSE hand-off ------------------------------------------------------

    def workload(self, elems: int, grid_w: int = 0):
        """Bind the program to a stream length: a
        :class:`~repro.core.dse.StreamWorkload` whose ``stages`` carry
        the per-stage (flops, words, halo) triples the fusion-aware
        model prices cluster by cluster (docs/pipeline.md §program)."""
        from .dse import StreamWorkload

        reports = [st.compiled.hardware_report for st in self.stages]
        stage_geom = tuple(
            (r.flops, self.P, self.stage_halo(k))
            for k, r in enumerate(reports)
        )
        return StreamWorkload(
            name=self.name,
            flops_per_elem=sum(r.flops for r in reports),
            words_in=self.P,
            words_out=self.P,
            depth=sum(r.depth for r in reports),
            buffer_bits=sum(r.buffer_bits for r in reports),
            elems=int(elems),
            grid_w=int(grid_w),
            halo=sum(h for _, _, h in stage_geom),
            stages=stage_geom,
        )

    def explorer(self, elems: int, grid_w: int = 0, **kw):
        """A DSE :class:`~repro.core.explorer.Explorer` over this
        program — ``sweep_tpu(fusion_values=...)`` then adds the
        partition to the lattice and ``search`` executes points through
        :func:`program_run_factory`."""
        from .explorer import Explorer

        kw.setdefault("core", self)
        return Explorer(self.workload(elems, grid_w), **kw)


class ProgramKernel:
    """A :class:`StreamProgram` lowered under one fusion partition.

    A single-cluster partition runs as the ordinary ``m``-blocked
    temporal-blocking launch of the fused wrapper kernel; a
    multi-cluster partition runs *pipelined* — one jitted ``fori_loop``
    whose body chains every cluster's stripe launch at one program step
    each, keeping intermediate fields on device (docs/pipeline.md
    §program). :meth:`run_unfused` is the naive baseline (a separate
    host dispatch per cluster per step, intermediates synced to host)
    that ``benchmarks/dse_sweep.py`` section 2h clocks the other two
    against.
    """

    def __init__(self, program: StreamProgram, fusion: str = ""):
        self.program = program
        sizes = parse_fusion(fusion, program.nstages)
        self.fusion = "+".join(str(s) for s in sizes)
        spans, lo = [], 0
        for s in sizes:
            spans.append((lo, lo + s))
            lo += s
        self.spans = tuple(spans)
        self.clusters = tuple(
            program.cluster_kernel(a, b) for a, b in spans
        )
        #: max per-cluster composed halo (info; legalization reads the
        #: per-stage geometry, the launches read each cluster kernel's
        #: own inferred halo).
        self.halo = max(k.halo for k in self.clusters)

        def spd_run_pipelined(state, scals, *, steps, block_h,
                              double_buffer, interpret):
            """``steps`` program steps as one jitted chain: every
            cluster launches once per step at ``m=1`` (temporal blocking
            does not cross a cut edge), and because the whole loop is a
            single jit the inter-cluster fields never leave the
            device."""

            def body(_, s):
                for kern, scal in zip(self.clusters, scals):
                    s = kern._streamed(
                        s, scal, m=1, block_h=block_h,
                        double_buffer=double_buffer, interpret=interpret,
                    )
                return s

            return jax.lax.fori_loop(0, steps, body, state)

        self._pipelined = jax.jit(
            spd_run_pipelined,
            static_argnames=("steps", "block_h", "double_buffer",
                             "interpret"),
        )

    @property
    def pipelined(self) -> bool:
        return len(self.clusters) > 1

    def _scals(self, regs: Sequence) -> tuple:
        names = self.program.reg_names()
        if len(regs) != len(names):
            raise CodegenError(
                f"program {self.program.name}: expected {len(names)} "
                f"register values {names}, got {len(regs)}"
            )
        return tuple(
            kern._scal(tuple(regs)[self.program.reg_slice(a, b)])
            for kern, (a, b) in zip(self.clusters, self.spans)
        )

    def _count_steps(self, state, steps: int, block_h: int) -> None:
        """Count ``steps`` program steps of one launch per cluster."""
        step_bytes = sum(k.launch_dma_bytes(state, m=1, block_h=block_h)
                         for k in self.clusters)
        step_flops = sum(k.launch_flops(state, m=1, block_h=block_h)
                         for k in self.clusters)
        tracing.count(launches=steps * len(self.clusters), steps=steps,
                      dma_bytes=steps * step_bytes,
                      kernel_flops=steps * step_flops)

    def run_blocked(self, state, regs: Sequence = (), *, steps: int,
                    m: int, block_h: int, double_buffer: bool = True,
                    interpret: bool | None = None, d: int = 1, dx: int = 1):
        """Advance ``steps`` program steps under this partition.

        Fused (one cluster): the standard ``m``-blocked launch chain.
        Pipelined: the jitted per-step cluster chain (``m`` bounds the
        host-visible dispatch granularity but does not change the
        arithmetic — a program step is always one pass through every
        cluster). ``d > 1`` shards every cluster launch across the
        device mesh ``(d // dx, dx)`` — the row ring when ``dx == 1``
        (docs/pipeline.md §distribute, DESIGN.md §15).
        """
        scals = self._scals(regs)  # validates the register count
        if d > 1:
            return self._run_sharded(
                state, regs, steps=steps, m=m, block_h=block_h,
                double_buffer=double_buffer, interpret=interpret, d=d,
                dx=dx,
            )
        if not self.pipelined:
            (a, b), kern = self.spans[0], self.clusters[0]
            return kern.run_blocked(
                state, tuple(regs)[self.program.reg_slice(a, b)],
                steps=steps, m=m, block_h=block_h,
                double_buffer=double_buffer, interpret=interpret,
            )
        with jax.profiler.TraceAnnotation(tracing.RUN):
            out = self._pipelined(
                state, scals, steps=int(steps), block_h=int(block_h),
                double_buffer=bool(double_buffer), interpret=interpret,
            )
            self._count_steps(state, int(steps), int(block_h))
        return out

    def _run_sharded(self, state, regs, *, steps, m, block_h,
                     double_buffer, interpret, d, dx=1):
        if not self.pipelined:
            (a, b), kern = self.spans[0], self.clusters[0]
            return kern.sharded(d, dx=dx).run_blocked(
                state, tuple(regs)[self.program.reg_slice(a, b)],
                steps=steps, m=m, block_h=block_h,
                double_buffer=double_buffer, interpret=interpret,
            )
        # Pipelined + sharded: each cluster advances one program step
        # per sharded launch. The shard_map outputs stay device-resident
        # between launches; only the dispatch returns to the host.
        for _ in range(int(steps)):
            for kern, (a, b) in zip(self.clusters, self.spans):
                state = kern.sharded(d, dx=dx).run_blocked(
                    state, tuple(regs)[self.program.reg_slice(a, b)],
                    steps=1, m=1, block_h=block_h,
                    double_buffer=double_buffer, interpret=interpret,
                )
        return state

    def run_unfused(self, state, regs: Sequence = (), *, steps: int,
                    block_h: int, double_buffer: bool = True,
                    interpret: bool | None = None):
        """The no-pipelining baseline: one host dispatch per cluster per
        step, with every intermediate field synced through the host —
        what a program executed as unrelated single-core runs costs
        (the wall-clock ``benchmarks/dse_sweep.py`` records as
        ``unfused``)."""
        import numpy as np

        scals = self._scals(regs)
        for _ in range(int(steps)):
            with jax.profiler.TraceAnnotation(tracing.RUN):
                for kern, scal in zip(self.clusters, scals):
                    out = kern._streamed(
                        state, scal, m=1, block_h=block_h,
                        double_buffer=double_buffer, interpret=interpret,
                    )
                    state = jnp.asarray(np.asarray(out))  # host round-trip
                self._count_steps(state, 1, int(block_h))
        return state

    def run_for_point(self, state, regs: Sequence = (), *, point,
                      steps: int | None = None,
                      interpret: bool | None = None):
        """Advance the grid using a DSE design point, legalized for the
        whole partition via
        :func:`repro.core.legalize.program_blocking_plan` (every
        cluster's composed-halo stripe set must fit).
        Returns ``(result, (block_h, m, double_buffer))``.
        """
        *_, h, w = state.shape
        block_h, m, nsteps, double_buffer = resolve_run_plan(
            h, point, steps, width=w,
            stages=self.program.stage_geometry(), fusion=self.fusion,
            interpret=interpret,
        )
        out = self.run_blocked(
            state, regs, steps=nsteps, m=m, block_h=block_h,
            double_buffer=double_buffer, interpret=interpret,
        )
        return out, (block_h, m, double_buffer)

    def reference(self, state, regs: Sequence = (), *, m: int = 1):
        """``m`` program steps through the compiler's reference path of
        the fully fused wrapper (``CompiledCore.apply`` on whole grids)
        — the semantics every partition must reproduce bit for bit."""
        return self.program.monolithic_kernel().reference(
            state, regs, m=m
        )

    def pack(self, arrays: Sequence) -> jnp.ndarray:
        """Stack per-port (H, W) grids into the (P, H, W) program state."""
        return self.program.monolithic_kernel().pack(arrays)


def program_run_factory(program: StreamProgram, state, regs,
                        interpret: bool | None = None):
    """Adapt a program + initial state into the search runner's
    ``run_factory(nsteps, m, block_h, d, double_buffer, b, fusion,
    dx)`` protocol (docs/pipeline.md §search): the fusion partition
    selects the cached :class:`ProgramKernel`, everything else
    parameterizes its launch — ``dx`` picks the device-mesh column
    count (DESIGN.md §15). Batched program launches (``b > 1``) are
    declared unsupported (``None`` — the point is skipped), matching
    the model's infeasible cell.
    """

    def run_factory(nsteps, m, block_h, d, double_buffer=True, b=1,
                    fusion="", dx=1):
        if b > 1:
            return None
        pk = program.kernel(fusion)

        def run():
            return pk.run_blocked(
                state, regs, steps=nsteps, m=m, block_h=block_h,
                double_buffer=double_buffer, interpret=interpret, d=d,
                dx=dx,
            )

        return run

    return run_factory


__all__ = [
    "ProgramError",
    "ProgramKernel",
    "ProgramStage",
    "StreamProgram",
    "fusion_partitions",
    "program_run_factory",
]
