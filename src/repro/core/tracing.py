"""The launch path's names and counters, always on.

Device-side names: every fused launch runs under the
``jax.named_scope`` :data:`LAUNCH`, the halo exchange's collectives and
the slices that feed them under :data:`EXCHANGE`, and the assembly of
what was exchanged under :data:`ASSEMBLE` (``repro.core.distribute``:
the ring's guard-block concatenates, the mesh's guard-sized pieces). XLA writes the scope into each op's
``op_name``, which the profiler keeps as the op's ``tf_op``, so an op
the compiler inserts on its own (a loop-carry copy) is the one left
with no ``spd.*`` scope. The kernel itself is named by
:func:`kernel_name`. Host side, each ``run_blocked`` call's dispatch
runs inside the ``jax.profiler.TraceAnnotation`` :data:`RUN`, on the
profiler's clock beside the device ops.

Counters (process-wide, read with :func:`snapshot`):

* ``launches`` and ``steps``: fused launches and time steps dispatched,
  added once per call from its plan (:func:`count`);
* ``dma_bytes``: the bytes those launches' DMAs are programmed to move
  (:func:`repro.core.legalize.launch_dma_bytes`, summed over shards);
* ``kernel_flops``: the float operations those launches execute, halo
  rows of every stripe included (:func:`repro.core.legalize.launch_flops`
  from the compiled core's per-site count, summed over shards and over
  a program's fused cores);
* ``aliased_launches``: those launches that wrote into a buffer the
  launch loop recycles (``input_output_aliases``) rather than a new
  one: ``max(0, launches - 2)`` per ``StreamKernel.run_blocked`` call
  and per mesh ``ShardedStreamKernel.run_blocked`` call
  (:func:`repro.kernels.spd_stream.stream_run_blocked`);
* ``kernel_edge_launches``: those launches whose column guards came
  from the edge columns the launch before them wrote
  (:func:`repro.kernels.spd_stream.spd_multistep`): ``launches - 1``
  per ``ShardedStreamKernel.run_blocked`` call on a mesh that shards
  columns (``dx > 1``), 0 elsewhere;
* ``jit_traces``: jaxpr traces, one per jit cache miss of any function
  in the process (the program's and its caller's alike);
* ``jit_s``: seconds spent tracing, lowering and compiling (a
  persistent-cache load counts as the compile it replaces), as the
  union of those stages' spans so that a nested trace is not counted
  twice.

The names cost nothing at run time; a counter update is a few additions
per call, not per step.
"""

from __future__ import annotations

import re
import threading

from jax import monitoring

#: Host span around one call's dispatch (``run_blocked``, ``__call__``).
RUN = "spd.run"
#: Device scope of every fused launch.
LAUNCH = "spd.launch"
#: Device scope of the mesh's halo exchange: ppermutes and their slices.
EXCHANGE = "spd.exchange"
#: Device scope of the exchanged halo's assembly: concatenates and pads.
ASSEMBLE = "spd.assemble"

#: The JAX compile stages whose spans ``jit_s`` unites; the first is
#: the one ``jit_traces`` counts. A persistent-cache read happens
#: inside the backend-compile stage, so it is in ``jit_s`` once.
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENTS = (
    TRACE_EVENT,
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)

_lock = threading.Lock()
_counters = {"launches": 0, "steps": 0, "dma_bytes": 0, "kernel_flops": 0,
             "aliased_launches": 0, "kernel_edge_launches": 0,
             "jit_traces": 0, "jit_s": 0.0}
#: Disjoint compile spans seen so far, ``[start, end]`` in seconds.
_spans: list[list[float]] = []


def kernel_name(core_name: str) -> str:
    """The ``pallas_call`` name of a core's kernel: ``spd_<core>``,
    with every character outside ``[A-Za-z0-9_]`` replaced by ``_``."""
    return "spd_" + re.sub(r"[^A-Za-z0-9_]", "_", core_name)


def count(*, launches: int, steps: int, dma_bytes: int, kernel_flops: int,
          aliased_launches: int = 0, kernel_edge_launches: int = 0) -> None:
    """Add one call's launches, steps, programmed DMA bytes, executed
    float operations, the launches among them that wrote into a
    recycled buffer and those whose column guards a kernel wrote."""
    with _lock:
        _counters["launches"] += int(launches)
        _counters["steps"] += int(steps)
        _counters["dma_bytes"] += int(dma_bytes)
        _counters["kernel_flops"] += int(kernel_flops)
        _counters["aliased_launches"] += int(aliased_launches)
        _counters["kernel_edge_launches"] += int(kernel_edge_launches)


def snapshot() -> dict:
    """A copy of every counter."""
    with _lock:
        return dict(_counters)


def _on_compile_span(event: str, start: float, end: float, **_) -> None:
    if event not in COMPILE_EVENTS:
        return
    with _lock:
        if event == TRACE_EVENT:
            _counters["jit_traces"] += 1
        # Merge [start, end] into the disjoint spans; jit_s grows by the
        # part no earlier span covered.
        lo, hi, covered = start, end, 0.0
        keep = []
        for s, e in _spans:
            if e < lo or s > hi:
                keep.append([s, e])
            else:
                covered += e - s
                lo, hi = min(lo, s), max(hi, e)
        keep.append([lo, hi])
        _spans[:] = keep
        _counters["jit_s"] += (hi - lo) - covered


monitoring.register_event_time_span_listener(_on_compile_span)
