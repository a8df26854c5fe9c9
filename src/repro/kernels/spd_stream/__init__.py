"""Generic SPD→Pallas temporal-blocking stream kernels.

This package is the *codegen target*: `repro.core.codegen` lowers any
compiled SPD core into the stripe-update function that
:func:`spd_multistep` launches on the TPU (docs/pipeline.md §codegen).
The launch keeps the state in HBM and stages its stripes through
ping/pong VMEM buffers by explicit async copies (docs/pipeline.md
§stream); on a device mesh the same launch gathers the halo pieces
that ``repro.core.distribute`` exchanges (docs/pipeline.md
§distribute). :func:`stream_run_blocked` is the launch loop.
"""

from .ops import stream_run_blocked
from .streaming import edge_columns, spd_multistep

__all__ = [
    "edge_columns",
    "spd_multistep",
    "stream_run_blocked",
]
