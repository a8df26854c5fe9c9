"""Run-plan wrappers for codegen'd SPD stream kernels.

Mirrors :mod:`repro.kernels.lbm_stream.ops`: multi-launch stepping over
the fused kernel plus the explorer hand-off, with (block_h, m) plans
legalized through the shared :mod:`repro.core.legalize`
(docs/pipeline.md §legalize). The kernel-building side lives in
:class:`repro.core.codegen.StreamKernel`, which wraps these for a
specific compiled core.
"""

from __future__ import annotations

from typing import Callable

import jax

from repro.core.legalize import blocking_plan, resolve_run_plan

from .spd_stream import spd_multistep


def stream_run_blocked(multistep: Callable, state, scal, *, steps: int,
                       m: int, block_h: int, interpret: bool | None = None):
    """Advance ``steps`` time steps using m-fused kernel launches.

    ``multistep`` is a (typically jitted) closure over
    :func:`spd_multistep` with the stripe function and halo bound —
    ``multistep(state, scal, m=, block_h=, interpret=)``.
    """
    if steps % m:
        raise ValueError(f"steps={steps} must be a multiple of m={m}")

    def body(_, s):
        return multistep(s, scal, m=m, block_h=block_h, interpret=interpret)

    return jax.lax.fori_loop(0, steps // m, body, state)


__all__ = [
    "blocking_plan",
    "resolve_run_plan",
    "spd_multistep",
    "stream_run_blocked",
]
