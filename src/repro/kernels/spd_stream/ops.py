"""The launch loop of codegen'd SPD stream kernels.

Multi-launch stepping over the fused kernel
(:func:`repro.kernels.spd_stream.spd_multistep`), with (block_h, m)
plans legalized through the shared :mod:`repro.core.legalize`
(docs/pipeline.md §legalize). The kernel-building side lives in
:class:`repro.core.codegen.StreamKernel`, which runs this loop for a
specific compiled core, and ``repro.core.distribute`` runs it on every
shard of a device mesh.
"""

from __future__ import annotations

from typing import Callable

import jax

from repro.core.legalize import blocking_plan, resolve_run_plan


def stream_run_blocked(multistep: Callable, state, scal, *, steps: int,
                       m: int, block_h: int, interpret: bool | None = None,
                       carry=None):
    """Advance ``steps`` time steps using m-fused kernel launches.

    ``multistep`` is a (typically jitted) closure over
    :func:`repro.kernels.spd_stream.spd_multistep` with the stripe
    function and halo bound —
    ``multistep(state, scal, m=, block_h=, interpret=, dst=)``.

    The launches ping-pong between two buffers that the kernel writes
    (docs/pipeline.md §stream): the first two make A and B, and every
    later one reads the buffer last written and writes into the other
    one (``dst``), so the loop carries no buffer that a launch both
    reads and overwrites, and XLA copies nothing. ``state`` is only
    read: it is never a destination, so the caller may keep using it.
    ``max(0, steps // m - 2)`` launches write into a recycled buffer.

    With ``carry`` (a mesh launch's edge columns, docs/pipeline.md
    §distribute), ``multistep`` also takes ``carry=`` and returns
    ``(out, carry)``: the first launch gets ``carry`` and each later one
    what the launch before it returned.
    """
    if steps % m:
        raise ValueError(f"steps={steps} must be a multiple of m={m}")
    n = steps // m

    def launch(src, dst=None):
        x, c = src
        kw = dict(m=m, block_h=block_h, interpret=interpret, dst=dst)
        if carry is None:
            return multistep(x, scal, **kw), None
        return multistep(x, scal, carry=c, **kw)

    if n < 1:
        return state
    a = launch((state, carry))
    if n < 2:
        return a[0]
    b = launch(a)

    def body(_, ab):
        a, b = ab
        a = launch(b, a[0])
        return a, launch(a, b[0])

    a, b = jax.lax.fori_loop(0, (n - 2) // 2, body, (a, b))
    return (launch(b, a[0]) if (n - 2) % 2 else b)[0]


__all__ = [
    "blocking_plan",
    "resolve_run_plan",
    "stream_run_blocked",
]
