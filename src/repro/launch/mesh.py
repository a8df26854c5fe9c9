"""Production mesh construction.

A function (never a module-level constant) so importing this module never
touches JAX device state — the dry-run sets XLA_FLAGS before first init.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a 2-pod leading axis.

    The axes are ``Auto``: the LM tier shards through GSPMD constraints
    (``repro.parallel.hints``), not explicit-sharding types, and
    ``jax.make_mesh`` defaults to ``Explicit`` axes on the installed JAX.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_axes_for(mesh, global_batch: int):
    """Data-parallel axes for a batch: ('pod','data') when both divide,
    'data' when only the single-pod width divides, else None (replicate —
    the long_500k batch=1 case)."""
    sizes = mesh_axis_sizes(mesh)
    if "pod" in sizes:
        full = sizes["pod"] * sizes["data"]
        if global_batch % full == 0:
            return ("pod", "data")
    if global_batch % sizes["data"] == 0:
        return ("data",)
    return None
