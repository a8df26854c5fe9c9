"""PolyBench jacobi-2d: the five-point Jacobi sweep as a benchmark app.

What this adapter gives the harness (the interface of ``apps/ulbm.py``):

* :func:`system` -- the system under test, built through the program's
  own path: ``DiffusionSimulation`` compiles the SPD diffusion core
  (``u + alpha*(un+us+ue+uw-4u)``) into the codegen'd stream kernel
  and its ``Explorer``.
* :func:`init_state` -- the ``(1, H, W)`` float32 state from a PRNG
  key: six whole-period sinusoidal modes with seeded phases and seeded
  noise. Pure ``jax.numpy``, built on the device in one jitted call.
* :func:`step` -- the plain reference, one sweep in PolyBench's own
  form, ``0.2*(c+w+e+s+n)``, in the state's dtype. It imports nothing
  from the program. At ``alpha = 0.2`` the two forms agree up to
  rounding, since there ``1 - 4*alpha = alpha``.
* :data:`READBACKS` -- the diagnostics a user's loop reads back.

Axis 0 of the state is the one word per site, axis 1 is y (PolyBench's
``i``), axis 2 is x (``j``); both wrap.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

WORDS = 1
#: The one diffusivity at which PolyBench's update and the program's
#: ``u + alpha*lap`` are the same sweep.
ALPHA = 0.2


def system(cfg: dict):
    """``(kernel, explorer, regs)`` for the configured problem."""
    from repro.apps.diffusion import DiffusionSimulation

    h, w = cfg["grid"]
    sim = DiffusionSimulation(h, w, cfg["alpha"])
    return sim.kernel, sim.explorer(), (cfg["alpha"],)


def init_state(cfg: dict, key) -> jnp.ndarray:
    """Seeded initial state, ``(1, H, W)`` float32.

    For each wavelength ``L`` in ``cfg["modes"]`` (sites; each divides
    the grid, so every mode is whole-period and wraps smoothly), one
    unit mode along y and one along x, each with a seeded phase, plus
    uniform noise of amplitude ``cfg["noise"]``. The long modes decay
    slowly, so a skipped launch still shows after thousands of steps;
    the work is the same for every seed.
    """
    h, w = cfg["grid"]
    modes = cfg["modes"]
    k_phase, k_noise = jax.random.split(key)
    phase = jax.random.uniform(k_phase, (len(modes), 2), jnp.float32, 0.0,
                               2 * math.pi)

    def waves(n, axis):
        pos = jax.lax.broadcasted_iota(jnp.int32, (n,), 0)
        total = jnp.zeros((n,), jnp.float32)
        for i, wavelength in enumerate(modes):
            if n % wavelength:
                raise ValueError(f"mode of {wavelength} sites does not "
                                 f"divide the grid's {n}")
            turn = (pos % wavelength).astype(jnp.float32) / wavelength
            total += jnp.sin(2 * math.pi * turn + phase[i, axis])
        return total

    noise = cfg["noise"] * jax.random.uniform(k_noise, (h, w), jnp.float32,
                                              -1.0, 1.0)
    u = waves(h, 0)[:, None] + waves(w, 1)[None, :] + noise
    return u[None]


def step(cfg: dict, state: jnp.ndarray) -> jnp.ndarray:
    """One reference sweep, PolyBench's
    ``B[i][j] = 0.2*(A[i][j]+A[i][j-1]+A[i][j+1]+A[i+1][j]+A[i-1][j])``
    with periodic neighbours, computed in ``state.dtype``."""
    if cfg["alpha"] != ALPHA:
        raise ValueError(f"the jacobi-2d reference is the diffusion sweep "
                         f"at alpha = {ALPHA} only, got {cfg['alpha']}")
    west = jnp.roll(state, 1, axis=-1)
    east = jnp.roll(state, -1, axis=-1)
    south = jnp.roll(state, -1, axis=-2)
    north = jnp.roll(state, 1, axis=-2)
    return (ALPHA * (state + west + east + south + north)).astype(state.dtype)


def _mass_rows(state: jnp.ndarray) -> jnp.ndarray:
    """Per-row sums of the field: periodic Jacobi conserves the total
    heat (float32 on the device; the harness adds the rows in float64 on
    the host)."""
    return jnp.sum(state, axis=(0, 2))


READBACKS = {"mass": _mass_rows}
