"""uLBM D2Q9: the paper's lattice-Boltzmann PE as a benchmark app.

What this adapter gives the harness:

* :func:`system` -- the system under test, built through the program's
  own path: ``LBMSimulation`` compiles the SPD PE (collide, Trans2D,
  bounce-back) into the codegen'd stream kernel and its ``Explorer``.
* :func:`init_state` -- the ``(10, H, W)`` float32 state from a PRNG
  key: a Taylor-Green vortex with seeded density noise, seeded solid
  discs (bounce-back) and a moving-wall band (the lid correction). Pure
  ``jax.numpy``, so the harness builds it on the device, in its
  sharding, in one jitted call.
* :func:`step` -- the plain reference, one time step in
  ``jax.numpy`` in the state's own dtype. It is the benchmark's copy of
  the textbook D2Q9 BGK update and imports nothing from the program;
  every seed gives the same work, only the wall layout moves.
* :data:`READBACKS` -- the diagnostics a user's loop reads back.

Lattice convention: e0=(0,0) e1=(1,0) e2=(0,1) e3=(-1,0) e4=(0,-1)
e5=(1,1) e6=(-1,1) e7=(-1,-1) e8=(1,-1); axis 0 of a field is y, axis 1
is x; channels 0-8 hold the populations, channel 9 the site attribute
(0 fluid, 1 solid wall, 2 moving wall with velocity ``u_lid`` in +x).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EX = (0, 1, 0, -1, 0, 1, -1, -1, 1)
EY = (0, 0, 1, 0, -1, 1, 1, -1, -1)
W = (4 / 9,) + (1 / 9,) * 4 + (1 / 36,) * 4
OPP = (0, 3, 4, 1, 2, 7, 8, 5, 6)
WORDS = 10


def system(cfg: dict):
    """``(kernel, explorer, regs)`` for the configured problem."""
    from repro.apps import lbm

    h, w = cfg["grid"]
    sim = lbm.LBMSimulation(
        lbm.LBMProblem(h, w, tau=cfg["tau"], u_lid=cfg["u_lid"]))
    return sim.stream_kernel(), sim.explorer(), sim.stream_regs()


def _equilibrium(rho, ux, uy):
    usq = ux * ux + uy * uy
    out = []
    for i in range(9):
        cu = EX[i] * ux + EY[i] * uy
        out.append(W[i] * rho * (1.0 + 3.0 * cu + 4.5 * cu * cu - 1.5 * usq))
    return out


def init_state(cfg: dict, key) -> jnp.ndarray:
    """Seeded initial state, ``(10, H, W)`` float32.

    The flow is a Taylor-Green vortex of peak speed ``u0`` with its
    phase and the density noise (amplitude ``perturbation``) drawn from
    ``key``; ``walls.discs`` solid discs of radius in ``walls.radius``
    sit at seeded centres, and the top ``walls.lid_rows`` rows are a
    moving wall. The shapes, and so the work, are the same for every
    seed.
    """
    h, w = cfg["grid"]
    walls = cfg["walls"]
    k_phase, k_rho, k_disc, k_rad = jax.random.split(key, 4)
    y = jax.lax.broadcasted_iota(jnp.float32, (h, w), 0)
    x = jax.lax.broadcasted_iota(jnp.float32, (h, w), 1)
    py, px = jax.random.uniform(k_phase, (2,), jnp.float32, 0.0, 2 * math.pi)
    kx, ky = 2 * math.pi / w, 2 * math.pi / h
    u0 = cfg["u0"]
    ux = -u0 * jnp.cos(kx * x + px) * jnp.sin(ky * y + py)
    uy = u0 * (kx / ky) * jnp.sin(kx * x + px) * jnp.cos(ky * y + py)
    rho = 1.0 + cfg["perturbation"] * jax.random.uniform(
        k_rho, (h, w), jnp.float32, -1.0, 1.0)

    n = walls["discs"]
    rmin, rmax = walls["radius"]
    rad = jax.random.uniform(k_rad, (n,), jnp.float32, rmin, rmax)
    cen = jax.random.uniform(k_disc, (n, 2), jnp.float32)
    cy = rmax + cen[:, 0] * (h - 2 * rmax)
    cx = rmax + cen[:, 1] * (w - 2 * rmax)
    solid = jnp.zeros((h, w), bool)
    for i in range(n):
        solid |= (y - cy[i]) ** 2 + (x - cx[i]) ** 2 <= rad[i] ** 2
    attr = jnp.where(solid, 1.0, 0.0)
    attr = jnp.where(y < walls["lid_rows"], 2.0, attr).astype(jnp.float32)
    fluid = attr < 0.5
    f = _equilibrium(rho, jnp.where(fluid, ux, 0.0), jnp.where(fluid, uy, 0.0))
    return jnp.stack(f + [attr])


def step(cfg: dict, state: jnp.ndarray) -> jnp.ndarray:
    """One reference time step: BGK collision on fluid sites, periodic
    streaming, full-way bounce-back with the moving-wall correction
    ``6 w_i rho0 (e_i . u_lid)``. Computed in ``state.dtype``."""
    one_tau = 1.0 / cfg["tau"]
    u_lid = cfg["u_lid"]
    f = [state[i] for i in range(9)]
    attr = state[9]
    rho = f[0] + f[1] + f[2] + f[3] + f[4] + f[5] + f[6] + f[7] + f[8]
    inv = 1.0 / rho
    ux = (f[1] + f[5] + f[8] - f[3] - f[6] - f[7]) * inv
    uy = (f[2] + f[5] + f[6] - f[4] - f[7] - f[8]) * inv
    feq = _equilibrium(rho, ux, uy)
    fluid = attr < 0.5
    post = [jnp.where(fluid, f[i] - one_tau * (f[i] - feq[i]), f[i])
            for i in range(9)]
    streamed = [jnp.roll(post[i], (EY[i], EX[i]), axis=(0, 1))
                for i in range(9)]
    solid = attr >= 0.5
    moving = attr >= 1.5
    out = []
    for i in range(9):
        refl = streamed[OPP[i]]
        corr = 6.0 * W[i] * EX[i] * u_lid  # rho0 = 1
        bb = jnp.where(moving, refl + corr, refl) if corr else refl
        out.append(jnp.where(solid, bb, streamed[i]).astype(state.dtype))
    return jnp.stack(out + [attr])


def _mass_rows(state: jnp.ndarray) -> jnp.ndarray:
    """Per-row sums of the nine populations (float32 on the device; the
    harness adds the rows in float64 on the host)."""
    return jnp.sum(state[:9], axis=(0, 2))


READBACKS = {"mass": _mass_rows}
