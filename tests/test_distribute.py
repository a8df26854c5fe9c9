"""Multi-device spatial parallelism (`repro.core.distribute`): the device
axis d through model, legalizer, kernels, and explorer.

Load-bearing assertions (ISSUE 3 acceptance criteria):
* the sharded kernel ≡ the single-device kernel, *bitwise*, for
  d ∈ {1, 2, 4} × m ∈ {1, 2} on both shipped apps (lbm, diffusion);
* `Explorer.sweep_tpu` enumerates d ∈ {1, 2, 4} and at least one d > 1
  point sits on the Pareto frontier under the inter-chip bandwidth model;
* `execute_frontier` times multi-device points (and skips points the
  platform has too few devices for);
* legalization is per-shard (halo + VMEM accounted against H/d) and an
  indivisible height is a hard error, in the legalizer and as a model
  infeasibility alike.

The d > 1 cases need real (host) devices:
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — the CI
distribution job sets it; under a plain single-device run they skip.
"""

import numpy as np
import pytest

import jax

from repro.apps import diffusion as dif
from repro.apps import lbm
from repro.core.distribute import (
    ShardedStreamKernel,
    device_axis_values,
    device_mesh,
)
from repro.core.dse import StreamWorkload, TPUModel
from repro.core.legalize import (
    VMEM_BYTES,
    blocking_plan,
    resolve_run_plan,
    shard_height,
    stripe_vmem_bytes,
)

LBM_REGS = (1 / 0.8, 0.0, 1.0)


def _needs_devices(d: int):
    return pytest.mark.skipif(
        jax.device_count() < d,
        reason=f"needs {d} devices "
               f"(XLA_FLAGS=--xla_force_host_platform_device_count=8)",
    )


@pytest.fixture(scope="module")
def lbm_sim():
    return lbm.LBMSimulation(lbm.LBMProblem(16, 64, mode="wrap"))


@pytest.fixture(scope="module")
def dif_sim():
    return dif.DiffusionSimulation(16, 64, alpha=0.2)


# ----------------------- per-shard legalization -----------------------


def test_shard_height_and_indivisible_error():
    assert shard_height(64, 4) == 16
    assert shard_height(64, 1) == 64
    with pytest.raises(ValueError, match="shards"):
        shard_height(30, 4)
    with pytest.raises(ValueError, match="device axis"):
        shard_height(30, 0)


def test_blocking_plan_is_per_shard():
    # d=4 shards of 16 rows: the block must divide the *shard*, not the grid.
    assert blocking_plan(64, 64, 2, d=4) == (16, 2, True)
    assert blocking_plan(64, 12, 2, d=4) == (8, 2, True)  # divisor of 16
    # halo floor applies within the shard: m*halo <= block_h <= h/d.
    bh, m, _ = blocking_plan(64, 4, 8, halo=2, d=4)
    assert bh <= 16 and 16 % bh == 0 and m * 2 <= bh
    # d=1 keeps the exact single-device behavior.
    assert blocking_plan(64, 24, 4) == (16, 4, True)


def test_blocking_plan_indivisible_height_is_an_error():
    with pytest.raises(ValueError, match="shards"):
        blocking_plan(300, 32, 4, d=7)


def test_blocking_plan_vmem_clamp_is_per_shard():
    # A stripe that fits the shard but would not have fit the full grid
    # is irrelevant — VMEM is per chip, accounted against h/d divisors.
    h, width, words = 4096, 720, 10
    bh, m, db = blocking_plan(h, 4096, 4, width=width, words=words, d=4)
    assert 1024 % bh == 0  # a divisor of the shard height
    assert stripe_vmem_bytes(bh, m, width, words,
                             double_buffer=db) <= VMEM_BYTES
    # An over-budget smallest stripe still fails loudly per shard —
    # even the single-buffer streaming fallback cannot fit this one.
    with pytest.raises(ValueError, match="VMEM"):
        blocking_plan(512, 256, 1, width=100_000, words=200, d=2)


def test_resolve_run_plan_threads_d():
    w = StreamWorkload("t", 7, 1, 1, 100, 1000, 64 * 64, grid_w=64)
    pt = TPUModel().evaluate(w, bh=64, m=2, d=4)
    block_h, m, nsteps, db = resolve_run_plan(64, pt, d=4)
    assert 16 % block_h == 0 and m == 2 and nsteps == m and db is True


def test_device_axis_values():
    assert device_axis_values(1) == (1,)
    assert device_axis_values(4) == (1, 2, 4)
    assert device_axis_values(6) == (1, 2, 4)
    assert device_axis_values(8) == (1, 2, 4, 8)
    with pytest.raises(ValueError):
        device_axis_values(0)


# ----------------------- the model's device axis -----------------------


def test_model_marks_indivisible_shards_infeasible():
    w = StreamWorkload("t", 7, 1, 1, 100, 1000, 30 * 64, grid_w=64)  # h=30
    model = TPUModel()
    assert model.evaluate(w, 8, 2, d=2).feasible  # 30 % 2 == 0
    bad = model.evaluate(w, 8, 2, d=4)  # 30 % 4 != 0
    assert not bad.feasible
    assert any("shard" in lim for lim in bad.limits)
    batch = model.evaluate_batch(w, [8, 8], [2, 2], d=[2, 4])
    assert batch["feasible"].tolist() == [True, False]


@pytest.mark.parametrize("make_sim", [
    pytest.param(lambda: lbm.LBMSimulation(lbm.LBMProblem(128, 128)),
                 id="lbm"),
    pytest.param(lambda: dif.DiffusionSimulation(128, 128, alpha=0.2),
                 id="diffusion"),
])
def test_device_axis_reaches_both_apps_frontiers(make_sim):
    """ISSUE 3 acceptance: for both apps the default sweep enumerates
    d ∈ {1, 2, 4} and a d > 1 point is Pareto-optimal under the
    inter-chip bandwidth model."""
    sweep = make_sim().explorer().sweep_tpu(
        bh_values=(8, 16, 32), m_values=(1, 2, 4)
    )
    assert set(np.unique(sweep.data["d"])) == {1, 2, 4}
    frontier = sweep.frontier()
    assert any(p.n > 1 for p in frontier), "no multi-device frontier point"
    assert any(p.n == 1 for p in frontier), "single-device fell off"
    # The collective term prices the halo exchange: d>1 points carry it.
    multi = next(p for p in frontier if p.n > 1)
    assert multi.detail["t_collective_s"] > 0.0


# ----------------------- mesh / kernel plumbing -----------------------


def test_ring_mesh_needs_enough_devices():
    with pytest.raises(ValueError, match="device"):
        device_mesh(jax.device_count() + 1, 1)
    with pytest.raises(ValueError, match="mesh axes"):
        device_mesh(0, 1)


def test_sharded_d1_delegates(dif_sim):
    u0, _ = dif.sine_init(16, 64)
    state = dif_sim.state(u0)
    kern = dif_sim.kernel
    sk = kern.sharded(1)
    assert isinstance(sk, ShardedStreamKernel) and sk.mesh is None
    got = sk.run_blocked(state, (0.2,), steps=2, m=2, block_h=8)
    want = kern.run_blocked(state, (0.2,), steps=2, m=2, block_h=8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@_needs_devices(2)
def test_sharded_rejects_illegal_plans(dif_sim):
    u0, _ = dif.sine_init(16, 64)
    state = dif_sim.state(u0)
    sk = dif_sim.kernel.sharded(2)
    with pytest.raises(ValueError, match="shards"):
        # 16 rows over d=2 is fine, but a 15-row grid is not.
        sk.run_blocked(state[:, :15, :], (0.2,), steps=1, m=1, block_h=5)
    with pytest.raises(ValueError, match="divisible"):
        sk.run_blocked(state, (0.2,), steps=1, m=1, block_h=3)  # 8 % 3
    with pytest.raises(ValueError, match="halo"):
        sk.run_blocked(state, (0.2,), steps=8, m=8, block_h=4)  # m*halo > bh
    with pytest.raises(ValueError, match="multiple"):
        sk.run_blocked(state, (0.2,), steps=3, m=2, block_h=8)


# ----------------------- sharded ≡ single device, bitwise ------------------


@pytest.mark.parametrize("d", [1, 2, 4])
@pytest.mark.parametrize("m", [1, 2])
def test_diffusion_sharded_bitmatch(dif_sim, d, m):
    """ISSUE 3 correctness contract, diffusion: sharded ≡ single-device,
    bit for bit, across fused launches (halo re-exchanged every m)."""
    if jax.device_count() < d:
        pytest.skip(f"needs {d} devices (force host devices in XLA_FLAGS)")
    u0, _ = dif.sine_init(16, 64)
    state = dif_sim.state(u0)
    kern = dif_sim.kernel
    single = kern.run_blocked(state, (0.2,), steps=2 * m, m=m, block_h=4)
    shard = kern.sharded(d).run_blocked(
        state, (0.2,), steps=2 * m, m=m, block_h=4
    )
    np.testing.assert_array_equal(np.asarray(shard), np.asarray(single))


@pytest.mark.parametrize("d", [1, 2, 4])
@pytest.mark.parametrize("m", [1, 2])
def test_lbm_sharded_bitmatch(lbm_sim, d, m):
    """ISSUE 3 correctness contract, lbm (all nine D2Q9 stencils cross
    the shard boundary, fluid lattice)."""
    if jax.device_count() < d:
        pytest.skip(f"needs {d} devices (force host devices in XLA_FLAGS)")
    kern = lbm_sim.stream_kernel()
    f, attr, _ = lbm.taylor_green_init(16, 64)
    state = lbm_sim.stream_state(f, attr)
    single = kern.run_blocked(state, LBM_REGS, steps=2 * m, m=m, block_h=4)
    shard = kern.sharded(d).run_blocked(
        state, LBM_REGS, steps=2 * m, m=m, block_h=4
    )
    np.testing.assert_array_equal(np.asarray(shard), np.asarray(single))


@_needs_devices(4)
def test_lbm_sharded_bitmatch_walls(lbm_sim):
    """Walls + moving lid: the bounce-back mux also crosses shards."""
    kern = lbm_sim.stream_kernel()
    f, attr = lbm.couette_init(16, 64)
    state = lbm_sim.stream_state(f, attr)
    regs = (1 / 0.9, 0.07, 1.0)
    single = kern.run_blocked(state, regs, steps=4, m=2, block_h=4)
    shard = kern.sharded(4).run_blocked(state, regs, steps=4, m=2, block_h=4)
    np.testing.assert_array_equal(np.asarray(shard), np.asarray(single))


@_needs_devices(2)
def test_diffusion_app_runs_end_to_end_sharded(dif_sim):
    """The app-level driver runs sharded and keeps the right physics
    (jnp oracle), not just kernel-vs-kernel equality."""
    u0, _ = dif.sine_init(16, 64)
    got = dif_sim.run(u0, 4, m=2, d=2)
    want = dif.diffusion_ref_run(u0, 0.2, 4)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=1e-6
    )
    # ...and bit-matches the single-device app run.
    single = dif_sim.run(u0, 4, m=2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(single))


@_needs_devices(2)
def test_sharded_run_for_point_legalizes_per_shard(dif_sim):
    """run_for_point legalizes against the shard height and the result
    still bit-matches the single-device run of the same plan."""
    ex = dif_sim.explorer()
    sweep = ex.sweep_tpu(bh_values=(8, 16), m_values=(1, 2), d_values=(2,))
    pt = sweep.best("sustained_gflops")
    u0, _ = dif.sine_init(16, 64)
    state = dif_sim.state(u0)
    sk = dif_sim.kernel.sharded(2)
    out, (bh, m, db) = sk.run_for_point(state, (0.2,), point=pt)
    assert 8 % bh == 0  # divisor of the shard height 16/2
    want = dif_sim.kernel.run_blocked(
        state, (0.2,), steps=m, m=m, block_h=bh, double_buffer=db
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


# ----------------------- explorer: timing multi-device points ---------------


@_needs_devices(4)
def test_execute_frontier_times_multi_device_points():
    """ISSUE 3 acceptance: execute_frontier runs d > 1 frontier points
    through the sharded kernel on forced host devices. The grid is tall
    enough (256 rows) that sharding beats the halo-exchange cost in the
    model — on a toy grid d > 1 is *correctly* dominated and never
    reaches the frontier."""
    sim = dif.DiffusionSimulation(256, 64, alpha=0.2)
    ex = sim.explorer()
    sweep = ex.sweep_tpu(bh_values=(32, 64), m_values=(1, 2))
    u0, _ = dif.sine_init(256, 64)
    runs = ex.execute_frontier(sweep, sim.state(u0), (0.2,), k=3)
    assert runs, "no frontier point executed"
    assert any(r.d > 1 for r in runs), "no multi-device point was timed"
    for r in runs:
        assert (256 // r.d) % r.block_h == 0  # per-shard legal plan
        assert r.wall_s > 0 and np.isfinite(r.rel_error)


def test_execute_frontier_warns_when_device_starved():
    """On a tall grid the frontier can be all-d>1; a platform without
    the devices gets an explanatory warning, not a silent empty list."""
    sim = dif.DiffusionSimulation(256, 64, alpha=0.2)
    ex = sim.explorer()
    sweep = ex.sweep_tpu(bh_values=(32, 64), m_values=(1, 2))
    assert all(p.n > 1 for p in sweep.frontier())  # the starved scenario
    u0, _ = dif.sine_init(256, 64)
    with pytest.warns(RuntimeWarning, match="device"):
        runs = ex.execute_frontier(
            sweep, sim.state(u0), (0.2,), k=2, max_devices=1
        )
    assert runs == []


def test_execute_frontier_skips_points_beyond_device_count(dif_sim):
    """Points needing more shards than the platform has devices are
    skipped, not fatal — the walk continues down the frontier."""
    ex = dif_sim.explorer()
    sweep = ex.sweep_tpu(bh_values=(4, 8), m_values=(1, 2))
    u0, _ = dif.sine_init(16, 64)
    runs = ex.execute_frontier(
        sweep, dif_sim.state(u0), (0.2,), k=2, max_devices=1
    )
    assert runs and all(r.d == 1 for r in runs)
