"""Explorer engine: batched sweeps, Pareto frontiers, and the
model -> Pallas-kernel measurement loop.

The load-bearing assertions (ISSUE 1 acceptance criteria):
* the FPGA Pareto sweep recovers the paper's best configuration (1, 4);
* batched evaluation agrees with the scalar model point-for-point;
* no point returned by ``frontier()`` is dominated by any feasible point.
"""

import numpy as np
import pytest

from repro.core.dse import FPGAModel, StreamWorkload, TPUModel
from repro.core.explorer import (
    DEFAULT_MAXIMIZE,
    DEFAULT_OBJECTIVES,
    Explorer,
    pareto_mask,
)
from repro.kernels.lbm_stream.ops import blocking_plan

# The paper's LBM pipeline (same literal as tests/test_dse.py).
LBM_W = StreamWorkload(
    name="lbm-x1",
    flops_per_elem=131,
    words_in=10,
    words_out=10,
    depth=855,
    buffer_bits=573_370 - 80_000,
    elems=720 * 300,
    grid_w=720,
)
LBM_CENSUS = {"add": 70, "mul": 60, "div": 1}

# A small family of synthetic workloads for property-style frontier checks:
# light/heavy compute, narrow/wide streams, shallow/deep pipelines.
WORKLOADS = [
    LBM_W,
    StreamWorkload("light", 16, 2, 2, 64, 40_000, 100_000, grid_w=500),
    StreamWorkload("wide-io", 200, 24, 24, 1200, 900_000, 720 * 300, grid_w=720),
    StreamWorkload("deep", 64, 6, 6, 4000, 200_000, 50_000, grid_w=250),
]


@pytest.fixture(scope="module")
def explorer():
    return Explorer(LBM_W, census=LBM_CENSUS)


# ----------------------- pareto_mask primitive -----------------------


def test_pareto_mask_hand_case():
    # (throughput up, cost down): c dominated by a; d dominated by b.
    pts = np.array([[10, 5], [8, 2], [9, 5], [7, 3]], dtype=float)
    mask = pareto_mask(pts, maximize=(True, False))
    assert mask.tolist() == [True, True, False, False]


def test_pareto_mask_duplicates_survive():
    pts = np.array([[1.0, 1.0], [1.0, 1.0]])
    assert pareto_mask(pts, maximize=(True, True)).all()


def test_pareto_mask_single_objective_is_argmax():
    v = np.array([3.0, 9.0, 9.0, 1.0])
    assert pareto_mask(v[:, None], maximize=(True,)).tolist() == [
        False, True, True, False,
    ]


def test_pareto_mask_excludes_non_finite_rows():
    """NaN compares False against everything, so a NaN row used to be
    'never dominated' and polluted the frontier; non-finite rows must be
    masked out up front — even an inf row that would dominate."""
    pts = np.array(
        [[np.nan, 1.0], [1.0, 2.0], [np.inf, 0.0], [2.0, 1.0]]
    )
    mask = pareto_mask(pts, maximize=(True, True))
    assert mask.tolist() == [False, True, False, True]
    assert not pareto_mask(np.full((3, 2), np.nan)).any()
    # all-finite behavior is unchanged
    ok = np.array([[1.0, 2.0], [2.0, 1.0], [0.5, 0.5]])
    assert pareto_mask(ok, maximize=(True, True)).tolist() == [
        True, True, False,
    ]


# ----------------------- batched == scalar -----------------------


def test_fpga_batched_matches_scalar_point_for_point(explorer):
    sweep = explorer.sweep_fpga(
        n_values=(1, 2, 3, 4, 6, 8), m_values=(1, 2, 3, 4, 6, 8)
    )
    model = FPGAModel()
    assert len(sweep) == 36
    for i in range(len(sweep)):
        n, m = int(sweep.data["n"][i]), int(sweep.data["m"][i])
        pt = model.evaluate(LBM_W, n, m, LBM_CENSUS)
        assert pt.feasible == bool(sweep.data["feasible"][i])
        for key, want in [
            ("peak_gflops", pt.peak_gflops),
            ("utilization", pt.utilization),
            ("sustained_gflops", pt.sustained_gflops),
            ("power_w", pt.power_w),
            ("perf_per_watt", pt.perf_per_watt),
            ("alms", pt.detail["alms"]),
            ("dsps", pt.detail["dsps"]),
            ("bram_bits", pt.detail["bram_bits"]),
            ("u_bw", pt.detail["u_bw"]),
            ("depth", pt.detail["depth"]),
        ]:
            assert sweep.data[key][i] == pytest.approx(want, rel=1e-12), (
                key, n, m,
            )


def test_fpga_batched_matches_scalar_non_overlapped(explorer):
    sweep = explorer.sweep_fpga(
        n_values=(1, 2), m_values=(1, 8), overlapped_passes=False
    )
    model = FPGAModel()
    for i in range(len(sweep)):
        n, m = int(sweep.data["n"][i]), int(sweep.data["m"][i])
        pt = model.evaluate(LBM_W, n, m, LBM_CENSUS, overlapped_passes=False)
        assert sweep.data["utilization"][i] == pytest.approx(
            pt.utilization, rel=1e-12
        )
        # point() materialization must thread the flag through too
        assert sweep.point(i).utilization == pytest.approx(
            pt.utilization, rel=1e-12
        )


def test_tpu_batched_matches_scalar_point_for_point(explorer):
    sweep = explorer.sweep_tpu(
        bh_values=(8, 32, 256, 4096),
        m_values=(1, 4, 64),
        d_values=(1, 4),
    )
    model = TPUModel()
    assert len(sweep) == 24
    for i in range(len(sweep)):
        bh = int(sweep.data["block_rows"][i])
        m = int(sweep.data["m"][i])
        chips = int(sweep.data["n"][i])
        pt = model.evaluate(LBM_W, bh, m, d=chips)
        assert pt.feasible == bool(sweep.data["feasible"][i])
        for key, want in [
            ("peak_gflops", pt.peak_gflops),
            ("utilization", pt.utilization),
            ("sustained_gflops", pt.sustained_gflops),
            ("power_w", pt.power_w),
            ("perf_per_watt", pt.perf_per_watt),
            ("vmem_bytes", pt.detail["vmem_bytes"]),
            ("t_compute_s", pt.detail["t_compute_s"]),
            ("t_memory_s", pt.detail["t_memory_s"]),
            ("t_collective_s", pt.detail["t_collective_s"]),
            ("arithmetic_intensity", pt.detail["arithmetic_intensity"]),
        ]:
            assert sweep.data[key][i] == pytest.approx(want, rel=1e-12), (
                key, bh, m, chips,
            )
        # one spelling for the binding resource, scalar ≡ batch verbatim
        bound = str(sweep.data["bound"][i])
        assert bound.endswith("-bound")
        assert bound in pt.limits


# ----------------------- frontier properties -----------------------


def _dominates(a, b, maximize) -> bool:
    better_eq = all(
        (x >= y) if mx else (x <= y) for x, y, mx in zip(a, b, maximize)
    )
    strictly = any(
        (x > y) if mx else (x < y) for x, y, mx in zip(a, b, maximize)
    )
    return better_eq and strictly


@pytest.mark.parametrize("w", WORKLOADS, ids=lambda w: w.name)
@pytest.mark.parametrize("target", ["fpga", "tpu"])
def test_no_frontier_point_is_dominated(w, target):
    ex = Explorer(w, census=LBM_CENSUS if w is LBM_W else None)
    sweep = ex.sweep(target)
    mask = sweep.pareto_mask()
    X = sweep.metrics(DEFAULT_OBJECTIVES)
    feas = sweep.feasible
    for i in np.flatnonzero(mask):
        for j in np.flatnonzero(feas):
            assert not _dominates(X[j], X[i], DEFAULT_MAXIMIZE), (
                f"frontier point {i} dominated by {j}"
            )


@pytest.mark.parametrize("w", WORKLOADS, ids=lambda w: w.name)
def test_every_off_frontier_point_is_dominated(w):
    """Completeness: a feasible point off the frontier has a dominator."""
    ex = Explorer(w, census=LBM_CENSUS if w is LBM_W else None)
    sweep = ex.sweep_fpga()
    mask = sweep.pareto_mask()
    X = sweep.metrics(DEFAULT_OBJECTIVES)
    feas = sweep.feasible
    for i in np.flatnonzero(feas & ~mask):
        assert any(
            _dominates(X[j], X[i], DEFAULT_MAXIMIZE)
            for j in np.flatnonzero(feas)
        ), f"off-frontier point {i} has no dominator"


def test_fpga_frontier_recovers_paper_winner(explorer):
    """The paper's 'best among them': (n, m) = (1, 4) on the Stratix V."""
    sweep = explorer.sweep_fpga(
        n_values=(1, 2, 4, 8), m_values=(1, 2, 4, 8)
    )
    frontier_keys = {p.key() for p in sweep.frontier()}
    assert (1, 4) in frontier_keys
    best = sweep.best("perf_per_watt")
    assert best.key() == (1, 4)
    assert best.perf_per_watt == pytest.approx(2.416, rel=0.03)
    assert sweep.best("sustained_gflops").key() == (1, 4)


def test_frontier_sorted_and_feasible(explorer):
    pts = explorer.sweep_fpga().frontier()
    assert all(p.feasible for p in pts)
    sus = [p.sustained_gflops for p in pts]
    assert sus == sorted(sus, reverse=True)


def test_tpu_frontier_prefers_temporal_blocking(explorer):
    """m=1 (no temporal reuse) is memory-bound and never frontier-best."""
    sweep = explorer.sweep_tpu(d_values=(1,))
    best = sweep.best("sustained_gflops")
    assert best.m > 1
    assert "compute-bound" in best.limits


def test_deprecated_spellings_are_gone(explorer):
    """The PR-3-era deprecated spellings (chip_values on the sweep,
    n_chips on the model, the module-level execute_frontier wrapper)
    have completed their deprecation cycle and are removed."""
    with pytest.raises(TypeError, match="chip_values"):
        explorer.sweep_tpu(bh_values=(8,), m_values=(1,), chip_values=(1, 2))
    with pytest.raises(TypeError, match="n_chips"):
        TPUModel().evaluate(LBM_W, 8, 1, n_chips=2)
    import repro.core.explorer as exp_mod

    assert not hasattr(exp_mod, "execute_frontier")


def test_tpu_default_sweep_enumerates_device_axis(explorer):
    """The default TPU lattice carries the device axis d ∈ {1, 2, 4} and
    scaling out stays on the frontier (more chips, more throughput)."""
    sweep = explorer.sweep_tpu()
    assert set(np.unique(sweep.data["d"])) == {1, 2, 4}
    np.testing.assert_array_equal(sweep.data["d"], sweep.data["n"])
    frontier = sweep.frontier()
    assert any(p.n > 1 for p in frontier)
    best = sweep.best("sustained_gflops")
    assert best.n == 4  # throughput scales with the device axis
    assert best.m > 1  # ...but temporal blocking still pays


def test_tpu_sweep_point_threads_d_and_scalar_kwargs(explorer):
    """Sweep.point must re-materialize TPU points via the d= spelling
    and thread scalar kwargs (double_buffer) like the FPGA branch does
    — it used to drop both, silently diverging from the batch arrays."""
    sweep = explorer.sweep_tpu(
        bh_values=(8, 16), m_values=(2,), d_values=(1, 2),
        double_buffer=False,
    )
    assert sweep.scalar_kwargs == {"double_buffer": False}
    model = TPUModel()
    for i in range(len(sweep)):
        pt = sweep.point(i)
        d = int(sweep.data["d"][i])
        assert pt.n == d and pt.detail["d"] == d  # device axis preserved
        want = model.evaluate(
            LBM_W,
            int(sweep.data["block_rows"][i]),
            int(sweep.data["m"][i]),
            d=d,
            double_buffer=False,
        )
        # double_buffer reached both the batch arrays and the scalar path
        assert pt.detail["vmem_bytes"] == want.detail["vmem_bytes"]
        assert sweep.data["vmem_bytes"][i] == want.detail["vmem_bytes"]


def test_top_returns_k_best_feasible(explorer):
    sweep = explorer.sweep_fpga()
    top2 = sweep.top(2, key="perf_per_watt")
    assert len(top2) == 2
    assert top2[0].perf_per_watt >= top2[1].perf_per_watt
    assert all(p.feasible for p in top2)


# ----------------------- compile -> explore plumbing -----------------------


def test_explorer_from_compiled_core():
    from repro.apps import lbm

    sim = lbm.LBMSimulation(lbm.LBMProblem(32, 64, mode="wrap"))
    w = sim.stream_workload()
    assert w.elems == 32 * 64 and w.grid_w == 64
    assert w.flops_per_elem == sim.hardware_report.flops
    ex = sim.explorer()
    assert ex.census == sim.hardware_report.census
    best = ex.sweep_fpga().best("perf_per_watt")
    assert best.feasible


def test_hardware_report_workload_roundtrip():
    from repro.apps import lbm

    sim = lbm.LBMSimulation(lbm.LBMProblem(32, 64, mode="wrap"))
    w1 = sim.hardware_report.workload(elems=2048, grid_w=64)
    w2 = StreamWorkload.from_report(sim.hardware_report, elems=2048, grid_w=64)
    assert w1 == w2


# ----------------------- blocking legalization -----------------------


def test_blocking_plan_legalizes():
    assert blocking_plan(64, 64, 4) == (64, 4, True)
    assert blocking_plan(64, 256, 4) == (64, 4, True)  # clamp to grid
    assert blocking_plan(64, 24, 4) == (16, 4, True)  # nearest divisor below
    # m forces the block up to the next tile-aligned divisor
    assert blocking_plan(48, 8, 12) == (16, 12, True)
    bh, m, _ = blocking_plan(40, 7, 4)
    assert 40 % bh == 0 and bh % 8 == 0 and m <= bh
    # no 8-row-aligned block divides 30 rows: an error, not a guess
    with pytest.raises(ValueError, match="8-row tiles"):
        blocking_plan(30, 7, 4)


# ----------------------- execution loop (interpret mode) -----------------------


def test_run_factory_path_gets_vmem_stripe_check(explorer):
    """Regression (ISSUE 4): the custom run_factory path used to call
    resolve_run_plan with width=0, words=0, silently skipping the VMEM
    stripe clamp the codegen path gets. On a 30000-wide grid the
    (64, 8) stripe is over budget, so both paths must legalize it down
    identically."""
    from repro.core.legalize import resolve_run_plan, stripe_vmem_bytes

    sweep = explorer.sweep_tpu(
        bh_values=(64,), m_values=(8,), d_values=(1,)
    )
    seen = []

    def rf(nsteps, m, block_h, d, double_buffer=True):
        seen.append((block_h, m, nsteps, d))
        return lambda: None

    h, w = 256, 30_000
    runs = explorer.__class__(sweep.workload).execute_frontier(
        sweep, run_factory=rf, grid_shape=(h, w), k=1, reps=1,
        calibrate=False,
    )
    assert len(runs) == 1 and seen
    r = runs[0]
    assert r.block_h < 64  # the over-budget stripe was clamped
    from repro.core.legalize import VMEM_BYTES

    assert stripe_vmem_bytes(
        r.block_h, r.m, w, sweep.workload.words_in, sweep.workload.halo,
        double_buffer=r.double_buffer,
    ) <= VMEM_BYTES
    want = resolve_run_plan(
        h, r.point, None, halo=sweep.workload.halo, width=w,
        words=sweep.workload.words_in, d=1,
    )
    # identical to codegen path (incl. the buffer protocol)
    assert (r.block_h, r.m, r.steps, r.double_buffer) == want
    assert seen[-1] == (r.block_h, r.m, r.steps, 1)


def test_execute_frontier_closes_the_loop_hand_written_kernel():
    """The hand-written lbm_stream kernel plugs into the one timing path
    via run_factory (the former module-level wrapper's job, now a
    caller-side four-liner). Single-device only: d > 1 plans return
    None and are skipped."""
    from repro.apps import lbm
    from repro.kernels.lbm_stream.ops import lbm_run_blocked

    sim = lbm.LBMSimulation(lbm.LBMProblem(16, 32, mode="wrap"))
    sweep = sim.explorer().sweep_tpu(bh_values=(8, 16), m_values=(1, 2))
    f, attr, _ = lbm.taylor_green_init(16, 32)

    def run_factory(nsteps, m, block_h, d, double_buffer=True):
        if d != 1:
            return None  # the hand-written kernel has no sharded form
        return lambda: lbm_run_blocked(
            f, attr, 1 / 0.8, 0.0,
            steps=nsteps, m=m, block_h=block_h, interpret=True,
        )

    runs = Explorer(sweep.workload).execute_frontier(
        sweep, k=2, interpret=True, run_factory=run_factory,
        grid_shape=(16, 32), cache_tag="lbm_stream",
    )
    assert 1 <= len(runs) <= 2
    for r in runs:
        assert r.d == 1
        assert 16 % r.block_h == 0 and r.m <= r.block_h
        assert r.wall_s > 0 and r.measured_mlups > 0
        assert np.isfinite(r.rel_error)
        assert r.predicted_gflops == pytest.approx(
            r.point.sustained_gflops
        )


def test_execute_frontier_rejects_fpga_sweep(explorer):
    import jax.numpy as jnp

    sweep = explorer.sweep_fpga()
    dummy = jnp.zeros((9, 8, 16), jnp.float32)
    with pytest.raises(ValueError, match="TPU sweep"):
        explorer.execute_frontier(sweep, dummy, dummy[0])


def test_lbm_run_for_point_matches_reference():
    from repro.apps import lbm
    from repro.kernels.lbm_stream.ops import (
        lbm_multistep_ref,
        lbm_run_for_point,
    )

    sim = lbm.LBMSimulation(lbm.LBMProblem(16, 32, mode="wrap"))
    pt = sim.explorer().sweep_tpu(
        bh_values=(8, 16), m_values=(2, 4)
    ).best("sustained_gflops")
    f, attr, _ = lbm.taylor_green_init(16, 32)
    out, (bh, m) = lbm_run_for_point(f, attr, 1 / 0.8, pt, interpret=True)
    assert 16 % bh == 0 and m == pt.m
    want = lbm_multistep_ref(f, attr, 1 / 0.8, 0.0, m=m)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
