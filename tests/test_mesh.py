"""2-D device mesh (dy × dx): the mesh shape through kernels, model,
legalizer, search, and study identity (DESIGN.md §15).

Load-bearing assertions (ISSUE 10 acceptance criteria):

* 2-D-sharded execution ≡ single-device execution, *bitwise*, across
  the mesh matrix {(1,2), (2,1), (2,2), (1,4), (4,1), (2,4)} ×
  m ∈ {1, 2} × double_buffer ∈ {on, off} on both shipped apps
  (diffusion; lbm fluid and couette walls) — the column-halo
  ``ppermute`` exchange plus corner second hop is a scheduling choice,
  never a numerics choice;
* model and legalizer price the same ``(H/dy, W/dx)`` shard geometry
  (one ``stripe_vmem_bytes``, guard columns included) so the two
  cannot drift;
* pre-mesh study journals (``d``-only trial records) resume into the
  ``(dy, dx)`` identity with **zero** re-measurement;
* the minimal parallel-trial seam: ``SearchRunner.prefetch`` warms the
  next candidate on idle devices and ``measure`` joins the warm-up
  before its timed reps start (timings never overlap).

The d > 1 cases need real (host) devices:
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — the CI
distribution job sets it; under a plain single-device run they skip.
"""

import json
from pathlib import Path

import numpy as np
import pytest

import jax

from _search_harness import TOY, ModelTimer, _rf

from repro.apps import diffusion as dif
from repro.apps import lbm
from repro.core.dse import StreamWorkload, TPUModel
from repro.core.explorer import Explorer
from repro.core.legalize import (
    VMEM_BYTES,
    blocking_plan,
    legal_block_values,
    mesh_shape,
    shard_width,
    stripe_vmem_bytes,
)
from repro.core.search import (
    BudgetExhausted,
    ExhaustiveSearch,
    RunPlan,
    SearchRunner,
    SearchStepper,
)

#: The ISSUE 10 mesh matrix: row-only, column-only, and genuinely 2-D
#: factorizations, up to the CI job's 8 forced host devices.
MESHES = ((1, 2), (2, 1), (2, 2), (1, 4), (4, 1), (2, 4))

LBM_FLUID_REGS = (1 / 0.8, 0.0, 1.0)
LBM_COUETTE_REGS = (1 / 0.9, 0.07, 1.0)


@pytest.fixture(scope="module")
def lbm_sim():
    return lbm.LBMSimulation(lbm.LBMProblem(16, 64, mode="wrap"))


@pytest.fixture(scope="module")
def dif_sim():
    return dif.DiffusionSimulation(16, 64, alpha=0.2)


def _mesh_case(kern, state, regs, dy, dx, m, db):
    """sharded((dy, dx)) ≡ single-device, bit for bit, same plan."""
    d = dy * dx
    if jax.device_count() < d:
        pytest.skip(f"needs {d} devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    single = kern.run_blocked(state, regs, steps=2 * m, m=m, block_h=2,
                              double_buffer=db)
    meshed = kern.sharded(d, dx=dx).run_blocked(
        state, regs, steps=2 * m, m=m, block_h=2, double_buffer=db
    )
    np.testing.assert_array_equal(np.asarray(meshed), np.asarray(single))


# ----------------------- the bit-match matrix -----------------------


@pytest.mark.parametrize("db", [True, False], ids=["db", "single"])
@pytest.mark.parametrize("m", [1, 2])
@pytest.mark.parametrize("dy,dx", MESHES, ids=[f"{a}x{b}" for a, b in MESHES])
def test_diffusion_mesh_bitmatch(dif_sim, dy, dx, m, db):
    u0, _ = dif.sine_init(16, 64)
    _mesh_case(dif_sim.kernel, dif_sim.state(u0), (0.2,), dy, dx, m, db)


@pytest.mark.parametrize("db", [True, False], ids=["db", "single"])
@pytest.mark.parametrize("m", [1, 2])
@pytest.mark.parametrize("dy,dx", MESHES, ids=[f"{a}x{b}" for a, b in MESHES])
def test_lbm_fluid_mesh_bitmatch(lbm_sim, dy, dx, m, db):
    """All nine D2Q9 stencils cross both shard boundaries — the corner
    second hop is load-bearing for every diagonal population."""
    f, attr, _ = lbm.taylor_green_init(16, 64)
    _mesh_case(lbm_sim.stream_kernel(), lbm_sim.stream_state(f, attr),
               LBM_FLUID_REGS, dy, dx, m, db)


@pytest.mark.parametrize("db", [True, False], ids=["db", "single"])
@pytest.mark.parametrize("m", [1, 2])
@pytest.mark.parametrize("dy,dx", MESHES, ids=[f"{a}x{b}" for a, b in MESHES])
def test_lbm_couette_mesh_bitmatch(lbm_sim, dy, dx, m, db):
    """Walls + moving lid: the bounce-back mux crosses column shards."""
    f, attr = lbm.couette_init(16, 64)
    _mesh_case(lbm_sim.stream_kernel(), lbm_sim.stream_state(f, attr),
               LBM_COUETTE_REGS, dy, dx, m, db)


# ----------------------- legalizer mesh geometry -----------------------


def test_shard_width_and_mesh_shape():
    assert shard_width(64, 4) == 16
    assert shard_width(64, 1) == 64
    with pytest.raises(ValueError, match="shards"):
        shard_width(30, 4)
    with pytest.raises(ValueError, match="column device axis"):
        shard_width(30, 0)
    assert mesh_shape(8, 4) == (2, 4)
    assert mesh_shape(4, 1) == (4, 1)
    assert mesh_shape(1, 1) == (1, 1)
    with pytest.raises(ValueError, match="mesh"):
        mesh_shape(8, 3)


def test_run_plan_from_dict_defaults_the_mesh_axis():
    """Pre-mesh plan dicts (PR-6/PR-9 journals) parse as the 1-D ring."""
    plan = RunPlan.from_dict({"block_h": 8, "m": 2, "steps": 2, "d": 4,
                              "reps": 3, "double_buffer": True})
    assert plan.dx == 1
    assert plan.key() == RunPlan(8, 2, 2, 4, 3, True, 1, "", 1).key()


# ----------------------- model ↔ legalizer drift -----------------------


def test_model_and_legalizer_agree_on_shard_geometry():
    """ISSUE 10 satellite: both account the same (H/dy, W/dx) shard —
    one stripe_vmem_bytes, guard columns included, so dse.py and
    legalize.py cannot drift on the mesh geometry."""
    model = TPUModel()
    w = StreamWorkload("t", 7, 3, 3, 100, 1000, 256 * 640,
                       grid_w=640, halo=1)
    for d, dx in ((2, 1), (4, 2), (8, 4), (4, 4), (8, 8)):
        dy = d // dx
        for bh, m in ((8, 1), (32, 4)):
            pt = model.evaluate(w, bh, m, d=d, dx=dx)
            assert pt.detail["dy"] == dy and pt.detail["dx"] == dx
            guard = w.halo if dx > 1 else 0
            assert pt.detail["vmem_bytes"] == stripe_vmem_bytes(
                bh, m, shard_width(640, dx), 3, halo=1,
                double_buffer=True, halo_x=guard,
            )
            # The legalizer's divisor chain runs over the same shard
            # height and prices the same guarded stripe.
            legal = legal_block_values(256, m, halo=1, width=640,
                                       words=3, d=d, dx=dx, halo_x=1)
            assert legal and all((256 // dy) % v == 0 for v in legal)
            bh2, m2, db2 = blocking_plan(256, bh, m, width=640, words=3,
                                         d=d, dx=dx, halo_x=1)
            assert (256 // dy) % bh2 == 0
            assert stripe_vmem_bytes(
                bh2, m2, shard_width(640, dx), 3, 1, db2, halo_x=guard
            ) <= VMEM_BYTES


def test_model_prices_the_sharded_launch_geometry():
    """The model's recompute geometry under a (2, 2) mesh is the one the
    shard launches allocate: block_h × shard width useful sites out of
    the stripe buffer's rows × guard-extended columns."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    import jax.numpy as jnp

    kern = dif.DiffusionSimulation(64, 256).kernel
    sk = kern.sharded(4, dx=2)
    w = StreamWorkload("t", 7, 1, 1, 100, 1000, 64 * 256, grid_w=256,
                       halo=kern.halo)

    def calls(jx):
        for e in jx.eqns:
            if e.primitive.name == "pallas_call":
                yield e
            for v in e.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    yield from calls(inner)

    state = jnp.zeros((1, 64, 256), jnp.float32)
    for bh, m in ((8, 1), (16, 2), (32, 3)):
        fn = sk._fn(m, m, bh, True, True)
        (eqn,) = calls(jax.make_jaxpr(fn)(state, kern._scal((0.2,))).jaxpr)
        rows, cols = [v.aval for v in eqn.params["jaxpr"].invars
                      if "vmem" in str(v.aval)][0].shape[-2:]
        pt = TPUModel().evaluate(w, bh, m, d=4, dx=2)
        assert pt.detail["halo_useful_fraction"] == pytest.approx(
            bh * 128 / (rows * cols), rel=1e-12)


def test_model_marks_bad_meshes_infeasible():
    model = TPUModel()
    w = StreamWorkload("t", 7, 1, 1, 100, 1000, 64 * 70, grid_w=70)
    bad = model.evaluate(w, 8, 1, d=4, dx=3)  # 4 % 3 != 0
    assert not bad.feasible
    assert any("mesh" in s for s in bad.limits)
    badw = model.evaluate(w, 8, 1, d=4, dx=4)  # 70 % 4 != 0
    assert not badw.feasible
    assert any("colshard" in s for s in badw.limits)


def test_mesh_scalar_and_batch_models_agree():
    """evaluate ≡ evaluate_batch on the mesh axis, bit for bit."""
    model = TPUModel()
    w = StreamWorkload("t", 7, 1, 1, 100, 1000, 256 * 128, grid_w=128)
    cases = [(8, 1, 4, 2), (16, 2, 8, 4), (32, 2, 8, 8),
             (8, 1, 4, 1), (64, 2, 8, 3)]
    bhs, ms, ds, dxs = (list(t) for t in zip(*cases))
    batch = model.evaluate_batch(w, bhs, ms, d=ds, dx=dxs)
    for i, (bh, m, d, dx) in enumerate(cases):
        pt = model.evaluate(w, bh, m, d=d, dx=dx)
        assert bool(batch["feasible"][i]) == pt.feasible
        assert float(batch["sustained_gflops"][i]) == pt.sustained_gflops
        assert int(batch["dx"][i]) == pt.detail["dx"]
        assert int(batch["dy"][i]) == pt.detail["dy"]


def test_sweep_tpu_enumerates_the_mesh_axis():
    """The dx lattice axis reaches Sweep.point: a swept point carries
    its (dy, dx) in detail, and d stays the total device count."""
    ex = Explorer(StreamWorkload("t", 7, 1, 1, 100, 1000, 256 * 128,
                                 grid_w=128))
    sweep = ex.sweep_tpu(bh_values=(8, 16), m_values=(1, 2),
                         d_values=(8,), dx_values=(1, 2, 4, 8))
    assert set(np.unique(sweep.data["dx"]).tolist()) == {1, 2, 4, 8}
    i = int(np.argmax(sweep.data["dx"] == 4))
    pt = sweep.point(i)
    assert pt.n == 8
    assert pt.detail["dx"] == 4 and pt.detail["dy"] == 2


def test_wide_grid_prefers_columns_tall_prefers_rows():
    """The mesh axis earns its place in the search: at a fixed device
    count the model matches the mesh to the grid's aspect — a wide grid
    picks a column-heavy mesh, a tall grid the row ring (mirrored). The
    grids are wide enough that a shard's guard columns (whole half-lane
    tiles) stay small next to it."""
    model = TPUModel()
    wide = StreamWorkload("w", 7, 1, 1, 100, 1000, 128 * 4096, grid_w=4096)
    tall = StreamWorkload("t", 7, 1, 1, 100, 1000, 4096 * 128, grid_w=128)

    def best_dx(w):
        return max(
            (1, 2, 4, 8),
            key=lambda dx: model.evaluate(w, 64, 8, d=8, dx=dx)
            .sustained_gflops,
        )

    assert best_dx(wide) == 8
    assert best_dx(tall) == 1


# ----------------------- old journals replay -----------------------


def test_premesh_journal_replays_with_zero_remeasurement(search_harness):
    """ISSUE 10 acceptance: a PR-6/PR-9-era journal (trial points with
    no ``dx`` field) resumes into the (dy, dx) study identity and plan
    keys with zero re-measurement."""
    hz = search_harness
    strat = ExhaustiveSearch(k=4, frontier_only=False)
    t1 = hz.timer()
    first = hz.search(hz.sweep(), timer=t1, strategy=strat, budget=4,
                      study="premesh")
    assert first.budget_spent == 4 == len(t1.calls)

    # Rewrite the journal as its pre-mesh ancestor: strip the dx plan
    # dimension from every trial record (exactly what a journal written
    # before DESIGN.md §15 contains).
    path = Path(hz.study_dir) / "premesh.jsonl"
    lines = []
    stripped = 0
    for line in path.read_text(encoding="utf-8").splitlines():
        rec = json.loads(line)
        if isinstance(rec.get("point"), dict) and "dx" in rec["point"]:
            del rec["point"]["dx"]
            stripped += 1
        lines.append(json.dumps(rec, sort_keys=True))
    assert stripped == 4
    path.write_text("\n".join(lines) + "\n", encoding="utf-8")

    # Resume: every trial must come back replayed — zero live timings,
    # zero budget spent, and the timer records no calls.
    t2 = hz.timer()
    resumed = hz.search(hz.sweep(), timer=t2, strategy=strat, budget=1,
                        study="premesh")
    assert resumed.replayed == 4
    assert resumed.budget_spent == 0 and not t2.calls
    assert len(resumed.executed) == 4
    assert all(e.cached and e.dx == 1 for e in resumed.executed)


# ----------------------- parallel trials: prefetch -----------------------


def test_prefetch_warms_candidate_and_never_overlaps_timing():
    """Satellite 1: a sub-mesh trial leaves devices idle — the next
    candidate's warm-up runs on a background thread, and measure joins
    it before the timed reps start (per-trial isolation)."""
    import threading
    import time as _time

    done = threading.Event()

    def rf(nsteps, m, block_h, d, double_buffer=True, b=1, dx=1):
        def run():
            if not done.is_set():
                _time.sleep(0.02)
                done.set()
        return run

    def timer(plan, run, reps, warmup):
        # Isolation contract: by the time the clock starts, no warm-up
        # thread is in flight.
        assert runner._prefetch is None
        return 1e-3

    runner = SearchRunner(
        workload=TOY, grid_shape=(64, 64), run_factory=rf,
        model=TPUModel(), fingerprint="mesh-prefetch", calibrate=False,
        cache=False, timer=timer, max_devices=4,
    )
    first = runner.point(8, 1)
    nxt = runner.point(16, 1)
    assert runner.measure(first) is not None
    assert runner.prefetch(nxt) is True
    assert runner.prefetched == 1
    assert runner.measure(nxt) is not None
    assert done.is_set()
    assert runner._prefetch is None


def test_prefetch_warm_up_error_surfaces_on_measure():
    """A warm-up that raises (on a chip: a compile the compiler refuses)
    is recorded, and measuring that plan raises it instead of timing."""

    def rf(nsteps, m, block_h, d, double_buffer=True, b=1, dx=1):
        def run():
            if block_h == 16:
                raise ValueError("compiler refused block_h=16")
        return run

    runner = SearchRunner(
        workload=TOY, grid_shape=(64, 64), run_factory=rf,
        model=TPUModel(), fingerprint="mesh-prefetch-error",
        calibrate=False, cache=False, timer=lambda *a: 1e-3, max_devices=4,
    )
    bad = runner.point(16, 1)
    assert runner.prefetch(bad) is True
    with pytest.raises(RuntimeError, match="warm-up") as info:
        runner.measure(bad)
    assert isinstance(info.value.__cause__, ValueError)
    assert runner.budget_spent == 0


def test_prefetch_gates_on_idle_devices():
    """A trial meshing every device leaves nothing idle: no dispatch."""
    runner = SearchRunner(
        workload=TOY, grid_shape=(64, 64), run_factory=_rf,
        model=TPUModel(), fingerprint="mesh-prefetch-gate",
        calibrate=False, cache=False, timer=ModelTimer(), max_devices=1,
    )
    assert runner.prefetch(runner.point(8, 1)) is False
    assert runner.prefetched == 0


def test_budget_cutoff_records_the_blocked_candidate():
    """BudgetExhausted remembers the candidate it cut off — exactly the
    point the stepper will ask for next — and prefetch() consumes it."""
    runner = SearchRunner(
        workload=TOY, grid_shape=(64, 64), run_factory=_rf,
        model=TPUModel(), fingerprint="mesh-prefetch-cutoff",
        calibrate=False, cache=False, timer=ModelTimer(),
        budget=1, max_devices=4,
    )
    first = runner.point(8, 1)
    nxt = runner.point(16, 1)
    assert runner.measure(first) is not None
    with pytest.raises(BudgetExhausted):
        runner.measure(nxt)
    assert runner.last_blocked is nxt
    assert runner.prefetch() is True
    assert runner.last_blocked is None
    runner._join_prefetch()


def test_stepper_prefetches_between_steps():
    """The SearchStepper wires the seam: after each fresh measurement
    the cut-off candidate's compile/warm-up dispatches in background."""
    runner = SearchRunner(
        workload=TOY, grid_shape=(64, 64), run_factory=_rf,
        model=TPUModel(), fingerprint="mesh-stepper", calibrate=False,
        cache=False, timer=ModelTimer(), budget=8, max_devices=4,
    )
    sweep = Explorer(TOY).sweep_tpu(bh_values=(8, 16, 32),
                                    m_values=(1, 2))
    stepper = SearchStepper(
        ExhaustiveSearch(frontier_only=False), sweep, runner
    )
    assert stepper.step() is not None
    assert runner.prefetched >= 1
    runner._join_prefetch()
