"""SPD→Pallas stream codegen: stencil inference, bit-match vs the
compiler's reference function, equivalence with the hand-written
lbm_stream kernel, and the second-app explorer loop.

Load-bearing assertions (ISSUE 2 acceptance criteria):
* the codegen'd kernel ≡ m repeated applications of the compiled core's
  reference JAX function, *bitwise*, in interpret mode — for m ∈ {1,2,4}
  on fluid-only and walled lattices;
* the generated uLBM PE kernel ≡ the hand-written ``lbm_stream`` kernel;
* a second, non-LBM SPD app (2-D diffusion) sweeps, Pareto-filters, and
  executes its top-k TPU frontier points through its codegen'd kernel;
* the inferred halo is >= the largest stencil offset in the core
  (property test, hypothesis-optional).
"""

import numpy as np
import pytest
from _hypothesis_stub import given, settings, st

import jax.numpy as jnp

from repro.apps import diffusion as dif
from repro.apps import lbm
from repro.core import (
    CodegenError,
    Registry,
    parse_spd,
    stencil_summary,
)
from repro.core.legalize import (
    VMEM_BYTES,
    blocking_plan,
    resolve_run_plan,
    stripe_vmem_bytes,
)

ONE_TAU = 1 / 0.8
LBM_REGS = (ONE_TAU, 0.0, 1.0)


@pytest.fixture(scope="module")
def lbm_kernel():
    sim = lbm.LBMSimulation(lbm.LBMProblem(16, 128, mode="wrap"))
    return sim.pe.stream_kernel()


def _lbm_state(kern, f, attr):
    return kern.pack([f[i] for i in range(9)] + [attr])


# ----------------------- stencil-offset inference -----------------------


def test_lbm_pe_stencil_inference(lbm_kernel):
    """The D2Q9 PE reads all 9 lattice directions; halo is one row."""
    s = lbm_kernel.summary
    want = {(int(lbm.EY[i]), int(lbm.EX[i])) for i in range(9)}
    assert set(s.offsets) == want
    assert s.halo_y == 1 and s.halo_x == 1
    assert s.modes == {"wrap"}


def test_offsets_compose_through_subcores():
    """Offsets accumulate additively along sub-core call chains."""
    reg = Registry()
    reg.compile(parse_spd("""
        Name ShiftY;
        Main_In {mi::a};
        Main_Out {mo::b};
        HDL S1, 0, (b) = Stencil2D(a), dy=1, dx=0, W=64, mode=wrap;
    """))
    outer = reg.compile(parse_spd("""
        Name Twice;
        Main_In {mi::x};
        Main_Out {mo::y};
        HDL N1, 0, (t) = ShiftY(x);
        HDL N2, 0, (y) = ShiftY(t);
    """))
    s = stencil_summary(outer)
    assert s.offsets == frozenset({(2, 0)})
    assert s.halo_y == 2 and s.halo_x == 0
    assert s.port_reads["y"] == frozenset({("x", 2, 0)})


def test_inference_rejects_1d_stream_state():
    reg = Registry()
    c = reg.compile(parse_spd("""
        Name HasDelay;
        Main_In {mi::x};
        Main_Out {mo::y};
        HDL D1, 0, (y) = Delay(x), 3;
    """))
    with pytest.raises(CodegenError, match="1-D stream"):
        stencil_summary(c)


def test_codegen_rejects_zero_mode_and_branch_ports():
    reg = Registry()
    zero = reg.compile(parse_spd("""
        Name ZeroMode;
        Main_In {mi::x};
        Main_Out {mo::y};
        HDL S1, 0, (y) = Stencil2D(x), dy=1, dx=0, W=64, mode=zero;
    """))
    with pytest.raises(CodegenError, match="mode"):
        zero.stream_kernel()
    brch = reg.compile(parse_spd("""
        Name HasBranch;
        Main_In {mi::x};
        Main_Out {mo::y};
        Brch_Out {bo::t};
        EQU N1, y = x + 1.0;
        DRCT (t) = (y);
    """))
    with pytest.raises(CodegenError, match="branch"):
        brch.stream_kernel()


def test_codegen_rejects_unchainable_port_counts():
    reg = Registry()
    c = reg.compile(parse_spd("""
        Name TwoToOne;
        Main_In {mi::a,b};
        Main_Out {mo::y};
        EQU N1, y = a + b;
    """))
    with pytest.raises(CodegenError, match="main_out"):
        c.stream_kernel()


@st.composite
def _rand_offsets(draw):
    n = draw(st.integers(1, 4))
    return [
        (draw(st.integers(-3, 3)), draw(st.integers(-3, 3)))
        for _ in range(n)
    ]


@given(_rand_offsets())
@settings(max_examples=30, deadline=None)
def test_inferred_halo_covers_max_offset(offsets):
    """Property: inferred halo >= the largest stencil offset in the DFG."""
    L = ["Name Rand;", "Main_In {mi::u};", "Main_Out {mo::v};"]
    terms = []
    for k, (dy, dx) in enumerate(offsets):
        L.append(
            f"HDL S{k}, 0, (t{k}) = Stencil2D(u), "
            f"dy={dy}, dx={dx}, W=32, mode=wrap;"
        )
        terms.append(f"t{k}")
    L.append(f"EQU N1, v = {' + '.join(terms)};")
    s = stencil_summary(Registry().compile(parse_spd("\n".join(L))))
    assert s.halo_y >= max(abs(dy) for dy, _ in offsets)
    assert s.halo_x >= max(abs(dx) for _, dx in offsets)
    assert s.offsets == frozenset(offsets)


# ----------------------- kernel ≡ compiler reference -----------------------


@pytest.mark.parametrize("m,block_h", [(1, 8), (2, 8), (4, 16)])
def test_kernel_bitmatches_reference_fluid(lbm_kernel, m, block_h):
    """Interpret-mode kernel == m applications of CompiledCore.apply,
    bit for bit, on a fluid-only (Taylor-Green) lattice."""
    f, attr, _ = lbm.taylor_green_init(16, 128)
    state = _lbm_state(lbm_kernel, f, attr)
    got = lbm_kernel(state, LBM_REGS, m=m, block_h=block_h, interpret=True)
    want = lbm_kernel.reference(state, LBM_REGS, m=m)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m", [1, 2, 4])
def test_kernel_bitmatches_reference_walls(lbm_kernel, m):
    """Same contract on a walled lattice with a moving lid (Couette)."""
    f, attr = lbm.couette_init(16, 128)
    regs = (1 / 0.9, 0.07, 1.0)
    state = _lbm_state(lbm_kernel, f, attr)
    got = lbm_kernel(state, regs, m=m, block_h=8, interpret=True)
    want = lbm_kernel.reference(state, regs, m=m)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernel_block_decomposition_independence(lbm_kernel):
    f, attr, _ = lbm.taylor_green_init(16, 128)
    state = _lbm_state(lbm_kernel, f, attr)
    a = lbm_kernel(state, LBM_REGS, m=2, block_h=8, interpret=True)
    b = lbm_kernel(state, LBM_REGS, m=2, block_h=16, interpret=True)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_run_blocked_multi_launch(lbm_kernel):
    f, attr, _ = lbm.taylor_green_init(16, 128)
    state = _lbm_state(lbm_kernel, f, attr)
    got = lbm_kernel.run_blocked(
        state, LBM_REGS, steps=8, m=4, block_h=8, interpret=True
    )
    want = lbm_kernel.reference(state, LBM_REGS, m=8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernel_rejects_illegal_plans(lbm_kernel):
    f, attr, _ = lbm.taylor_green_init(16, 128)
    state = _lbm_state(lbm_kernel, f, attr)
    with pytest.raises(ValueError):
        lbm_kernel(state, LBM_REGS, m=1, block_h=5)  # 16 % 5 != 0
    with pytest.raises(ValueError):
        lbm_kernel(state, LBM_REGS, m=16, block_h=8)  # m*halo > block_h
    with pytest.raises(CodegenError):
        lbm_kernel(state, (1.0,), m=1, block_h=8)  # wrong register count


def test_x_offsets_beyond_row_width_wrap_modularly():
    """A dx larger than the concrete grid width must wrap like roll."""
    reg = Registry()
    big = reg.compile(parse_spd("""
        Name BigDX;
        Main_In {mi::u};
        Main_Out {mo::v};
        HDL S1, 0, (t) = Stencil2D(u), dy=0, dx=11, W=8, mode=wrap;
        EQU N1, v = t + 0.0;
    """))
    kern = big.stream_kernel()
    rng = np.random.default_rng(0)
    state = kern.pack([rng.standard_normal((8, 8)).astype(np.float32)])
    got = kern(state, m=1, block_h=8, interpret=True)
    want = kern.reference(state, m=1)  # fully periodic (jnp.roll)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_inference_rejects_output_arity_mismatch():
    """A call site declaring fewer outputs than the callee produces must
    error, not silently truncate."""
    reg = Registry()
    reg.compile(parse_spd("""
        Name TwoOut;
        Main_In {mi::a};
        Main_Out {mo::p,q};
        EQU N1, p = a + 1.0;
        EQU N2, q = a + 2.0;
    """))
    outer = reg.compile(parse_spd("""
        Name Truncates;
        Main_In {mi::x};
        Main_Out {mo::y};
        HDL N1, 0, (y) = TwoOut(x);
    """))
    with pytest.raises(CodegenError, match="declares"):
        stencil_summary(outer)


# ----------------------- generated ulbm ≡ hand-written kernel ---------------


@pytest.mark.parametrize("m,block_h", [(1, 8), (4, 8)])
def test_codegen_matches_handwritten_lbm_stream(lbm_kernel, m, block_h):
    """The generated uLBM kernel reproduces repro.kernels.lbm_stream."""
    from repro.kernels.lbm_stream.ops import lbm_multistep

    f, attr = lbm.couette_init(16, 128)
    state = _lbm_state(lbm_kernel, f, attr)
    got = lbm_kernel(
        state, (1 / 0.9, 0.07, 1.0), m=m, block_h=block_h, interpret=True
    )
    hand = lbm_multistep(f, attr, 1 / 0.9, 0.07, m=m, block_h=block_h)
    np.testing.assert_allclose(
        np.asarray(got[:9]), np.asarray(hand), rtol=2e-5, atol=1e-7
    )


# ----------------------- the second SPD app -----------------------


def test_diffusion_kernel_bitmatches_reference():
    sim = dif.DiffusionSimulation(32, 128, alpha=0.2)
    u0, _ = dif.sine_init(32, 128)
    state = sim.state(u0)
    for m in (1, 2, 4):
        got = sim.kernel(state, (0.2,), m=m, block_h=8, interpret=True)
        want = sim.kernel.reference(state, (0.2,), m=m)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_diffusion_kernel_matches_jnp_oracle():
    sim = dif.DiffusionSimulation(16, 128, alpha=0.15)
    u0, _ = dif.sine_init(16, 128)
    got = sim.run(u0, 8, m=4, block_h=8)
    want = dif.diffusion_ref_run(u0, 0.15, 8)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=1e-6
    )


def test_diffusion_run_legalizes_default_block():
    """Default block_h must be legal for grids 32 does not divide."""
    sim = dif.DiffusionSimulation(40, 64, alpha=0.2)
    u0, _ = dif.sine_init(40, 64)
    got = sim.run(u0, 2, m=2)
    want = dif.diffusion_ref_run(u0, 0.2, 2)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=1e-6
    )


def test_diffusion_physics_decay():
    """Sinusoidal mode decays by the exact discrete factor per step."""
    sim = dif.DiffusionSimulation(32, 128, alpha=0.2)
    u0, decay = dif.sine_init(32, 128)
    steps = 40
    u = sim.run(u0, steps, m=4, block_h=8)
    ratio = float(jnp.linalg.norm(u) / jnp.linalg.norm(u0))
    assert ratio == pytest.approx(decay(0.2) ** steps, rel=1e-4)


def test_second_app_sweeps_and_executes_frontier():
    """ISSUE 2 acceptance: a non-LBM SPD core sweeps, Pareto-filters, and
    executes its top-k TPU frontier points through its codegen'd kernel."""
    sim = dif.DiffusionSimulation(32, 64, alpha=0.2)
    ex = sim.explorer()
    assert ex.core is sim.core  # compile -> explore plumbing
    sweep = ex.sweep_tpu(bh_values=(8, 16, 32), m_values=(1, 2, 4))
    frontier = sweep.frontier()
    assert frontier, "diffusion sweep produced an empty frontier"
    u0, _ = dif.sine_init(32, 64)
    state = sim.state(u0)
    runs = ex.execute_frontier(sweep, state, (0.2,), k=2)
    assert 1 <= len(runs) <= 2
    for r in runs:
        assert 32 % r.block_h == 0 and r.m <= r.block_h
        assert r.wall_s > 0 and np.isfinite(r.rel_error)
        assert r.predicted_gflops == pytest.approx(r.point.sustained_gflops)
    # ... and the executed state is the right physics, not just timed.
    out, (bh, m, _) = sim.kernel.run_for_point(
        state, (0.2,), point=frontier[0], interpret=True
    )
    want = dif.diffusion_ref_run(u0, 0.2, m)
    np.testing.assert_allclose(
        np.asarray(out[0]), np.asarray(want), rtol=2e-5, atol=1e-6
    )


# ----------------------- shared legalization -----------------------


def test_blocking_plan_halo_aware():
    # halo=2 doubles the per-step row consumption: m=4 needs block >= 8.
    assert blocking_plan(64, 64, 4, halo=2) == (64, 4, True)
    assert blocking_plan(64, 4, 4, halo=2) == (8, 4, True)  # up to m*halo
    # halo=0 (elementwise core): any tile-aligned divisor works.
    assert blocking_plan(64, 7, 64, halo=0) == (8, 64, True)
    # m*halo larger than the whole grid: m shrinks until sourceable...
    bh, m, _ = blocking_plan(8, 8, 8, halo=4)
    assert m >= 1 and m * 4 <= bh <= 8
    # ...but never below one step: an unsourceable halo is an error,
    # not a silent (bh, 0) plan.
    with pytest.raises(ValueError, match="halo"):
        blocking_plan(8, 8, 1, halo=16)


def test_model_and_legalizer_agree_on_stripe_geometry():
    """A model-feasible point is never shrunk by the VMEM clamp: both
    sides account the same (bh + 2·m·halo)-row stripe, for any halo."""
    from repro.core.dse import StreamWorkload, TPUModel

    for halo in (0, 1, 2):
        w = StreamWorkload("t", 7, 10, 10, 100, 1000, 4096 * 1440,
                           grid_w=1440, halo=halo)
        pt = TPUModel().evaluate(w, bh=512, m=8)
        assert pt.detail["vmem_bytes"] == stripe_vmem_bytes(
            512, 8, 1440, 10, halo=halo
        )
        if pt.feasible:
            bh, m, db = blocking_plan(4096, 512, 8, halo=halo,
                                      width=1440, words=10)
            assert (bh, m, db) == (512, 8, True), (
                f"feasible point shrunk at halo={halo}"
            )


def test_report_halo_propagates_to_workload():
    """Composed dy=1 sub-cores infer halo 2, and it reaches the DSE
    workload through HardwareReport (no implicit halo=1 anywhere)."""
    reg = Registry()
    reg.compile(parse_spd("""
        Name ShiftY1;
        Main_In {mi::a};
        Main_Out {mo::b};
        HDL S1, 0, (b) = Stencil2D(a), dy=1, dx=0, W=64, mode=wrap;
    """))
    outer = reg.compile(parse_spd("""
        Name Chain2;
        Main_In {mi::x};
        Main_Out {mo::y};
        HDL N1, 0, (t) = ShiftY1(x);
        HDL N2, 0, (y) = ShiftY1(t);
    """))
    assert outer.hardware_report.halo == 2
    assert outer.hardware_report.workload(elems=64 * 64, grid_w=64).halo == 2
    # Cores the codegen rejects (1-D stream state) fall back to halo=1.
    delayed = reg.compile(parse_spd("""
        Name HasDelay1;
        Main_In {mi::x};
        Main_Out {mo::y};
        HDL D1, 0, (y) = Delay(x), 3;
    """))
    assert delayed.hardware_report.halo == 1


def test_blocking_plan_vmem_clamp():
    # A stripe of 10 f32 words x 720 columns: huge blocks blow VMEM, so
    # the legalizer must come down to a divisor whose stripe fits.
    h, width, words = 4096, 720, 10
    bh, m, db = blocking_plan(h, 4096, 4, width=width, words=words)
    assert stripe_vmem_bytes(bh, m, width, words,
                             double_buffer=db) <= VMEM_BYTES
    assert h % bh == 0
    # Without the clamp the request would have been honored.
    assert blocking_plan(h, 4096, 4) == (4096, 4, True)
    # When no legal block fits the budget — not even the single-buffer
    # streaming fallback — fail loudly rather than hand back a plan
    # that dies with an on-device allocation error.
    with pytest.raises(ValueError, match="VMEM"):
        blocking_plan(256, 256, 1, width=100_000, words=200)


def test_resolve_run_plan_threads_halo():
    from repro.core.dse import TPUModel, StreamWorkload

    w = StreamWorkload("t", 7, 1, 1, 100, 1000, 32 * 64, grid_w=64)
    pt = TPUModel().evaluate(w, bh=16, m=8)
    block_h, m, nsteps, db = resolve_run_plan(32, pt, halo=2)
    assert 32 % block_h == 0 and m * 2 <= block_h
    assert nsteps == m and db is True


@given(
    h=st.sampled_from([32, 64, 256, 4096]),
    block_h=st.integers(min_value=1, max_value=8192),
    m=st.integers(min_value=1, max_value=64),
    halo=st.integers(min_value=1, max_value=3),
    width=st.integers(min_value=1, max_value=200_000),
    words=st.integers(min_value=1, max_value=32),
)
@settings(max_examples=80, deadline=None)
def test_prop_blocking_plan_never_exceeds_vmem(h, block_h, m, halo,
                                               width, words):
    """ISSUE 6 satellite property: any plan blocking_plan hands back
    fits the shared VMEM budget — the same invariant the codegen'd
    kernels rely on to never die with an on-device allocation error."""
    from repro.core.legalize import constraint_violation

    try:
        bh, mm, db = blocking_plan(h, block_h, m, halo=halo, width=width,
                                   words=words)
    except ValueError:
        # infeasible request: the continuous distance must agree
        assert constraint_violation(
            h, block_h, m, halo=halo, width=width, words=words
        ) > 0.0
        return
    assert h % bh == 0
    assert stripe_vmem_bytes(bh, mm, width, words, halo, db) <= VMEM_BYTES
