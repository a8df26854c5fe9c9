"""The manually pipelined streaming path (`repro.kernels.spd_stream.
streaming`): double_buffer as a real, end-to-end plan dimension.

Load-bearing assertions (ISSUE 7 acceptance criteria):
* **differential bit-match matrix** — the ping/pong streamed launch
  (``double_buffer=True``), the single-buffer streamed launch
  (``double_buffer=False``), and the jnp oracle
  (:meth:`~repro.core.codegen.StreamKernel.reference`) produce
  identical bits across (block_h, m ∈ {1, 2, 4}, d ∈ {1, 2})
  for both shipped apps (lbm fluid + walls, diffusion);
* **VMEM-overflow fallback** — a grid whose minimal double-buffered
  stripe exceeds the VMEM budget legalizes onto the single-buffer
  streaming path instead of raising, executes bit-matched against the
  jnp oracle, and the clamp error names the fallback when even one
  buffer cannot fit;
* **no duplicated accounting** — ``TPUModel`` prices VMEM with the
  legalizer's own :func:`~repro.core.legalize.stripe_vmem_bytes`
  (drift test over both buffer protocols);
* a hypothesis property: every legal double-buffered plan costs exactly
  twice its single-buffered twin and still bit-matches.

The d = 2 cases need real (host) devices:
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; under a plain
single-device run they skip.
"""

import numpy as np
import pytest

import jax

from _hypothesis_stub import HAVE_HYPOTHESIS, given, settings, st  # noqa: F401
from repro.apps import diffusion as dif
from repro.apps import lbm
from repro.core.dse import StreamWorkload, TPUModel, TPUTarget
from repro.core.legalize import (
    LANES,
    VMEM_BYTES,
    blocking_plan,
    constraint_violation,
    halo_rows,
    legal_block_values,
    program_blocking_plan,
    resolve_run_plan,
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
def dif_sim():
    return dif.DiffusionSimulation(16, 64, alpha=0.2)


@pytest.fixture(scope="module")
def lbm_sim():
    return lbm.LBMSimulation(lbm.LBMProblem(16, 64, mode="wrap"))


# ----------------- differential matrix: ping/pong ≡ single-buffer -----------


def _run_both(kern, state, regs, *, m, block_h, d):
    """(double-buffered, single-buffered) outputs of the same plan."""
    launcher = kern if d == 1 else kern.sharded(d)
    outs = []
    for db in (True, False):
        outs.append(launcher.run_blocked(
            state, regs, steps=2 * m, m=m, block_h=block_h,
            double_buffer=db,
        ))
    return outs


@pytest.mark.parametrize("d", [1, 2])
@pytest.mark.parametrize("m", [1, 2, 4])
@pytest.mark.parametrize("block_h", [4, 8])
def test_diffusion_double_vs_single_buffer_bitmatch(dif_sim, block_h, m, d):
    """ISSUE 7 matrix, diffusion: nbuf is a protocol choice, never a
    numerics choice — and both match the jnp reference."""
    if jax.device_count() < d:
        pytest.skip(f"needs {d} devices (force host devices in XLA_FLAGS)")
    if m > block_h or (d > 1 and m * dif_sim.kernel.halo > 16 // d):
        pytest.skip("halo does not fit this (block_h, m, d) cell")
    u0, _ = dif.sine_init(16, 64)
    state = dif_sim.state(u0)
    pp, sb = _run_both(dif_sim.kernel, state, (0.2,),
                       m=m, block_h=block_h, d=d)
    np.testing.assert_array_equal(np.asarray(pp), np.asarray(sb))
    if d == 1:
        ref = dif_sim.kernel.reference(state, (0.2,), m=2 * m)
        np.testing.assert_array_equal(np.asarray(pp), np.asarray(ref))


@pytest.mark.parametrize("d", [1, 2])
@pytest.mark.parametrize("m", [1, 2, 4])
def test_lbm_fluid_double_vs_single_buffer_bitmatch(lbm_sim, m, d):
    """ISSUE 7 matrix, lbm fluid lattice (all nine D2Q9 stencils cross
    every stripe boundary)."""
    if jax.device_count() < d:
        pytest.skip(f"needs {d} devices (force host devices in XLA_FLAGS)")
    kern = lbm_sim.stream_kernel()
    if d > 1 and m * kern.halo > 16 // d // 2:
        pytest.skip("halo does not fit this (m, d) cell")
    f, attr, _ = lbm.taylor_green_init(16, 64)
    state = lbm_sim.stream_state(f, attr)
    pp, sb = _run_both(kern, state, LBM_REGS, m=m, block_h=4, d=d)
    np.testing.assert_array_equal(np.asarray(pp), np.asarray(sb))


@pytest.mark.parametrize("m", [1, 2])
def test_lbm_walls_double_vs_single_buffer_bitmatch(lbm_sim, m):
    """Walls + moving lid: the bounce-back mux rides the same stripes."""
    kern = lbm_sim.stream_kernel()
    f, attr = lbm.couette_init(16, 64)
    state = lbm_sim.stream_state(f, attr)
    regs = (1 / 0.9, 0.07, 1.0)
    pp, sb = _run_both(kern, state, regs, m=m, block_h=4, d=1)
    np.testing.assert_array_equal(np.asarray(pp), np.asarray(sb))


def test_single_block_grid_streams(dif_sim):
    """nblk == 1 (block_h == h): the stream loop degenerates to one
    prefetch + drain pair and still matches, both protocols."""
    u0, _ = dif.sine_init(16, 64)
    state = dif_sim.state(u0)
    pp, sb = _run_both(dif_sim.kernel, state, (0.2,), m=2, block_h=16, d=1)
    np.testing.assert_array_equal(np.asarray(pp), np.asarray(sb))
    want = dif.diffusion_ref_run(u0, 0.2, 4)
    np.testing.assert_allclose(np.asarray(pp[0]), np.asarray(want),
                               rtol=2e-5, atol=1e-6)


# ----------------- the launch loop ping-pongs, never aliasing its input -----


@pytest.mark.parametrize("launches", [1, 2, 3, 4, 5])
@pytest.mark.parametrize("app", ["lbm", "diffusion"])
def test_run_blocked_equals_single_launches(dif_sim, lbm_sim, app,
                                            launches):
    """``run_blocked``'s two recycled buffers (``dst`` aliased to the
    output) give the bits of as many single ``__call__`` launches, and
    the caller's input is only read: unchanged after the call, and a
    second call on it gives the same output."""
    if app == "lbm":
        kern, regs = lbm_sim.stream_kernel(), LBM_REGS
        f, attr, _ = lbm.taylor_green_init(16, 64)
        state = lbm_sim.stream_state(f, attr)
    else:
        kern, regs = dif_sim.kernel, (0.2,)
        state = dif_sim.state(dif.sine_init(16, 64)[0])
    m, block_h = 2, 4
    before = np.asarray(state).copy()
    out = kern.run_blocked(state, regs, steps=launches * m, m=m,
                           block_h=block_h)
    want = state
    for _ in range(launches):
        want = kern(want, regs, m=m, block_h=block_h)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(state), before)
    again = kern.run_blocked(state, regs, steps=launches * m, m=m,
                             block_h=block_h)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(out))


# ----------------- VMEM overflow: the streaming fallback ---------------------


def test_blocking_plan_falls_back_to_single_buffer():
    """A minimal stripe that overflows double-buffered but fits
    single-buffered legalizes onto the fallback instead of raising."""
    # smallest launch (bh=8, m=2, halo carried as one 8-row tile):
    #   24-row stripe + 8-row output block per slot + 5 stripe-sized
    #   temporaries, × 64 × 4 B = 38912 B single-buffered, 47104 B
    #   ping/pong.
    bh, m, db = blocking_plan(16, 8, 2, width=64, words=1,
                              vmem_bytes=40000)
    assert db is False
    assert stripe_vmem_bytes(bh, m, 64, 1, 1, False) <= 40000
    # With the room, the requested ping/pong protocol is honored.
    assert blocking_plan(16, 8, 2, width=64, words=1,
                         vmem_bytes=10**9) == (8, 2, True)
    # An explicit single-buffer request is never upgraded.
    assert blocking_plan(16, 8, 2, width=64, words=1, vmem_bytes=10**9,
                         double_buffer=False) == (8, 2, False)


def test_clamp_error_names_the_streaming_fallback():
    """When even one buffer cannot fit, the error says the fallback was
    tried — the actionable half of the ISSUE 7 contract."""
    with pytest.raises(ValueError) as ei:
        blocking_plan(16, 8, 2, width=64, words=1, vmem_bytes=100)
    msg = str(ei.value)
    assert "single-buffer streaming fallback" in msg
    assert "double_buffer=False" in msg


def test_vmem_overflow_grid_executes_via_streaming(dif_sim):
    """ISSUE 7 acceptance: a grid that is VMEM-infeasible double-buffered
    legalizes (double_buffer=False), executes through the streamed
    kernel, and matches the jnp oracle — where the seed's blocking_plan
    raised."""
    u0, _ = dif.sine_init(16, 64)
    state = dif_sim.state(u0)
    pt = TPUModel().evaluate(
        dif_sim.explorer().workload, bh=8, m=2, double_buffer=True
    )
    budget = 40000  # fits (8, 2) single-buffered only (38912 vs 47104 B)
    with pytest.raises(ValueError, match="fallback"):
        # sanity: with the fallback forbidden this budget is hopeless
        blocking_plan(16, 8, 2, width=64, words=1, vmem_bytes=budget // 2)
    block_h, m, nsteps, db = resolve_run_plan(
        16, pt, halo=dif_sim.kernel.halo, width=64, words=1,
        vmem_bytes=budget,
    )
    assert db is False and stripe_vmem_bytes(
        block_h, m, 64, 1, dif_sim.kernel.halo, db
    ) <= budget
    out = dif_sim.kernel.run_blocked(
        state, (0.2,), steps=nsteps, m=m, block_h=block_h, double_buffer=db
    )
    want = dif.diffusion_ref_run(u0, 0.2, nsteps)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want),
                               rtol=2e-5, atol=1e-6)
    # ...and bitwise against the unconstrained ping/pong run of the
    # same plan: the fallback changed the protocol, not the numerics.
    full = dif_sim.kernel.run_blocked(
        state, (0.2,), steps=nsteps, m=m, block_h=block_h,
        double_buffer=True,
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(full))


# ----------------- accounting: one source of truth ---------------------------


@pytest.mark.parametrize("double_buffer", [True, False])
def test_model_vmem_accounting_is_the_legalizers(double_buffer):
    """ISSUE 7 satellite: the model's VMEM price IS
    legalize.stripe_vmem_bytes — for both protocols, any halo — so the
    multiplier cannot drift between dse.py and legalize.py again."""
    model = TPUModel()
    for halo in (0, 1, 2):
        w = StreamWorkload("t", 7, 3, 3, 100, 1000, 256 * 640,
                           grid_w=640, halo=halo)
        for bh, m in ((8, 1), (32, 4), (256, 8)):
            pt = model.evaluate(w, bh, m, double_buffer=double_buffer)
            assert pt.detail["vmem_bytes"] == stripe_vmem_bytes(
                bh, m, 640, 3, halo, double_buffer
            )
            assert pt.detail["double_buffer"] is double_buffer
            batch = model.evaluate_batch(
                w, [bh], [m], double_buffer=double_buffer
            )
            assert int(batch["vmem_bytes"][0]) == pt.detail["vmem_bytes"]


def _kernel_stripe_rows(kern, h, w, block_h, m):
    """Rows of the input stripe buffer the streamed launch allocates,
    read from the traced ``pallas_call`` (its first VMEM scratch ref)."""
    import functools

    import jax.numpy as jnp

    state = jnp.zeros((len(kern._ports), h, w), jnp.float32)
    jaxpr = jax.make_jaxpr(functools.partial(
        kern._streamed, m=m, block_h=block_h, double_buffer=True,
        interpret=True,
    ))(state, kern._scal((0.0,) * len(kern._regs)))

    def calls(jx):
        for e in jx.eqns:
            if e.primitive.name == "pallas_call":
                yield e
            for v in e.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    yield from calls(inner)

    (eqn,) = calls(jaxpr.jaxpr)
    vmem = [v.aval for v in eqn.params["jaxpr"].invars
            if "vmem" in str(v.aval)]
    return vmem[0].shape[-2]


@pytest.mark.parametrize("app", ["diffusion", "lbm"])
def test_model_compute_geometry_is_the_kernels(app, dif_sim):
    """The model prices the stripe the kernel runs: its useful fraction
    is block_h over the rows of the launch's own stripe buffer (halo
    carried in whole 8-row tiles), scalar and batched alike."""
    if app == "diffusion":
        kern, words = dif_sim.kernel, 1
    else:
        kern, words = lbm.LBMSimulation(lbm.LBMProblem(64, 64)) \
            .stream_kernel(), 10
    h, w = 64, 64
    wl = StreamWorkload("t", 7, words, words, 100, 1000, h * w, grid_w=w,
                        halo=kern.halo)
    model = TPUModel()
    for bh, m in ((8, 1), (16, 2), (32, 3), (16, 8), (64, 8)):
        rows = _kernel_stripe_rows(kern, h, w, bh, m)
        pt = model.evaluate(wl, bh, m)
        assert pt.detail["halo_useful_fraction"] == bh / rows
        batch = model.evaluate_batch(wl, [bh], [m])
        assert float(batch["halo_useful_fraction"][0]) == bh / rows
        assert float(batch["t_memory_s"][0]) == pt.detail["t_memory_s"]


def test_lane_rule_is_a_legalizer_rule():
    """Compiled for the TPU the launch stages whole 128-lane rows: a
    shard width that is not a multiple of them has no plan, and the
    legalizer, the violation distance and the model all say so. Under
    the interpreter any width runs."""
    with pytest.raises(ValueError, match="128 lanes"):
        blocking_plan(64, 32, 2, width=96, words=1, interpret=False)
    assert legal_block_values(64, 2, width=96, words=1,
                              interpret=False) == ()
    assert constraint_violation(64, 32, 2, width=96, words=1,
                                interpret=False) > 0
    with pytest.raises(ValueError, match="128 lanes"):
        program_blocking_plan(64, 32, 2, stages=[(1, 1), (1, 1)],
                              width=96, interpret=False)
    # dx = 2: 256 columns give 128-column shards; 128 give 64
    assert blocking_plan(64, 32, 2, width=256, words=1, d=2, dx=2,
                         halo_x=1, interpret=False)
    with pytest.raises(ValueError, match="over dx=2"):
        blocking_plan(64, 32, 2, width=128, words=1, d=2, dx=2, halo_x=1,
                      interpret=False)
    assert constraint_violation(64, 32, 2, width=128, words=1, d=2, dx=2,
                                halo_x=1, interpret=False) > 0
    # interpreted, the same requests legalize
    assert blocking_plan(64, 32, 2, width=96, words=1, interpret=True)
    assert constraint_violation(64, 32, 2, width=96, words=1,
                                interpret=True) == 0.0
    wl = StreamWorkload("t", 7, 1, 1, 100, 1000, 64 * 96, grid_w=96)
    for lanes, ok in ((LANES, False), (1, True)):
        model = TPUModel(TPUTarget(lanes=lanes))
        assert model.evaluate(wl, 32, 2).feasible is ok
        assert bool(model.evaluate_batch(wl, [32], [2])["feasible"][0]) is ok


def test_single_buffer_halves_the_budget_and_widens_feasibility():
    """The fallback exists to buy headroom: a stripe priced infeasible
    ping/pong can be feasible single-buffered (one buffer slot less)."""
    w = StreamWorkload("t", 7, 8, 8, 100, 1000, 4096 * 1440,
                       grid_w=1440, halo=1)
    model = TPUModel()
    over = next(
        bh for bh in range(8, 4097, 8)
        if stripe_vmem_bytes(bh, 4, 1440, 8, 1, True) > VMEM_BYTES
        and stripe_vmem_bytes(bh, 4, 1440, 8, 1, False) <= VMEM_BYTES
    )
    assert not model.evaluate(w, over, 4, double_buffer=True).feasible
    assert model.evaluate(w, over, 4, double_buffer=False).feasible


# ----------------- property: legal ⇒ half the budget, same bits --------------


@given(
    block_h=st.sampled_from([2, 4, 8, 16]),
    m=st.integers(min_value=1, max_value=4),
    words=st.integers(min_value=1, max_value=16),
    width=st.integers(min_value=1, max_value=400_000),
)
@settings(max_examples=40, deadline=None)
def test_prop_double_buffer_costs_exactly_double(block_h, m, words, width):
    """Any legal double-buffered plan costs its single-buffered twin
    plus exactly one more buffer slot (input stripe + output block);
    the stripe body's temporaries are not doubled — the saving the
    fallback banks on."""
    try:
        bh, mm, db = blocking_plan(16, block_h, m, width=width, words=words)
    except ValueError:
        return
    rows = bh + 2 * halo_rows(mm, bh)
    slot = words * (rows + bh) * width * 4
    assert stripe_vmem_bytes(bh, mm, width, words, 1, True) == (
        stripe_vmem_bytes(bh, mm, width, words, 1, False) + slot
    )
    if db:
        # the honored ping/pong plan fits; its fallback twin fits with
        # one slot to spare
        assert stripe_vmem_bytes(bh, mm, width, words, 1, False) + slot \
            <= VMEM_BYTES


@given(
    block_h=st.sampled_from([2, 4, 8, 16]),
    m=st.integers(min_value=1, max_value=4),
)
@settings(max_examples=10, deadline=None)
def test_prop_legal_plans_bitmatch_across_protocols(block_h, m):
    """Executable property (ISSUE 7): every legal (block_h, m) plan on
    the diffusion grid produces identical bits under both protocols."""
    sim = _prop_sim()
    if block_h not in legal_block_values(16, m, halo=sim.kernel.halo):
        return
    u0, _ = dif.sine_init(16, 64)
    state = sim.state(u0)
    pp, sb = _run_both(sim.kernel, state, (0.2,), m=m, block_h=block_h, d=1)
    np.testing.assert_array_equal(np.asarray(pp), np.asarray(sb))


_PROP_SIM = []


def _prop_sim():
    if not _PROP_SIM:
        _PROP_SIM.append(dif.DiffusionSimulation(16, 64, alpha=0.2))
    return _PROP_SIM[0]
