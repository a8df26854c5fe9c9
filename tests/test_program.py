"""Streaming program graphs (`repro.core.program`): the fusion axis.

Load-bearing assertions (ISSUE 9 acceptance criteria):

* every fusion partition of both program apps — fused, partial splits,
  fully pipelined — is **bitwise** identical to the app's monolithic
  single-core kernel, across m ∈ {1, 2, 4} × double_buffer on/off
  (and d ∈ {1, 2} where the platform has the devices), and matches the
  pure-jnp oracle to f32 tolerance;
* pipelined cluster intermediates never round-trip to host: the
  pipelined launch runs clean under ``jax.transfer_guard("disallow")``
  while the unfused baseline (which syncs every intermediate) trips it;
* fusion legality is the legalizer's job: partitions that fit stripe
  their clusters within ``VMEM_BYTES`` at the resolved plan, partitions
  that don't raise naming the offending cluster (hypothesis-optional
  property test over random stage chains);
* the plan tuple is single-sourced: ``RunPlan`` mirrors ``PLAN_FIELDS``
  exactly and tolerates pre-fusion records (drift test);
* stencil inference is memoized per (core, incoming-edge extents) — the
  same sub-core summarized under two different extents gets two
  summaries, each cached;
* the fusion partition rides the whole search stack: sweep lattice →
  executed points → measurement-cache keys.

The d = 2 cases need real (host) devices:
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` — the CI
program job sets it; under a plain single-device run they skip.
"""

import numpy as np
import pytest

import jax

from _hypothesis_stub import HAVE_HYPOTHESIS, given, settings, st

from repro.apps import lbm
from repro.apps.advection_diffusion import (
    AdvectionDiffusionSimulation,
    advdiff_ref_run,
    blob_init,
)
from repro.core.legalize import (
    PLAN_FIELDS,
    RunPlan,
    VMEM_BYTES,
    cluster_vmem_bytes,
    parse_fusion,
    program_blocking_plan,
)
from repro.core.program import (
    ProgramError,
    StreamProgram,
    fusion_partitions,
)

H, W = 16, 64
STEPS = 4


def _needs_devices(d: int):
    return pytest.mark.skipif(
        jax.device_count() < d,
        reason=f"needs {d} devices "
               f"(XLA_FLAGS=--xla_force_host_platform_device_count=8)",
    )


@pytest.fixture(scope="module")
def lbm_app():
    sim = lbm.LBMSimulation(lbm.LBMProblem(H, W, mode="wrap"))
    f0, attr, _ = lbm.taylor_green_init(H, W)
    return {
        "prog": sim.program(),
        "mono": sim.stream_kernel(),  # the pre-program single-core path
        "state": sim.stream_state(f0, attr),
        "regs": sim.stream_regs(),
    }


@pytest.fixture(scope="module")
def ad_app():
    sim = AdvectionDiffusionSimulation(H, W)
    return {
        "sim": sim,
        "prog": sim.program,
        "mono": sim.monolithic_core.stream_kernel(),
        "state": sim.state(blob_init(H, W)),
        "regs": sim.regs(),
    }


# --------------------------------------------------------------------------
# Partition structure
# --------------------------------------------------------------------------


def test_fusion_partitions_enumeration():
    assert fusion_partitions(1) == ("1",)
    assert fusion_partitions(2) == ("2", "1+1")
    assert fusion_partitions(3) == ("3", "2+1", "1+2", "1+1+1")
    assert len(fusion_partitions(4)) == 8  # 2^(n-1) compositions


def test_program_rejects_non_chain_graphs(ad_app):
    reg = ad_app["prog"].registry
    with pytest.raises(ProgramError, match="not a chain edge"):
        StreamProgram(
            reg, ["Advect2D", "ReactDiffuse2D"],
            edges=[(1, 0)], width=W,
        )
    with pytest.raises(ProgramError, match="disconnected"):
        StreamProgram(reg, ["Advect2D", "ReactDiffuse2D"], edges=[],
                      width=W)


def test_stage_geometry(lbm_app, ad_app):
    # uLBM: collide+stream carries the 9-dir stencil (halo 1); the
    # boundary and moments stages are pointwise (halo 0).
    assert lbm_app["prog"].stage_geometry() == ((10, 1), (10, 0), (10, 0))
    assert ad_app["prog"].stage_geometry() == ((1, 1), (1, 1))


# --------------------------------------------------------------------------
# Bit-match matrix: every partition == the monolithic single-core kernel
# --------------------------------------------------------------------------


@pytest.mark.parametrize("m", [1, 2, 4])
@pytest.mark.parametrize("double_buffer", [True, False])
def test_lbm_partitions_bitwise_match_monolith(lbm_app, m, double_buffer):
    prog, state, regs = lbm_app["prog"], lbm_app["state"], lbm_app["regs"]
    ref = np.asarray(lbm_app["mono"].run_blocked(
        state, regs, steps=STEPS, m=m, block_h=8,
        double_buffer=double_buffer, interpret=True,
    ))
    for spec in fusion_partitions(prog.nstages):
        out = np.asarray(prog.kernel(spec).run_blocked(
            state, regs, steps=STEPS, m=m, block_h=8,
            double_buffer=double_buffer, interpret=True,
        ))
        assert np.array_equal(out, ref), (spec, m, double_buffer)


@pytest.mark.parametrize("m", [1, 2, 4])
@pytest.mark.parametrize("double_buffer", [True, False])
def test_advdiff_partitions_bitwise_match_monolith(ad_app, m,
                                                   double_buffer):
    prog, state, regs = ad_app["prog"], ad_app["state"], ad_app["regs"]
    ref = np.asarray(ad_app["mono"].run_blocked(
        state, regs, steps=STEPS, m=m, block_h=8,
        double_buffer=double_buffer, interpret=True,
    ))
    for spec in fusion_partitions(prog.nstages):
        out = np.asarray(prog.kernel(spec).run_blocked(
            state, regs, steps=STEPS, m=m, block_h=8,
            double_buffer=double_buffer, interpret=True,
        ))
        assert np.array_equal(out, ref), (spec, m, double_buffer)


def test_advdiff_matches_jnp_oracle(ad_app):
    sim, prog = ad_app["sim"], ad_app["prog"]
    u0 = blob_init(H, W)
    want = np.asarray(advdiff_ref_run(
        u0, sim.vx, sim.vy, sim.alpha, sim.r, STEPS
    ))
    for spec in fusion_partitions(prog.nstages):
        got = np.asarray(sim.run(u0, STEPS, fusion=spec, m=2, block_h=8))
        np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


def test_partitions_match_reference_path(lbm_app, ad_app):
    """Every partition == the compiler's reference function (the
    CompiledCore.apply chain of the fused wrapper), bitwise."""
    for app in (lbm_app, ad_app):
        prog, state, regs = app["prog"], app["state"], app["regs"]
        ref = np.asarray(prog.kernel("").reference(state, regs, m=STEPS))
        for spec in fusion_partitions(prog.nstages):
            out = np.asarray(prog.kernel(spec).run_blocked(
                state, regs, steps=STEPS, m=2, block_h=8, interpret=True,
            ))
            assert np.array_equal(out, ref), spec


@_needs_devices(2)
@pytest.mark.parametrize("app_fixture", ["lbm_app", "ad_app"])
def test_partitions_bitwise_match_sharded(app_fixture, request):
    app = request.getfixturevalue(app_fixture)
    prog, state, regs = app["prog"], app["state"], app["regs"]
    for spec in fusion_partitions(prog.nstages):
        one = np.asarray(prog.kernel(spec).run_blocked(
            state, regs, steps=2, m=1, block_h=8, interpret=True, d=1,
        ))
        two = np.asarray(prog.kernel(spec).run_blocked(
            state, regs, steps=2, m=1, block_h=8, interpret=True, d=2,
        ))
        assert np.array_equal(one, two), spec


# --------------------------------------------------------------------------
# Pipelined clusters: intermediates stay on device
# --------------------------------------------------------------------------


def test_pipelined_intermediates_never_visit_host(ad_app):
    prog, state, regs = ad_app["prog"], ad_app["state"], ad_app["regs"]
    pk = prog.kernel("1+1")
    kwargs = dict(steps=2, m=1, block_h=8, interpret=True)
    pk.run_blocked(state, regs, **kwargs)  # warm-up compile
    # Device-to-host is the round-trip being asserted away (uploading
    # the launch's register scalars host-to-device is fine).
    with jax.transfer_guard_device_to_host("disallow"):
        out = pk.run_blocked(state, regs, **kwargs)
    # Materializing afterwards is the caller's (allowed) transfer.
    assert np.asarray(out).shape == state.shape


def test_unfused_baseline_does_round_trip(ad_app, monkeypatch):
    """The contrast path, by transfer count: run_unfused materializes
    every cluster's output on the host (the CPU backend's same-memory
    "transfer" is invisible to the guard, so count the crossings)."""
    prog, state, regs = ad_app["prog"], ad_app["state"], ad_app["regs"]
    pk = prog.kernel("1+1")
    crossings = []
    orig = np.asarray

    def spy(x, *args, **kwargs):
        if isinstance(x, jax.Array):
            crossings.append(x.shape)
        return orig(x, *args, **kwargs)

    monkeypatch.setattr(np, "asarray", spy)
    out = pk.run_unfused(state, regs, steps=2, block_h=8, interpret=True)
    # one host materialization per cluster per step
    assert len(crossings) >= 2 * len(pk.clusters)
    assert orig(out).shape == state.shape


# --------------------------------------------------------------------------
# Fusion legality: composed halos and summed cluster stripes
# --------------------------------------------------------------------------


def _clusters(stages, spec):
    sizes = parse_fusion(spec, len(stages))
    out, lo = [], 0
    for s in sizes:
        out.append(stages[lo:lo + s])
        lo += s
    return out


def test_legal_partitions_fit_vmem():
    stages = ((10, 1), (10, 0), (10, 0))  # the uLBM program geometry
    for spec in fusion_partitions(3):
        bh, m, db = program_blocking_plan(
            64, 16, 4, stages=stages, fusion=spec, width=128,
        )
        m_c = m if "+" not in spec else 1
        for c in _clusters(stages, spec):
            assert cluster_vmem_bytes(
                bh, m_c, 128, [w for w, _ in c], [h for _, h in c], db,
            ) <= VMEM_BYTES, (spec, c)


def test_unsourceable_composed_halo_names_cluster():
    # Fusing two halo-5 stages composes halo 10 > the 8-row shard.
    with pytest.raises(ValueError,
                       match=r"fusion cluster 0 of spec '2'.*composed "
                             r"stencil halo 10"):
        program_blocking_plan(8, 8, 1, stages=((1, 5), (1, 5)),
                              fusion="2", width=W)


def test_vmem_overflow_names_cluster_and_spec():
    with pytest.raises(ValueError,
                       match=r"fusion cluster \d+ of spec '1\+2'.*"
                             r"budget 4096 B"):
        program_blocking_plan(64, 16, 2, stages=((1, 1), (1, 1), (1, 1)),
                              fusion="1+2", width=4096, vmem_bytes=4096)


@given(
    st.lists(
        st.tuples(st.integers(1, 4), st.integers(0, 2)),
        min_size=1, max_size=4,
    ),
    st.integers(0, 63),
    st.integers(1, 8),
)
@settings(max_examples=30, deadline=None)
def test_partition_legality_property(stages, pick, m):
    """Any partition of any stage chain either yields a plan whose
    every cluster stripes within VMEM_BYTES, or raises naming the
    offending cluster."""
    stages = tuple(stages)
    specs = fusion_partitions(len(stages))
    spec = specs[pick % len(specs)]
    try:
        bh, m_res, db = program_blocking_plan(
            64, 16, m, stages=stages, fusion=spec, width=2048,
        )
    except ValueError as e:
        assert "fusion cluster" in str(e)
        assert repr(spec) in str(e)
        return
    m_c = m_res if "+" not in spec else 1
    assert 64 % bh == 0
    for c in _clusters(stages, spec):
        assert cluster_vmem_bytes(
            bh, m_c, 2048, [w for w, _ in c], [h for _, h in c], db,
        ) <= VMEM_BYTES


def test_cluster_vmem_is_sum_of_member_stripes():
    """Linearity in words at the composed halo — the §14 accounting."""
    one = cluster_vmem_bytes(16, 2, 128, [3], [2])
    two = cluster_vmem_bytes(16, 2, 128, [3, 3], [1, 1])
    assert two == 2 * one  # same composed halo, twice the fields


# --------------------------------------------------------------------------
# Plan identity: single-sourced tuple, drift-tested
# --------------------------------------------------------------------------


def test_plan_fields_single_source():
    from dataclasses import fields

    from repro.core import search

    assert tuple(f.name for f in fields(RunPlan)) == PLAN_FIELDS
    # mesh axis (DESIGN.md §15) appended after fusion, defaults last
    assert PLAN_FIELDS[-2:] == ("fusion", "dx")
    # the search package re-exports the one definition
    assert search.RunPlan is RunPlan
    assert search.PLAN_FIELDS is PLAN_FIELDS
    # every plan dimension lands in the executed-point schema
    assert set(PLAN_FIELDS) <= set(search.EXECUTED_POINT_FIELDS)


def test_run_plan_round_trip_and_back_compat():
    p = RunPlan(8, 2, 4, 1, 3, False, 2, "2+1")
    assert RunPlan.from_dict(p.as_dict()) == p
    assert p.key() == (8, 2, 4, 1, 3, False, 2, "2+1", 1)
    # records written before the fusion (and b, double_buffer, reps)
    # dimensions existed resolve to the legacy defaults
    old = RunPlan.from_dict({"block_h": 8, "m": 2, "steps": 4, "d": 1})
    assert (old.reps, old.double_buffer, old.b, old.fusion) == (
        1, True, 1, "",
    )


def test_cache_key_carries_fusion():
    from repro.core.measure import MeasurementCache

    base = ("fp", (H, W), (8, 1, 2, 1, 1, 1), "cpu", True, 1, 1)
    k_legacy = MeasurementCache.make_key(*base)
    k_fused = MeasurementCache.make_key(
        "fp", (H, W), (8, 1, 2, 1, 1, 1, "1+1"), "cpu", True, 1, 1,
    )
    k_other = MeasurementCache.make_key(
        "fp", (H, W), (8, 1, 2, 1, 1, 1, "2"), "cpu", True, 1, 1,
    )
    assert len({k_legacy, k_fused, k_other}) == 3


# --------------------------------------------------------------------------
# Stencil-inference memoization per (core, incoming extents)
# --------------------------------------------------------------------------


def test_stencil_summary_memoized_per_incoming_extents(ad_app):
    from repro.core.codegen import stencil_summary

    compiled = ad_app["prog"].stages[1].compiled  # ReactDiffuse2D
    plain = stencil_summary(compiled)
    shifted = stencil_summary(compiled, incoming=((1, 0),))
    assert plain.halo() == 1
    assert shifted.halo() == 2  # edge extent composes with the stencil
    # each variant is cached; asking again returns the same object
    assert stencil_summary(compiled) is plain
    assert stencil_summary(compiled, incoming=((1, 0),)) is shifted
    # the fused wrapper's kernel sees the composed reach end to end
    assert ad_app["prog"].cluster_kernel(0, 2).halo == 2


# --------------------------------------------------------------------------
# The fusion axis through sweep → search → executed points
# --------------------------------------------------------------------------


def test_fusion_axis_sweeps_and_executes(ad_app):
    from repro.core.search import EXECUTED_POINT_FIELDS, ExhaustiveSearch

    prog, state, regs = ad_app["prog"], ad_app["state"], ad_app["regs"]
    ex = prog.explorer(H * W, grid_w=W)
    sweep = ex.sweep_tpu(
        bh_values=(8, 16), m_values=(1, 2),
        fusion_values=fusion_partitions(prog.nstages),
    )
    assert sorted(set(map(str, sweep.data["fusion"]))) == ["1+1", "2"]
    res = ex.search(
        sweep, state, regs, strategy=ExhaustiveSearch(k=8),
        reps=1, calibrate=False, cache=False, interpret=True,
    )
    executed = res.executed
    assert executed, "exhaustive search executed nothing"
    assert {e.fusion for e in executed} == {"2", "1+1"}
    for e in executed:
        assert tuple(e.as_dict().keys()) == EXECUTED_POINT_FIELDS
        assert e.as_dict()["fusion"] in ("2", "1+1")
