"""Search subsystem: strategies, budget accounting, plan dedupe, cache
composition (docs/pipeline.md §search, DESIGN.md §10).

The load-bearing assertions (ISSUE 5 acceptance criteria):

* on the CI lattice, LocalRefine and SuccessiveHalving each find a
  point whose *measured* GFLOPS is >= 95% of the exhaustively-measured
  best while spending strictly fewer measurements than exhaustive;
* the hard budget is never exceeded (asserted with a deterministic
  fake timer that counts every live timing);
* successive halving promotes the *measured* best even when the model
  mis-ranks it;
* measurement-cache hits carry across strategy re-runs, so strategies
  compose.

All strategy-logic tests run with an injected deterministic timer
(wall time derived from the analytic model of the legalized plan), so
no kernel executes and no host-timing noise can flake the assertions;
one end-to-end test drives a real codegen'd kernel through
``Explorer.search``.
"""

import numpy as np
import pytest
from _hypothesis_stub import HAVE_HYPOTHESIS, given, settings, st
from _search_harness import (
    BH_VALUES,
    H,
    M_VALUES,
    TOY,
    W,
    ModelTimer,
    _rf,
)

from repro.core.explorer import Explorer
from repro.core.legalize import (
    VMEM_BYTES,
    blocking_plan,
    constraint_violation,
    legal_block_values,
    shard_height,
    stripe_vmem_bytes,
)
from repro.core.measure import MeasurementCache
from repro.core.search import (
    PLAN_FIELDS,
    BudgetExhausted,
    ExhaustiveSearch,
    LocalRefine,
    SearchResult,
    SuccessiveHalving,
    TPESearch,
    get_strategy,
)


@pytest.fixture()
def ex():
    return Explorer(TOY)


@pytest.fixture()
def sweep(ex):
    return ex.sweep_tpu(
        bh_values=BH_VALUES, m_values=M_VALUES, d_values=(1,)
    )


def _search(ex, sweep, timer, **kw):
    kw.setdefault("run_factory", _rf)
    kw.setdefault("grid_shape", (H, W))
    kw.setdefault("calibrate", False)
    return ex.search(sweep, timer=timer, **kw)


# ----------------------- strategy registry -----------------------


def test_get_strategy_registry():
    assert isinstance(get_strategy("exhaustive"), ExhaustiveSearch)
    assert isinstance(get_strategy("refine"), LocalRefine)
    assert isinstance(get_strategy("halving"), SuccessiveHalving)
    assert isinstance(get_strategy("tpe"), TPESearch)
    inst = SuccessiveHalving(eta=2)
    assert get_strategy(inst) is inst
    assert isinstance(get_strategy(LocalRefine), LocalRefine)
    with pytest.raises(ValueError, match="unknown search strategy"):
        get_strategy("simulated-annealing")
    with pytest.raises(TypeError, match="SearchStrategy"):
        get_strategy(object())


# ----------------------- acceptance: strategies vs exhaustive ---------------


def test_budgeted_strategies_match_exhaustive_best(ex, sweep):
    """ISSUE 5 acceptance: on the CI lattice, refine and halving each
    find a point whose measured GFLOPS is >= 95% of the exhaustively-
    measured best while spending strictly fewer measurements."""
    timer = ModelTimer()
    exhaustive = _search(
        ex, sweep, timer, strategy=ExhaustiveSearch(frontier_only=False)
    )
    n_candidates = len({
        (e.block_h, e.m, e.steps, e.d) for e in exhaustive.executed
    })
    assert n_candidates > 12  # wide enough that budgeting means something
    assert exhaustive.budget_spent == n_candidates
    best = exhaustive.best.measured_gflops

    for strat in ("refine", "halving"):
        timer_s = ModelTimer()
        res = _search(ex, sweep, timer_s, strategy=strat, budget=12)
        assert res.strategy == strat
        assert res.best is not None
        assert res.best.measured_gflops >= 0.95 * best, strat
        assert res.budget_spent < exhaustive.budget_spent, strat
        assert res.budget_spent == len(timer_s.calls), strat


def test_exhaustive_frontier_only_reproduces_execute_frontier(ex, sweep):
    """The facade strategy walks the frontier top-down and stops at k."""
    timer = ModelTimer()
    res = _search(
        ex, sweep, timer,
        strategy=ExhaustiveSearch(k=2, frontier_only=True),
    )
    frontier = sweep.frontier()
    assert len(res.executed) == 2
    assert [e.point.key() for e in res.executed] == [
        p.key() for p in frontier[:2]
    ]


# ----------------------- budget: hard, never exceeded -----------------------


@pytest.mark.parametrize("strat", ["exhaustive", "refine", "halving", "tpe"])
def test_budget_never_exceeded(ex, sweep, strat):
    for budget in (1, 3, 7):
        timer = ModelTimer()
        res = _search(ex, sweep, timer, strategy=strat, budget=budget)
        assert res.budget == budget
        assert res.budget_spent <= budget, (strat, budget)
        assert len(timer.calls) == res.budget_spent, (strat, budget)
        # the ledger agrees with the timer's own count
        assert sum(m["count"] for m in res.measurements) == res.budget_spent


def test_budget_validation_and_exhaustion(ex, sweep):
    with pytest.raises(ValueError, match="budget"):
        _search(ex, sweep, ModelTimer(), budget=0)

    class Greedy:
        name = "greedy"

        def search(self, sweep, runner):
            # a buggy strategy that ignores exhaustion must be stopped
            with pytest.raises(BudgetExhausted):
                for pt in sweep.frontier() * 50:
                    runner.measure(pt)
            return []

    timer = ModelTimer()
    res = _search(ex, sweep, timer, strategy=Greedy(), budget=2)
    assert res.budget_spent == 2 and len(timer.calls) == 2


# ----------------------- successive halving -----------------------


def test_halving_promotes_the_measured_best(ex, sweep):
    """When measurement disagrees with the model, the measured winner
    must survive every rung and come out full-rep at the top."""
    # model rank of (8, 1) is near the bottom (memory-bound, m=1) —
    # boost it 16x so it *measures* fastest (the model's spread across
    # this lattice is ~8x, so 16x puts it clear of every prediction).
    timer = ModelTimer(boost={(8, 1, 1): 16.0})
    res = _search(
        ex, sweep, timer, strategy=SuccessiveHalving(eta=2), reps=3,
    )
    b = res.best
    assert (b.block_h, b.m, b.d) == (8, 1, 1)
    assert b.reps == 3  # full-rep final, not the 1-rep screening number
    # ... and the runner really did screen cheap first
    assert any(p.reps == 1 for p in timer.calls)
    assert any(
        p.reps == 3 and (p.block_h, p.m) == (8, 1) for p in timer.calls
    )


def test_best_ignores_lucky_screening_rep(ex, sweep):
    """A 1-rep screening fluke on a plan must not outrank that same
    plan's honest full-rep final in ``SearchResult.best``."""
    base = ModelTimer()

    def flaky(plan, run, reps, warmup):
        wall = base(plan, run, reps, warmup)
        if reps == 1:  # screening runs get a lucky 10x-short wall
            wall /= 10.0
        return wall

    res = _search(
        ex, sweep, flaky, strategy=SuccessiveHalving(eta=2), reps=3,
    )
    b = res.best
    assert b.reps == 3  # the honest final, not the flukey screening
    # the same plan's screening measurement is in `executed` and looks
    # 10x better — best must have skipped past it
    screened = [
        e for e in res.executed
        if (e.block_h, e.m, e.d) == (b.block_h, b.m, b.d) and e.reps == 1
    ]
    assert screened and screened[0].measured_gflops > b.measured_gflops


def test_injected_timer_walls_never_serve_honest_runs(ex, sweep, tmp_path):
    """Synthetic walls from a fake timer live in their own cache-key
    namespace: an honest search over the same plans must re-time, not
    inherit fabricated numbers."""
    cache = MeasurementCache(tmp_path / "m.json")
    fake = _search(
        ex, sweep, ModelTimer(),
        strategy=ExhaustiveSearch(k=2, frontier_only=True),
        cache=cache, cache_tag="toy",
    )
    assert fake.budget_spent > 0
    # identical reps/plans: only the key namespace separates the runs
    honest = _search(
        ex, sweep, None,  # timer=None: the real harness
        strategy=ExhaustiveSearch(k=2, frontier_only=True),
        cache=cache, cache_tag="toy",
    )
    assert honest.budget_spent > 0  # not served the fabricated walls
    assert not any(e.cached for e in honest.executed)


def test_halving_sizes_rung0_to_the_budget(ex, sweep):
    """With budget B and eta, rung 0 takes ~B(eta-1)/eta candidates so
    the whole geometric schedule fits inside B."""
    timer = ModelTimer()
    res = _search(
        ex, sweep, timer, strategy=SuccessiveHalving(eta=3), budget=12,
    )
    rung0 = [p for p in timer.calls if p.reps == 1]
    assert len(rung0) <= 8  # 12 * (3-1)/3
    assert res.budget_spent <= 12


# ----------------------- local refine -----------------------


def test_refine_walks_block_h_off_the_lattice(ex):
    """block_h is first-class: refine reaches divisors of h the sweep
    lattice never proposed when they measure faster."""
    # Lattice only offers bh in {16, 64}; on h=64 the divisor chain has
    # 32 between them. Boost 32 so measurement pulls the climb there.
    sweep = ex.sweep_tpu(bh_values=(16, 64), m_values=(2,), d_values=(1,))
    best_m = 2
    timer = ModelTimer(boost={(32, best_m, 1): 10.0})
    res = _search(ex, sweep, timer, strategy=LocalRefine(seeds=1))
    assert res.best.block_h == 32  # not a lattice value
    assert 32 in legal_block_values(H, best_m, halo=TOY.halo)


def test_refine_improves_on_a_mis_ranked_seed(ex, sweep):
    """Hill-climb: when a neighbor measures better than the model-best
    seed, refine moves to it."""
    timer = ModelTimer(boost={(32, 8, 1): 6.0})
    res = _search(ex, sweep, timer, strategy=LocalRefine(seeds=1))
    assert (res.best.block_h, res.best.m) == (32, 8)


# ----------------------- plan dedupe -----------------------


def test_distinct_lattice_points_same_plan_timed_once(ex):
    """Satellite (ISSUE 5): lattice points that legalize to the same
    concrete plan are measured once per search even with the cache
    off."""
    # On h=64, requests 64/128/256 with m=2 all legalize to block 64.
    sweep = ex.sweep_tpu(
        bh_values=(64, 128, 256), m_values=(2,), d_values=(1,)
    )
    assert all(
        blocking_plan(H, int(bh), 2) == (64, 2, True)
        for bh in (64, 128, 256)
    )
    timer = ModelTimer()
    res = _search(
        ex, sweep, timer, strategy=ExhaustiveSearch(frontier_only=False)
    )
    assert len(timer.calls) == 1  # one concrete plan -> one live timing
    assert res.budget_spent == 1


# ----------------------- cache composition across strategies ----------------


def test_cache_hits_carry_across_strategy_reruns(ex, sweep, tmp_path):
    """Satellite (ISSUE 5): a second strategy (and a repeated search)
    over the same lattice is served from the measurement cache — its
    budget goes only to plans nobody timed yet."""
    cache = MeasurementCache(tmp_path / "m.json")
    t1 = ModelTimer()
    first = _search(
        ex, sweep, t1, strategy=ExhaustiveSearch(frontier_only=False),
        cache=cache, cache_tag="toy",
    )
    assert first.budget_spent == len(t1.calls) > 12
    assert not any(e.cached for e in first.executed)

    # identical exhaustive re-run: all hits, zero spent
    t2 = ModelTimer()
    again = _search(
        ex, sweep, t2, strategy=ExhaustiveSearch(frontier_only=False),
        cache=cache, cache_tag="toy",
    )
    assert again.budget_spent == 0 and not t2.calls
    assert all(e.cached for e in again.executed)

    # a different strategy at the same reps pays only for new plans
    t3 = ModelTimer()
    refined = _search(
        ex, sweep, t3, strategy="refine", cache=cache, cache_tag="toy",
    )
    hits = sum(1 for e in refined.executed if e.cached)
    assert hits > 0  # the seeds were already timed by the exhaustive pass
    assert refined.budget_spent < first.budget_spent
    assert refined.budget_spent == len(t3.calls)


# ----------------------- result schema -----------------------


def test_search_result_schema(ex, sweep):
    res = _search(ex, sweep, ModelTimer(), strategy="halving", budget=6)
    assert isinstance(res, SearchResult)
    d = res.as_dict()
    for key in ("strategy", "budget", "budget_spent", "measurements",
                "best", "executed", "skipped_devices", "skipped_illegal"):
        assert key in d
    assert d["strategy"] == "halving" and d["budget"] == 6
    assert d["budget_spent"] == res.budget_spent
    for m in d["measurements"]:
        assert set(m) == set(PLAN_FIELDS) | {"count"}
        assert m["count"] >= 1
    assert d["best"] == res.best.as_dict()


# ----------------------- legalize: deterministic properties -----------------


def test_constraint_violation_zero_iff_feasible():
    """ISSUE 6 satellite: the continuous distance is 0 exactly when
    blocking_plan would produce a legal plan — over a dense grid of
    (h, block_h, m, d, width) requests, including VMEM-tight ones."""
    words = 8
    for h in (7, 16, 60, 64):
        for m in (1, 2, 4, 16):
            for d in (1, 2, 3):
                for width in (0, 64, 600_000, 3_000_000):
                    v = constraint_violation(
                        h, 16, m, halo=1, width=width, words=words, d=d
                    )
                    try:
                        blocking_plan(
                            h, 16, m, halo=1, width=width, words=words, d=d
                        )
                        legal = True
                    except ValueError:
                        legal = False
                    assert (v == 0.0) == legal, (h, m, d, width)
                    assert v >= 0.0


def test_constraint_violation_monotone_in_vmem_overshoot():
    """The deeper the smallest legal stripe overflows VMEM, the larger
    the distance — the gradient surrogate samplers follow."""
    words = 8
    widths = (1_000_000, 2_000_000, 4_000_000, 8_000_000)
    vals = [
        constraint_violation(64, 64, 2, halo=1, width=w, words=words)
        for w in widths
    ]
    assert vals[0] > 0.0  # all of these overflow the budget
    assert all(b > a for a, b in zip(vals, vals[1:]))  # strictly monotone
    # ... and scale-free: violation is the fractional overshoot of the
    # *single-buffer streaming fallback* — the last protocol blocking_plan
    # tries before giving up, so distance-to-feasible is measured from it.
    need = min(
        stripe_vmem_bytes(v, 2, widths[0], words, 1, double_buffer=False)
        for v in legal_block_values(64, 2, halo=1, double_buffer=False)
    )
    assert vals[0] == pytest.approx((need - VMEM_BYTES) / VMEM_BYTES)


def test_constraint_violation_unshardable_and_unsourceable():
    # h % d != 0: no closest legal plan at all — above every VMEM case
    assert constraint_violation(64, 16, 2, d=3) > 1.0
    # halo taller than the shard: the m-shrink loop cannot save it
    assert constraint_violation(4, 4, 1, halo=8) > 1.0
    with pytest.raises(ValueError):
        constraint_violation(0, 8, 1)
    with pytest.raises(ValueError):
        constraint_violation(64, 8, 1, d=0)


# ----------------------- legalize: hypothesis properties ---------------------


@given(
    h=st.integers(min_value=1, max_value=512),
    m=st.integers(min_value=1, max_value=64),
    halo=st.integers(min_value=0, max_value=4),
    d=st.sampled_from([1, 2, 4]),
)
@settings(max_examples=80, deadline=None)
def test_prop_legal_block_values_divide_the_shard(h, m, halo, d):
    if h % d:
        with pytest.raises(ValueError, match="shards"):
            legal_block_values(h, m, halo=halo, d=d)
        return
    chain = legal_block_values(h, m, halo=halo, d=d)
    local_h = shard_height(h, d)
    for v in chain:
        assert local_h % v == 0
        assert v >= max(1, min(m, local_h) * halo) or halo == 0
    assert list(chain) == sorted(chain)


@given(
    h=st.sampled_from([16, 64, 120, 256]),
    block_h=st.integers(min_value=1, max_value=512),
    m=st.integers(min_value=1, max_value=32),
    width=st.integers(min_value=1, max_value=400_000),
    words=st.integers(min_value=1, max_value=16),
)
@settings(max_examples=80, deadline=None)
def test_prop_blocking_plan_respects_vmem(h, block_h, m, width, words):
    """Whenever blocking_plan returns, its stripe fits the VMEM budget
    — and constraint_violation agrees it is feasible."""
    try:
        bh, mm, db = blocking_plan(h, block_h, m, halo=1, width=width,
                                   words=words)
    except ValueError:
        assert constraint_violation(
            h, block_h, m, halo=1, width=width, words=words
        ) > 0.0
        return
    assert h % bh == 0 and mm * 1 <= bh * mm  # legal divisor, sane m
    assert stripe_vmem_bytes(bh, mm, width, words, 1, db) <= VMEM_BYTES
    assert constraint_violation(
        h, block_h, m, halo=1, width=width, words=words
    ) == 0.0


def test_hypothesis_stub_contract():
    """The shim must expose the four names whether or not hypothesis is
    installed (so this module always collects)."""
    assert isinstance(HAVE_HYPOTHESIS, bool)
    assert callable(given) and callable(settings)


def test_legal_block_values_units():
    # tile-aligned divisor chain of 64 that can source m*halo rows
    assert legal_block_values(64, 4, halo=1) == (8, 16, 32, 64)
    assert legal_block_values(64, 1, halo=0) == (8, 16, 32, 64)
    assert legal_block_values(64, 12, halo=1) == (16, 32, 64)
    # per-shard: chain over 64/2 = 32 rows
    assert legal_block_values(64, 2, halo=1, d=2) == (8, 16, 32)
    # VMEM clamp prunes the top of the chain like blocking_plan does
    wide = legal_block_values(64, 2, halo=1, width=20_000, words=10)
    assert wide and max(wide) < 64
    with pytest.raises(ValueError, match="shards"):
        legal_block_values(64, 2, d=3)


# ----------------------- end to end: a real kernel -----------------------


def test_search_executes_real_codegen_kernel():
    """One honest pass: LocalRefine drives the real diffusion Pallas
    kernel (interpret mode) through Explorer.search."""
    from repro.apps import diffusion as dif

    sim = dif.DiffusionSimulation(32, 64, alpha=0.2)
    ex = sim.explorer()
    sweep = ex.sweep_tpu(
        bh_values=(8, 16, 32), m_values=(1, 2, 4), d_values=(1,)
    )
    u0, _ = dif.sine_init(32, 64)
    res = ex.search(
        sweep, sim.state(u0), (sim.alpha,), strategy="refine",
        budget=8, reps=1, calibrate=False,
    )
    assert res.budget_spent <= 8
    assert res.executed and res.best.wall_s > 0
    for e in res.executed:
        assert 32 % e.block_h == 0 and e.m <= e.block_h
        assert np.isfinite(e.measured_gflops) and e.measured_gflops > 0
