"""Load generator for the simulation-serving engine (DESIGN.md §13,
docs/pipeline.md §serve): open-loop Poisson arrivals over a multi-tenant
mix — 2-D diffusion at two grid sizes plus the uLBM core — driven
through :class:`repro.serve.sim.SimEngine` end to end:

1. **Cold start** — a fresh study directory: every context autotunes on
   first request through the budgeted non-blocking stepper
   (``live_timings`` > 0, one measurement per engine tick, interleaved
   with serving the already-warm tenants).
2. **Warm start** — a second engine over the *same* study directory and
   measurement cache: the journals replay into the runners' dedupe
   tables and every plan pins with **zero** live timings
   (``live_timings == 0``); the cold-vs-warm latency gap is the
   recorded price of first-request tuning.
3. **Batching win** — the same arrival schedule served by a resolver
   restricted to ``b_values=(1,)`` (sequential per-tenant launches):
   steady-state aggregate member-steps/s of the batched configuration
   must exceed it (``batched_wins``), the acceptance fact for the batch
   axis. Launch wall clock only — tuning time is excluded from
   ``steps_per_s`` on both sides.
4. **Backpressure** — a burst into a tiny admission queue: rejects are
   counted and *every accepted request completes* (no silent drops,
   ``accepted == completed``).

Reported per phase: steady-state aggregate steps/s, p50/p95/p99
submit→retire latency, the batch-occupancy histogram, tuning-tick and
live-timing counts, and the pinned per-context plans. Invoked as a
script this writes ``BENCH_serve.json`` next to the repo root (the
PR-over-PR trajectory file); ``--check`` re-runs the bench and
hard-fails against the committed baseline (warm p99 regression > 2x,
non-backpressure drops, a lost batching win, or a warm start that
timed anything live) — the CI ``serve`` job's gate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

from repro.apps import diffusion as dif
from repro.apps import lbm
from repro.compat import enable_compile_cache
from repro.core.measure import MeasurementCache
from repro.serve.sim import PlanResolver, SimEngine, SimRequest

#: Hard cap on live measurements per trial context (autotune-on-first-
#: request): the cold phase must never exceed ``n_contexts * BUDGET``.
BUDGET = 4

#: Requests per tenant and fused steps per request — small enough that
#: the whole bench (four phases, interpret mode) stays inside the CI
#: smoke window, large enough that per-launch overhead dominates noise.
REQUESTS_PER_TENANT = 8
STEPS_PER_REQUEST = 16

#: Open-loop arrival intensity: expected requests per engine tick. The
#: engine never paces the generator (rejects are counted, not retried).
#: Deliberately *saturating* — a group retires at most one batched
#: launch per tick, so arrivals outpacing the tick loop build the
#: backlog that lets the batch axis engage at full width (an idle
#: engine serves width-1 launches and batching is moot).
ARRIVAL_RATE = 8.0

BENCH_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_serve.json",
)


# --------------------------------------------------------------------------
# Tenant mix + arrival schedule
# --------------------------------------------------------------------------


def make_tenants() -> list[dict]:
    """The tenant mix: one entry per (core, grid, regs) trial context.

    Kernels are built once per tenant and shared across its requests so
    the engine's per-object kernel cache sees one fingerprinting per
    context — the realistic serving shape.
    """
    tenants = []
    for h, w, alpha in ((32, 128, 0.2), (64, 128, 0.1)):
        sim = dif.DiffusionSimulation(h, w, alpha=alpha)
        u0, _ = dif.sine_init(h, w)
        tenants.append({
            "name": f"diffusion-{h}x{w}",
            "core": sim.kernel,
            "state": sim.state(u0),
            "regs": (sim.alpha,),
        })
    lsim = lbm.LBMSimulation(lbm.LBMProblem(32, 128, mode="wrap"))
    f0, attr, _ = lbm.taylor_green_init(32, 128)
    tenants.append({
        "name": "lbm-32x128",
        "core": lsim.stream_kernel(),
        "state": lsim.stream_state(f0, attr),
        "regs": lsim.stream_regs(),
    })
    return tenants


def make_schedule(tenants, *, seed: int = 0,
                  rate: float = ARRIVAL_RATE,
                  per_tenant: int = REQUESTS_PER_TENANT) -> list[tuple]:
    """Open-loop Poisson arrivals: ``(arrival_tick, tenant_index)``.

    Inter-arrival gaps are exponential in *ticks* (the engine's clock),
    tenant assignment is a seeded uniform draw constrained to exactly
    ``per_tenant`` requests each — the same seed reproduces the same
    trace for every phase, so cold/warm/b=1 comparisons see identical
    offered load.
    """
    rng = np.random.default_rng(seed)
    total = per_tenant * len(tenants)
    gaps = rng.exponential(1.0 / rate, size=total)
    ticks = np.floor(np.cumsum(gaps)).astype(int)
    order = rng.permutation(
        np.repeat(np.arange(len(tenants)), per_tenant)
    )
    return list(zip(ticks.tolist(), order.tolist()))


def drive(engine: SimEngine, tenants, schedule, *, rid_base: int = 0,
          max_ticks: int = 5_000):
    """Feed the schedule open-loop and tick until drained.

    Arrivals whose tick has come are submitted before each tick;
    rejected submissions (queue full) are dropped and counted by the
    engine — open-loop means the generator never retries or paces.
    Arrival ticks are relative to the engine's clock at entry, so
    repeated passes over the same schedule offer identical load (and
    hence identical launch shapes) regardless of prior ticks.
    """
    completions = []
    base = engine.tick_count
    rid = rid_base
    i = 0
    while i < len(schedule) or engine.queue or engine._active_count():
        while (i < len(schedule)
               and schedule[i][0] + base <= engine.tick_count):
            t = tenants[schedule[i][1]]
            engine.submit(SimRequest(
                rid=rid, core=t["core"], state=t["state"],
                steps=STEPS_PER_REQUEST, regs=t["regs"],
            ))
            rid += 1
            i += 1
        completions.extend(engine.step())
        if engine.tick_count - base > max_ticks:
            raise RuntimeError(
                f"load generator hit max_ticks={max_ticks} with "
                f"{len(schedule) - i} arrival(s) unsubmitted"
            )
    return completions


def steady_state(engine: SimEngine, tenants, schedule) -> dict:
    """Two-pass steady-state measurement: a warmup pass absorbs tuning
    and the one-time per-launch-shape trace/lower cost, then the
    accounting window resets and an identical measured pass reports
    pure launch work (throughput, latency, occupancy)."""
    drive(engine, tenants, schedule)
    engine.reset_counters()
    completions = drive(engine, tenants, schedule,
                        rid_base=len(schedule))
    return _phase_report(engine, completions)


def _phase_report(engine: SimEngine, completions) -> dict:
    """One phase's record: engine stats + latency percentiles."""
    stats = engine.stats()
    lat = np.array([c.latency_s for c in completions], dtype=float)
    waits = np.array([c.queue_wait_ticks for c in completions])
    stats["latency"] = {
        "p50_s": float(np.percentile(lat, 50)) if lat.size else 0.0,
        "p95_s": float(np.percentile(lat, 95)) if lat.size else 0.0,
        "p99_s": float(np.percentile(lat, 99)) if lat.size else 0.0,
        "mean_s": float(lat.mean()) if lat.size else 0.0,
        "max_queue_wait_ticks": int(waits.max()) if waits.size else 0,
    }
    return stats


# --------------------------------------------------------------------------
# The benchmark
# --------------------------------------------------------------------------


def run(bench: dict | None = None, *, seed: int = 0) -> list[str]:
    """Run the four phases; fill ``bench`` (if given) for the JSON."""
    out = []
    t0 = time.time()
    tenants = make_tenants()
    schedule = make_schedule(tenants, seed=seed)
    out.append(
        f"## serve bench: {len(schedule)} requests over "
        f"{len(tenants)} tenant context(s) "
        f"({', '.join(t['name'] for t in tenants)}), "
        f"open-loop Poisson rate {ARRIVAL_RATE}/tick, "
        f"{STEPS_PER_REQUEST} steps/request, tuning budget {BUDGET}"
    )

    with tempfile.TemporaryDirectory(prefix="serve-bench-") as tmp:
        study_dir = os.path.join(tmp, "studies")
        cache = MeasurementCache(os.path.join(tmp, "measurements.json"))

        def resolver(**kw):
            kw.setdefault("budget", BUDGET)
            kw.setdefault("b_values", (1, 2, 4, 8))
            kw.setdefault("bh_values", (8, 16, 32))
            kw.setdefault("m_values", (1, 2, 4))
            kw.setdefault("study_dir", study_dir)
            kw.setdefault("cache", cache)
            return PlanResolver(**kw)

        # ---- phase 1: cold start --------------------------------------
        cold_eng = SimEngine(resolver())
        cold = _phase_report(cold_eng, drive(cold_eng, tenants, schedule))
        out.append(
            f"\n## phase 1: cold start — {cold['live_timings']} live "
            f"timing(s) across {len(cold['plans'])} context(s), "
            f"{cold['tuning_ticks']} tuning tick(s), "
            f"{cold['steps_per_s']:.1f} member-steps/s steady state"
        )
        for key, plan in sorted(cold["plans"].items()):
            out.append(
                f"  {key}: block_h={plan['block_h']} m={plan['m']} "
                f"b={plan['b']} db={plan['double_buffer']} "
                f"[{plan['source']}, {plan['budget_spent']} timed, "
                f"{plan['replayed']} replayed]"
            )

        # ---- phase 2: warm start (same studies + cache) ----------------
        warm_eng = SimEngine(resolver())
        warm = steady_state(warm_eng, tenants, schedule)
        out.append(
            f"\n## phase 2: warm start — {warm['live_timings']} live "
            f"timing(s) (study replay pins every plan), "
            f"{warm['steps_per_s']:.1f} member-steps/s steady state, "
            f"p99 latency {warm['latency']['p99_s']*1e3:.1f} ms "
            f"(cold p99 {cold['latency']['p99_s']*1e3:.1f} ms — the "
            f"price of first-request tuning + tracing)"
        )
        out.append(
            "  occupancy: " + ", ".join(
                f"b={k}: {v} launch(es)"
                for k, v in warm["occupancy"].items()
            )
        )

        # ---- phase 3: b=1 sequential baseline --------------------------
        b1_eng = SimEngine(resolver(
            b_values=(1,),
            study_dir=os.path.join(tmp, "studies-b1"),
        ))
        b1 = steady_state(b1_eng, tenants, schedule)
        batched_wins = warm["steps_per_s"] > b1["steps_per_s"]
        out.append(
            f"\n## phase 3: batching win — batched "
            f"{warm['steps_per_s']:.1f} vs b=1 sequential "
            f"{b1['steps_per_s']:.1f} member-steps/s "
            f"({warm['steps_per_s'] / b1['steps_per_s']:.2f}x, "
            f"{warm['launches']} vs {b1['launches']} launches) -> "
            f"{'WIN' if batched_wins else 'LOSS'}"
        )

        # ---- phase 4: backpressure burst -------------------------------
        bp_eng = SimEngine(resolver(), max_queue=4, max_active=4)
        bp_completions = []
        accepted = 0
        for rid, t in enumerate(tenants * 4):  # burst, no pacing
            accepted += bp_eng.submit(SimRequest(
                rid=1000 + rid, core=t["core"], state=t["state"],
                steps=STEPS_PER_REQUEST, regs=t["regs"],
            ))
        bp_completions = bp_eng.run_until_drained()
        bp = _phase_report(bp_eng, bp_completions)
        out.append(
            f"\n## phase 4: backpressure — burst of "
            f"{accepted + bp['rejected']} into max_queue=4: "
            f"{bp['rejected']} rejected at submit, {accepted} accepted, "
            f"{bp['completed']} completed (no silent drops)"
        )

    out.append(
        f"\nserve_bench,{(time.time() - t0) * 1e6:.0f},"
        f"batched={warm['steps_per_s']:.1f};b1={b1['steps_per_s']:.1f};"
        f"warm_live={warm['live_timings']}"
    )

    if bench is not None:
        bench["mix"] = {
            "tenants": [t["name"] for t in tenants],
            "requests": len(schedule),
            "steps_per_request": STEPS_PER_REQUEST,
            "arrival_rate_per_tick": ARRIVAL_RATE,
            "budget": BUDGET,
            "seed": seed,
        }
        bench["cold"] = cold
        bench["warm"] = warm
        bench["b1"] = b1
        bench["backpressure"] = {
            "accepted": int(accepted),
            "rejected": int(bp["rejected"]),
            "completed": int(bp["completed"]),
        }
        bench["batching"] = {
            "batched_steps_per_s": float(warm["steps_per_s"]),
            "b1_steps_per_s": float(b1["steps_per_s"]),
            "speedup": float(warm["steps_per_s"] / b1["steps_per_s"]),
            "batched_wins": bool(batched_wins),
        }
    return out


# --------------------------------------------------------------------------
# Gates (the CI serve job's hard checks)
# --------------------------------------------------------------------------


def check(bench: dict, baseline: dict | None = None) -> list[str]:
    """The acceptance gates; raises ``RuntimeError`` on any violation.

    ``bench`` is a fresh run's record; ``baseline`` the committed
    ``BENCH_serve.json`` (p99 regression is only checkable against it).
    """
    errors = []
    if bench["warm"]["live_timings"] != 0:
        errors.append(
            f"warm start timed {bench['warm']['live_timings']} "
            f"point(s) live (study replay must pin every plan)"
        )
    if not bench["batching"]["batched_wins"]:
        errors.append(
            f"batching win lost: batched "
            f"{bench['batching']['batched_steps_per_s']:.1f} <= b=1 "
            f"{bench['batching']['b1_steps_per_s']:.1f} member-steps/s"
        )
    bp = bench["backpressure"]
    if bp["completed"] != bp["accepted"]:
        errors.append(
            f"non-backpressure drop: {bp['accepted']} accepted but "
            f"{bp['completed']} completed"
        )
    for phase in ("cold", "warm", "b1"):
        ph = bench[phase]
        if ph["completed"] != ph["submitted"]:
            errors.append(
                f"{phase}: {ph['submitted']} accepted but "
                f"{ph['completed']} completed"
            )
    max_live = bench["mix"]["budget"] * len(bench["cold"]["plans"])
    if bench["cold"]["live_timings"] > max_live:
        errors.append(
            f"cold start overspent: {bench['cold']['live_timings']} "
            f"live timing(s) > budget x contexts = {max_live}"
        )
    if baseline is not None:
        base_p99 = baseline["warm"]["latency"]["p99_s"]
        fresh_p99 = bench["warm"]["latency"]["p99_s"]
        if base_p99 > 0 and fresh_p99 > 2.0 * base_p99:
            errors.append(
                f"warm p99 regression: {fresh_p99*1e3:.1f} ms > 2x "
                f"committed baseline {base_p99*1e3:.1f} ms"
            )
    if errors:
        raise RuntimeError(
            "serve bench gate failure:\n  - " + "\n  - ".join(errors)
        )
    return [
        "## gates: warm-zero-tuning OK, batching-win OK, "
        "no-silent-drops OK, budget OK"
        + (", p99-vs-baseline OK" if baseline is not None else "")
    ]


def write_bench(path: str = BENCH_PATH, *, seed: int = 0) -> list[str]:
    """Run the load generator and record ``BENCH_serve.json``."""
    bench: dict = {}
    out = run(bench, seed=seed)
    out.extend(check(bench))
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(bench, fh, indent=2, sort_keys=True)
        fh.write("\n")
    out.append(f"[wrote {path}]")
    return out


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="run the bench and hard-fail against the "
                         "committed BENCH_serve.json instead of "
                         "rewriting it (the CI serve job's gate)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.check:
        with open(BENCH_PATH, encoding="utf-8") as fh:
            baseline = json.load(fh)
        bench: dict = {}
        out = run(bench, seed=args.seed)
        try:
            out.extend(check(bench, baseline))
        except RuntimeError as e:
            print("\n".join(out))
            print(f"\nFAIL: {e}", file=sys.stderr)
            raise SystemExit(1)
        print("\n".join(out))
    else:
        print("\n".join(write_bench(seed=args.seed)))


if __name__ == "__main__":
    main()
