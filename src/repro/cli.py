"""Console entry points (`repro-explore`, see pyproject.toml).

The design-space-exploration walkthrough lives here (importable after
``pip install``); ``examples/dse_explore.py`` is a thin wrapper for
running it straight from a checkout. The flow is the paper's workflow as
a tool — compile SPD cores, sweep both target models in batched NumPy
(including the device axis ``d``, docs/pipeline.md §distribute), extract
Pareto frontiers, and execute TPU frontier points through real Pallas
kernels via the pluggable search subsystem, ``Explorer.search``
(docs/pipeline.md §execute, §search): ``--strategy`` picks how the
measurement budget is spent — ``exhaustive`` walks the Pareto frontier
top-down (the default), ``refine`` hill-climbs the (block_h, m, d)
neighborhood of the model's best points, ``halving`` races a wide
model-ranked pool with cheap screening reps and full-rep finals —
``tpe`` learns where to measure next with a seeded Tree-structured
Parzen Estimator (docs/pipeline.md §study) — and ``--budget N`` caps
live measurements hard. ``--study NAME`` journals every trial into a
durable study (``--study-dir``, default ``~/.cache/repro/studies``):
re-running with the same name replays completed trials into the plan
dedupe table, so an interrupted search resumes with zero
re-measurement; ``--seed`` fixes the TPE sampler's RNG and ``--trials``
bounds its total observations. Single-device points
run the codegen'd kernel directly, ``d > 1`` points run sharded with
halo exchange when the platform has the devices. ``--devices N`` caps
the swept d axis; ``--mesh DYxDX`` pins a 2-D device mesh (rows shard
across DY, columns across DX — DESIGN.md §15) and ``--mesh auto``
sweeps the column axis so the search enumerates factorizations of the
device count. ``--json PATH`` dumps the machine-readable results
(including ``strategy``, ``budget_spent``, and per-candidate
measurement counts) for scripting.

Measurement policy (docs/pipeline.md §measure): runs are timed with the
honest harness (``--reps`` median-of-reps, every rep synchronized), the
platform is calibrated so ``rel err`` diffs against the backend actually
running (``--no-calibrate`` to compare against raw TPU-v5e roofline
constants instead), and wall times persist in the on-disk measurement
cache (``--no-cache`` to always re-time).
"""

from __future__ import annotations

import argparse
import json


def _point_dict(p) -> dict:
    return {
        "d": int(p.n),
        "dx": int(p.detail.get("dx", 1)),
        "dy": int(p.detail.get("dy", p.n)),
        "m": int(p.m),
        "block_h": int(p.detail.get("block_rows", 0)) or None,
        "feasible": bool(p.feasible),
        "sustained_gflops": float(p.sustained_gflops),
        "perf_per_watt": float(p.perf_per_watt),
        "limits": list(p.limits),
    }


def explore_main(argv: list[str] | None = None) -> None:
    """The `repro-explore` command: DSE walkthrough, end to end."""
    from repro.apps import diffusion as dif
    from repro.apps import lbm
    from repro.configs import get_arch
    from repro.core.distribute import device_axis_values
    from repro.core.explorer import render_executed
    from repro.core.planner import ArchStats, plan, render_plans
    from repro.core.search import STRATEGIES, ExhaustiveSearch

    ap = argparse.ArgumentParser(prog="repro-explore", description=__doc__)
    ap.add_argument("--arch", default="granite-34b")
    ap.add_argument("--chips", type=int, default=256)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--topk", type=int, default=2,
                    help="frontier points to execute with --strategy "
                         "exhaustive; refine/halving choose their own "
                         "candidate counts (bound them with --budget)")
    ap.add_argument("--devices", type=int, default=4, metavar="N",
                    help="sweep the device axis d over powers of two up to "
                         "N (execution shards onto real devices; off-TPU "
                         "force host devices with XLA_FLAGS=--xla_force_"
                         "host_platform_device_count=N)")
    ap.add_argument("--mesh", type=str, default=None, metavar="DYxDX",
                    help="2-D device mesh for the TPU sweeps (DESIGN.md "
                         "§15): 'DYxDX' pins the mesh shape (d = DY*DX; "
                         "rows shard across DY, columns across DX with "
                         "ppermute column-halo exchange), 'auto' sweeps "
                         "every power-of-two column count up to --devices "
                         "so the search enumerates the legal "
                         "factorizations of each device count")
    ap.add_argument("--json", type=str, default=None, metavar="PATH",
                    help="write the sweep/execution results as JSON")
    ap.add_argument("--no-execute", action="store_true",
                    help="skip the Pallas kernel runs (interpret mode on "
                         "the CPU backend, compiled on a TPU)")
    ap.add_argument("--strategy", default="exhaustive",
                    choices=sorted(STRATEGIES),
                    help="search strategy for the measured sweep "
                         "(docs/pipeline.md §search): exhaustive = walk "
                         "the Pareto frontier top-down, refine = "
                         "model-seeded (block_h, m, d) hill-climb, "
                         "halving = budgeted successive halving")
    ap.add_argument("--budget", type=int, default=None, metavar="N",
                    help="hard cap on live measurements per app search "
                         "(cache hits are free; default: unbudgeted)")
    ap.add_argument("--reps", type=int, default=3, metavar="N",
                    help="measured timing reps per executed point (median "
                         "is reported; every rep is synchronized)")
    ap.add_argument("--calibrate", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="calibrate predictions against the live backend's "
                         "measured throughput/bandwidth so rel err is a "
                         "model-fidelity signal (--no-calibrate diffs "
                         "against raw TPU-v5e roofline constants)")
    ap.add_argument("--no-cache", action="store_true",
                    help="skip the persistent measurement cache and "
                         "re-time every point")
    ap.add_argument("--double-buffer", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="stream stripes through ping/pong VMEM buffers "
                         "(DMA/compute overlap, docs/pipeline.md §stream); "
                         "--no-double-buffer requests the single-buffer "
                         "streaming fallback (half the VMEM, no overlap). "
                         "The legalizer may still fall back per point when "
                         "the ping/pong pair cannot fit")
    ap.add_argument("--study", type=str, default=None, metavar="NAME",
                    help="journal every trial into a durable named study "
                         "(docs/pipeline.md §study); re-running with the "
                         "same name resumes it, replaying completed "
                         "trials with zero re-measurement")
    ap.add_argument("--study-dir", type=str, default=None, metavar="PATH",
                    help="directory holding study journals (default: "
                         "$REPRO_STUDY_DIR or ~/.cache/repro/studies)")
    ap.add_argument("--seed", type=int, default=0, metavar="N",
                    help="RNG seed for --strategy tpe (a seeded search "
                         "reproduces the identical trial sequence)")
    ap.add_argument("--trials", type=int, default=None, metavar="N",
                    help="cap on total tpe observations, replayed + "
                         "measured (a resumed study whose replays cover "
                         "N spends zero budget)")
    ap.add_argument("--program", action="store_true",
                    help="also search the multi-core stream programs "
                         "(docs/pipeline.md §program): LBM as a 3-core "
                         "collide+stream -> boundary -> moments chain "
                         "and the 2-core advection-diffusion app, with "
                         "the fusion partition (which stages share one "
                         "pallas_call) swept as a lattice axis — the "
                         "report table gains a `fuse` column and --json "
                         "carries the partition per executed point")
    args = ap.parse_args(argv)
    d_values = device_axis_values(args.devices)
    dx_values: tuple[int, ...] = (1,)
    if args.mesh:
        if args.mesh.strip().lower() == "auto":
            # Sweep every power-of-two column count; evaluate_batch
            # marks the non-factorizations (d % dx != 0) infeasible, so
            # the cross product enumerates exactly the legal meshes.
            dx_values = d_values
        else:
            try:
                dy_s, dx_s = args.mesh.strip().lower().split("x")
                mesh_dy, mesh_dx = int(dy_s), int(dx_s)
            except ValueError:
                ap.error(f"--mesh {args.mesh!r}: expected DYxDX "
                         "(e.g. 2x4) or auto")
            if mesh_dy < 1 or mesh_dx < 1:
                ap.error("--mesh: DY and DX must be >= 1")
            d_values = (mesh_dy * mesh_dx,)
            dx_values = (mesh_dx,)
    report: dict = {"d_values": list(d_values),
                    "dx_values": list(dx_values), "mesh": args.mesh}

    print("=" * 72)
    print("1) The paper's case study: LBM on the Stratix V model")
    print("=" * 72)
    sim = lbm.LBMSimulation(lbm.LBMProblem(300, 720, mode="wrap"))
    ex = sim.explorer()
    sweep = ex.sweep_fpga(n_values=(1, 2, 4, 8), m_values=(1, 2, 4, 8))
    print(sweep.table(k=10))
    print()
    print("Pareto frontier (max throughput, max perf/W, min resources):")
    print(sweep.table(frontier_only=True))
    best = sweep.best("perf_per_watt")
    print(f"-> best configuration: (n, m) = ({best.n}, {best.m})  "
          f"[paper §III: (1, 4)]")
    report["fpga"] = {
        "best": {"n": int(best.n), "m": int(best.m),
                 "perf_per_watt": float(best.perf_per_watt)},
    }

    print()
    print("=" * 72)
    print("2) Hardware adaptation: temporal blocking on TPU v5e,")
    print(f"   device axis d ∈ {d_values} (sharding + halo exchange)")
    print("=" * 72)
    tsweep = ex.sweep_tpu(d_values=d_values, dx_values=dx_values,
                          double_buffer=args.double_buffer)
    print(tsweep.table(k=8))
    print()
    print("TPU Pareto frontier:")
    print(tsweep.table(frontier_only=True, k=6))
    tbest = tsweep.best("sustained_gflops")
    report["tpu"] = {
        "best": _point_dict(tbest),
        "frontier": [_point_dict(p) for p in tsweep.frontier()],
    }

    if not args.no_execute:
        import jax

        from repro.compat import default_interpret, enable_compile_cache
        from repro.core.measure import MeasurementCache

        enable_compile_cache()
        mode = (
            "interpret mode on cpu" if default_interpret()
            else f"compiled on {jax.default_backend()}"
        )

        mcache = None if args.no_cache else MeasurementCache()
        # Only propose device counts the platform can run: on the tall
        # measurement grid the model drops d=1 off the frontier, so an
        # uncapped sweep leaves a single-device machine nothing to time.
        exec_d = device_axis_values(min(args.devices, jax.device_count()))
        if args.mesh and args.mesh.strip().lower() != "auto":
            exec_d = tuple(
                d for d in d_values if d <= jax.device_count()
            ) or exec_d
        exec_dx = tuple(
            x for x in dx_values if x <= jax.device_count()
        ) or (1,)
        # The default strategy reproduces the original behavior: walk
        # the Pareto frontier until --topk points executed. The others
        # (--strategy refine/halving) search measured-in-the-loop under
        # the --budget cap (docs/pipeline.md §search).
        if args.strategy == "exhaustive":
            strategy = ExhaustiveSearch(k=args.topk, frontier_only=True)
        elif args.strategy == "tpe":
            from repro.core.search import TPESearch

            strategy = TPESearch(seed=args.seed, max_trials=args.trials)
        else:
            strategy = args.strategy
        # One named study can hold both app searches: trials are keyed
        # by core fingerprint, so each search replays only its own.
        study_kw = dict(study=args.study, study_dir=args.study_dir)
        print()
        print("=" * 72)
        print(f"3) Model -> measurement: --strategy {args.strategy} "
              f"(budget: {args.budget if args.budget else 'none'}) over the")
        print(f"   codegen'd uLBM Pallas kernel ({mode}, 256x128; "
              "d>1 points run")
        print("   sharded — the grid is tall enough that sharding beats "
              "the halo exchange)")
        print("=" * 72)
        msim = lbm.LBMSimulation(lbm.LBMProblem(256, 128, mode="wrap"))
        mex = msim.explorer()
        msweep = mex.sweep_tpu(bh_values=(8, 16, 32, 64),
                               m_values=(1, 2, 4, 8), d_values=exec_d,
                               dx_values=exec_dx,
                               double_buffer=args.double_buffer)
        f0, attr, _ = lbm.taylor_green_init(256, 128)
        mres = mex.search(
            msweep, msim.stream_state(f0, attr), msim.stream_regs(),
            strategy=strategy, budget=args.budget, reps=args.reps,
            calibrate=args.calibrate, cache=mcache,
            **study_kw,
        )
        print(render_executed(mres.executed))
        print(f"(strategy={mres.strategy}: {mres.budget_spent} live "
              f"measurement(s), {len(mres.executed)} point(s) executed"
              + (f", {mres.replayed} replayed from study "
                 f"{mres.study!r}" if mres.study else "") + ")")
        report["lbm"] = mres.as_dict()

        print()
        print("=" * 72)
        print("3b) Any SPD core on the frontier: 2-D diffusion through the")
        print("    generic SPD->Pallas codegen (docs/pipeline.md, 256x128)")
        print("=" * 72)
        dsim = dif.DiffusionSimulation(256, 128, alpha=0.2)
        dex = dsim.explorer()
        dsweep = dex.sweep_tpu(bh_values=(8, 16, 32, 64),
                               m_values=(1, 2, 4, 8), d_values=exec_d,
                               dx_values=exec_dx,
                               double_buffer=args.double_buffer)
        u0, _ = dif.sine_init(256, 128)
        dres = dex.search(dsweep, dsim.state(u0), (dsim.alpha,),
                          strategy=strategy, budget=args.budget,
                          reps=args.reps,
                          calibrate=args.calibrate, cache=mcache,
                          **study_kw)
        print(render_executed(dres.executed))
        print(f"(strategy={dres.strategy}: {dres.budget_spent} live "
              f"measurement(s), {len(dres.executed)} point(s) executed"
              + (f", {dres.replayed} replayed from study "
                 f"{dres.study!r}" if dres.study else "") + ")")
        halo = dsim.kernel.summary
        print(f"(inferred stencil: {len(halo.offsets)} offsets, "
              f"halo = {halo.halo_y} row/step — no hand-written kernel)")
        report["diffusion"] = dres.as_dict()

        if args.program:
            from repro.apps.advection_diffusion import (
                AdvectionDiffusionSimulation, blob_init)
            from repro.core.program import fusion_partitions

            print()
            print("=" * 72)
            print("3c) Stream programs: the fusion partition as a "
                  "search axis")
            print("    (docs/pipeline.md §program; `fuse` column = "
                  "cluster sizes, e.g. 2+1)")
            print("=" * 72)
            report["program"] = {}
            psim = lbm.LBMSimulation(lbm.LBMProblem(128, 128, mode="wrap"))
            pprog = psim.program()
            pf, pattr, _ = lbm.taylor_green_init(128, 128)
            asim = AdvectionDiffusionSimulation(128, 128)
            for label, prog, state, regs in (
                ("lbm_program", pprog,
                 psim.stream_state(pf, pattr), psim.stream_regs()),
                ("advection_diffusion", asim.program,
                 asim.state(blob_init(128, 128)), asim.regs()),
            ):
                pex = prog.explorer(128 * 128, grid_w=128)
                psweep = pex.sweep_tpu(
                    bh_values=(8, 16, 32), m_values=(1, 2, 4),
                    d_values=exec_d, dx_values=exec_dx,
                    double_buffer=args.double_buffer,
                    fusion_values=fusion_partitions(prog.nstages),
                )
                pres = pex.search(
                    psweep, state, regs, strategy=strategy,
                    budget=args.budget, reps=args.reps,
                    calibrate=args.calibrate, cache=mcache, **study_kw,
                )
                print(f"-- {label} ({prog.nstages} stages, partitions: "
                      f"{', '.join(fusion_partitions(prog.nstages))})")
                print(render_executed(pres.executed))
                print(f"(strategy={pres.strategy}: {pres.budget_spent} "
                      f"live measurement(s), {len(pres.executed)} "
                      f"point(s) executed)")
                report["program"][label] = pres.as_dict()

        report["measure"] = {
            "reps": args.reps,
            "calibrate": bool(args.calibrate),
            "double_buffer": bool(args.double_buffer),
            "strategy": args.strategy,
            "budget": args.budget,
            "mesh": args.mesh,
            "cache": None if mcache is None else mcache.stats(),
            "study": args.study,
            "seed": args.seed,
            "trials": args.trials,
        }
        if mcache is not None:
            s = mcache.stats()
            print(f"(measurement cache: {s['hits']} hit(s), "
                  f"{s['misses']} miss(es) — {s['path']})")

    print()
    print("=" * 72)
    print(f"4) The same trade on an LM fleet: {args.arch} on "
          f"{args.chips} chips")
    print("   (spatial n -> dp, temporal m -> pp, in-PE -> tp)")
    print("=" * 72)
    cfg = get_arch(args.arch)
    stats = ArchStats(
        name=cfg.name, params=cfg.num_params(),
        active_params=cfg.active_params(), n_layers=cfg.n_layers,
        d_model=cfg.d_model, global_batch=args.batch, seq_len=args.seq,
    )
    print(render_plans(plan(stats, args.chips), top=10))

    if args.json:
        with open(args.json, "w", encoding="utf-8") as fh:
            json.dump(report, fh, indent=2, sort_keys=True)
        print(f"\n[wrote {args.json}]")


def serve_main(argv: list[str] | None = None) -> None:
    """The `repro-serve` command: the multi-tenant simulation-serving
    engine (DESIGN.md §13, docs/pipeline.md §serve) under open-loop
    Poisson load.

    Builds a tenant mix (2-D diffusion at two grid sizes plus the uLBM
    core), submits ``--requests`` jobs per tenant at ``--arrival-rate``
    expected arrivals per engine tick, and serves them through
    :class:`repro.serve.sim.SimEngine`: requests sharing a trial
    context stack along the batch axis ``b``, each context autotunes on
    first request under a hard ``--budget`` of live measurements, and
    ``--study-dir`` makes the tuning durable — a second invocation with
    the same directory warm-starts every plan with zero live timings.
    """
    import numpy as np

    from repro.apps import diffusion as dif
    from repro.apps import lbm
    from repro.compat import enable_compile_cache
    from repro.serve.sim import PlanResolver, SimEngine, SimRequest

    ap = argparse.ArgumentParser(prog="repro-serve", description=__doc__)
    ap.add_argument("--tenants", type=int, default=3, metavar="N",
                    help="tenant contexts in the mix, drawn cyclically "
                         "from the built-in set (diffusion 32x128 / "
                         "64x128, lbm 32x128: widths the compiled "
                         "kernel can stage); each is a distinct trial "
                         "context with its own autotuned plan")
    ap.add_argument("--requests", type=int, default=8, metavar="N",
                    help="requests submitted per tenant")
    ap.add_argument("--steps", type=int, default=16, metavar="N",
                    help="simulation steps per request")
    ap.add_argument("--arrival-rate", type=float, default=8.0,
                    metavar="R",
                    help="open-loop Poisson intensity: expected "
                         "arrivals per engine tick (saturating rates "
                         "build the backlog that fills the batch axis)")
    ap.add_argument("--budget", type=int, default=4, metavar="N",
                    help="hard cap on live tuning measurements per "
                         "trial context (autotune-on-first-request; "
                         "exhaustion falls back to the model's plan)")
    ap.add_argument("--study-dir", type=str, default=None, metavar="PATH",
                    help="directory for the per-context tuning studies "
                         "(default: $REPRO_STUDY_DIR or ~/.cache/repro/"
                         "studies); reuse it to warm-start with zero "
                         "live timings")
    ap.add_argument("--max-queue", type=int, default=64, metavar="N",
                    help="admission queue bound — submissions beyond it "
                         "are rejected with backpressure, never dropped "
                         "silently")
    ap.add_argument("--seed", type=int, default=0, metavar="N",
                    help="RNG seed for the arrival schedule")
    ap.add_argument("--json", type=str, default=None, metavar="PATH",
                    help="write the engine stats as JSON")
    args = ap.parse_args(argv)
    enable_compile_cache()

    mix = []
    for h, w, alpha in ((32, 128, 0.2), (64, 128, 0.1)):
        sim = dif.DiffusionSimulation(h, w, alpha=alpha)
        u0, _ = dif.sine_init(h, w)
        mix.append((f"diffusion-{h}x{w}", sim.kernel, sim.state(u0),
                    (sim.alpha,)))
    lsim = lbm.LBMSimulation(lbm.LBMProblem(32, 128, mode="wrap"))
    f0, attr, _ = lbm.taylor_green_init(32, 128)
    mix.append(("lbm-32x128", lsim.stream_kernel(),
                lsim.stream_state(f0, attr), lsim.stream_regs()))
    tenants = [mix[i % len(mix)] for i in range(args.tenants)]

    engine = SimEngine(
        PlanResolver(budget=args.budget, study_dir=args.study_dir),
        max_queue=args.max_queue,
    )
    rng = np.random.default_rng(args.seed)
    total = args.requests * len(tenants)
    ticks = np.floor(np.cumsum(
        rng.exponential(1.0 / args.arrival_rate, size=total)
    )).astype(int)
    order = rng.permutation(
        np.repeat(np.arange(len(tenants)), args.requests)
    )
    schedule = list(zip(ticks.tolist(), order.tolist()))

    print("=" * 72)
    print(f"simulation-as-a-service: {total} request(s) over "
          f"{len(tenants)} tenant(s),")
    print(f"rate {args.arrival_rate}/tick, {args.steps} steps/request, "
          f"tuning budget {args.budget}")
    print("=" * 72)
    completions = []
    rid = 0
    i = 0
    while i < len(schedule) or engine.queue or engine._active_count():
        while i < len(schedule) and schedule[i][0] <= engine.tick_count:
            name, core, state, regs = tenants[schedule[i][1]]
            engine.submit(SimRequest(rid=rid, core=core, state=state,
                                     steps=args.steps, regs=regs))
            rid += 1
            i += 1
        completions.extend(engine.step())
    stats = engine.stats()
    lat = sorted(c.latency_s for c in completions)

    def pct(p):
        return lat[min(len(lat) - 1, int(p / 100 * len(lat)))] if lat else 0.0

    print(f"{stats['completed']}/{stats['submitted']} completed "
          f"({stats['rejected']} rejected with backpressure), "
          f"{stats['launches']} launch(es) in {stats['ticks']} tick(s)")
    print(f"steady-state {stats['steps_per_s']:.1f} member-steps/s; "
          f"latency p50 {pct(50) * 1e3:.1f} ms / p95 {pct(95) * 1e3:.1f} "
          f"ms / p99 {pct(99) * 1e3:.1f} ms")
    print("batch occupancy: " + ", ".join(
        f"b={k}: {v}" for k, v in stats["occupancy"].items()))
    print(f"tuning: {stats['live_timings']} live timing(s), "
          f"{stats['tuning_ticks']} tuning tick(s)"
          + (" — warm start" if stats["live_timings"] == 0 else ""))
    for key, plan in sorted(stats["plans"].items()):
        print(f"  {key}: block_h={plan['block_h']} m={plan['m']} "
              f"b={plan['b']} db={plan['double_buffer']} "
              f"[{plan['source']}, {plan['budget_spent']} timed, "
              f"{plan['replayed']} replayed]")

    if args.json:
        stats["latency"] = {"p50_s": pct(50), "p95_s": pct(95),
                            "p99_s": pct(99)}
        with open(args.json, "w", encoding="utf-8") as fh:
            json.dump(stats, fh, indent=2, sort_keys=True)
        print(f"\n[wrote {args.json}]")


if __name__ == "__main__":
    explore_main()
