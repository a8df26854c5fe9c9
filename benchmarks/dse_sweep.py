"""Design-space exploration sweeps (the paper's §III carried further),
driven end-to-end by ``repro.core.explorer``:

1. FPGA target: the full (n, m) lattice evaluated in one batched call,
   Pareto frontier over (throughput, perf/W, resources), and the paper's
   winning configuration (n, m) = (1, 4) recovered by ``best()``.
2. TPU v5e target: the (block_h, m, d) temporal-blocking lattice — d is
   the device axis (y-sharding with halo exchange,
   ``repro.core.distribute``) — its frontier, and the model<->measurement
   loop: the top-k frontier points *executed* through the codegen'd uLBM
   Pallas kernel via the search subsystem's single measurement engine
   (``Explorer.search``, docs/pipeline.md §search); d > 1 points run
   sharded when the platform has the devices and are skipped otherwise.
   Measurements use the honest policy of ``repro.core.measure``
   (docs/pipeline.md §measure): median-of-reps timing with per-rep
   synchronization, *backend-calibrated* predictions — off-TPU the
   calibration anchors the model to the Pallas interpreter's measured
   throughput, so ``rel_error`` is a model-fidelity signal instead of
   the old meaningless host-vs-TPU speed ratio (≈ 0.9999 on every
   point) — and the persistent measurement cache, whose hit/miss stats
   land in the JSON (a repeated benchmark run re-times nothing).
   An **autotune smoke** then runs the budgeted strategies (LocalRefine,
   SuccessiveHalving, and the surrogate TPESearch) under a hard budget
   of ≤ 12 measurements each and hard-fails if a strategy overspends.
   The TPE pass journals into a durable named study
   (docs/pipeline.md §study) whose convergence/Pareto report is written
   next to the JSON as ``BENCH_study.html`` / ``BENCH_study.txt`` —
   the CI bench job uploads it as an artifact.
   A **stream-program sweep** (2h, docs/pipeline.md §program) then
   clocks every fusion partition of the two program apps — fused vs
   pipelined vs the unfused host-round-trip baseline — and hard-fails
   if the calibrated model's partition pick measures >10% worse than
   the best measured partition.
   A **2-D mesh sweep** (2i, DESIGN.md §15) measures every legal
   ``(dy, dx)`` factorization of a fixed device count on a wide and a
   tall diffusion grid through the search runner — block_h swept
   jointly so each mesh runs at its own best block — records
   best-mesh-per-aspect in the JSON's ``mesh`` section, and hard-fails
   if the calibrated model's mesh pick measures >10% worse than the
   best measured mesh (the §2h contract applied to the mesh axis).
3. LM mesh planner: (dp, tp, pp) ranking for a transformer arch — the
   paper's spatial/temporal trade lifted to the fleet (DESIGN.md §4).

Invoked as a script this also writes ``BENCH_dse.json`` next to the repo
root — best point, sustained GFLOPS, calibrated predicted-vs-measured
error, search ``strategy``/``budget_spent`` metadata and cache stats per
app — so the performance trajectory stays comparable across PRs.
"""

from __future__ import annotations

import json
import os
import time

from repro.apps import lbm
from repro.compat import enable_compile_cache, resolve_interpret
from repro.core.explorer import render_executed
from repro.core.measure import MeasurementCache, calibrate_backend
from repro.core.planner import ArchStats, plan, render_plans
from repro.core.search import ExhaustiveSearch
from repro.configs import get_arch

#: Hard cap on live measurements for the autotune smoke (sweep 2e): the
#: budgeted strategies must stay within it or the benchmark fails.
AUTOTUNE_BUDGET = 12

# Interpret-mode execution is host-speed; measure on a small lattice so the
# whole benchmark stays in seconds — but tall enough (256 rows) that the
# model puts d > 1 points on the frontier (on a short grid the halo
# exchange dominates and sharding is correctly dominated).
MEASURE_H, MEASURE_W = 256, 128

BENCH_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_dse.json",
)


def run(topk: int = 3, interpret: bool | None = None, reps: int = 3,
        bench: dict | None = None,
        cache: MeasurementCache | None = None) -> list[str]:
    """Print the sweep sections; fill ``bench`` (if given) for the JSON."""
    interpret = resolve_interpret(interpret)
    out = []
    t0 = time.time()
    sim = lbm.LBMSimulation(lbm.LBMProblem(300, 720, mode="wrap"))
    ex = sim.explorer()

    out.append("## DSE sweep 1: FPGA (n, m) lattice -> Pareto frontier")
    sweep = ex.sweep_fpga(n_values=(1, 2, 4, 8), m_values=(1, 2, 4, 8))
    out.append(sweep.table(k=10))
    frontier = sweep.frontier()
    out.append(
        f"frontier ({len(frontier)} of {len(sweep)} points): "
        + " ".join(f"(n={p.n},m={p.m})" for p in frontier)
    )
    best = sweep.best("perf_per_watt")
    out.append(
        f"best perf/W: (n={best.n},m={best.m}) -> "
        f"{best.perf_per_watt:.3f} GF/sW (paper: (1,4) -> 2.416)"
    )

    out.append("\n## DSE sweep 2: TPU v5e temporal blocking (block_h, m, d)")
    tsweep = ex.sweep_tpu()
    out.append(tsweep.table(k=10))
    tbest = tsweep.best("sustained_gflops")
    out.append(
        f"best: block_h={tbest.detail['block_rows']} m={tbest.m} "
        f"d={tbest.n} -> {tbest.sustained_gflops:.0f} GF/s "
        f"({tbest.utilization*100:.0f}% of the {tbest.n}-chip VPU roof), "
        f"AI={tbest.detail['arithmetic_intensity']:.1f} flop/B"
    )

    # The measured sweep only proposes device counts the platform can
    # actually run: on a tall grid the model (correctly) drops d=1 off
    # the frontier entirely, which would leave a single-device machine
    # with nothing executable.
    import jax

    from repro.core.distribute import device_axis_values

    exec_d = device_axis_values(min(4, jax.device_count()))
    out.append(
        f"\n## DSE sweep 2b: top-{topk} frontier points through the "
        f"codegen'd uLBM Pallas kernel ({MEASURE_H}x{MEASURE_W}, "
        f"{'interpret' if interpret else 'tpu'} mode; d swept over "
        f"{exec_d}, d>1 sharded)"
    )
    msim = lbm.LBMSimulation(
        lbm.LBMProblem(MEASURE_H, MEASURE_W, mode="wrap")
    )
    mex = msim.explorer()
    msweep = mex.sweep_tpu(bh_values=(8, 16, 32, 64), m_values=(1, 2, 4, 8),
                           d_values=exec_d)
    f0, attr, _ = lbm.taylor_green_init(MEASURE_H, MEASURE_W)
    mstate, mregs = msim.stream_state(f0, attr), msim.stream_regs()
    mres = mex.search(
        msweep, mstate, mregs,
        strategy=ExhaustiveSearch(k=topk, frontier_only=True),
        interpret=interpret, reps=reps, calibrate=True, cache=cache,
    )
    runs = mres.executed
    out.append(render_executed(runs))
    out.append(
        f"(strategy={mres.strategy}: {mres.budget_spent} live "
        f"measurement(s) spent)"
    )
    if interpret:
        out.append(
            "(interpret mode: the calib column anchors the model to the "
            "measured Pallas-interpreter throughput, so rel err is "
            "model fidelity, not host-vs-TPU speed; run on TPU with "
            "interpret=False to close the loop on hardware)"
        )

    out.append(
        "\n## DSE sweep 2c: second SPD app (2-D diffusion) through the "
        "generic SPD->Pallas codegen"
    )
    from repro.apps import diffusion as dif

    dsim = dif.DiffusionSimulation(MEASURE_H, MEASURE_W, alpha=0.2)
    dex = dsim.explorer()
    dsweep = dex.sweep_tpu(bh_values=(8, 16, 32, 64), m_values=(1, 2, 4, 8),
                           d_values=exec_d)
    u0, _ = dif.sine_init(MEASURE_H, MEASURE_W)
    dres = dex.search(
        dsweep, dsim.state(u0), (dsim.alpha,),
        strategy=ExhaustiveSearch(k=topk, frontier_only=True),
        interpret=interpret, reps=reps, calibrate=True, cache=cache,
    )
    druns = dres.executed
    out.append(render_executed(druns))
    out.append(
        f"(no hand-written kernel: {len(dsim.kernel.summary.offsets)} "
        f"stencil offsets inferred from the DFG, halo = "
        f"{dsim.kernel.summary.halo_y} row/step — docs/pipeline.md)"
    )

    # Measurement-cache verification pass: the same frontier again — every
    # point (and the calibration anchor) must come back from the cache
    # without recompiling or retiming (docs/pipeline.md §measure).
    pass2_hits = 0
    if cache is not None:
        hits_before = cache.hits
        reruns = mex.execute_frontier(
            msweep, mstate, mregs, k=topk, interpret=interpret, reps=reps,
            calibrate=True, cache=cache,
        )
        pass2_hits = cache.hits - hits_before
        # Hard check, not just a printout (and not a stripped-under--O
        # assert): an identical sweep in the same process must re-time
        # nothing (fingerprint/key stability).
        retimed = [(e.block_h, e.m, e.d) for e in reruns if not e.cached]
        if retimed:
            raise RuntimeError(
                f"measurement-cache regression: repeated frontier pass "
                f"re-timed {retimed}"
            )
        out.append(
            f"\n## DSE sweep 2d: repeated uLBM frontier pass — "
            f"{pass2_hits} measurement-cache hit(s), "
            f"{sum(1 for e in reruns if e.cached)}/{len(reruns)} points "
            "served from cache"
        )

    # Autotune smoke (docs/pipeline.md §search): the budgeted strategies
    # search the same uLBM lattice measured-in-the-loop under a hard cap
    # of AUTOTUNE_BUDGET live measurements each. Overspending is a
    # regression, not a printout. Sharing the measurement cache with the
    # frontier pass above is the intended composition: plans the
    # exhaustive walk already timed are free, so the strategies' budget
    # goes to the plans only they propose.
    out.append(
        f"\n## DSE sweep 2e: autotune smoke — measured-in-the-loop "
        f"search, hard budget {AUTOTUNE_BUDGET} measurements/strategy"
    )
    from repro.core.search import Study, TPESearch

    exhaustive_best = max(e.measured_gflops for e in runs) if runs else 0.0
    autotune: dict = {"budget": AUTOTUNE_BUDGET}
    # The TPE pass journals into a durable named study: a re-run of the
    # benchmark replays completed trials from it (and from the cache)
    # instead of re-measuring (docs/pipeline.md §study).
    study_name = "bench-dse"
    specs = (
        ("refine", "refine", {}),
        ("halving", "halving", {}),
        ("tpe", TPESearch(seed=0), {"study": study_name}),
    )
    for label, strat, extra in specs:
        sres = mex.search(
            msweep, mstate, mregs, strategy=strat, budget=AUTOTUNE_BUDGET,
            interpret=interpret, reps=reps, calibrate=True, cache=cache,
            **extra,
        )
        if sres.budget_spent > AUTOTUNE_BUDGET:
            raise RuntimeError(
                f"autotune budget regression: strategy {label!r} spent "
                f"{sres.budget_spent} > {AUTOTUNE_BUDGET} measurements"
            )
        b = sres.best
        ratio = (
            b.measured_gflops / exhaustive_best
            if b is not None and exhaustive_best else 0.0
        )
        out.append(
            f"  {label}: best "
            + (f"(block_h={b.block_h}, m={b.m}, d={b.d}) "
               f"{b.measured_gflops:.4g} GF/s measured"
               if b is not None else "n/a")
            + f" ({ratio:.2f}x the exhaustive frontier best), "
            f"{sres.budget_spent}/{AUTOTUNE_BUDGET} budget spent, "
            f"{len(sres.executed)} point(s) measured"
            + (f", {sres.replayed} replayed from study {sres.study!r}"
               if sres.study else "")
        )
        # One schema for every search section: SearchResult.as_dict
        # (SEARCH_RESULT_FIELDS) — the derived ratio rides along.
        autotune[label] = {
            **sres.as_dict(), "vs_exhaustive_best": float(ratio),
        }

    # 2h --------------------------------------------------------------
    # Stream programs: the fusion partition as a measured axis
    # (docs/pipeline.md §program, DESIGN.md §14). For each program app,
    # clock every partition of the chain — fused (one pallas_call per
    # m-step block), pipelined (chained on-device launches), and the
    # naive unfused baseline (host round-trip per cluster) — then ask
    # the calibrated model to pick a partition and hard-fail if its
    # pick measures >10% worse than the best measured partition. The
    # calibration gives the model this platform's throughput *and* its
    # per-launch dispatch overhead (TPUTarget.launch_overhead_s, backed
    # out of a tiny-grid probe where launches dominate the wall).
    import dataclasses

    from repro.apps.advection_diffusion import (
        AdvectionDiffusionSimulation, blob_init,
    )
    from repro.core import measure as measure_mod
    from repro.core.dse import TPUModel
    from repro.core.measure import time_run
    from repro.core.program import fusion_partitions, program_run_factory

    out.append(
        "\n## DSE sweep 2h: stream programs — fused vs pipelined vs "
        "unfused (per app)"
    )
    program_bench: dict = {}
    pg_h, pg_w = 128, 128
    pg_bh, pg_m, pg_steps = 16, 2, 16
    psim = lbm.LBMSimulation(lbm.LBMProblem(pg_h, pg_w, mode="wrap"))
    pf, pattr, _ = lbm.taylor_green_init(pg_h, pg_w)
    asim = AdvectionDiffusionSimulation(pg_h, pg_w)
    for pname, prog, pstate, pregs in (
        ("lbm_program", psim.program(), psim.stream_state(pf, pattr),
         psim.stream_regs()),
        ("advection_diffusion", asim.program,
         asim.state(blob_init(pg_h, pg_w)), asim.regs()),
    ):
        specs = fusion_partitions(prog.nstages)
        wl = prog.workload(pg_h * pg_w, grid_w=pg_w)
        prf = program_run_factory(prog, pstate, pregs, interpret)
        cal2h = measure_mod.calibrate_execution(
            prf, workload=wl, grid_shape=(pg_h, pg_w), width=pg_w,
            words=prog.P, interpret=interpret, reps=reps, warmup=1,
        )
        # Launch-overhead probe: the fully pipelined partition on a
        # 16-row slab — per-launch dispatch dominates the wall there.
        split = specs[-1]
        nclusters = split.count("+") + 1
        tiny = pstate[..., :16, :]
        tiny_steps = 8
        tp = time_run(
            lambda: prog.kernel(split).run_blocked(
                tiny, pregs, steps=tiny_steps, m=1, block_h=8,
                interpret=interpret,
            ),
            reps=reps, warmup=1,
        )
        ovh = float(tp.wall_s) / (tiny_steps * nclusters)
        model2h = TPUModel(dataclasses.replace(
            cal2h.target(d=1), launch_overhead_s=ovh
        ))
        walls: dict = {}
        for spec in specs:
            pk = prog.kernel(spec)
            timing = time_run(
                lambda: pk.run_blocked(
                    pstate, pregs, steps=pg_steps, m=pg_m, block_h=pg_bh,
                    interpret=interpret,
                ),
                reps=reps, warmup=1,
            )
            walls[spec] = float(timing.wall_s)
        unfused_t = time_run(
            lambda: prog.kernel(split).run_unfused(
                pstate, pregs, steps=pg_steps, block_h=pg_bh,
                interpret=interpret,
            ),
            reps=reps, warmup=1,
        )
        pick = max(
            specs,
            key=lambda s: model2h.evaluate(
                wl, pg_bh, pg_m, fusion=s
            ).sustained_gflops,
        )
        best_measured = min(walls, key=walls.get)
        for spec in specs:
            tag = ("fused" if "+" not in spec else
                   ("pipelined" if spec == split else "partial"))
            out.append(
                f"  {pname}: fusion={spec:<8s} {walls[spec]*1e3:8.2f} ms "
                f"/{pg_steps} steps ({tag})"
            )
        out.append(
            f"  {pname}: unfused  {float(unfused_t.wall_s)*1e3:8.2f} ms "
            f"(host round-trip per cluster); model pick {pick!r}, best "
            f"measured {best_measured!r} "
            f"(launch overhead {ovh*1e6:.1f} us/launch)"
        )
        if walls[pick] > 1.10 * walls[best_measured]:
            raise RuntimeError(
                f"program sweep 2h: model-picked partition {pick!r} "
                f"measured {walls[pick]*1e3:.2f} ms — more than 10% "
                f"worse than the best measured partition "
                f"{best_measured!r} at {walls[best_measured]*1e3:.2f} ms "
                f"({pname})"
            )
        # Machine-independent trajectory record: the raw-model lattice
        # best over the full fusion axis (same convention as the lbm/
        # diffusion "best" blocks — measurements stay platform-bound).
        pex = prog.explorer(pg_h * pg_w, grid_w=pg_w)
        psw = pex.sweep_tpu(
            bh_values=(8, 16, 32, 64), m_values=(1, 2, 4, 8),
            fusion_values=specs,
        )
        pbest = psw.best("sustained_gflops")
        program_bench[pname] = {
            "grid": [pg_h, pg_w],
            "block_h": pg_bh, "m": pg_m, "steps": pg_steps,
            "partitions_s": walls,
            "fused_s": walls[specs[0]],
            "pipelined_s": walls[split],
            "unfused_s": float(unfused_t.wall_s),
            "model_pick": pick,
            "best_measured": best_measured,
            "launch_overhead_s": ovh,
            "best": {
                "fusion": str(pbest.detail["fusion"]),
                "m": int(pbest.m),
                "block_h": int(pbest.detail["block_rows"]),
                "sustained_gflops": float(pbest.sustained_gflops),
            },
        }

    # 2i --------------------------------------------------------------
    # 2-D device mesh (DESIGN.md §15): wide vs tall grids at one fixed
    # total device count, every legal (dy, dx) factorization measured
    # through the search runner, and the calibrated model's mesh pick
    # gated against the best measured mesh — the §2h contract applied
    # to the mesh axis. A wide grid should pick a column-heavy mesh
    # (short shards make the row ring recompute-bound), a tall grid the
    # row ring; the recorded best-(dy, dx)-per-aspect is the committed
    # evidence.
    mesh_bench: dict = {}
    mesh_d = min(8, jax.device_count())
    if mesh_d >= 2:
        # block_h is swept *jointly* with the mesh: a dy-heavy ring on a
        # short grid caps the legal block at the shard height H/dy (more
        # stripes, worse halo-recompute fraction), while a column mesh
        # keeps full-height blocks at the price of 2·m·halo_x guard
        # columns — that trade is the measurable mesh signal, and it
        # only exists if each mesh runs at its own best block_h.
        mesh_bhs, mesh_m, mesh_steps = (16, 32, 64, 128), 2, 8
        out.append(
            f"\n## DSE sweep 2i: 2-D device mesh (dy x dx) — every "
            f"factorization of d={mesh_d}, wide vs tall diffusion grid"
        )
        for aspect, (gh, gw) in (("wide", (128, 512)), ("tall", (512, 128))):
            gsim = dif.DiffusionSimulation(gh, gw, alpha=0.2)
            gu0, _ = dif.sine_init(gh, gw)
            gex = gsim.explorer()
            dxs = tuple(
                x for x in (1, 2, 4, 8, 16)
                if x <= mesh_d and mesh_d % x == 0
                and gw % x == 0 and gh % (mesh_d // x) == 0
            )
            gsw = gex.sweep_tpu(bh_values=mesh_bhs, m_values=(mesh_m,),
                                d_values=(mesh_d,), dx_values=dxs)
            gres = gex.search(
                gsw, gsim.state(gu0), (gsim.alpha,),
                strategy=ExhaustiveSearch(
                    k=len(dxs) * len(mesh_bhs), frontier_only=False,
                ),
                steps=mesh_steps, interpret=interpret, reps=reps,
                calibrate=True, cache=cache,
            )
            # Mesh-level records: each (dy, dx) is represented by its
            # best-measured block_h; the model's pick is the mesh of its
            # best-calibrated executed point. Comparing meshes (not raw
            # points) keeps the gate about the axis under test.
            per: dict = {}
            model_best: dict = {}
            for e in gres.executed:
                dy = e.d // max(int(e.dx), 1)
                key = f"{dy}x{e.dx}"
                cg = (None if e.calibrated_gflops is None
                      else float(e.calibrated_gflops))
                rec = {
                    "dy": int(dy), "dx": int(e.dx),
                    "block_h": int(e.block_h),
                    "wall_s": float(e.wall_s),
                    "steps": int(e.steps),
                    "steps_per_s": float(e.steps / e.wall_s),
                    "measured_gflops": float(e.measured_gflops),
                    "calibrated_gflops": cg,
                }
                if key not in per or rec["wall_s"] < per[key]["wall_s"]:
                    per[key] = rec
                score = cg if cg is not None else float(e.measured_gflops)
                if key not in model_best or score > model_best[key]:
                    model_best[key] = score
            if not per:
                mesh_bench[aspect] = {"skipped": "no executable mesh"}
                continue
            pick = max(model_best, key=model_best.get)
            best_meas = max(per, key=lambda k: per[k]["steps_per_s"])
            rings = [k for k, v in per.items() if v["dx"] == 1]
            cols = [k for k, v in per.items() if v["dx"] > 1]
            best_ring = (max(rings, key=lambda k: per[k]["steps_per_s"])
                         if rings else None)
            best_col = (max(cols, key=lambda k: per[k]["steps_per_s"])
                        if cols else None)
            for key in sorted(per, key=lambda k: -per[k]["steps_per_s"]):
                v = per[key]
                out.append(
                    f"  {aspect} {gh}x{gw}: mesh {key:<5s} "
                    f"bh={v['block_h']:<3d} "
                    f"{v['steps_per_s']:9.2f} steps/s measured, "
                    f"calibrated {(v['calibrated_gflops'] or 0):8.1f} GF/s"
                )
            out.append(
                f"  {aspect}: model pick {pick}, best measured {best_meas}"
                + (f", best ring {best_ring}" if best_ring else "")
                + (f", best column mesh {best_col}" if best_col else "")
            )
            if per[pick]["wall_s"] > 1.10 * per[best_meas]["wall_s"]:
                raise RuntimeError(
                    f"mesh sweep 2i: model-picked mesh {pick} measured "
                    f"{per[pick]['wall_s'] * 1e3:.2f} ms — more than 10% "
                    f"worse than the best measured mesh {best_meas} at "
                    f"{per[best_meas]['wall_s'] * 1e3:.2f} ms "
                    f"({aspect} {gh}x{gw})"
                )
            mesh_bench[aspect] = {
                "grid": [gh, gw], "d": int(mesh_d),
                "block_h_values": list(mesh_bhs),
                "m": mesh_m, "steps": mesh_steps,
                "meshes": per,
                "model_pick": pick, "best_measured": best_meas,
                "best_ring": best_ring, "best_col": best_col,
            }
    else:
        reason = (f"needs >= 2 devices, have {jax.device_count()} "
                  "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        mesh_bench = {"skipped": reason}
        out.append(f"\n## DSE sweep 2i: 2-D mesh sweep skipped — {reason}")

    # Render the study's convergence/Pareto report next to the JSON —
    # the artifact the CI bench job uploads.
    study = Study.resume(study_name)
    study_report = study.report(
        out_dir=os.path.dirname(BENCH_PATH), basename="BENCH_study"
    )
    out.append(
        f"\n## DSE sweep 2f: study report — "
        + study.report_text().splitlines()[0]
    )
    out.append(f"[wrote {study_report['text']} / {study_report['html']}]")

    out.append("\n## DSE sweep 3: LM mesh planner (granite-34b, 256 chips)")
    g = get_arch("granite-34b")
    stats = ArchStats(
        name=g.name, params=g.num_params(), active_params=g.active_params(),
        n_layers=g.n_layers, d_model=g.d_model, global_batch=256,
        seq_len=4096,
    )
    plans = plan(stats, 256)
    out.append(render_plans(plans, top=8))
    mlups = f"{runs[0].measured_mlups:.2f}" if runs else "n/a"
    out.append(
        f"dse_sweep,{(time.time()-t0)*1e6:.0f},"
        f"fpga_best=({best.n};{best.m});tpu_best_m={tbest.m};"
        f"tpu_best_d={tbest.n};"
        f"measured_mlups={mlups}"
    )

    if bench is not None:
        bench["fpga"] = {
            "best": {"n": int(best.n), "m": int(best.m),
                     "sustained_gflops": float(best.sustained_gflops),
                     "perf_per_watt": float(best.perf_per_watt)},
            "paper_best": {"n": 1, "m": 4, "perf_per_watt": 2.416},
        }
        cal = calibrate_backend(interpret=interpret, reps=reps)
        for name, app_ex, sr in (("lbm", mex, mres),
                                 ("diffusion", dex, dres)):
            # The recorded best comes from the *model* lattice over the
            # full device axis — machine-independent, so the committed
            # PR-over-PR trajectory doesn't move with how many devices
            # the regenerating machine happened to have. Executed points
            # are measurements and are necessarily platform-bound.
            sw = app_ex.sweep_tpu(bh_values=(8, 16, 32, 64),
                                  m_values=(1, 2, 4, 8))
            b = sw.best("sustained_gflops")
            # The headline prediction is *calibrated* to the backend
            # this run measured on — a raw TPU-v5e roofline number next
            # to interpret-mode measurements is not comparable; the raw
            # model figure stays as model_gflops for the machine-free
            # trajectory.
            cb = cal.model(d=int(b.n)).evaluate(
                app_ex.workload, int(b.detail["block_rows"]), int(b.m),
                d=int(b.n), dx=int(b.detail.get("dx", 1)),
            )
            bench[name] = {
                "best": {"d": int(b.n), "m": int(b.m),
                         "block_h": int(b.detail["block_rows"]),
                         "calibrated_gflops": float(cb.sustained_gflops),
                         "model_gflops": float(b.sustained_gflops)},
                "executed": [e.as_dict() for e in sr.executed],
                # The one search-result schema (SEARCH_RESULT_FIELDS):
                # never a hand-picked subset that can drift from the CLI.
                "search": sr.as_dict(),
            }
        bench["autotune"] = autotune
        bench["program"] = program_bench
        bench["study"] = {
            "name": study_name,
            "records": len(study.records),
            "report_html": os.path.basename(study_report["html"]),
            "report_text": os.path.basename(study_report["text"]),
        }
        bench["grid"] = [MEASURE_H, MEASURE_W]
        bench["mesh"] = mesh_bench
        bench["exec_d"] = [int(d) for d in exec_d]
        bench["interpret"] = bool(interpret)
        bench["measure"] = {
            "backend": cal.backend,
            "reps": int(reps),
            "platform_elem_gflops": float(cal.elem_gflops),
            "platform_mem_gbs": float(cal.mem_gbs),
            "cache": None if cache is None else cache.stats(),
            "cache_hits_on_repeat": int(pass2_hits),
        }
    return out


def write_bench(path: str = BENCH_PATH, topk: int = 3,
                interpret: bool | None = None, reps: int = 3) -> list[str]:
    """Run the sweeps and record ``BENCH_dse.json`` (the PR-over-PR
    trajectory file: best point, sustained GFLOPS, calibrated
    predicted-vs-measured error, and measurement-cache stats per app).

    Uses the default persistent measurement cache, so re-invoking the
    benchmark skips recompile+retime for every already-seen frontier
    point and calibration anchor. The generic platform probes
    (``platform_elem_gflops`` / ``platform_mem_gbs``) are deliberately
    re-measured each run — they record the platform this run actually
    had, not a cached one."""
    bench: dict = {}
    out = run(topk=topk, interpret=interpret, reps=reps, bench=bench,
              cache=MeasurementCache())
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(bench, fh, indent=2, sort_keys=True)
        fh.write("\n")
    out.append(f"[wrote {path}]")
    return out


if __name__ == "__main__":
    enable_compile_cache()
    print("\n".join(write_bench()))
