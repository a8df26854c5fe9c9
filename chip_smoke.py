#!/usr/bin/env python3
"""Smoke run of the main path on a TPU: one process, compiled kernels.

    python chip_smoke.py              # one chip: phases (a)-(e)
    python chip_smoke.py --chips 4    # four chips: the sharded mesh only

Phases on one chip (``interpret=False`` throughout, profiler off):

(a) uLBM D2Q9, periodic, 4096x4096 — ``StreamKernel.run_blocked`` at a
    plan from ``resolve_run_plan``, against ``StreamKernel.reference``
    run by XLA on the same chip, plus mass conservation.
(b) 2-D diffusion, 8192x8192 — ``StreamKernel.run_for_point``, against
    the reference, the exact decay of the sine mode, and conservation.
(c) The 3-core LBM program at a fused and a pipelined partition, against
    the monolithic uLBM kernel.
(d) ``Explorer.search`` on the 4096x4096 uLBM sweep (budget 3, no
    measurement cache, calibration on) — the ``repro-explore`` path; the
    best measured plan is re-run and checked against the reference.
(e) ``SimEngine`` serving requests from two tenant contexts with a small
    tuning budget; every completion is checked against an independent
    ``run_blocked`` of the same member.

With ``--chips 4`` only ``ShardedStreamKernel`` runs, on meshes (4, 1)
and (2, 2) over uLBM at 8192x8192, each compared with the single-chip
streamed kernel on the same data.

Each phase prints one JSON line (phase, shape, plan, compile seconds,
steady seconds per step, error against its reference). These are smoke
timings, not benchmark numbers. The last line of standard output is
``{"ok": true, "device": {...}}`` only when every phase passed; the exit
code is non-zero when any phase failed, when JAX finds no TPU, or when
the script is run outside a checkout of this repository. The phase
functions take the grid size, so tests run them tiny on the CPU.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

#: Max |kernel - reference| allowed for f32 state of magnitude <= 1 after
#: a few steps: the compiled kernel and XLA's reference may round and
#: contract differently, while a halo or indexing fault moves values by
#: 1e-3 or more.
TOL = 1e-4
#: Relative drift allowed in a conserved total (mass, or the diffusion
#: integral relative to the field's L1 norm).
CONSERVE_TOL = 1e-5


def _import_repro():
    """Import the package from this checkout's ``src`` and nowhere else."""
    src = ROOT / "src"
    sys.path.insert(0, str(src))
    import repro

    if Path(repro.__file__).resolve().parent != src / "repro":
        raise ImportError(f"repro found outside {src}: {repro.__file__}")
    return repro


def _timed(run, steps: int) -> tuple[object, float, float]:
    """First call (compile + one run) and the steady seconds per step."""
    import jax

    from repro.core.measure import time_run

    t0 = time.perf_counter()
    out = jax.block_until_ready(run())
    first = time.perf_counter() - t0
    steady = time_run(run, reps=3, warmup=0).wall_s
    return out, max(first - steady, 0.0), steady / steps


def _max_err(a, b) -> float:
    import jax.numpy as jnp

    return float(jnp.max(jnp.abs(a - b)))


def _total(x) -> float:
    """Sum of ``x`` accurate enough to see conservation: f32 sums along
    the rows on the device, then a float64 sum of the row sums on the
    host (one f32 sum over ~1e8 values drifts by 1e-5 on its own)."""
    import jax.numpy as jnp
    import numpy as np

    return float(np.asarray(jnp.sum(x, axis=-1), np.float64).sum())


def _check(rec: dict, **bounds) -> dict:
    """Fail the phase when a value exceeds its bound or is not finite."""
    import math

    for key, bound in bounds.items():
        val = rec[key]
        if not (math.isfinite(val) and val <= bound):
            raise AssertionError(f"{rec['phase']}: {key}={val} > {bound}")
    return rec


def _reference(kern, state, regs, steps: int, m: int):
    """``steps`` reference steps as ``steps / m`` jitted calls of ``m``
    unrolled steps each (one compile, bounded XLA temporaries)."""
    for _ in range(steps // m):
        state = kern.reference(state, regs, m=m)
    return state


def _lbm(n: int):
    from repro.apps import lbm

    sim = lbm.LBMSimulation(lbm.LBMProblem(n, n))
    f0, attr, _ = lbm.taylor_green_init(n, n)
    return sim, sim.stream_state(f0, attr), sim.stream_regs()


def phase_lbm(n: int = 4096, *, block_rows: int = 32, m: int = 4,
              launches: int = 3) -> dict:
    """(a) uLBM through ``run_blocked`` at a legalized plan."""
    from repro.core.dse import TPUModel
    from repro.core.legalize import resolve_run_plan

    sim, state, regs = _lbm(n)
    kern = sim.stream_kernel()
    point = TPUModel().evaluate(sim.stream_workload(), block_rows, m)
    bh, mm, steps, db = resolve_run_plan(
        n, point, launches * m, halo=kern.halo, width=n,
        words=state.shape[0],
    )
    out, compile_s, step_s = _timed(
        lambda: kern.run_blocked(state, regs, steps=steps, m=mm,
                                 block_h=bh, double_buffer=db), steps)
    ref = _reference(kern, state, regs, steps, mm)
    mass0 = _total(state[:9])
    rec = {
        "phase": "a_lbm", "shape": list(state.shape),
        "plan": {"block_h": bh, "m": mm, "steps": steps,
                 "double_buffer": db},
        "compile_s": compile_s, "step_s": step_s,
        "max_abs_err": _max_err(out, ref), "tol": TOL,
        "mass_drift": abs(_total(out[:9]) - mass0) / mass0,
    }
    return _check(rec, max_abs_err=TOL, mass_drift=CONSERVE_TOL)


def phase_diffusion(n: int = 8192, *, block_rows: int = 64, m: int = 8,
                    launches: int = 3, alpha: float = 0.2) -> dict:
    """(b) Diffusion through the codegen path's ``run_for_point``."""
    import jax.numpy as jnp

    from repro.apps import diffusion as dif
    from repro.core.dse import TPUModel

    sim = dif.DiffusionSimulation(n, n, alpha=alpha)
    u0, decay = dif.sine_init(n, n)
    state = sim.state(u0)
    point = TPUModel().evaluate(sim.explorer().workload, block_rows, m)
    steps = launches * m
    plan = {}

    def run():
        out, (bh, mm, db) = sim.kernel.run_for_point(
            state, (alpha,), point=point, steps=steps)
        plan.update(block_h=bh, m=mm, steps=steps, double_buffer=db)
        return out

    out, compile_s, step_s = _timed(run, steps)
    ref = _reference(sim.kernel, state, (alpha,), steps, plan["m"])
    ratio = float(jnp.linalg.norm(out) / jnp.linalg.norm(state))
    l1 = _total(jnp.abs(state))
    rec = {
        "phase": "b_diffusion", "shape": list(state.shape), "plan": plan,
        "compile_s": compile_s, "step_s": step_s,
        "max_abs_err": _max_err(out, ref), "tol": TOL,
        "decay_rel_err": abs(ratio / decay(alpha) ** steps - 1.0),
        "sum_drift": abs(_total(out) - _total(state)) / l1,
    }
    return _check(rec, max_abs_err=TOL, decay_rel_err=TOL,
                  sum_drift=CONSERVE_TOL)


def phase_program(n: int = 2048, *, block_rows: int = 32, m: int = 4,
                  launches: int = 2) -> dict:
    """(c) The 3-core LBM program, fused and pipelined, against the
    monolithic uLBM kernel."""
    from repro.core.dse import TPUModel
    from repro.core.legalize import resolve_run_plan

    sim, state, regs = _lbm(n)
    prog = sim.program()
    steps = launches * m
    want = sim.stream_kernel().run_blocked(
        state, regs, steps=steps, m=m, block_h=block_rows)
    rec = {"phase": "c_program", "shape": list(state.shape),
           "partitions": {}, "tol": TOL}
    for spec in ("3", "1+1+1"):
        point = TPUModel().evaluate(prog.workload(n * n, grid_w=n),
                                    block_rows, m, fusion=spec)
        bh, mm, nsteps, db = resolve_run_plan(
            n, point, steps, width=n, stages=prog.stage_geometry(),
            fusion=spec,
        )
        pk = prog.kernel(spec)
        out, compile_s, step_s = _timed(
            lambda: pk.run_blocked(state, regs, steps=nsteps, m=mm,
                                   block_h=bh, double_buffer=db), nsteps)
        rec["partitions"][spec] = {
            "plan": {"block_h": bh, "m": mm, "steps": nsteps,
                     "double_buffer": db},
            "compile_s": compile_s, "step_s": step_s,
            "max_abs_err": _max_err(out, want),
        }
    rec["max_abs_err"] = max(p["max_abs_err"]
                             for p in rec["partitions"].values())
    return _check(rec, max_abs_err=TOL)


def phase_search(n: int = 4096, *, budget: int = 3) -> dict:
    """(d) ``Explorer.search`` with measurement in the loop."""
    from repro.compat import default_interpret

    sim, state, regs = _lbm(n)
    ex = sim.explorer()
    sweep = ex.sweep_tpu(bh_values=(16, 32), m_values=(1, 2, 4),
                         d_values=(1,))
    t0 = time.perf_counter()
    res = ex.search(sweep, state, regs, budget=budget, cache=None,
                    calibrate=True)
    search_s = time.perf_counter() - t0
    if not res.executed or res.budget_spent > budget:
        raise AssertionError(
            f"d_search: {len(res.executed)} executed, "
            f"{res.budget_spent} of budget {budget} spent")
    if any(e.interpret != default_interpret() for e in res.executed):
        raise AssertionError("d_search: a point ran in the wrong mode")
    best = max(res.executed, key=lambda e: e.measured_mlups)
    kern = sim.stream_kernel()
    out, (bh, mm, db) = kern.run_for_point(state, regs, point=best.point,
                                           steps=best.steps)
    rec = {
        "phase": "d_search", "shape": list(state.shape),
        "plan": {"block_h": bh, "m": mm, "steps": best.steps,
                 "double_buffer": db},
        "search_s": search_s, "step_s": best.wall_s / best.steps,
        "budget_spent": res.budget_spent,
        "executed": [{"block_h": e.block_h, "m": e.m,
                      "mlups": e.measured_mlups, "rel_error": e.rel_error}
                     for e in res.executed],
        "max_abs_err": _max_err(out, kern.reference(state, regs,
                                                     m=best.steps)),
        "tol": TOL,
    }
    return _check(rec, max_abs_err=TOL)


def phase_serve(n: int = 256, *, requests: int = 3, steps: int = 8,
                budget: int = 2, study_dir: Path | None = None) -> dict:
    """(e) ``SimEngine``: two tenant contexts, tuned on first request."""
    from repro.apps import diffusion as dif
    from repro.serve.sim import PlanResolver, SimEngine, SimRequest

    dsim = dif.DiffusionSimulation(n, n, alpha=0.2)
    lsim, lstate, lregs = _lbm(n)
    tenants = [
        (dsim.kernel, dsim.state(dif.sine_init(n, n)[0]), (dsim.alpha,)),
        (lsim.stream_kernel(), lstate, lregs),
    ]
    study_dir = study_dir or ROOT / ".smoke_studies"
    shutil.rmtree(study_dir, ignore_errors=True)  # tune live every run
    engine = SimEngine(PlanResolver(
        budget=budget, b_values=(1, 2), bh_values=(8, 16, 32),
        m_values=(1, 2, 4), study_dir=str(study_dir)))
    reqs = {}
    for i in range(requests * len(tenants)):
        kern, state, regs = tenants[i % len(tenants)]
        reqs[i] = (kern, state, regs)
        if not engine.submit(SimRequest(rid=i, core=kern, state=state,
                                        steps=steps, regs=regs)):
            raise AssertionError(f"e_serve: request {i} rejected")
    t0 = time.perf_counter()
    done = engine.run_until_drained(max_ticks=1000)
    serve_s = time.perf_counter() - t0
    if sorted(c.rid for c in done) != sorted(reqs):
        raise AssertionError(f"e_serve: completed {len(done)} of "
                             f"{len(reqs)} requests")
    err = 0.0
    for c in done:
        kern, state, regs = reqs[c.rid]
        want = kern.run_blocked(state, regs, steps=steps, m=1, block_h=8)
        err = max(err, _max_err(c.state, want))
    stats = engine.stats()
    rec = {
        "phase": "e_serve", "shape": [n, n], "requests": len(done),
        "plans": stats["plans"], "launches": stats["launches"],
        "live_timings": stats["live_timings"], "serve_s": serve_s,
        "step_s": stats["launch_wall_s"] / max(stats["member_steps"], 1),
        "max_abs_err": err, "tol": TOL,
    }
    return _check(rec, max_abs_err=TOL)


def phase_mesh(n: int = 8192, *, block_rows: int = 32, m: int = 4,
               launches: int = 2, devices=None) -> dict:
    """Four chips: ``ShardedStreamKernel`` on (4, 1) and (2, 2) against
    the single-chip streamed kernel on the same data."""
    import jax

    from repro.core.dse import TPUModel
    from repro.core.legalize import resolve_run_plan

    devices = list(devices if devices is not None else jax.devices())[:4]
    sim, state, regs = _lbm(n)
    kern = sim.stream_kernel()
    steps = launches * m
    point = TPUModel().evaluate(sim.stream_workload(), block_rows, m)
    bh, mm, steps, db = resolve_run_plan(
        n, point, steps, halo=kern.halo, width=n, words=state.shape[0])
    want, compile_s, step_s = _timed(
        lambda: kern.run_blocked(state, regs, steps=steps, m=mm,
                                 block_h=bh, double_buffer=db), steps)
    rec = {"phase": "mesh", "shape": list(state.shape),
           "single": {"plan": {"block_h": bh, "m": mm, "steps": steps,
                               "double_buffer": db},
                      "compile_s": compile_s, "step_s": step_s},
           "meshes": {}, "tol": TOL}
    for dy, dx in ((4, 1), (2, 2)):
        sk = kern.sharded(4, devices=devices, dx=dx)
        ids = sorted(d.id for d in sk.mesh.devices.flat)
        if (sk.dy, sk.dx) != (dy, dx) or len(set(ids)) != 4:
            raise AssertionError(f"mesh ({dy}, {dx}) placed on {ids}")
        mbh, mm2, msteps, mdb = resolve_run_plan(
            n, point, steps, halo=kern.halo, width=n,
            words=state.shape[0], d=4, dx=dx, halo_x=kern.halo_x)
        if msteps != steps:
            raise AssertionError(f"mesh ({dy}, {dx}): plan runs {msteps} "
                                 f"steps, the single chip {steps}")
        def run(x):
            return sk.run_blocked(x, regs, steps=msteps, m=mm2,
                                  block_h=mbh, double_buffer=mdb)

        # The first call compiles and scatters the chip-0 state over the
        # mesh; the steady time runs on the state already sharded.
        t0 = time.perf_counter()
        out = jax.block_until_ready(run(state))
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        sharded = jax.block_until_ready(jax.device_put(state, out.sharding))
        scatter_s = time.perf_counter() - t0
        out, _, s_s = _timed(lambda: run(sharded), msteps)
        placed = sorted(s.device.id for s in out.addressable_shards)
        if len(set(placed)) != 4:
            raise AssertionError(f"mesh ({dy}, {dx}) output on {placed}")
        rec["meshes"][f"{dy}x{dx}"] = {
            "plan": {"block_h": mbh, "m": mm2, "steps": msteps,
                     "double_buffer": mdb},
            "devices": placed, "compile_s": max(first - s_s * msteps, 0.0),
            "scatter_s": scatter_s, "step_s": s_s,
            "max_abs_err": _max_err(out, want),
        }
    rec["max_abs_err"] = max(v["max_abs_err"]
                             for v in rec["meshes"].values())
    return _check(rec, max_abs_err=TOL)


SINGLE_CHIP = (phase_lbm, phase_diffusion, phase_program, phase_search,
               phase_serve)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (a)-(e) on one chip; 4: the sharded "
                         "mesh phase on four chips, and nothing else")
    args = ap.parse_args(argv)
    try:
        _import_repro()
        import jax

        from repro.compat import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import this checkout's package: {e}",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "this smoke run needs the chip", file=sys.stderr)
        return 1
    enable_compile_cache()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    phases = (phase_mesh,) if args.chips == 4 else SINGLE_CHIP
    failed = []
    for phase in phases:
        t0 = time.perf_counter()
        try:
            rec = phase()
        except Exception:
            traceback.print_exc()
            failed.append(phase.__name__)
            print(json.dumps({"phase": phase.__name__, "ok": False}),
                  flush=True)
            continue
        rec["wall_s"] = time.perf_counter() - t0
        print(json.dumps(rec), flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
