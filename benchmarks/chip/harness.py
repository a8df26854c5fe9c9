"""The chip benchmark's harness: one cell, one run, one result.

Everything that belongs to one cell is found by name from
``BENCHMARK.json`` at the checkout root:

* ``configs/<config>.json`` (the manifest's ``file``): the app, grid,
  chips, the mesh column splits the plan may use, physics, and the
  limits of the check;
* ``apps/<app>.py``: builds the system under test, the seeded state and
  the plain reference (see ``apps/ulbm.py`` for the interface);
* ``traffic/<mix>.json``: steps per call and what is read back after
  each call;
* ``metrics/<metric>.py``: ``read(rec) -> float | None``, one number
  from the run record (end to end) or its trace (per layer);
* ``peaks.json``: the chip's peaks, keyed by ``device_kind``.

A run is what a user of ``repro-explore`` does, minus live timing: the
plan the model picks with no measurement (``Explorer.sweep_tpu`` over
its default lattice, ``Sweep.best("sustained_gflops")``,
``resolve_run_plan``), the state built on the device from the seed, one
warm-up call, then calls of ``steps_per_call`` steps through the timed
entry (``StreamKernel.run_blocked`` on one chip,
``ShardedStreamKernel.run_blocked`` on a mesh) for ``seconds``, each
followed by a readback to the host. Once the window has closed, the
last call's output is compared with the app's reference run from that
call's input, in blocks of rows.
"""

from __future__ import annotations

import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from functools import partial
from pathlib import Path

#: The benchmark's directory under the checkout root.
BENCH_SUBDIR = Path("benchmarks") / "chip"
#: Reference rows computed per block (the reference fits in memory at
#: any grid because it never holds more than one block's stripe).
REF_ROWS = 1024
#: The precision the control computes in, for each configured dtype:
#: the nearest one below it.
CONTROL_DTYPE = {"float32": "bfloat16"}


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    app: object
    metrics: dict  # name -> manifest entry, for this cell and trace mode
    readers: dict  # name -> read(rec)


def load_module(path: Path, name: str):
    """Import one file of the benchmark by path."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: Path, workload: str, trace: bool) -> Cell:
    """The cell named ``workload`` in ``root/BENCHMARK.json``, with the
    files its names point to."""
    root = Path(root)
    bench = root / BENCH_SUBDIR
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((bench / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    app = load_module(bench / "apps" / f"{config['app']}.py",
                      f"bench_app_{config['app']}")
    kind = "per_layer" if trace else "end_to_end"
    metrics = {m["name"]: m for m in manifest[kind]
               if workload in m.get("workloads", [workload])}
    readers = {name: load_module(bench / "metrics" / f"{name}.py",
                                 f"bench_metric_{name}").read
               for name in metrics}
    return Cell(workload, int(w["chips"]), config, traffic, app, metrics,
                readers)


def peak(root: Path, kind: str, key: str) -> float:
    """A peak of the chip ``kind`` from ``peaks.json``; a kind that is
    not in the table is an error, never a default."""
    table = json.loads((Path(root) / BENCH_SUBDIR / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"(have {sorted(table['devices'])})")
    return float(table["devices"][kind][key])


def prng_key(seed: int):
    """A PRNG key that keeps all the bits of a seed wider than 32."""
    import jax

    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32) if seed >> 32 else key


# ---- the system under test ---------------------------------------------


@dataclass
class Plan:
    block_h: int
    m: int
    steps: int
    double_buffer: bool
    dy: int
    dx: int
    model_step_s: float

    def as_dict(self) -> dict:
        return dict(self.__dict__)


def choose_plan(cell: Cell, kernel, explorer) -> Plan:
    """The plan the system picks with no live measurement."""
    from repro.core.legalize import resolve_run_plan

    h, w = cell.config["grid"]
    sweep = explorer.sweep_tpu(d_values=(cell.chips,),
                               dx_values=tuple(cell.config["mesh_dx"]))
    point = sweep.best("sustained_gflops")
    dx = int(point.detail["dx"])
    bh, m, steps, db = resolve_run_plan(
        h, point, cell.traffic["steps_per_call"], halo=kernel.halo,
        width=w, words=cell.app.WORDS, d=cell.chips, dx=dx,
        halo_x=kernel.halo_x)
    if steps != cell.traffic["steps_per_call"]:
        raise ValueError(f"plan m={m} does not divide steps_per_call="
                         f"{cell.traffic['steps_per_call']}")
    # The model's prediction for the plan as legalized.
    pred = explorer.tpu.evaluate(explorer.workload, bh, m, d=cell.chips,
                                 double_buffer=db, dx=dx).detail
    block_s = max(pred["t_compute_s"], pred["t_memory_s"],
                  pred["t_collective_s"]) + pred["t_launch_s"]
    return Plan(bh, m, steps, db, cell.chips // dx, dx, block_s / m)


@dataclass
class System:
    """The system under test, set up for one cell: the timed entry at
    the chosen plan, and the seeded state builder in its sharding."""

    cell: Cell
    runner: object  # StreamKernel, or ShardedStreamKernel on a mesh
    regs: tuple
    plan: Plan
    init_fn: object  # jitted: PRNG key -> state in its sharding

    def call(self, x):
        """One call of the timed entry: ``plan.steps`` steps."""
        p = self.plan
        return self.runner.run_blocked(x, self.regs, steps=p.steps, m=p.m,
                                       block_h=p.block_h,
                                       double_buffer=p.double_buffer)

    def init(self, seed: int):
        """The seeded state, built on the device in its sharding."""
        return self.init_fn(prng_key(seed))


def build(cell: Cell) -> System:
    """Build the app's system, choose the plan, and place it."""
    import jax
    from jax.sharding import NamedSharding, SingleDeviceSharding

    kernel, explorer, regs = cell.app.system(cell.config)
    plan = choose_plan(cell, kernel, explorer)
    if cell.chips == 1:
        runner, sharding = kernel, SingleDeviceSharding(jax.devices()[0])
    else:
        from repro.core.distribute import DEVICE_AXIS, DEVICE_AXIS_X
        from repro.parallel.sharding import stream_grid_pspec

        runner = kernel.sharded(cell.chips, dx=plan.dx)
        spec = stream_grid_pspec(
            DEVICE_AXIS, axis_x=DEVICE_AXIS_X if plan.dx > 1 else None)
        sharding = NamedSharding(runner.mesh, spec)

    def bench_init(key):
        return cell.app.init_state(cell.config, key)

    return System(cell, runner, regs, plan,
                  jax.jit(bench_init, out_shardings=sharding))


# ---- the comparison that decides ``correct`` ---------------------------


def _ref_block(app, cfg, steps, rows, dtype, x_prev, start):
    """Reference rows ``[start, start + rows)`` after ``steps`` steps
    from ``x_prev``, computed in ``dtype`` on a stripe extended by
    ``steps`` rows each side (periodic in y): information moves one row
    per step, so the centre rows are exact. ``start`` is a multiple of
    ``rows``, and ``steps <= rows``, so no piece wraps."""
    import jax
    import jax.numpy as jnp

    h = x_prev.shape[1]

    def piece(at, n):
        return jax.lax.dynamic_slice_in_dim(x_prev, at % h, n, axis=1)

    ext = jnp.concatenate([piece(start - steps, steps), piece(start, rows),
                           piece(start + rows, steps)], axis=1).astype(dtype)
    ext = jax.lax.fori_loop(0, steps, lambda _, s: app.step(cfg, s), ext)
    return ext[:, steps:steps + rows].astype(jnp.float32)


def bench_gap(app, cfg, steps, rows, x_prev, got, start):
    """Largest |got - reference| over one block of rows."""
    import jax
    import jax.numpy as jnp

    ref = _ref_block(app, cfg, steps, rows, jnp.float32, x_prev, start)
    blk = jax.lax.dynamic_slice_in_dim(got, start, rows, axis=1)
    return jnp.max(jnp.abs(blk - ref))


def bench_control_gap(app, cfg, steps, rows, dtype, x_prev, start):
    """Largest |reference in ``dtype`` - reference in float32| over one
    block of rows: the control, the reference put in the program's
    place at the next precision down."""
    import jax.numpy as jnp

    ref = _ref_block(app, cfg, steps, rows, jnp.float32, x_prev, start)
    low = _ref_block(app, cfg, steps, rows, dtype, x_prev, start)
    return jnp.max(jnp.abs(low - ref))


def max_gap(cell: Cell, steps: int, x_prev, got=None, dtype=None) -> float:
    """The widest gap between an answer and the reference over the whole
    grid, block by block. With ``got`` the answer is the program's
    output; without it, the control's (the reference in ``dtype``)."""
    import jax

    h = x_prev.shape[1]
    rows = math.gcd(h, REF_ROWS)
    if steps > rows:
        raise ValueError(f"{steps} steps per call exceed the reference "
                         f"block of {rows} rows")
    cfg = cell.config
    if got is not None:
        fn = jax.jit(partial(bench_gap, cell.app, cfg, steps, rows))
        gaps = [fn(x_prev, got, s) for s in range(0, h, rows)]
    else:
        fn = jax.jit(partial(bench_control_gap, cell.app, cfg, steps, rows,
                             dtype))
        gaps = [fn(x_prev, s) for s in range(0, h, rows)]
    return max(float(g) for g in gaps)


def check(cell: Cell, max_abs_err: float, nonfinite: int) -> dict:
    """Each number compared, beside its limit."""
    return {
        "max_abs_err": {"value": max_abs_err,
                        "limit": cell.config["limits"]["max_abs_err"]},
        "nonfinite_readbacks": {"value": nonfinite, "limit": 0},
    }


def passed(checks: dict) -> bool:
    # A NaN compares false, so it fails.
    return all(c["value"] <= c["limit"] for c in checks.values())


# ---- one run --------------------------------------------------------------


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float | None = None,
             log=print) -> dict:
    """Run one cell once; return the result line's object.

    ``t_start`` is the process's start on ``time.perf_counter``'s clock
    (set-up is measured from it); ``log`` takes the earlier lines (the
    plan). The caller has checked the device.
    """
    t_start = time.perf_counter() if t_start is None else t_start
    import jax
    import numpy as np

    cell = load_cell(root, workload, trace)
    devices = jax.devices()[:cell.chips]
    if len(devices) < cell.chips:
        raise RuntimeError(f"{workload} needs {cell.chips} devices, JAX "
                           f"found {len(devices)}")
    with jax.profiler.TraceAnnotation("bench.plan"):
        system = build(cell)
    plan = system.plan
    log(json.dumps({"workload": workload, "seed": seed,
                    "plan": plan.as_dict()}))
    read_rows = cell.app.READBACKS[cell.traffic["readback"]]

    @jax.jit
    def bench_readback(x):
        return read_rows(x)

    def readback(x) -> float:
        return float(np.asarray(bench_readback(x), np.float64).sum())

    with jax.profiler.TraceAnnotation("bench.init"):
        x = system.init(seed)
    with jax.profiler.TraceAnnotation("bench.warmup"):
        x = system.call(x)
        readback(x)
    setup_s = time.perf_counter() - t_start

    profile_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(profile_dir)
    values, calls = [], 0
    t0 = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("bench.call"):
            prev = x
            x = system.call(prev)
        with jax.profiler.TraceAnnotation("bench.readback"):
            values.append(readback(x))
        calls += 1
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    mem_peak = memory_peak(devices)

    gap = max_gap(cell, plan.steps, prev, got=x)
    itemsize = x.dtype.itemsize
    del prev, x
    nonfinite = sum(not math.isfinite(v) for v in values)
    checks = check(cell, gap, nonfinite)

    h, w = cell.config["grid"]
    rec = {
        "root": str(root), "chips": cell.chips, "sites": h * w,
        "words": cell.app.WORDS, "itemsize": itemsize,
        "plan": plan.as_dict(), "steps_per_call": plan.steps,
        "calls": calls, "window_s": window_s, "setup_s": setup_s,
        "device_kind": devices[0].device_kind, "trace": None,
    }
    device = dict(device_info(jax.devices()), memory_peak_bytes=mem_peak)
    result = {"correct": passed(checks), "attempted": calls,
              "failed": nonfinite}
    if trace:
        import tracefile

        rec["trace"] = tracefile.load(profile_dir, plan.steps)
        shutil.rmtree(profile_dir, ignore_errors=True)
        busy = tracefile.busy_s(rec["trace"], cell.chips)
        device.update(busy_s=busy, window_s=tracefile.window_s(rec["trace"]))
        result["breakdown"] = tracefile.breakdown(rec["trace"], cell.chips)
    metrics = {}
    for name, entry in cell.metrics.items():
        value = cell.readers[name](rec)
        if value is not None:
            metrics[name] = {"value": value, "unit": entry["unit"]}
    result.update(metrics=metrics, device=device)
    result["mass"] = {"first": values[0], "last": values[-1]}
    # A NaN or infinite reading is printed as text, so the line stays
    # JSON that any reader parses.
    result["check"] = {
        k: {"value": c["value"] if math.isfinite(c["value"])
            else str(c["value"]), "limit": c["limit"]}
        for k, c in checks.items()}
    return result


def report(result: dict, out=None, err=None) -> None:
    """The compared numbers as the last lines of ``err`` (standard
    error), then the result as the last line of ``out`` (standard
    output)."""
    out, err = out or sys.stdout, err or sys.stderr
    for name, c in result["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
