#!/usr/bin/env python3
"""Readings that set a cell's limit: the program on many seeds, and the
control on a few, in one process at the cell's own size.

    python3 benchmarks/chip/control.py --workload ulbm8192.chunk64 \\
        --seeds 101-112 --control-seeds 101-103 --calls 2

For each seed the cell's state is built and ``--calls`` calls go
through the timed entry, as in a run's window. The last call's output
is compared with the reference (the program's reading: the lower end
of the limit). On the control seeds the reference itself, computed at
the next precision below the configured one (``harness.CONTROL_DTYPE``),
is put in the program's place and compared the same way (the control's
reading: the upper end). Each reading goes through the harness's own
check (``harness.check`` and ``harness.passed``, as a run's window
does), so each row says whether the program and the control come out
``correct``: the program has to, the control must not. One JSON line
per seed, then a summary line. The benchmark's own runs never run this.
It needs a TPU, like ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]


def seed_list(text: str) -> list[int]:
    """``"3,5,10-12"`` -> ``[3, 5, 10, 11, 12]``."""
    out = []
    for part in filter(None, text.split(",")):
        lo, _, hi = part.partition("-")
        out.extend(range(int(lo), int(hi or lo) + 1))
    return out


def readings(root: Path, workload: str, seeds, control_seeds, calls: int,
             log=print) -> dict:
    """The program's gap on every seed and the control's on
    ``control_seeds``, each judged by the cell's limit; returns
    ``{"lower", "upper", "program_correct", "control_correct", "rows"}``,
    where ``program_correct`` holds when every program reading passed and
    ``control_correct`` when any control reading did."""
    import jax.numpy as jnp

    import harness

    cell = harness.load_cell(root, workload, trace=False)
    system = harness.build(cell)
    plan = system.plan
    low = jnp.dtype(harness.CONTROL_DTYPE[cell.config["dtype"]])
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        x = system.init(seed)
        for _ in range(calls):
            prev = x
            x = system.call(prev)
        gap = harness.max_gap(cell, plan.steps, prev, got=x)
        row = {"seed": seed, "program": gap,
               "program_correct": harness.passed(harness.check(cell, gap, 0))}
        del x
        if seed in control_seeds:
            gap = harness.max_gap(cell, plan.steps, prev, dtype=low)
            row.update(control=gap, control_correct=harness.passed(
                harness.check(cell, gap, 0)))
        del prev
        row["seconds"] = time.perf_counter() - t0
        log(json.dumps(row))
        rows.append(row)
    controls = [r for r in rows if "control" in r]
    return {"workload": workload, "plan": plan.as_dict(),
            "limit": cell.config["limits"]["max_abs_err"],
            "lower": max(r["program"] for r in rows),
            "upper": min((r["control"] for r in controls), default=None),
            "program_correct": all(r["program_correct"] for r in rows),
            "control_correct": any(r["control_correct"] for r in controls),
            "control_dtype": str(low), "rows": rows}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, type=seed_list)
    ap.add_argument("--control-seeds", default="", type=seed_list)
    ap.add_argument("--calls", type=int, default=2)
    args = ap.parse_args(argv)
    sys.path.insert(1, str(ROOT / "src"))
    import jax

    from repro.compat import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("control.py: no TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    summary = readings(ROOT, args.workload, args.seeds,
                       set(args.control_seeds), args.calls)
    summary.pop("rows")
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
