#!/usr/bin/env python3
"""Run one cell of the chip benchmark once, in this process.

    python3 benchmarks/chip/run.py --workload ulbm8192.chunk64 \\
        --seed 7 --seconds 10 --trace 0

The cell, its configuration, traffic and metrics are named in
``BENCHMARK.json`` at the checkout root (see ``harness.py``). Earlier
lines of standard output give the plan; the last line is one JSON
object with ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics
read from a profiler trace of the window), ``device`` and, last, the
numbers compared with their limits, which also end standard error.

It exits non-zero, with no result line, when JAX finds no TPU or fewer
chips than the cell asks for, or when this checkout's ``src`` is
missing. JAX's persistent compile cache is kept where the program
keeps it (``repro.compat.enable_compile_cache``): in
``$JAX_COMPILATION_CACHE_DIR`` when that is set, else in the checkout's
``.jax_cache``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]


def _import_repro():
    """Import the program from this checkout's ``src`` and nowhere else."""
    src = ROOT / "src"
    sys.path.insert(1, str(src))
    import repro

    if Path(repro.__file__).resolve().parent != src / "repro":
        raise ImportError(f"repro found outside {src}: {repro.__file__}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        _import_repro()
    except ImportError as e:
        print(f"run.py: cannot import this checkout's program: {e}",
              file=sys.stderr)
        return 2
    import jax

    import harness
    from repro.compat import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: no TPU (JAX found {len(devices)} "
              f"{devices[0].platform} device(s), kind "
              f"{devices[0].device_kind!r}); the benchmark runs on the chip "
              "only", file=sys.stderr)
        return 1
    chips = harness.load_cell(ROOT, args.workload, bool(args.trace)).chips
    if len(devices) < chips:
        print(f"run.py: {args.workload} needs {chips} chips, JAX found "
              f"{len(devices)} {devices[0].device_kind!r}", file=sys.stderr)
        return 1
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START)
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
