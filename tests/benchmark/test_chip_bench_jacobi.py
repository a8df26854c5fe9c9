"""The jacobi-2d configuration on the CPU, cut to 64x256: a run through
the harness, the benchmark's own PolyBench reference against the
program's, answers that must fail the configured limit, and
``kernel_gflops`` by hand."""

from __future__ import annotations

import json
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from _chip_bench_util import BENCH, REPO, add_cell, tiny_root

import harness  # noqa: E402  (benchmarks/chip, put on sys.path above)
import tracefile  # noqa: E402

TINY_JACOBI = "tinyjacobi.c8"


def tiny_jacobi_config() -> dict:
    """The jacobi-2d configuration cut to 64x256, with wavelengths that
    divide it."""
    cfg = json.loads((BENCH / "configs" / "jacobi2d-16384.json").read_text())
    cfg.update(name="tiny-jacobi", grid=[64, 256], modes=[16, 32, 64])
    return cfg


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny_root(tmp_path_factory.mktemp("bench"))
    add_cell(root, TINY_JACOBI, tiny_jacobi_config(), "tiny8")
    return root


@pytest.fixture(scope="module")
def cell(root):
    return harness.load_cell(root, TINY_JACOBI, trace=False)


def test_tiny_jacobi_cell_runs_correct(root):
    lines = []
    res = harness.run_cell(root, TINY_JACOBI, 2**33 + 17, 0.2, False,
                           log=lines.append)
    plan = json.loads(lines[0])["plan"]
    assert plan["steps"] == 8 and plan["steps"] % plan["m"] == 0
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"mlups", "setup_s"}
    # Periodic Jacobi conserves the total heat.
    assert res["mass"]["last"] == pytest.approx(res["mass"]["first"],
                                                abs=1e-2)


def test_polybench_step_agrees_with_the_programs_reference(cell):
    from repro.apps.diffusion import diffusion_ref_run

    cfg = cell.config
    x = cell.app.init_state(cfg, harness.prng_key(21))
    assert x.shape == (1, 64, 256) and x.dtype == jnp.float32
    ours = x
    for _ in range(128):
        ours = cell.app.step(cfg, ours)
    theirs = diffusion_ref_run(x[0], cfg["alpha"], 128)
    np.testing.assert_allclose(np.asarray(ours[0]), np.asarray(theirs),
                               rtol=0, atol=1e-5)
    assert cell.app.step(cfg, x.astype(jnp.bfloat16)).dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="alpha"):
        cell.app.step(dict(cfg, alpha=0.25), x)


def test_seeds_move_the_phases_and_keep_the_work(cell):
    a, b = (cell.app.init_state(cell.config, harness.prng_key(s))
            for s in (5, 2**32 + 5))
    assert a.shape == b.shape
    assert not np.allclose(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="divide"):
        cell.app.init_state(dict(cell.config, modes=[48]),
                            harness.prng_key(5))


def _skip_a_launch(system, x):
    """The timed entry's answer with its last launch left out."""
    p = system.plan
    if p.steps == p.m:
        return x
    return system.runner.run_blocked(x, system.regs, steps=p.steps - p.m,
                                     m=p.m, block_h=p.block_h,
                                     double_buffer=p.double_buffer)


@pytest.mark.parametrize("answer", ["program", "bfloat16 control",
                                    "one launch left out"])
def test_only_the_programs_answer_meets_the_limit(cell, answer):
    system = harness.build(cell)
    x = system.call(system.init(7))
    steps = system.plan.steps
    if answer == "program":
        gap = harness.max_gap(cell, steps, x, got=system.call(x))
    elif answer == "bfloat16 control":
        low = jnp.dtype(harness.CONTROL_DTYPE[cell.config["dtype"]])
        gap = harness.max_gap(cell, steps, x, dtype=low)
    else:
        gap = harness.max_gap(cell, steps, x, got=_skip_a_launch(system, x))
    correct = harness.passed(harness.check(cell, gap, 0))
    assert correct is (answer == "program"), gap


# ---- kernel_gflops, by hand ---------------------------------------------

KERNEL = ("%spd_Diff2D.3 = f32[1,16384,16384]{2,1,0:T(8,128)} custom-call("
          "f32[1]{0:T(128)S(1)} %scal, f32[1,16384,16384]{2,1,0:T(8,128)}"
          " %spd_Diff2D.2), custom_call_target=\"tpu_custom_call\"")
RUN = "jit_spd_run_blocked(11073413488715052313)"


def _trace(*names):
    """One call of 128 steps whose kernel ran 1,000,000 ns a launch."""
    ops = [{"chip": 0, "name": n, "module": RUN,
            "start_ns": 1050 + 1_000_000 * i, "dur_ns": 1_000_000,
            "category": "", "long_name": "", "tf_op": "",
            "kind": tracefile.op_kind(n, "", "", RUN, "")}
           for i, n in enumerate(names)]
    return {"steps_per_call": 128,
            "spans": [{"name": "bench.call", "start_ns": 1000,
                       "dur_ns": 100},
                      {"name": "bench.readback", "start_ns": 1100,
                       "dur_ns": 16_000_000}],
            "ops": ops}


def _read(trace, chips=1):
    rec = {"trace": trace, "chips": chips, "root": str(REPO),
           "device_kind": "TPU v5 lite"}
    return harness.load_module(BENCH / "metrics" / "kernel_gflops.py",
                               "gflops_test").read(rec)


@pytest.fixture
def counters(monkeypatch):
    """The program's counters, set by the test."""
    from repro.core import tracing

    values = {}
    monkeypatch.setattr(tracing, "snapshot", lambda: dict(values))
    return values


def test_kernel_gflops_by_hand(counters):
    # Two calls of the jacobi cell's plan (16 launches of 8 steps each,
    # 128 blocks of 144-row stripes, 7 operations a site); the window
    # ran one call, its 16 launches 1 ms each.
    per_launch = 7 * 128 * 144 * 16384 * 8
    counters.update(launches=32, steps=256, dma_bytes=1,
                    kernel_flops=32 * per_launch)
    trace = _trace(*[KERNEL] * 16)
    assert _read(trace) == pytest.approx(per_launch / 1e-3 / 1e9)
    # On four chips the operations and the kernel time are both per chip.
    assert _read(trace, chips=4) == pytest.approx(per_launch / 1e-3 / 1e9)


def test_kernel_gflops_reads_nothing_without_its_inputs(counters,
                                                        monkeypatch):
    counters.update(launches=32, steps=256, kernel_flops=10**12)
    assert _read(None) is None
    # A CPU trace: no device op, so no kernel time.
    assert _read(_trace()) is None
    counters.update(steps=0, kernel_flops=0)
    assert _read(_trace(KERNEL)) is None
    # A program that keeps launches and bytes but not this counter.
    counters.clear()
    counters.update(launches=32, steps=256, dma_bytes=1)
    assert _read(_trace(KERNEL)) is None
    # A program with no counters module: the import fails.
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    assert _read(_trace(KERNEL)) is None
