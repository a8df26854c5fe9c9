"""The reduction from a trace to the per-layer metrics, by hand.

The trace is written out in the reduced form ``tracefile.load`` keeps:
two calls of 64 steps on one chip, with kernel launches, a collective,
launch glue, one op of the harness's readback, an op cut by the
window's end, and idle gaps (times in ns).
"""

from __future__ import annotations

import pytest

from _chip_bench_util import BENCH, REPO

import harness  # noqa: E402  (benchmarks/chip, put on sys.path above)
import tracefile  # noqa: E402


def _op(name, start, dur, category="", module="jit_run", tf_op=""):
    return {"chip": 0, "name": name, "module": module, "start_ns": start,
            "dur_ns": dur, "category": category, "long_name": "",
            "tf_op": tf_op, "kind": tracefile.op_kind(
                name, category, "", module, tf_op)}


TRACE = {
    "steps_per_call": 64,
    "spans": [
        {"name": "bench.call", "start_ns": 1000, "dur_ns": 100},
        {"name": "bench.readback", "start_ns": 1100, "dur_ns": 9000},
        {"name": "bench.call", "start_ns": 10100, "dur_ns": 100},
        {"name": "bench.readback", "start_ns": 10200, "dur_ns": 9800},
    ],
    "ops": [
        _op("closed_call.9", 1050, 4000, category="custom-call"),
        _op("collective-permute-done.1", 5050, 500),
        _op("fusion.3", 5550, 500, category="loop fusion"),
        _op("reduce.1", 6050, 100, module="jit_bench_readback"),
        _op("_unknown_.3", 10150, 8000, tf_op="jit(f)/while/pallas_call"),
        _op("copy.2", 19500, 1000),
    ],
}


def _read(name, **rec):
    base = {"trace": TRACE, "chips": 1, "words": 10, "sites": 1024,
            "itemsize": 4, "plan": {"m": 4, "model_step_s": 1e-7},
            "root": str(REPO), "device_kind": "TPU v5 lite"}
    base.update(rec)
    return harness.load_module(BENCH / "metrics" / f"{name}.py",
                               f"trace_test_{name}").read(base)


def test_kinds():
    kinds = [op["kind"] for op in TRACE["ops"]]
    assert kinds == ["kernel", "collective", "other", "harness", "kernel",
                     "other"]


def test_window_steps_busy_and_gaps():
    assert tracefile.window(TRACE) == (1000, 20000)
    assert tracefile.steps(TRACE) == 128
    # Busy: [1050, 6150) + [10150, 18150) + [19500, 20000) = 13,600 ns.
    assert tracefile.busy_s(TRACE, 1) == pytest.approx(13600e-9)
    assert tracefile.idle_gaps(TRACE) == [
        ("bench.readback", 0, pytest.approx(4000e-9)),
        ("bench.readback", 0, pytest.approx(1350e-9)),
        ("bench.call", 0, pytest.approx(50e-9)),
    ]
    top = tracefile.breakdown(TRACE, 1)
    assert top["device_ops"][0] == ["kernel _unknown_.3",
                                    pytest.approx(8000e-9)]
    assert top["idle_gaps"][0][0] == "bench.readback (chip 0)"


def test_per_layer_metrics_by_hand():
    # Kernel: 4,000 + 8,000 ns over 128 steps.
    assert _read("kernel_ms_per_step") == pytest.approx(12000e-6 / 128)
    # Glue: fusion.3 and the 500 ns of copy.2 inside the window; the
    # readback op and the collective are not glue.
    assert _read("nonkernel_ms_per_step") == pytest.approx(1000e-6 / 128)
    assert _read("device_idle_share") == pytest.approx(
        100 * 5400 / 19000)
    # 32 launches of 10 words x 1024 sites x 4 B, read and written once,
    # at 819 GB/s, over 12,000 ns of kernel time.
    assert _read("kernel_hbm_share") == pytest.approx(
        100 * 32 * 81920 / 819e9 / 12000e-9)
    assert _read("model_step_ratio") == pytest.approx(1e-7 / (19000e-9 / 128))
    # The collective-permute: 500 ns over 128 steps.
    assert _read("collective_ms_per_step") == pytest.approx(500e-6 / 128)


def test_no_device_ops_reads_nothing():
    empty = dict(TRACE, ops=[])
    for name in ("kernel_ms_per_step", "kernel_hbm_share",
                 "nonkernel_ms_per_step", "device_idle_share",
                 "collective_ms_per_step"):
        assert _read(name, trace=empty) is None
    assert _read("kernel_ms_per_step", trace=None) is None


def test_enclosing_op_is_not_counted_twice():
    """A ``while`` around the launches spans them on the same line; it
    is marked ``outer`` and only its inner ops are counted by kind."""
    ops = [_op("while.4", 1000, 9000), _op("closed_call.9", 1100, 4000,
                                           category="custom-call"),
           _op("fusion.3", 5200, 500, category="loop fusion"),
           _op("closed_call.9", 6000, 3500, category="custom-call"),
           _op("copy.1", 10500, 200)]
    tracefile.mark_outer(ops)
    assert [op["kind"] for op in ops] == ["outer", "kernel", "other",
                                          "kernel", "other"]
    trace = dict(TRACE, ops=ops)
    assert tracefile.device_s(trace, "kernel", 1) == pytest.approx(7500e-9)
    assert tracefile.device_s(trace, "other", 1) == pytest.approx(700e-9)
    # Busy is the union of all ops, the enclosing one included.
    assert tracefile.busy_s(trace, 1) == pytest.approx(9200e-9)
    assert all(not name.startswith("outer")
               for name, _ in tracefile.breakdown(trace, 1)["device_ops"])


def test_device_ops_without_a_kernel_are_an_error():
    ops = [_op("fusion.3", 100, 50, category="loop fusion")]
    with pytest.raises(ValueError, match="no kernel op"):
        tracefile.check_kernel_found(ops)
    tracefile.check_kernel_found([])
    tracefile.check_kernel_found(TRACE["ops"])
