"""``kernel_dma_share`` and the program's kernel name, by hand.

The trace is written out in the reduced form ``tracefile.load`` keeps,
as the chip shows it since the program names its kernel: the op's name
is its HLO text (``%spd_<core>.N = … custom-call(…)``), its module is
read off the ``XLA Modules`` line, and its category, long name and
``tf_op`` are empty (they live in the event's metadata, which the
reduction does not read). Two calls of 64 steps on one chip.
"""

from __future__ import annotations

import sys

import pytest

from _chip_bench_util import BENCH, REPO

import harness  # noqa: E402  (benchmarks/chip, put on sys.path above)
import tracefile  # noqa: E402

from repro.core.legalize import launch_dma_bytes

KERNEL = ("%spd_PEx1.3 = f32[10,8192,8192]{2,1,0:T(8,128)} custom-call("
          "f32[3]{0:T(128)S(1)} %copy-done, f32[10,8192,8192]{2,1,0:T(8,128)}"
          " %copy.11), custom_call_target=\"tpu_custom_call\"")
COPY = ("%copy.11 = f32[10,8192,8192]{2,1,0:T(8,128)} copy(f32[10,8192,8192]"
        "{2,1,0:T(8,128)} %get-tuple-element.36)")
RUN = "jit_spd_run_blocked(11073413488715052313)"


def _op(name, start, dur, module=RUN):
    return {"chip": 0, "name": name, "module": module, "start_ns": start,
            "dur_ns": dur, "category": "", "long_name": "", "tf_op": "",
            "kind": tracefile.op_kind(name, "", "", module, "")}


def _trace(kernel=KERNEL):
    ops = [
        _op("%while = (s32[], f32[10,8192,8192]) while(...)", 1010, 18000),
        _op(kernel, 1050, 4000),
        _op("%slice_reduce_fusion = f32[8192]{0:T(1024)} fusion(...)", 6050,
            100, module="jit_bench_readback(12681706231178860984)"),
        _op(COPY, 10150, 2000),
        _op(kernel, 12150, 6000),
    ]
    tracefile.mark_outer(ops)
    return {
        "steps_per_call": 64,
        "spans": [
            {"name": "bench.call", "start_ns": 1000, "dur_ns": 100},
            {"name": "bench.readback", "start_ns": 1100, "dur_ns": 9000},
            {"name": "bench.call", "start_ns": 10100, "dur_ns": 100},
            {"name": "bench.readback", "start_ns": 10200, "dur_ns": 9800},
        ],
        "ops": ops,
    }


TRACE = _trace()


def _read(name, trace=TRACE, **rec):
    base = {"trace": trace, "chips": 1, "words": 10, "sites": 8192 * 8192,
            "itemsize": 4, "plan": {"m": 4, "model_step_s": 1e-7},
            "root": str(REPO), "device_kind": "TPU v5 lite"}
    base.update(rec)
    return harness.load_module(BENCH / "metrics" / f"{name}.py",
                               f"dma_test_{name}").read(base)


@pytest.fixture
def counters(monkeypatch):
    """The program's counters, set by the test."""
    from repro.core import tracing

    values = {}
    monkeypatch.setattr(tracing, "snapshot", lambda: dict(values))
    return values


def test_kernel_dma_share_by_hand(counters):
    # Three calls at 1 MB per step; the window ran two (128 steps) and
    # the kernel ran 10,000 ns of it.
    counters.update(launches=48, steps=192, dma_bytes=192_000_000,
                    jit_traces=5, jit_s=2.0)
    share = 100 * 128 * 1_000_000 / 819e9 / 10000e-9
    assert _read("kernel_dma_share") == pytest.approx(share)
    # On four chips the bytes and the kernel time are both per chip.
    assert _read("kernel_dma_share", chips=4) == pytest.approx(share)


def test_dma_share_is_the_hbm_share_times_the_stripe_rows(counters):
    """At 8192², plan (32, 4) and halo 1, each 32-row block reads a
    48-row stripe and writes 32 rows: 2.5 rows moved per row against
    the least 2, so the DMA share is 1.25 times the HBM share."""
    per_launch = launch_dma_bytes(8192, 8192, 10, block_h=32, m=4, halo=1,
                                  itemsize=4)
    counters.update(launches=16, steps=64, dma_bytes=16 * per_launch)
    assert _read("kernel_dma_share") == pytest.approx(
        1.25 * _read("kernel_hbm_share"))


def test_kernel_dma_share_reads_nothing_without_its_inputs(counters,
                                                           monkeypatch):
    assert _read("kernel_dma_share", None) is None
    counters.update(launches=0, steps=0, dma_bytes=0)
    assert _read("kernel_dma_share") is None
    # A program with no counters module: the import fails.
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    assert _read("kernel_dma_share") is None


def test_the_named_kernel_leaves_every_accepted_reading_as_it_was():
    old = _trace(KERNEL.replace("%spd_PEx1.3", "%_unknown_.3"))
    tracefile.check_kernel_found(TRACE["ops"])
    assert [op["kind"] for op in TRACE["ops"]] == \
        ["outer", "kernel", "harness", "other", "kernel"]
    for name in ("kernel_ms_per_step", "kernel_hbm_share",
                 "nonkernel_ms_per_step", "device_idle_share",
                 "model_step_ratio"):
        assert _read(name) == pytest.approx(_read(name, old)), name
    ours, theirs = (tracefile.breakdown(t, 1) for t in (TRACE, old))
    assert ours["idle_gaps"] == theirs["idle_gaps"]
    assert [v for _, v in ours["device_ops"]] == \
        [v for _, v in theirs["device_ops"]]
    assert ours["device_ops"][0][0].startswith("kernel %spd_PEx1.3 ")
