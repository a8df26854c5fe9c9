"""``aliased_launch_share``, by hand: the program's launches that wrote
into a recycled buffer, over all its launches, read where the trace
shows the kernel on the device and nowhere else."""

from __future__ import annotations

import sys

import pytest

from _chip_bench_util import BENCH, REPO

import harness  # noqa: E402  (benchmarks/chip, put on sys.path above)
import tracefile  # noqa: E402

KERNEL = ("%spd_PEx1.3 = f32[10,8192,8192]{2,1,0:T(8,128)} custom-call("
          "f32[3]{0:T(128)S(1)} %scal, f32[10,8192,8192]{2,1,0:T(8,128)}"
          " %spd_PEx1.2), custom_call_target=\"tpu_custom_call\"")
RUN = "jit_spd_run_blocked(11073413488715052313)"


def _trace(*names):
    ops = [{"chip": 0, "name": n, "module": RUN, "start_ns": 1050 + 100 * i,
            "dur_ns": 50, "category": "", "long_name": "", "tf_op": "",
            "kind": tracefile.op_kind(n, "", "", RUN, "")}
           for i, n in enumerate(names)]
    return {"steps_per_call": 64,
            "spans": [{"name": "bench.call", "start_ns": 1000,
                       "dur_ns": 100},
                      {"name": "bench.readback", "start_ns": 1100,
                       "dur_ns": 900}],
            "ops": ops}


TRACE = _trace(KERNEL)


def _read(trace=TRACE):
    rec = {"trace": trace, "chips": 1, "root": str(REPO),
           "device_kind": "TPU v5 lite"}
    return harness.load_module(BENCH / "metrics" / "aliased_launch_share.py",
                               "alias_test").read(rec)


@pytest.fixture
def counters(monkeypatch):
    """The program's counters, set by the test."""
    from repro.core import tracing

    values = {}
    monkeypatch.setattr(tracing, "snapshot", lambda: dict(values))
    return values


def test_aliased_launch_share_by_hand(counters):
    # Three calls of the cell's plan: 16 launches each, 14 of them into
    # a recycled buffer.
    counters.update(launches=48, steps=192, dma_bytes=1,
                    aliased_launches=42)
    assert _read() == pytest.approx(87.5)
    counters.update(aliased_launches=0)
    assert _read() == 0.0


def test_aliased_launch_share_reads_nothing_without_its_inputs(counters,
                                                               monkeypatch):
    counters.update(launches=48, steps=192, aliased_launches=42)
    assert _read(None) is None
    # A CPU trace: no device op, so no kernel on the device.
    assert _read(_trace()) is None
    # A device trace without the kernel.
    assert _read(_trace("%copy.1 = f32[3]{0} copy(%x)")) is None
    counters.update(launches=0, aliased_launches=0)
    assert _read() is None
    # A program that keeps launches but not this counter.
    counters.clear()
    counters.update(launches=48, steps=192, dma_bytes=1)
    assert _read() is None
    # A program with no counters module: the import fails.
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    assert _read() is None
