"""The chip benchmark's harness, driven on the CPU at 64x64.

Each test skips only the harness's look for a chip (``run.py`` does
that; ``harness.run_cell`` is the rest of a run). The faults are
planted underneath the timed entry, in the program's
``StreamKernel.run_blocked``, and each must turn ``correct`` false.
"""

from __future__ import annotations

import json

import jax
import pytest

from _chip_bench_util import TINY, tiny_root

import control  # noqa: E402  (benchmarks/chip, put on sys.path above)
import harness  # noqa: E402
from repro.core.codegen import StreamKernel

SECONDS = 0.2


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


def _run(root, trace=False, seed=2**31 + 99):
    lines = []
    res = harness.run_cell(root, TINY, seed, SECONDS, trace,
                           log=lines.append)
    return res, json.loads(lines[0])


def test_run_reports_the_cells_end_to_end_metrics(root, capsys):
    res, head = _run(root)
    assert head["plan"]["steps"] == 8 and head["plan"]["m"] >= 1
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {"mlups", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["metrics"]["mlups"]["unit"] == "Msite/s"
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "check"
    assert res["check"]["max_abs_err"]["value"] <= \
        res["check"]["max_abs_err"]["limit"]
    harness.report(res)
    out, err = capsys.readouterr()
    assert json.loads(out.splitlines()[-1])["correct"] is True
    assert err.splitlines()[-2].startswith("check max_abs_err ")
    assert err.splitlines()[-1] == "check nonfinite_readbacks 0 limit 0"


def test_traced_run_reports_per_layer_metrics_only(root):
    res, _ = _run(root, trace=True)
    assert res["correct"] is True
    # The CPU trace has no TPU plane: every device reading is absent,
    # none is reported as 0.
    assert set(res["metrics"]) == {"model_step_ratio"}
    assert res["device"]["window_s"] > 0
    assert res["device"]["busy_s"] == 0.0
    assert res["breakdown"] == {"device_ops": [], "idle_gaps": []}


def test_control_fails_the_limit_that_the_program_meets(root):
    out = control.readings(root, TINY, [5, 6, 7], {5, 6, 7}, calls=2,
                           log=lambda _: None)
    limit = json.loads(
        (root / "benchmarks/chip/configs/tiny-1.json").read_text()
    )["limits"]["max_abs_err"]
    assert out["control_dtype"] == "bfloat16"
    assert out["limit"] == limit
    assert out["lower"] <= limit < out["upper"]
    # Judged by the harness's own check, as a run is.
    assert out["program_correct"] is True
    assert out["control_correct"] is False
    assert all(r["program_correct"] is True for r in out["rows"])
    assert all(r["control_correct"] is False for r in out["rows"])


def _unchanged(run_blocked):
    def run(self, state, regs=(), **kw):
        return state
    return run


def _half_left_out(run_blocked):
    def run(self, state, regs=(), **kw):
        out = run_blocked(self, state, regs, **kw)
        h = out.shape[-2]
        return out.at[:, h // 2:].set(state[:, h // 2:])
    return run


def _answer_altered(run_blocked):
    def run(self, state, regs=(), **kw):
        out = run_blocked(self, state, regs, **kw)
        return out.at[3, out.shape[-2] // 2, 5].add(1e-3)
    return run


@pytest.mark.parametrize("fault", [_unchanged, _half_left_out,
                                   _answer_altered])
def test_fault_in_the_timed_path_is_not_correct(root, monkeypatch, fault):
    monkeypatch.setattr(StreamKernel, "run_blocked",
                        fault(StreamKernel.run_blocked))
    res, _ = _run(root)
    assert res["correct"] is False
    c = res["check"]["max_abs_err"]
    assert not c["value"] <= c["limit"]


def test_seed_keeps_all_its_bits():
    a, b = harness.prng_key(1), harness.prng_key(2**32 + 1)
    assert not (jax.random.key_data(a) == jax.random.key_data(b)).all()
    with pytest.raises(ValueError):
        harness.prng_key(-1)
