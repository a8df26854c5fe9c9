"""The chip benchmark's files: the manifest, cells added as files alone,
the peaks table, byte counts, the reference, and the command's refusals
off the chip."""

from __future__ import annotations

import hashlib
import json
import math
import os
import re
import shutil
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from _chip_bench_util import BENCH, REPO, TINY, add_cell, tiny_config, \
    tiny_root

import harness  # noqa: E402  (benchmarks/chip, put on sys.path above)

MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_manifest_names_files_that_exist():
    m = MANIFEST
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["command"] == ["python3", "benchmarks/chip/run.py"]
    assert all((REPO / p).is_dir() for p in m["paths"])
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in m[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in m["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert (BENCH / "apps" / f"{cfg['app']}.py").is_file()
    for w in m["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for x in m["end_to_end"] + m["per_layer"]:
        assert (BENCH / "metrics" / f"{x['name']}.py").is_file()
        assert x["better"] in ("lower", "higher")
    assert {x["name"] for x in m["end_to_end"]} == {"mlups", "setup_s"}
    assert all(0.01 <= x["bound"] <= 0.25 for x in m["end_to_end"])
    # A full regression check of 24 cells, 14 runs each plus two, with
    # a minute of overhead per run and three per cell for compiles, fits
    # in twelve hours.
    runs = 2 + 14 * 24
    assert runs * (m["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def _digests(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and p.name != "BENCHMARK.json"}


def test_a_new_cell_runs_from_new_files_alone(tmp_path):
    """A configuration, a traffic mix and a metric dropped into place,
    plus their manifest entries, make a cell that runs; no file that was
    there changes."""
    root = tmp_path
    shutil.copytree(BENCH, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    before = _digests(root)
    bench = root / "benchmarks" / "chip"
    (bench / "traffic" / "chunk4.json").write_text(
        json.dumps({"steps_per_call": 4, "readback": "mass"}))
    (bench / "metrics" / "calls_per_s.py").write_text(
        "def read(rec):\n    return rec['calls'] / rec['window_s']\n")
    cfg = tiny_config(1)
    cfg["name"] = "fresh"
    add_cell(root, "fresh.chunk4", cfg, "chunk4")
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["end_to_end"].append({
        "name": "calls_per_s", "unit": "1/s", "better": "higher",
        "bound": 0.05, "source": "host_clock",
        "workloads": ["fresh.chunk4"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    res = harness.run_cell(root, "fresh.chunk4", 3, 0.1, False,
                           log=lambda _: None)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"mlups", "setup_s", "calls_per_s"}
    after = _digests(root)
    assert all(after[p] == d for p, d in before.items())


def test_peaks_know_the_v5e_and_refuse_an_unknown_kind():
    assert harness.peak(REPO, "TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError, match="not in peaks.json"):
        harness.peak(REPO, "TPU v9 imaginary", "hbm_bytes_per_s")


def test_least_launch_bytes_by_hand():
    kb = harness.load_module(BENCH / "metrics" / "kernel_hbm_share.py",
                             "kb_test")
    # One chip, 8192^2, 10 f32 words: read once, written once.
    assert kb.least_launch_bytes(10, 8192 * 8192, 4) == 5_368_709_120
    # Four chips, 16384^2: each chip's shard is 16384^2 / 4 sites.
    assert kb.least_launch_bytes(10, 16384 * 16384 / 4, 4) == \
        5_368_709_120


def test_reference_copy_agrees_with_the_programs_reference(tmp_path):
    """The benchmark's own reference step (which imports nothing of the
    program) against ``repro.apps.lbm.ref_step`` on a seeded state."""
    from repro.apps import lbm

    root = tiny_root(tmp_path)
    cell = harness.load_cell(root, TINY, trace=False)
    cfg = cell.config
    x = cell.app.init_state(cfg, harness.prng_key(21))
    assert set(np.unique(np.asarray(x[9]))) == {0.0, 1.0, 2.0}
    ours = cell.app.step(cfg, x)
    theirs = lbm.ref_step(x[:9], x[9], 1.0 / cfg["tau"], cfg["u_lid"])
    np.testing.assert_allclose(np.asarray(ours[:9]), np.asarray(theirs),
                               rtol=0, atol=1e-6)
    assert (ours[9] == x[9]).all()
    low = cell.app.step(cfg, x.astype(jnp.bfloat16))
    assert low.dtype == jnp.bfloat16
    mass = [np.asarray(s[:9], np.float64).sum() for s in (x, ours)]
    assert math.isclose(mass[0], mass[1], rel_tol=1e-6)


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", *args], cwd=cwd,
        capture_output=True, text=True, timeout=300, env=env)


ARGS = ("--workload", "ulbm8192.chunk64", "--seed", "3", "--seconds", "1",
        "--trace", "0")


def test_command_fails_without_a_tpu():
    proc = _cli(REPO, *ARGS)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr and "'cpu'" in proc.stderr


def test_command_fails_with_only_the_benchmarks_files(tmp_path):
    for p in MANIFEST["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = _cli(tmp_path, *ARGS)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
