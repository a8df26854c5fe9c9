"""``chip_smoke.py``'s phases, run tiny on the CPU in interpret mode.

The script itself needs a TPU; these tests run the same phase functions
at grid sizes the interpreter handles in seconds, and check the
script's contract off the chip: it fails, and prints no result line,
without a TPU or outside a checkout.
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_phase_lbm_small():
    rec = chip_smoke.phase_lbm(32, block_rows=16, m=2, launches=2)
    assert rec["plan"] == {"block_h": 16, "m": 2, "steps": 4,
                           "double_buffer": True}
    assert rec["max_abs_err"] <= chip_smoke.TOL


def test_phase_diffusion_small():
    rec = chip_smoke.phase_diffusion(32, block_rows=16, m=2, launches=2)
    assert rec["plan"]["steps"] == 4
    assert rec["max_abs_err"] <= chip_smoke.TOL


def test_phase_program_small():
    rec = chip_smoke.phase_program(32, block_rows=16, m=2, launches=1)
    assert set(rec["partitions"]) == {"3", "1+1+1"}
    assert all(p["plan"]["steps"] == 2 for p in rec["partitions"].values())


def test_phase_search_small():
    rec = chip_smoke.phase_search(32, budget=2)
    assert 1 <= len(rec["executed"]) and rec["budget_spent"] <= 2


def test_phase_serve_small(tmp_path):
    rec = chip_smoke.phase_serve(32, requests=2, steps=4, budget=1,
                                 study_dir=tmp_path / "studies")
    assert rec["requests"] == 4 and rec["live_timings"] >= 1


def _run(args, cwd, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, capture_output=True, text=True,
        timeout=600, env=full,
    )


def test_phase_mesh_small_on_four_host_devices():
    out = _run(["-c", textwrap.dedent("""
        import sys
        sys.path.insert(0, "src")
        import chip_smoke
        rec = chip_smoke.phase_mesh(64, block_rows=16, m=2, launches=2)
        assert set(rec["meshes"]) == {"4x1", "2x2"}, rec
        for mesh in rec["meshes"].values():
            assert len(set(mesh["devices"])) == 4, rec
        print("mesh OK", rec["max_abs_err"])
    """)], REPO, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    assert out.returncode == 0, out.stderr
    assert "mesh OK" in out.stdout


def test_script_fails_without_a_tpu():
    out = _run(["chip_smoke.py"], REPO)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr


def test_script_fails_outside_a_checkout(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = _run(["chip_smoke.py"], tmp_path, PYTHONPATH="")
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("chips", ["2", "8"])
def test_script_rejects_other_chip_counts(chips):
    out = _run(["chip_smoke.py", "--chips", chips], REPO)
    assert out.returncode == 2 and not out.stdout
