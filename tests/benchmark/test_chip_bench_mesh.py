"""The four-chip cell's path on four virtual CPU devices.

One child process (the device count is fixed when JAX starts) runs a
tiny mesh cell twice through the harness: as it is, and with the halo
exchange left out (``lax.ppermute`` returning the shard's own rows), a
fault that must turn ``correct`` false.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]

CHILD = textwrap.dedent("""
    import json, sys
    sys.path.insert(0, sys.argv[2])
    from _chip_bench_util import TINY_MESH, tiny_root
    import harness
    import jax

    root = tiny_root(sys.argv[1])
    res = harness.run_cell(root, TINY_MESH, 11, 0.2, False, log=print)
    print(json.dumps({"sound": res}))
    jax.lax.ppermute = lambda x, axis_name, perm: x
    res = harness.run_cell(root, TINY_MESH, 11, 0.2, False, log=print)
    print(json.dumps({"no_exchange": res}))
""")


def test_mesh_cell_on_four_virtual_devices(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(REPO / "src"), os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run(
        [sys.executable, "-c", CHILD, str(tmp_path), str(HERE)],
        capture_output=True, text=True, timeout=600, env=env, cwd=tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines()]
    plan = lines[0]["plan"]
    assert plan["dy"] * plan["dx"] == 4
    sound = next(x["sound"] for x in lines if "sound" in x)
    assert sound["correct"] is True, sound["check"]
    assert sound["device"]["count"] == 4
    broken = next(x["no_exchange"] for x in lines if "no_exchange" in x)
    assert broken["correct"] is False
