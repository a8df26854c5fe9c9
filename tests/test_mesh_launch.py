"""The sharded launch loop (``ShardedStreamKernel._local_run``): each
launch gathers its stripes from the shard and the exchanged halo pieces
in place, and the launches ping-pong between two buffers the kernel
writes (DESIGN.md §8, §15, docs/pipeline.md §distribute).

Over the meshes (1, 4), (2, 2), (2, 4), (2, 1) and (4, 1), one to five
launches, both buffer protocols, diffusion and lattice Boltzmann with
walls and a moving lid, the mesh run is bitwise equal to the one-chip
``run_blocked``, leaves the caller's input as it was, and gives the
same result when called again. A shard of two row blocks and halo rows
carried as 4-row tiles (``m·halo`` = 2) take every path of the launch:
periodic rows on a one-row-shard mesh, exchanged rows and their corners
otherwise, and zero rows between the exchanged ones and the tile. The
(4, 1) shard is one row block, which reads both exchanged row pieces.

The meshes need eight devices, so the runs are made once, in a child
process per mesh with eight virtual CPU devices (all side by side), and
each case reads its own line of the children's results.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
MESHES = ((1, 4), (2, 2), (2, 4), (2, 1), (4, 1))
LAUNCHES = (1, 2, 3, 4, 5)
APPS = ("diffusion", "lbm_walls")
M, BLOCK_H = 2, 4

CHILD = textwrap.dedent(f"""
    import json
    import sys

    import numpy as np

    from repro.apps import diffusion as dif
    from repro.apps import lbm

    dsim = dif.DiffusionSimulation(16, 64, alpha=0.2)
    lsim = lbm.LBMSimulation(lbm.LBMProblem(16, 64, mode="wrap"))
    f, attr = lbm.couette_init(16, 64)
    apps = {{
        "diffusion": (dsim.kernel, dsim.state(dif.sine_init(16, 64)[0]),
                      (0.2,)),
        "lbm_walls": (lsim.stream_kernel(), lsim.stream_state(f, attr),
                      (1 / 0.9, 0.07, 1.0)),
    }}
    dy, dx = map(int, sys.argv[1:3])
    out = {{}}
    for app, (kern, state, regs) in apps.items():
        before = np.asarray(state).copy()
        sk = kern.sharded(dy * dx, dx=dx)
        for launches in {LAUNCHES}:
            for db in (True, False):
                plan = dict(steps={M} * launches, m={M}, block_h={BLOCK_H},
                            double_buffer=db)
                single = np.asarray(kern.run_blocked(state, regs, **plan))
                first = np.asarray(sk.run_blocked(state, regs, **plan))
                again = np.asarray(sk.run_blocked(state, regs, **plan))
                out[f"{{app}}-{{dy}}x{{dx}}-{{launches}}-{{db}}"] = {{
                    "equal": bool(np.array_equal(first, single)),
                    "input": bool(np.array_equal(np.asarray(state), before)),
                    "again": bool(np.array_equal(again, first)),
                }}
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def results(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.pathsep.join(
                   [str(REPO / "src"), os.environ.get("PYTHONPATH", "")]))
    cwd = tmp_path_factory.mktemp("mesh_launch")
    procs = [subprocess.Popen([sys.executable, "-c", CHILD, str(dy), str(dx)],
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                              text=True, env=env, cwd=cwd)
             for dy, dx in MESHES]
    out = {}
    for proc in procs:
        stdout, stderr = proc.communicate(timeout=900)
        assert proc.returncode == 0, stderr[-3000:]
        out.update(json.loads(stdout.splitlines()[-1]))
    return out


@pytest.mark.parametrize("db", [True, False], ids=["db", "single"])
@pytest.mark.parametrize("launches", LAUNCHES)
@pytest.mark.parametrize("dy,dx", MESHES, ids=[f"{a}x{b}" for a, b in MESHES])
@pytest.mark.parametrize("app", APPS)
def test_mesh_launch_matches_one_chip(results, app, dy, dx, launches, db):
    got = results[f"{app}-{dy}x{dx}-{launches}-{db}"]
    assert got == {"equal": True, "input": True, "again": True}
