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

A mesh that shards columns carries its column guards from launch to
launch in a compact layout: each kernel writes its own first and last
``m·halo_x`` columns beside its output, the next launch's neighbours
read them, and the call's first launch takes them from its input. The
edge columns a kernel writes equal its output's, and every launch of a
call but the first is counted in ``kernel_edge_launches``.

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
    from repro.core import tracing

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
                got, edge = [], []
                for run in (kern, sk):
                    was = tracing.snapshot()["kernel_edge_launches"]
                    got.append(np.asarray(run.run_blocked(state, regs,
                                                          **plan)))
                    edge.append(tracing.snapshot()["kernel_edge_launches"]
                                - was)
                single, first = got
                again = np.asarray(sk.run_blocked(state, regs, **plan))
                out[f"{{app}}-{{dy}}x{{dx}}-{{launches}}-{{db}}"] = {{
                    "equal": bool(np.array_equal(first, single)),
                    "input": bool(np.array_equal(np.asarray(state), before)),
                    "again": bool(np.array_equal(again, first)),
                    "edge_launches": edge,
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
    got = dict(results[f"{app}-{dy}x{dx}-{launches}-{db}"])
    got.pop("edge_launches")
    assert got == {"equal": True, "input": True, "again": True}


@pytest.mark.parametrize("launches", LAUNCHES)
@pytest.mark.parametrize("dy,dx", MESHES, ids=[f"{a}x{b}" for a, b in MESHES])
def test_mesh_counts_kernel_edge_launches(results, dy, dx, launches):
    """Every launch of a call after its first takes its column guards
    from the kernel before it on a mesh that shards columns; no launch
    does on one that does not, nor on one chip."""
    for app in APPS:
        for db in (True, False):
            single, mesh = results[f"{app}-{dy}x{dx}-{launches}-{db}"][
                "edge_launches"]
            assert single == 0
            assert mesh == (launches - 1 if dx > 1 else 0)


#: (app, block_h, m): one and ten words a site; halo rows a whole
#: neighbour block (block 4), one row tile as tall as the block (8) and
#: one tile of a taller block (16).
EDGE_CASES = [("diffusion", 4, 2), ("diffusion", 8, 1), ("diffusion", 16, 3),
              ("lbm", 4, 2), ("lbm", 8, 3), ("lbm", 16, 1)]


@pytest.mark.parametrize("double_buffer", [True, False],
                         ids=["db", "single"])
@pytest.mark.parametrize("app,block_h,m", EDGE_CASES)
def test_kernel_writes_its_edge_columns(app, block_h, m, double_buffer):
    """A launch with column guards returns, beside its output, its own
    first and last ``m·halo_x`` output columns in the compact layout
    the next launch's neighbours read: the layout ``edge_columns`` gives
    of the output itself."""
    import jax
    import numpy as np

    from repro.apps import diffusion as dif
    from repro.apps import lbm
    from repro.kernels.spd_stream import edge_columns, spd_multistep

    h, w = 32, 48
    if app == "diffusion":
        kern = dif.DiffusionSimulation(h, w, alpha=0.2).kernel
        regs = (0.2,)
    else:
        kern = lbm.LBMSimulation(lbm.LBMProblem(h, w)).stream_kernel()
        regs = (1 / 0.9, 0.07, 1.0)
    words = len(kern._ports)
    state = jax.random.uniform(jax.random.key(block_h * 10 + m),
                               (words, h, w))
    mhx = m * kern.halo_x
    guards = edge_columns(state[..., ::-1], mhx, block_h)
    out, cols = spd_multistep(
        kern._step_fn, state, kern._scal(regs), m=m, block_h=block_h,
        halo=kern.halo, guards=guards, double_buffer=double_buffer,
        interpret=True)
    assert cols[0].shape == (h // block_h, words, mhx, 128)
    for got, want in zip(cols, edge_columns(out, mhx, block_h)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
