"""The launch path's names and counters (``repro.core.tracing``).

On the CPU, with the Pallas kernels in interpret mode: one
``run_blocked`` call adds its plan's launches, steps, the DMA bytes
of :func:`repro.core.legalize.launch_dma_bytes` and the float
operations of :func:`repro.core.legalize.launch_flops` (each checked
here against the formula written out by hand) and the launches that
wrote into a recycled buffer, a first call traces and a second
does not, the jitted entries compile as ``jit_spd_…`` modules with the
launch under ``spd.launch``, and the dispatch is a ``spd.run`` host
span in the profiler's trace. The mesh path's counters and scopes run
in a child process with four virtual devices, on meshes of four chips
and of two.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.apps import diffusion as dif
from repro.apps import lbm
from repro.apps.advection_diffusion import (
    AdvectionDiffusionSimulation,
    blob_init,
)
from repro.core import tracing
from repro.core.legalize import launch_dma_bytes, launch_flops

REPO = Path(__file__).resolve().parents[1]
N = 64


@pytest.fixture(scope="module")
def kernels():
    return {
        "lbm": lbm.LBMSimulation(lbm.LBMProblem(N, N)).stream_kernel(),
        "diffusion": dif.DiffusionSimulation(N, N).kernel,
    }


def _state(kern, *lead):
    return jnp.ones((*lead, len(kern._ports), N, N), jnp.float32)


def _regs(kern):
    return [0.2] * len(kern._regs)


def _delta(before, after):
    return {k: after[k] - before[k] for k in before}


def test_kernel_name_keeps_only_identifier_characters():
    assert tracing.kernel_name("uLBM") == "spd_uLBM"
    assert tracing.kernel_name("a-b c.d") == "spd_a_b_c_d"


@pytest.mark.parametrize("rows,width,planes,block_h,m,halo,rows_moved", [
    # 4 blocks of 16 rows; m·halo = 4 carried as one 8-row tile a side:
    # each stripe reads 32 rows, and the 64 output rows are written.
    (64, 64, 10, 16, 4, 1, 4 * 32 + 64),
    # m·halo = 0: stripes are the blocks themselves.
    (64, 128, 1, 32, 3, 0, 2 * 32 + 64),
    # m·halo = 12 rounds up to 16 rows, capped by no block (block 32).
    (128, 256, 2, 32, 4, 3, 4 * 64 + 128),
])
def test_launch_dma_bytes_by_hand(rows, width, planes, block_h, m, halo,
                                  rows_moved):
    assert launch_dma_bytes(rows, width, planes, block_h=block_h, m=m,
                            halo=halo, itemsize=4) == \
        planes * width * 4 * rows_moved


@pytest.mark.parametrize("rows,width,batch,block_h,m,halo,flops,sites", [
    # 4 blocks of 16 rows, each a 32-row stripe (one 8-row halo tile a
    # side), every stripe row computed at each of the 4 steps.
    (64, 64, 1, 16, 4, 1, 7, 4 * 32 * 64 * 4),
    # m·halo = 0: the stripes are the blocks; a batch of two.
    (64, 128, 2, 32, 3, 0, 131, 2 * 2 * 32 * 128 * 3),
    # The jacobi cell's plan: 128 blocks of 144-row stripes, 8 steps.
    (16384, 16384, 1, 128, 8, 1, 7, 128 * 144 * 16384 * 8),
])
def test_launch_flops_by_hand(rows, width, batch, block_h, m, halo, flops,
                              sites):
    assert launch_flops(rows, width, batch, block_h=block_h, m=m, halo=halo,
                        flops=flops) == sites * flops


@pytest.mark.parametrize("app,lead,steps,m,block_h", [
    ("lbm", (), 8, 4, 16),
    ("diffusion", (), 6, 2, 8),
    ("diffusion", (3,), 4, 4, 32),  # a batch of three in one launch
    ("diffusion", (), 8, 2, 16),
    ("diffusion", (), 10, 2, 16),
    ("diffusion", (), 32, 2, 16),
])
def test_run_blocked_counts_its_plan(kernels, app, lead, steps, m,
                                     block_h):
    """Besides launches, steps, bytes and operations: the first two
    launches of a call write new buffers, and every later one writes
    into a buffer the loop recycles."""
    kern = kernels[app]
    launches = steps // m
    x = _state(kern, *lead)
    batch = lead[0] if lead else 1
    planes = len(kern._ports) * batch
    mh = -(-m * kern.halo // 8) * 8  # the halo in whole 8-row tiles
    per_launch = planes * N * 4 * ((N // block_h) * (block_h + 2 * mh) + N)
    flops = (batch * N * (N // block_h) * (block_h + 2 * mh) * m
             * kern.compiled.flops)
    before = tracing.snapshot()
    kern.run_blocked(x, _regs(kern), steps=steps, m=m,
                     block_h=block_h).block_until_ready()
    first = tracing.snapshot()
    d = _delta(before, first)
    assert (d["launches"], d["steps"]) == (launches, steps)
    assert d["dma_bytes"] == launches * per_launch
    assert d["kernel_flops"] == launches * flops
    assert d["aliased_launches"] == max(0, launches - 2)
    assert d["jit_traces"] > 0 and d["jit_s"] > 0
    kern.run_blocked(x, _regs(kern), steps=steps, m=m,
                     block_h=block_h).block_until_ready()
    d = _delta(first, tracing.snapshot())
    assert (d["launches"], d["dma_bytes"], d["kernel_flops"],
            d["aliased_launches"]) == \
        (launches, launches * per_launch, launches * flops,
         max(0, launches - 2))
    assert d["jit_traces"] == 0 and d["jit_s"] == 0


def test_single_launch_counts_one(kernels):
    kern = kernels["diffusion"]
    before = tracing.snapshot()
    kern(_state(kern), _regs(kern), m=2, block_h=16).block_until_ready()
    d = _delta(before, tracing.snapshot())
    assert (d["launches"], d["steps"]) == (1, 2)
    assert d["aliased_launches"] == 0
    assert d["dma_bytes"] == launch_dma_bytes(N, N, 1, block_h=16, m=2,
                                              halo=kern.halo, itemsize=4)
    assert d["kernel_flops"] == launch_flops(N, N, 1, block_h=16, m=2,
                                             halo=kern.halo,
                                             flops=kern.compiled.flops)
    # The diffusion core: four adds, a multiply and a subtract for the
    # Laplacian, a multiply-add for the update.
    assert kern.compiled.flops == 7


def test_pipelined_program_counts_a_launch_per_cluster_and_step():
    sim = AdvectionDiffusionSimulation(N, N)
    pk = sim.program.kernel("1+1")
    assert pk.pipelined and len(pk.clusters) == 2
    x = sim.state(blob_init(N, N))
    before = tracing.snapshot()
    pk.run_blocked(x, sim.regs(), steps=3, m=1,
                   block_h=16).block_until_ready()
    d = _delta(before, tracing.snapshot())
    assert (d["launches"], d["steps"]) == (6, 3)
    assert d["dma_bytes"] == 3 * sum(
        launch_dma_bytes(N, N, x.shape[0], block_h=16, m=1, halo=k.halo,
                         itemsize=4) for k in pk.clusters)
    assert d["kernel_flops"] == 3 * sum(
        launch_flops(N, N, 1, block_h=16, m=1, halo=k.halo,
                     flops=k.compiled.flops) for k in pk.clusters)


def test_fused_program_counts_its_one_core():
    """Fused, the program is one core whose operations are its stages'
    together, launched ``m`` steps at a time."""
    sim = AdvectionDiffusionSimulation(N, N)
    pk = sim.program.kernel("2")
    (kern,) = pk.clusters
    assert kern.compiled.flops == sum(
        k.compiled.flops for k in sim.program.kernel("1+1").clusters)
    x = sim.state(blob_init(N, N))
    before = tracing.snapshot()
    pk.run_blocked(x, sim.regs(), steps=4, m=2,
                   block_h=16).block_until_ready()
    d = _delta(before, tracing.snapshot())
    assert (d["launches"], d["steps"]) == (2, 4)
    assert d["kernel_flops"] == 2 * launch_flops(
        N, N, 1, block_h=16, m=2, halo=kern.halo, flops=kern.compiled.flops)


def test_compiled_entry_is_named_and_scoped(kernels):
    kern = kernels["lbm"]
    text = kern._run_blocked.lower(
        _state(kern), kern._scal(_regs(kern)), steps=8, m=4, block_h=16,
        double_buffer=True, interpret=True).compile().as_text()
    assert text.startswith("HloModule jit_spd_run_blocked")
    assert f"/{tracing.LAUNCH}/" in text
    assert "jit(<unknown>)" not in text


def test_dispatch_is_a_host_span_in_the_trace(kernels, tmp_path):
    from jax.profiler import ProfileData

    kern = kernels["diffusion"]
    x = _state(kern)
    kern.run_blocked(x, _regs(kern), steps=2, m=2, block_h=16)
    jax.profiler.start_trace(str(tmp_path))
    kern.run_blocked(x, _regs(kern), steps=2, m=2,
                     block_h=16).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    data = ProfileData.from_file(str(path))
    names = [ev.name for plane in data.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events]
    assert names.count(tracing.RUN) == 1


CHILD = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp
    from repro.apps import lbm
    from repro.core import tracing

    n, steps, m, bh = 64, 8, 2, 8
    kern = lbm.LBMSimulation(lbm.LBMProblem(n, n)).stream_kernel()
    x = jnp.ones((len(kern._ports), n, n), jnp.float32)
    regs = [0.2] * len(kern._regs)
    out = {}
    for d, dx in ((4, 1), (4, 2), (2, 1)):
        sk = kern.sharded(d, dx=dx)
        before = tracing.snapshot()
        sk.run_blocked(x, regs, steps=steps, m=m,
                       block_h=bh).block_until_ready()
        after = tracing.snapshot()
        fn = sk._fn(steps, m, bh, True, True)
        text = fn.lower(x, kern._scal(regs)).compile().as_text()
        out[f"{d}x{dx}"] = {
            "delta": {k: after[k] - before[k] for k in before},
            "module": text.splitlines()[0].split(",")[0],
            "scopes": sorted({s for s in (tracing.LAUNCH, tracing.EXCHANGE,
                                           tracing.ASSEMBLE)
                              if "/" + s + "/" in text}),
            "halo": kern.halo, "halo_x": kern.halo_x,
            "flops": kern.compiled.flops,
        }
    print(json.dumps(out))
""")


def test_mesh_counts_every_shard_and_scopes_its_glue(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(REPO / "src"), os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run([sys.executable, "-c", CHILD], capture_output=True,
                          text=True, timeout=600, env=env, cwd=tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.splitlines()[-1])
    steps, m, bh, n, words = 8, 2, 8, 64, 10
    for mesh, (d, dy, local_w) in {"4x1": (4, 4, n), "4x2": (4, 2, n // 2),
                                   "2x1": (2, 2, n)}.items():
        got = out[mesh]
        assert got["module"] == "HloModule jit_spd_run_sharded"
        assert got["scopes"] == ["spd.assemble", "spd.exchange",
                                 "spd.launch"]
        local_h = n // dy
        mh = -(-m * got["halo"] // 8) * 8
        # Guard columns: m·halo_x rounded up to half a lane tile (64).
        width = local_w + (2 * 64 if d != dy and got["halo_x"] else 0)
        per_shard = words * width * 4 * (
            (local_h // bh) * (bh + 2 * mh) + local_h)
        # Each shard computes m steps over every row of its stripes.
        shard_flops = width * (local_h // bh) * (bh + 2 * mh) * m \
            * got["flops"]
        assert got["delta"]["launches"] == steps // m
        assert got["delta"]["steps"] == steps
        assert got["delta"]["dma_bytes"] == steps // m * d * per_shard
        assert got["delta"]["kernel_flops"] == steps // m * d * shard_flops
        assert shard_flops == launch_flops(
            local_h, width, 1, block_h=bh, m=m, halo=got["halo"],
            flops=got["flops"])
        # Every mesh loop ping-pongs two kernel-written buffers, as on
        # one chip.
        launches = steps // m
        assert got["delta"]["aliased_launches"] == launches - 2
