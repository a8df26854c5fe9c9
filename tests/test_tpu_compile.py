"""Ahead-of-time compiles of the main path for a described TPU v5e.

Nothing here runs on a chip: each test lowers a kernel for a ``v5e:2x2``
topology that JAX describes without one attached and asserts the TPU
compiler accepted it (``tpu_custom_call`` in the compiled text). This is
what interpret mode cannot check: Mosaic refuses row slices that do not
start on an 8-row tile and kernels that need more VMEM than their scoped
limit. The topology is described inside a module fixture — never at
import — so pytest-xdist workers all collect the same tests, and every
test skips when the TPU compiler is not installed.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.apps import diffusion as dif
from repro.apps import lbm
from repro.core.legalize import (
    aligned_divisors,
    blocking_plan,
    stripe_vmem_bytes,
)

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu, or it cannot be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def kernels():
    return {
        "lbm": lbm.LBMSimulation(lbm.LBMProblem(64, 64)).stream_kernel(),
        "diffusion": dif.DiffusionSimulation(64, 64).kernel,
    }


def _shapes(kern, shape, sharding):
    state = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)
    scal = jax.ShapeDtypeStruct((max(1, len(kern._regs)),), jnp.float32,
                                sharding=sharding)
    return state, scal


def _assert_compiles(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


# (app, grid, block_h, m, double_buffer): real widths, and m·halo values
# that are not multiples of 8 (the halo is carried in whole 8-row tiles).
STREAMED = [
    ("lbm", 2048, 16, 2, True),
    ("lbm", 2048, 16, 1, False),
    ("lbm", 2048, 64, 8, True),  # refused under the compiler's default
    ("diffusion", 2048, 64, 8, True),
    ("diffusion", 2048, 32, 3, False),
    ("diffusion", 8192, 64, 8, True),
]


@pytest.mark.parametrize("app,n,block_h,m,double_buffer", STREAMED)
def test_streamed_launch_compiles(one_chip, kernels, app, n, block_h, m,
                                  double_buffer):
    kern = kernels[app]
    state, scal = _shapes(kern, (len(kern._ports), n, n), one_chip)
    _assert_compiles(
        functools.partial(kern._streamed, m=m, block_h=block_h,
                          double_buffer=double_buffer, interpret=False),
        state, scal,
    )


def _mesh_text(topo, kern, dy, dx, n, block_h, m, launches):
    """The compiled ``jit_spd_run_sharded`` of an n² grid on a (dy, dx)
    mesh of the described chips, ``launches`` launches of plan
    (block_h, m)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.distribute import ShardedStreamKernel
    from repro.parallel.sharding import stream_grid_pspec

    sk = ShardedStreamKernel(kern, dy * dx, devices=topo.devices, dx=dx)
    assert sk.mesh.devices.shape == (dy, dx)
    spec = stream_grid_pspec("d", axis_x="dx")
    state = jax.ShapeDtypeStruct((len(kern._ports), n, n), jnp.float32,
                                 sharding=NamedSharding(sk.mesh, spec))
    scal = jax.ShapeDtypeStruct((max(1, len(kern._regs)),), jnp.float32,
                                sharding=NamedSharding(sk.mesh, P(None)))
    fn = sk._fn(launches * m, m, block_h, True, False)
    return fn.lower(state, scal).compile().as_text()


#: Ops that may hold a shard-sized value without moving it: the loop
#: and its tuples, parameters and bitcasts. A kernel is the one
#: custom call that may write one.
PASS_THROUGH = {"parameter", "get-tuple-element", "tuple", "while",
                "bitcast"}


def _shard_sized_ops(text, shard):
    """Every op in ``text`` other than a kernel or a pass-through whose
    f32 result holds at least ``shard`` elements."""
    import math
    import re

    found = []
    for line in text.splitlines():
        op = re.match(r"\s*(?:ROOT )?%(\S+) = f32\[([\d,]*)\]\S* ([\w-]+)\(",
                      line)
        if not op or op.group(3) in PASS_THROUGH:
            continue
        if op.group(3) == "custom-call" and "tpu_custom_call" in line:
            continue
        if math.prod(int(v) for v in op.group(2).split(",") if v) >= shard:
            found.append(f"{op.group(3)} %{op.group(1)} f32[{op.group(2)}]")
    return found


@pytest.mark.parametrize("app,n,block_h,m", [
    ("diffusion", 2048, 32, 2),
    ("lbm", 2048, 16, 1),
])
def test_sharded_2x2_mesh_compiles(topo, kernels, app, n, block_h, m):
    kern = kernels[app]
    text = _mesh_text(topo, kern, 2, 2, n, block_h, m, 2)
    assert "tpu_custom_call" in text
    assert "collective-permute" in text
    # The exchange and the guard pieces' assembly carry the program's
    # scopes.
    assert text.startswith("HloModule jit_spd_run_sharded")
    assert "/spd.exchange/ppermute" in text
    assert "/spd.assemble/concatenate" in text


#: (app, n, block_h, m): grids whose shards do not fit in VMEM, so the
#: compiler leaves them in HBM as it does at full size.
MESH_LOOPS = [("lbm", 2048, 16, 2), ("diffusion", 8192, 32, 2)]


@pytest.mark.parametrize("launches", [16, 17])
@pytest.mark.parametrize("dy,dx", [(1, 4), (2, 2), (4, 1)],
                         ids=["1x4", "2x2", "4x1"])
@pytest.mark.parametrize("app,n,block_h,m", MESH_LOOPS,
                         ids=[c[0] for c in MESH_LOOPS])
def test_mesh_loop_copies_no_state(topo, kernels, app, n, block_h, m, dy,
                                   dx, launches):
    """The four-chip twin of ``test_run_blocked_copies_no_state``, on
    every mesh shape of four chips: each launch gathers its stripes
    from the shard and the exchanged halo pieces in place and writes
    its own columns into a buffer the loop recycles, so the compiled
    loop holds no copy, concatenate or fusion of a shard's size,
    neither from the loop carry nor from the caller's input. Every
    kernel is ``spd_<core>`` under ``spd.launch``, every collective
    permute under ``spd.exchange``."""
    import re

    kern = kernels[app]
    text = _mesh_text(topo, kern, dy, dx, n, block_h, m, launches)
    shard = len(kern._ports) * (n // dy) * (n // dx)
    assert not _shard_sized_ops(text, shard)
    _assert_kernel_named_under_launch_scope(kern, text,
                                            "jit_spd_run_sharded")
    permutes = [line for line in text.splitlines()
                if re.search(r" collective-permute(-start)?\(", line)]
    assert permutes
    for line in permutes:
        assert "/spd.exchange/" in re.search(r'op_name="([^"]*)"',
                                             line).group(1)


def _run_blocked_text(kern, one_chip, n, steps):
    """The compiled ``jit_spd_run_blocked`` of LBM-sized state at n²,
    plan (32, 4)."""
    state, scal = _shapes(kern, (len(kern._ports), n, n), one_chip)
    return kern._run_blocked.lower(
        state, scal, steps=steps, m=4, block_h=32, double_buffer=True,
        interpret=False).compile().as_text()


def _assert_kernel_named_under_launch_scope(kern, text,
                                            module="jit_spd_run_blocked"):
    import re

    assert text.startswith(f"HloModule {module}")
    calls = [line for line in text.splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in line]
    assert calls
    for line in calls:
        assert re.match(rf"\s*(ROOT )?%{kern.name}\.\d+ = ", line), line
        op_name = re.search(r'op_name="([^"]*)"', line).group(1)
        assert "/spd.launch/" in op_name


def test_run_blocked_kernel_is_named_under_its_launch_scope(one_chip,
                                                            kernels):
    """The timed entry compiles as ``jit_spd_run_blocked``, and its
    kernel is a custom call named ``spd_<core>`` whose ``op_name`` is
    under ``spd.launch``: what the device trace shows."""
    kern = kernels["lbm"]
    _assert_kernel_named_under_launch_scope(
        kern, _run_blocked_text(kern, one_chip, 1024, 8))


@pytest.mark.parametrize("launches", [16, 17])
def test_run_blocked_copies_no_state(one_chip, kernels, launches):
    """The launch loop ping-pongs between two buffers the kernel writes
    (``input_output_aliases``), so the compiled loop holds no copy of
    state size: neither the loop carry into each launch's input nor the
    caller's input into the carry. An odd launch count adds a launch
    after the loop; the kernel keeps its name and scope."""
    import re

    kern = kernels["lbm"]
    n = 1024
    text = _run_blocked_text(kern, one_chip, n, 4 * launches)
    state = rf"f32\[{len(kern._ports)},{n},{n}\]"
    copies = [line for line in text.splitlines()
              if re.search(rf"= {state}\S* copy\(", line)]
    assert not copies, copies
    _assert_kernel_named_under_launch_scope(kern, text)


@pytest.mark.parametrize("app,h,w", [
    ("diffusion", 64, 256),
    ("lbm", 32, 256),
])
def test_every_blocking_plan_compiles(one_chip, kernels, app, h, w):
    """Every plan the legalizer returns on a small lattice of requests
    compiles for the chip."""
    kern = kernels[app]
    words = len(kern._ports)
    plans = set()
    for block_h in (1, 8, 12, 24, 32, 64):
        for m in (1, 2, 3, 5, 8):
            for db in (True, False):
                plans.add(blocking_plan(h, block_h, m, halo=kern.halo,
                                        width=w, words=words,
                                        double_buffer=db, interpret=False))
    state, scal = _shapes(kern, (words, h, w), one_chip)
    for block_h, m, db in sorted(plans):
        _assert_compiles(
            functools.partial(kern._streamed, m=m, block_h=block_h,
                              double_buffer=db, interpret=False),
            state, scal,
        )


#: Block height whose stripe price sets the scoped-VMEM limit of the
#: budget-edge compiles: the smallest tile, where the fixed part of what
#: the compiler allocates weighs most against the priced stripe, and
#: where a compile at 8192 wide with m = 8 still takes seconds, not the
#: minutes a 100 MiB stripe does.
EDGE_BLOCK = 8


@pytest.mark.parametrize("double_buffer", [True, False])
@pytest.mark.parametrize("app,w", [
    ("lbm", 4096), ("lbm", 8192), ("diffusion", 4096), ("diffusion", 8192),
])
def test_largest_legal_plan_compiles_at_the_budget_edge(
        one_chip, kernels, monkeypatch, app, w, double_buffer):
    """The plan ``blocking_plan`` returns for a request of the whole grid
    height and m = 8, under a budget that is exactly the price of an
    ``EDGE_BLOCK``-row stripe, is the one the VMEM clamp picks: it fills
    the budget to the byte, the next taller legal block would not fit,
    and it compiles with that budget as its scoped-VMEM limit."""
    from repro.kernels.spd_stream import streaming

    kern = kernels[app]
    words = len(kern._ports)
    h = 1024
    budget = stripe_vmem_bytes(EDGE_BLOCK, 8, w, words, kern.halo,
                               double_buffer)
    bh, m, db = blocking_plan(h, h, 8, halo=kern.halo, width=w,
                              words=words, double_buffer=double_buffer,
                              vmem_bytes=budget, interpret=False)
    assert (bh, m, db) == (EDGE_BLOCK, 8, double_buffer)
    assert stripe_vmem_bytes(bh, m, w, words, kern.halo, db) == budget
    taller = min(v for v in aligned_divisors(h) if v > bh)
    assert stripe_vmem_bytes(taller, m, w, words, kern.halo, db) > budget
    monkeypatch.setattr(streaming, "VMEM_BYTES", budget)
    state, scal = _shapes(kern, (words, h, w), one_chip)
    _assert_compiles(
        functools.partial(streaming.spd_multistep, kern._step_fn,
                          m=m, block_h=bh, halo=kern.halo,
                          double_buffer=db, interpret=False),
        state, scal,
    )


def test_every_column_sharded_plan_compiles(topo, kernels):
    """Column-sharded plans on a narrow grid: at 256 columns over dx = 2
    every plan the legalizer returns compiles on a (2, 2) mesh; at 128
    columns the 64-column shards cannot be staged, and the legalizer
    refuses them instead of the compiler."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.distribute import ShardedStreamKernel
    from repro.parallel.sharding import stream_grid_pspec

    kern = kernels["diffusion"]
    with pytest.raises(ValueError, match="128 lanes"):
        blocking_plan(64, 16, 1, halo=kern.halo, width=128, words=1, d=4,
                      dx=2, halo_x=kern.halo_x, interpret=False)
    h, w = 64, 256
    plans = {
        blocking_plan(h, block_h, m, halo=kern.halo, width=w, words=1,
                      d=4, dx=2, halo_x=kern.halo_x, interpret=False)
        for block_h in (8, 16, 32) for m in (1, 3, 8)
    }
    sk = ShardedStreamKernel(kern, 4, devices=topo.devices, dx=2)
    state = jax.ShapeDtypeStruct(
        (1, h, w), jnp.float32,
        sharding=NamedSharding(sk.mesh, stream_grid_pspec("d", axis_x="dx")))
    scal = jax.ShapeDtypeStruct((1,), jnp.float32,
                                sharding=NamedSharding(sk.mesh, P(None)))
    for block_h, m, db in sorted(plans):
        fn = sk._fn(m, m, block_h, db, False)
        assert "tpu_custom_call" in fn.lower(state, scal).compile().as_text()
