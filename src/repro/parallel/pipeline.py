"""Pipeline parallelism: the paper's *temporal cascade* in LM form.

``S`` stages (layer groups) live on ``S`` mesh devices along a ``stage``
axis; ``M`` microbatches stream through. The schedule is the classic
GPipe-style fill/drain: utilization ``M / (M + S - 1)`` — exactly the
paper's prologue/epilogue loss with m*d replaced by (S-1) stage-steps
(DESIGN.md §4). Communication is a single ``lax.ppermute`` per tick, which
overlaps with the next tick's stage compute under XLA's async collectives.

Implementation: ``shard_map`` over the stage axis; each device scans over
T = M + S - 1 ticks, pushing activations to its right neighbor.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax

import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_utilization(n_micro: int, n_stages: int) -> float:
    return n_micro / (n_micro + n_stages - 1)


def pipelined_forward(
    mesh: Mesh,
    stage_fn: Callable,  # (stage_params, x) -> y, same shape
    stage_axis: str = "stage",
):
    """Build a pipelined forward: (stacked_stage_params, microbatches) -> out.

    ``stacked_stage_params``: pytree with leading axis S (one slice per
    stage). ``microbatches``: (M, mb, ...) array. Returns (M, mb, ...) after
    all S stages.
    """
    n_stages = mesh.shape[stage_axis]

    def run(stage_params, micro):
        # shard_map leaves a local size-1 stage axis on the params; drop it
        stage_params = jax.tree_util.tree_map(lambda a: a[0], stage_params)
        m = micro.shape[0]
        t_total = m + n_stages - 1
        stage = jax.lax.axis_index(stage_axis)

        # carries are device-varying (each stage holds different data):
        # mark them so under shard_map's varying-axis type system
        buf = jax.lax.pcast(jnp.zeros_like(micro), (stage_axis,),
                            to="varying")  # output slots
        state = jax.lax.pcast(jnp.zeros_like(micro[0]), (stage_axis,),
                              to="varying")  # in-flight

        def tick(carry, t):
            state, buf = carry
            # stage 0 ingests microbatch t (when available)
            feed = micro[jnp.clip(t, 0, m - 1)]
            x = jnp.where(stage == 0, feed, state)
            y = stage_fn(stage_params, x)
            # last stage retires microbatch t-(S-1) into the buffer
            out_idx = t - (n_stages - 1)
            do_store = jnp.logical_and(stage == n_stages - 1, out_idx >= 0)
            stored = jax.lax.dynamic_update_index_in_dim(
                buf, y, jnp.clip(out_idx, 0, m - 1), 0
            )
            buf = jnp.where(do_store, stored, buf)
            # shift to the right neighbor
            nxt = jax.lax.ppermute(
                y, stage_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)],
            )
            return (nxt, buf), None

        (_, buf), _ = jax.lax.scan(tick, (state, buf), jnp.arange(t_total))
        # only the last stage holds real outputs; broadcast them
        buf = jax.lax.ppermute(
            buf, stage_axis,
            [((n_stages - 1 + i) % n_stages, i) for i in range(n_stages)],
        )
        return buf

    return jax.jit(
        jax.shard_map(
            run,
            mesh=mesh,
            in_specs=(P(stage_axis), P()),
            out_specs=P(),
            # the final broadcast ppermute replicates buf across stages, but
            # the varying-axis checker cannot infer that statically
            check_vma=False,
        )
    )


def stack_stage_params(per_layer_params, n_stages: int):
    """Regroup (L, ...) scan-stacked layer params into (S, L/S, ...)."""
    def regroup(a):
        l = a.shape[0]
        if l % n_stages:
            raise ValueError(f"layers {l} must divide stages {n_stages}")
        return a.reshape((n_stages, l // n_stages) + a.shape[1:])

    return jax.tree_util.tree_map(regroup, per_layer_params)
