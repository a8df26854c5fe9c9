"""Explicit expert-parallel MoE dispatch: shard_map + lax.all_to_all.

GSPMD lowers the two-stage pjit dispatch's dp->ep reshard as
all-gather + slice (EXPERIMENTS.md §Perf kimi it.3) — each expert shard
receives ~ep_size x the bytes a real all-to-all would move. This module
implements the canonical pattern explicitly:

  per device: route local tokens -> per-destination-rank capacity buffers
  all_to_all over the expert ('model') axis      [token payload only]
  local expert FFN (weights all-gathered over the FSDP axes, as FSDP does)
  all_to_all back -> combine with gates

Wire bytes per device per layer: tokens_loc x top_k x d x dtype — the
information-theoretic minimum for token-choice routing.

Differentiable end-to-end (all_to_all transposes to all_to_all); used via
the 'a2a' sharding hint by ``repro.models.layers.moe_apply``.
"""

from __future__ import annotations

from functools import partial

import jax

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def moe_ep_apply(xt, idx, gates, w_gate, w_up, w_down, *, mesh, dp_axes,
                 ep_axis: str, fsdp_axes, capacity_factor: float,
                 top_k: int, n_experts: int):
    """xt: (N, d) tokens; idx/gates: (N, k) routing; weights (E, d, f) etc.

    Returns (N, d) combined expert outputs.
    """
    ep = mesh.shape[ep_axis]
    e_loc = n_experts // ep
    n = xt.shape[0]
    # tokens shard over dp AND ep axes: without the ep split, the ep ranks
    # of one dp row would all route the same (replicated) tokens and the
    # all_to_all would move/compute ep x duplicated work
    tok_axes = tuple(dp_axes or ()) + (ep_axis,)
    dp_size = 1
    for a in tok_axes:
        dp_size *= mesh.shape[a]
    n_loc = n // dp_size
    cap = int(max(top_k, capacity_factor * n_loc * top_k / n_experts))
    dtype = xt.dtype

    w_specs = (
        P(ep_axis, fsdp_axes, None),  # w_gate (E, d, f)
        P(ep_axis, fsdp_axes, None),  # w_up
        P(ep_axis, fsdp_axes, None),  # w_down (E, f, d): FSDP on f
    )

    def body(xt_l, idx_l, gates_l, wg_l, wu_l, wd_l):
        # weights: undo the FSDP shard for this layer (the FSDP gather)
        if fsdp_axes:
            wg_l = jax.lax.all_gather(wg_l, fsdp_axes, axis=1, tiled=True)
            wu_l = jax.lax.all_gather(wu_l, fsdp_axes, axis=1, tiled=True)
            wd_l = jax.lax.all_gather(wd_l, fsdp_axes, axis=1, tiled=True)

        nk = idx_l.reshape(-1)  # (N_loc*k,) global expert ids
        # position within each expert's local capacity via one-hot cumsum
        onehot = jax.nn.one_hot(nk, n_experts, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1
        keep = pos < cap
        src = jnp.repeat(xt_l, top_k, axis=0)  # (N_loc*k, d)
        # send buffer laid out (ep, E_loc, C, d): dim 0 is destination rank
        send = jnp.zeros((ep, e_loc, cap, xt_l.shape[-1]), dtype)
        dest = nk // e_loc
        el = nk % e_loc
        send = send.at[
            jnp.where(keep, dest, 0),
            jnp.where(keep, el, 0),
            jnp.where(keep, pos, cap - 1),
        ].add(jnp.where(keep[:, None], src, 0), mode="drop")

        # token payload crosses the wire exactly once each way
        recv = jax.lax.all_to_all(send, ep_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        # recv: (ep_src, E_loc, C, d) -> local experts serve all sources
        h = jnp.einsum("secd,edf->secf", recv, wg_l)
        u = jnp.einsum("secd,edf->secf", recv, wu_l)
        y = jnp.einsum("secf,efd->secd", jax.nn.silu(h) * u, wd_l)
        back = jax.lax.all_to_all(y, ep_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        # back: (ep_dest==expert rank, E_loc, C, d), same layout as `send`
        val = back[
            jnp.where(keep, dest, 0),
            jnp.where(keep, el, 0),
            jnp.where(keep, pos, cap - 1),
        ]
        val = jnp.where(keep[:, None], val, 0)
        out = (
            val.reshape(n_loc, top_k, -1)
            * gates_l[..., None].astype(dtype)
        ).sum(1)
        return out

    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(tok_axes, None), P(tok_axes, None), P(tok_axes, None), *w_specs
        ),
        out_specs=P(tok_axes, None),
        check_vma=False,
    )(xt, idx, gates, w_gate, w_up, w_down)
