"""Pallas TPU flash attention (blocked online softmax).

Grid: (batch, q_heads, q_blocks, k_blocks), k innermost and sequential
("arbitrary"); q/b/h axes parallel. Running max/denominator/accumulator live
in VMEM scratch across the k sweep; the output block is written once, on the
final contributing k block. Fully-masked k blocks (beyond the causal
diagonal or outside the sliding window) are skipped via ``pl.when``.

GQA is handled in the index maps: q head ``h`` reads kv head ``h // group``.
Block shapes keep the head dim D full (lane-dim multiple of 128 for f32/bf16
models used here) and tile the sequence dims — MXU-shaped matmuls of
(block_q x D) @ (D x block_k).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compat import resolve_interpret

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int,
            block_q: int, block_k: int, sq: int, sk: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    # Absolute positions; causal diagonal anchored to the end of KV so the
    # same kernel serves training (sq == sk) and prefill-with-prefix.
    q_off = sk - sq + qi * block_q
    k_off = ki * block_k

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Block-level reachability: skip blocks fully above the causal diagonal
    # or fully left of the sliding window.
    reachable = True
    if causal:
        reachable = jnp.asarray(q_off + block_q - 1 >= k_off)
    if window > 0:
        reachable = jnp.logical_and(
            reachable, jnp.asarray(q_off - (k_off + block_k - 1) < window)
        )

    @pl.when(reachable)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bq, bk)
        q_idx = q_off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_idx = k_off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= q_idx >= k_idx
        if window > 0:
            mask &= q_idx - k_idx < window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]  # (bq, 128) broadcast copies
        m_cur = jnp.max(s, axis=1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])  # (bq, 1)
        p = jnp.exp(s - m_new[:, :1])
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        l_ref[...] = l_ref[...] * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), l_ref.shape
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "window", "scale", "block_q", "block_k", "interpret"
    ),
)
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool | None = None):
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Sk, D) -> (B, Hq, Sq, D)."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    if hq % hkv:
        raise ValueError(f"Hq={hq} must be a multiple of Hkv={hkv}")
    group = hq // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(f"S ({sq},{sk}) must tile by ({block_q},{block_k})")
    scale = scale if scale is not None else d ** -0.5

    grid = (b, hq, sq // block_q, sk // block_k)
    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, sq=sq, sk=sk,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h, qi, ki: (b_, h, qi, 0)),
            pl.BlockSpec(
                (1, 1, block_k, d),
                lambda b_, h, qi, ki, g=group: (b_, h // g, ki, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, d),
                lambda b_, h, qi, ki, g=group: (b_, h // g, ki, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, d), lambda b_, h, qi, ki: (b_, h, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
