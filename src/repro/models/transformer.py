"""Decoder-only and encoder-decoder transformer LMs (dense / MoE / VLM /
audio backbones), scan-over-layers with per-layer remat.

Entry points (all shape-driven, usable under ``jax.eval_shape``):
  init_params(cfg, key)                      -> params
  forward(params, cfg, tokens, embeds, ...)  -> logits       (train/prefill)
  init_cache(cfg, batch, seq)                -> cache
  decode_step(params, cfg, token, cache, pos)-> (logits, cache)
  encode(params, cfg, frames)                -> encoder states   (enc_dec)
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from .layers import (
    attention_block,
    attn_init,
    attn_qkv,
    cross_entropy,
    decode_attention,
    dense_init,
    embed_init,
    mlp_apply,
    mlp_init,
    moe_apply,
    moe_init,
    rms_norm,
    _merge_heads,
    _split_heads,
)

# --------------------------------------------------------------------------
# Init
# --------------------------------------------------------------------------


def _init_layer(cfg, key, moe: bool):
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": jnp.ones((cfg.d_model,), cfg.param_dtype),
        "ln2": jnp.ones((cfg.d_model,), cfg.param_dtype),
        "attn": attn_init(k1, cfg),
    }
    p["moe" if moe else "mlp"] = (
        moe_init(k2, cfg) if moe else mlp_init(k2, cfg)
    )
    return p


def _init_cross_layer(cfg, key):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "ln1": jnp.ones((cfg.d_model,), cfg.param_dtype),
        "ln_x": jnp.ones((cfg.d_model,), cfg.param_dtype),
        "ln2": jnp.ones((cfg.d_model,), cfg.param_dtype),
        "attn": attn_init(k1, cfg),
        "xattn": attn_init(k2, cfg),
        "mlp": mlp_init(k3, cfg),
    }


def _stack_init(fn, keys):
    return jax.vmap(fn)(keys)


def init_params(cfg, key) -> dict:
    keys = jax.random.split(key, 8)
    p: dict = {
        "embed": embed_init(keys[0], cfg.vocab, cfg.d_model, cfg.param_dtype),
        "ln_f": jnp.ones((cfg.d_model,), cfg.param_dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(
            keys[1], cfg.d_model, cfg.vocab, cfg.param_dtype
        )
    moe_start = cfg.moe.moe_start_layer if cfg.moe else 0
    if cfg.enc_dec:
        ek = jax.random.split(keys[2], cfg.n_layers)
        dk = jax.random.split(keys[3], cfg.n_layers)
        p["enc_layers"] = _stack_init(
            lambda k: _init_layer(cfg, k, moe=False), ek
        )
        p["dec_layers"] = _stack_init(lambda k: _init_cross_layer(cfg, k), dk)
        p["ln_enc"] = jnp.ones((cfg.d_model,), cfg.param_dtype)
    else:
        n_moe = cfg.n_layers - moe_start if cfg.moe else 0
        n_dense = cfg.n_layers - n_moe
        if n_dense:
            lk = jax.random.split(keys[4], n_dense)
            p["layers"] = _stack_init(
                lambda k: _init_layer(cfg, k, moe=False), lk
            )
        if n_moe:
            mk = jax.random.split(keys[5], n_moe)
            p["moe_layers"] = _stack_init(
                lambda k: _init_layer(cfg, k, moe=True), mk
            )
    return p


# --------------------------------------------------------------------------
# Forward (train / prefill)
# --------------------------------------------------------------------------


def _sp_spec(h):
    """Residual-stream spec: (batch=dp, seq=sp-or-None, d=None).

    With sp=None this pins the residual to (dp, None, None) — forcing the
    TP all-reduce to land on the bf16 matmul output instead of a
    post-f32-convert tensor (GSPMD otherwise decomposes the AR into RS+AG
    around the norm's f32 internals, doubling wire bytes). Full sequence
    parallelism (sp='model') was tried and REFUTED for attention archs:
    the chunked-attention scan dynamic-slices the seq dim, which under
    seq-sharding becomes per-chunk cross-device gathers (EXPERIMENTS.md
    §Perf granite it.1)."""
    if not (h.get("dp") or h.get("sp")):
        return None
    from jax.sharding import PartitionSpec as P

    return P(h.get("dp"), h.get("sp"), None)


def _layer_apply(p, x, cfg, positions, *, causal: bool, moe: bool):
    from jax.ad_checkpoint import checkpoint_name

    from repro.parallel.hints import constrain

    h = constrain(rms_norm(x, p["ln1"]), _sp_spec)
    attn_out = attention_block(p["attn"], h, cfg, positions, causal=causal)
    # the post-TP-collective tensors: saving exactly these two lets the
    # backward pass skip re-running the forward all-reduces ('sublayers'
    # remat policy) at ~2 sharded activations/layer of memory
    attn_out = checkpoint_name(attn_out, "attn_out")
    x = constrain(x + attn_out, _sp_spec)
    h = constrain(rms_norm(x, p["ln2"]), _sp_spec)
    ff_out = moe_apply(p["moe"], h, cfg) if moe else mlp_apply(p["mlp"], h, cfg)
    ff_out = checkpoint_name(ff_out, "ff_out")
    x = constrain(x + ff_out, _sp_spec)
    return x


def _remat_policy():
    """Remat policy, selectable via the 'remat' sharding hint:
    'none' (save nothing, max recompute) | 'dots' (save weight-matmul
    outputs: backward skips recomputing the forward's TP collectives at
    the cost of saved activations)."""
    from repro.parallel.hints import hint

    name = hint("remat", "none")
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if name == "sublayers":
        return jax.checkpoint_policies.save_only_these_names(
            "attn_out", "ff_out"
        )
    return jax.checkpoint_policies.nothing_saveable


def _scan_layers(stacked, x, cfg, positions, *, causal: bool, moe: bool):
    @partial(jax.checkpoint, policy=_remat_policy())
    def body(carry, lp):
        return _layer_apply(lp, carry, cfg, positions, causal=causal,
                            moe=moe), None

    x, _ = jax.lax.scan(body, x, stacked)
    return x


def _require_untyped_sharding(**arrays):
    """The LM tier shards through GSPMD constraints (Auto mesh axes, or
    ``jit(in_shardings=...)``). Arrays whose *types* carry a sharding —
    placed with ``device_put`` on a mesh of Explicit axes,
    ``jax.make_mesh``'s default — leave its gathers and head-split
    reshapes without an inferable sharding, so fail here with the remedy
    instead of deep inside a layer."""
    for name, a in arrays.items():
        spec = getattr(jax.typeof(a).sharding, "spec", ())
        if any(s is not None for s in spec):
            raise ValueError(
                f"the LM tier needs arrays without explicit sharding "
                f"types, got {name}: {jax.typeof(a)}; place them on a "
                f"mesh with AxisType.Auto axes "
                f"(repro.launch.mesh.make_production_mesh, or "
                f"jax.make_mesh(..., axis_types=(AxisType.Auto,) * n))"
            )


def embed_tokens(params, cfg, tokens, embeds=None):
    """Token embedding with optional frontend (VLM patches / audio frames)
    prepended. embeds: (B, T_front, d_model)."""
    x = params["embed"][tokens]
    if embeds is not None:
        x = jnp.concatenate([embeds.astype(x.dtype), x], axis=1)
    return x


def forward(params, cfg, tokens, embeds=None, positions=None):
    """-> logits (B, S_total, vocab). Decoder-only path."""
    _require_untyped_sharding(embed=params["embed"], tokens=tokens)
    x = embed_tokens(params, cfg, tokens, embeds)
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s, dtype=jnp.int32)[None, :]
    if "layers" in params:
        x = _scan_layers(params["layers"], x, cfg, positions,
                         causal=True, moe=False)
    if "moe_layers" in params:
        x = _scan_layers(params["moe_layers"], x, cfg, positions,
                         causal=True, moe=True)
    x = rms_norm(x, params["ln_f"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return x @ head


def encode(params, cfg, frames):
    """Encoder stack over stubbed frame embeddings (B, T, d) -> states."""
    _require_untyped_sharding(frames=frames)
    x = frames.astype(cfg.param_dtype)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]
    x = _scan_layers(params["enc_layers"], x, cfg, positions,
                     causal=False, moe=False)
    return rms_norm(x, params["ln_enc"])


def _cross_layer_apply(p, x, cfg, positions, enc_kv):
    x = x + attention_block(p["attn"], rms_norm(x, p["ln1"]), cfg, positions,
                            causal=True)
    x = x + attention_block(p["xattn"], rms_norm(x, p["ln_x"]), cfg, positions,
                            causal=False, kv_override=enc_kv)
    x = x + mlp_apply(p["mlp"], rms_norm(x, p["ln2"]), cfg)
    return x


def _enc_kv(p_layer, cfg, enc_states):
    """Precompute cross-attention K/V from encoder states for one layer."""
    kx = enc_states @ p_layer["xattn"]["wk"]
    vx = enc_states @ p_layer["xattn"]["wv"]
    if cfg.qkv_bias:
        kx, vx = kx + p_layer["xattn"]["bk"], vx + p_layer["xattn"]["bv"]
    return _split_heads(kx, cfg.n_kv_heads), _split_heads(vx, cfg.n_kv_heads)


def forward_enc_dec(params, cfg, frames, tokens):
    """Whisper-style: encode frames, decode tokens with cross-attention."""
    enc = encode(params, cfg, frames)
    x = params["embed"][tokens]
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]

    @partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
    def body(carry, lp):
        kv = _enc_kv(lp, cfg, enc)
        return _cross_layer_apply(lp, carry, cfg, positions, kv), None

    x, _ = jax.lax.scan(body, x, params["dec_layers"])
    x = rms_norm(x, params["ln_f"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return x @ head


# --------------------------------------------------------------------------
# KV-cache decode
# --------------------------------------------------------------------------


def init_cache(cfg, batch: int, seq: int, enc_len: int | None = None) -> dict:
    hd = cfg.head_dim
    kv = lambda s: jnp.zeros(
        (cfg.n_layers, batch, cfg.n_kv_heads, s, hd), cfg.param_dtype
    )
    cache = {"k": kv(seq), "v": kv(seq)}
    if cfg.enc_dec:
        # cross-attention K/V: computed ONCE from encoder states (prefill),
        # then read-only during decode — never recomputed per token
        enc_len = enc_len if enc_len is not None else seq * 4
        cache["xk"] = kv(enc_len)
        cache["xv"] = kv(enc_len)
    return cache


def prime_cross_cache(params, cfg, cache: dict, enc_states) -> dict:
    """Fill the cross-attention K/V cache from encoder states (one-time)."""

    def per_layer(lp):
        return _enc_kv(lp, cfg, enc_states)

    xk, xv = jax.vmap(per_layer)(params["dec_layers"])
    cache = dict(cache)
    cache["xk"], cache["xv"] = xk, xv
    return cache


def decode_step(params, cfg, token, cache, pos):
    """token: (B, 1) int32; pos: scalar int32 -> (logits (B,1,V), cache)."""
    x = params["embed"][token]

    def body_fn(moe):
        def body(carry, scanned):
            xc, = carry
            lp, ck, cv = scanned
            h = rms_norm(xc, lp["ln1"])
            o, ck, cv = decode_attention(lp["attn"], h, cfg, ck, cv, pos)
            xc = xc + o
            h = rms_norm(xc, lp["ln2"])
            xc = xc + (
                moe_apply(lp["moe"], h, cfg) if moe
                else mlp_apply(lp["mlp"], h, cfg)
            )
            return (xc,), (ck, cv)

        return body

    new_k, new_v = [], []
    off = 0
    for group, moe in (("layers", False), ("moe_layers", True)):
        if group not in params:
            continue
        n = jax.tree_util.tree_leaves(params[group])[0].shape[0]
        ck = jax.lax.dynamic_slice_in_dim(cache["k"], off, n, axis=0)
        cv = jax.lax.dynamic_slice_in_dim(cache["v"], off, n, axis=0)
        (x,), (ck, cv) = jax.lax.scan(
            body_fn(moe), (x,), (params[group], ck, cv)
        )
        new_k.append(ck)
        new_v.append(cv)
        off += n
    cache = {"k": jnp.concatenate(new_k, 0), "v": jnp.concatenate(new_v, 0)}
    x = rms_norm(x, params["ln_f"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return x @ head, cache


def decode_step_enc_dec(params, cfg, token, cache, pos, enc_states=None):
    """Decoder step with self-attn cache + cached cross-attention K/V.

    ``enc_states`` is only needed when the cache was not primed (it then
    primes on the fly — the slow path kept for API compatibility)."""
    if enc_states is not None and "xk" not in cache:
        cache = prime_cross_cache(params, cfg, cache, enc_states)
    x = params["embed"][token]

    def body(carry, scanned):
        xc, = carry
        lp, ck, cv, xk, xv = scanned
        h = rms_norm(xc, lp["ln1"])
        o, ck, cv = decode_attention(lp["attn"], h, cfg, ck, cv, pos)
        xc = xc + o
        b = xc.shape[0]
        positions = jnp.full((b, 1), pos, jnp.int32)
        xc = xc + attention_block(
            lp["xattn"], rms_norm(xc, lp["ln_x"]), cfg, positions,
            causal=False, kv_override=(xk, xv),
        )
        xc = xc + mlp_apply(lp["mlp"], rms_norm(xc, lp["ln2"]), cfg)
        return (xc,), (ck, cv)

    (x,), (nk, nv) = jax.lax.scan(
        body, (x,),
        (params["dec_layers"], cache["k"], cache["v"], cache["xk"],
         cache["xv"]),
    )
    cache = {"k": nk, "v": nv, "xk": cache["xk"], "xv": cache["xv"]}
    x = rms_norm(x, params["ln_f"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return x @ head, cache


# --------------------------------------------------------------------------
# Loss
# --------------------------------------------------------------------------


def lm_loss(params, cfg, batch):
    """batch: {tokens, labels, [embeds], [frames]} -> scalar loss."""
    if cfg.enc_dec:
        logits = forward_enc_dec(params, cfg, batch["frames"], batch["tokens"])
    else:
        logits = forward(params, cfg, batch["tokens"], batch.get("embeds"))
        if batch.get("embeds") is not None:
            logits = logits[:, batch["embeds"].shape[1]:]
    return cross_entropy(logits, batch["labels"])
