"""Public wrappers for attention: kernel on TPU, chunked ref elsewhere."""

from __future__ import annotations

import jax

from .flash_attention import flash_attention
from .ref import attention_chunked_ref, attention_ref


def attention(q, k, v, *, causal: bool = True, window: int = 0,
              scale: float | None = None, use_pallas: bool | None = None,
              interpret: bool | None = None):
    """Dispatch attention to the Pallas kernel or the jnp reference.

    ``use_pallas=None`` auto-selects: the kernel on TPU backends, the
    chunked reference otherwise (CPU dry-runs must lower through XLA).
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        return flash_attention(
            q, k, v, causal=causal, window=window, scale=scale,
            interpret=interpret,
        )
    sk = k.shape[2]
    chunk = 512 if sk % 512 == 0 else sk
    return attention_chunked_ref(
        q, k, v, causal=causal, window=window, scale=scale, chunk=chunk
    )


__all__ = ["attention", "attention_chunked_ref", "attention_ref",
           "flash_attention"]
