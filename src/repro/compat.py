"""Platform decisions: interpreter vs chip, and the compile cache.

Two decisions live here and nowhere else:

* :func:`default_interpret` — whether Pallas kernels run under the
  interpreter. Every ``interpret: bool | None = None`` argument in the
  package resolves through :func:`resolve_interpret`, so the CPU backend
  (tests, rehearsals) interprets and an accelerator backend always runs
  the compiled kernel; nothing on the run path picks the interpreter on
  a TPU unless a caller passes ``interpret=True`` explicitly.
* :func:`enable_compile_cache` — where JAX's persistent compilation
  cache lives: ``$JAX_COMPILATION_CACHE_DIR`` when it is set, otherwise
  the fixed in-checkout directory :data:`CHECKOUT_CACHE_DIR`.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: Persistent compile cache used when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: a fixed, git-ignored directory at the root of the checkout
#: (``src/repro/compat.py`` → ``<checkout>/.jax_cache``). The path is part
#: of the cache's key, so it never depends on a temp name, pid or time.
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def default_interpret() -> bool:
    """True only on the CPU backend: there Pallas kernels run under the
    interpreter; on a TPU they are compiled."""
    return jax.default_backend() == "cpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """An explicit ``interpret`` wins; ``None`` asks
    :func:`default_interpret`."""
    return default_interpret() if interpret is None else bool(interpret)


def compile_cache_dir(environ=os.environ) -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else
    :data:`CHECKOUT_CACHE_DIR`."""
    return environ.get("JAX_COMPILATION_CACHE_DIR") or CHECKOUT_CACHE_DIR


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and
    no other directory is set here.
    """
    path = compile_cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", path)
    return path


__all__ = [
    "CHECKOUT_CACHE_DIR",
    "compile_cache_dir",
    "default_interpret",
    "enable_compile_cache",
    "resolve_interpret",
]
