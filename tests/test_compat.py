"""The platform decisions in ``repro.compat``: interpreter vs chip, and
where the persistent compile cache lives."""

from __future__ import annotations

import jax

from repro import compat


def test_default_interpret_follows_the_backend(monkeypatch):
    assert compat.default_interpret() is (jax.default_backend() == "cpu")
    monkeypatch.setattr(compat.jax, "default_backend", lambda: "tpu")
    assert compat.default_interpret() is False
    assert compat.resolve_interpret(None) is False
    monkeypatch.setattr(compat.jax, "default_backend", lambda: "cpu")
    assert compat.default_interpret() is True
    assert compat.resolve_interpret(None) is True


def test_explicit_interpret_wins(monkeypatch):
    monkeypatch.setattr(compat.jax, "default_backend", lambda: "tpu")
    assert compat.resolve_interpret(True) is True
    monkeypatch.setattr(compat.jax, "default_backend", lambda: "cpu")
    assert compat.resolve_interpret(False) is False


def test_compile_cache_env_var_wins():
    env = {"JAX_COMPILATION_CACHE_DIR": "/somewhere/else"}
    assert compat.compile_cache_dir(env) == "/somewhere/else"


def test_compile_cache_defaults_to_fixed_checkout_path():
    path = compat.compile_cache_dir({})
    assert path == compat.CHECKOUT_CACHE_DIR
    assert path.endswith(".jax_cache")
    # the same path on every call: part of the cache key, never a
    # temp name, pid or time
    assert compat.compile_cache_dir({}) == path
    assert compat.compile_cache_dir({"JAX_COMPILATION_CACHE_DIR": ""}) == path


def test_enable_compile_cache_sets_only_the_fallback(monkeypatch):
    calls = []
    monkeypatch.setattr(compat.jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/from/env")
    assert compat.enable_compile_cache() == "/from/env"
    assert calls == []  # JAX reads the variable itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compat.enable_compile_cache() == compat.CHECKOUT_CACHE_DIR
    assert calls == [("jax_compilation_cache_dir",
                      compat.CHECKOUT_CACHE_DIR)]


def test_stream_kernel_default_runs_interpreted_on_cpu():
    """With ``interpret`` left unset the CPU backend interprets: the
    launch accepts a width no TPU tile fits."""
    from repro.apps import diffusion as dif

    sim = dif.DiffusionSimulation(16, 24)
    u0, _ = dif.sine_init(16, 24)
    out = sim.kernel.run_blocked(sim.state(u0), (0.2,), steps=2, m=2,
                                 block_h=8)
    assert out.shape == (1, 16, 24)


def test_run_path_defaults_resolve_to_the_chip_on_tpu(monkeypatch):
    """On a TPU backend the serving engine, its resolver and the search
    runner all default to compiled kernels."""
    from repro.core.dse import StreamWorkload
    from repro.core.search import SearchRunner
    from repro.serve.sim import PlanResolver, SimEngine

    monkeypatch.setattr(compat.jax, "default_backend", lambda: "tpu")
    assert PlanResolver().interpret is False
    engine = SimEngine()
    assert engine.interpret is False and engine.resolver.interpret is False
    runner = SearchRunner(
        workload=StreamWorkload("t", 7, 1, 1, 100, 1000, 64 * 64,
                                grid_w=64),
        grid_shape=(64, 64), run_factory=lambda *a, **k: None,
        cache=False, max_devices=1,
    )
    assert runner.interpret is False
