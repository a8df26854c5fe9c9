"""Trip-count-aware HLO cost analyzer: scan == unroll == analytic truth."""

import jax
import jax.numpy as jnp

from repro.launch.hlo_cost import analyze_hlo

X = jax.ShapeDtypeStruct((128, 256), jnp.float32)
W = jax.ShapeDtypeStruct((256, 256), jnp.float32)
WANT10 = 2 * 128 * 256 * 256 * 10


def _flops(f):
    return analyze_hlo(jax.jit(f).lower(X, W).compile().as_text()).flops


def test_scan_trip_scaling():
    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    assert abs(_flops(f) - WANT10) / WANT10 < 0.01


def test_unrolled_matches_scan():
    def f(x, w):
        for _ in range(10):
            x = jnp.tanh(x @ w)
        return x

    assert abs(_flops(f) - WANT10) / WANT10 < 0.01


def test_nested_scans_multiply():
    def f(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            c, _ = jax.lax.scan(inner, c, None, length=5)
            return c, None
        y, _ = jax.lax.scan(outer, x, None, length=4)
        return y

    want = 2 * 128 * 256 * 256 * 20
    assert abs(_flops(f) - want) / want < 0.01


def test_collectives_counted_inside_loops():
    """psum inside a scan must scale by the trip count."""
    import numpy as np
    from jax.sharding import PartitionSpec as P

    if jax.device_count() < 1:
        return


    def f(x):
        def body(c, _):
            return jax.lax.psum(c, "i"), None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    mesh = jax.make_mesh((1,), ("i",))
    g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P()))
    hlo = g.lower(jax.ShapeDtypeStruct((64,), jnp.float32)).compile().as_text()
    hc = analyze_hlo(hlo)
    # 7 iterations x 64 floats x 4B (device_count=1 may elide the op; accept
    # either exact scaling or elision)
    assert hc.coll_bytes in (0, 7 * 64 * 4) or hc.coll_bytes % (64 * 4) == 0
