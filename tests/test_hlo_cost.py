"""The trip-count-aware HLO cost walker (roofline foundation)."""
import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import analyze


def walked(fn, *args):
    c = jax.jit(fn).lower(*args).compile()
    return analyze(c.as_text()), c


class TestWalker:
    def test_matmul_exact(self):
        a, b = jnp.ones((256, 512)), jnp.ones((512, 128))
        w, c = walked(lambda a, b: a @ b, a, b)
        assert w["flops"] == 2 * 256 * 512 * 128
        assert w["flops"] == c.cost_analysis()["flops"]

    def test_scan_multiplies_body(self):
        a = jnp.ones((128, 128))

        def f(x):
            def body(c, _):
                return c @ a, None
            y, _ = jax.lax.scan(body, x, None, length=10)
            return y

        w, c = walked(f, jnp.ones((128, 128)))
        dots = 10 * 2 * 128**3
        assert w["flops"] == pytest.approx(dots, rel=0.02)
        # XLA's own count misses the trip count
        assert c.cost_analysis()["flops"] < w["flops"]
        assert w["unknown_trip_loops"] == 0

    def test_nested_scan(self):
        a = jnp.ones((64, 64))

        def f(x):
            def outer(co, _):
                def inner(ci, _):
                    return ci @ a, None
                y, _ = jax.lax.scan(inner, co, None, length=4)
                return y, None
            y, _ = jax.lax.scan(outer, x, None, length=3)
            return y

        w, _ = walked(f, jnp.ones((64, 64)))
        assert w["flops"] == pytest.approx(12 * 2 * 64**3, rel=0.05)

    def test_fori_loop(self):
        a = jnp.ones((64, 64))

        def f(x):
            return jax.lax.fori_loop(0, 7, lambda i, c: jnp.tanh(c @ a), x)

        w, _ = walked(f, jnp.ones((64, 64)))
        assert w["flops"] >= 7 * 2 * 64**3

    def test_grad_counts_both_passes(self):
        a = jnp.ones((128, 64))

        def loss(w_):
            return jnp.sum(jnp.tanh(a @ w_) ** 2)

        w, _ = walked(jax.grad(loss), jnp.ones((64, 32)))
        fwd = 2 * 128 * 64 * 32
        # fwd matmul + dL/dw matmul (a is a constant: no dL/da matmul)
        assert w["flops"] >= 1.9 * fwd

    def test_bytes_positive(self):
        w, _ = walked(lambda x: x * 2.0, jnp.ones((1000,)))
        assert w["bytes"] >= 2 * 4000
