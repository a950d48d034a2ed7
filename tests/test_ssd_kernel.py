"""SSD intra-chunk Pallas kernel vs oracle + vs the model's chunked scan."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

pytestmark = pytest.mark.slow  # full-lane only; tier-1 covers this path via faster tests


def make_inputs(bs, nc, l, h, p, n, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (bs, nc, l, h, p), jnp.float32).astype(dtype)
    dt = jax.random.uniform(ks[1], (bs, nc, l, h), jnp.float32, 0.1, 0.9)
    # cumulative log-decay: positive, increasing within a chunk
    steps = jax.random.uniform(ks[2], (bs, nc, l, h), jnp.float32, 0.01, 0.2)
    cum = jnp.cumsum(steps, axis=2)
    b = jax.random.normal(ks[3], (bs, nc, l, n), jnp.float32)
    c = jax.random.normal(ks[4], (bs, nc, l, n), jnp.float32)
    return x, dt, cum, b, c


class TestSsdChunkKernel:
    @pytest.mark.parametrize("bs,nc,l,h,p,n", [
        (1, 2, 64, 2, 32, 16),
        (2, 1, 128, 3, 64, 32),
        (1, 4, 32, 1, 16, 8),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_oracle(self, bs, nc, l, h, p, n, dtype):
        x, dt, cum, b, c = make_inputs(bs, nc, l, h, p, n, dtype)
        out = ops.ssd_chunk(x, dt, cum, b, c, interpret=True)
        expect = ref.ssd_chunk_ref(x, dt, cum, b, c)
        tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(expect, np.float32), atol=tol, rtol=tol
        )

    def test_matches_model_intra_term(self):
        """The kernel computes exactly the y_intra of models.ssm._ssd_chunked
        when there is a single chunk (no inter-chunk contribution)."""
        from repro.models.ssm import _ssd_chunked

        bs, l, h, p, n = 2, 32, 2, 16, 8
        ks = jax.random.split(jax.random.PRNGKey(1), 4)
        x = jax.random.normal(ks[0], (bs, l, h, p), jnp.float32)
        dt = jax.random.uniform(ks[1], (bs, l, h), jnp.float32, 0.1, 0.9)
        a = jax.random.uniform(ks[2], (h,), jnp.float32, 0.1, 1.0)
        b = jax.random.normal(ks[3], (bs, l, n), jnp.float32)
        c = jax.random.normal(jax.random.PRNGKey(2), (bs, l, n), jnp.float32)

        y_model, _ = _ssd_chunked(x, dt, a, b, c, chunk=l)  # single chunk
        cum = jnp.cumsum(dt * a, axis=1)  # (B, L, H)
        y_kernel = ops.ssd_chunk(
            x[:, None], dt[:, None], cum[:, None], b[:, None], c[:, None],
            interpret=True,
        )[:, 0]
        np.testing.assert_allclose(
            np.asarray(y_kernel), np.asarray(y_model), atol=2e-4, rtol=1e-3
        )
