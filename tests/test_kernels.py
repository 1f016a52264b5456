"""Per-kernel shape/dtype sweeps vs. ref.py oracles (interpret mode: the
kernels compile by default; these CPU tests ask for the interpreter)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else dict(
        rtol=3e-5, atol=3e-5
    )


@pytest.mark.parametrize("M,K,N", [(128, 512, 1), (256, 1024, 1),
                                   (256, 2048, 4), (64, 256, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemv_sweep(M, K, N, dtype):
    a = jax.random.normal(RNG, (M, K), jnp.float32).astype(dtype)
    x = jax.random.normal(jax.random.PRNGKey(1), (K, N), jnp.float32).astype(dtype)
    y = ops.gemv(a, x, bm=64, bk=256, interpret=True)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(ref.gemv_ref(a, x), np.float32),
        **_tol(dtype),
    )


@pytest.mark.parametrize("n_dev,my_dev", [(4, 0), (4, 1), (4, 3), (8, 5)])
def test_gemv_tiles_values_and_schedule(n_dev, my_dev):
    M, K = 256, 1024
    a = jax.random.normal(RNG, (M, K), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (K, 1), jnp.float32)
    y, prog = ops.gemv_tiles(a, x, n_dev=n_dev, my_dev=my_dev, bm=32, bk=256,
                             interpret=True)
    np.testing.assert_allclose(
        y, ref.gemv_tiles_ref(a, x, n_dev, my_dev), rtol=3e-5, atol=3e-5
    )
    served = list(np.asarray(prog))
    tiles_per_dev = (M // 32) // n_dev
    # remote-first order: successor owners first, self last (paper Fig. 3)
    expect = []
    for step in range(1, n_dev + 1):
        expect += [(my_dev + step) % n_dev] * tiles_per_dev
    assert served == expect
    assert served[-1] == my_dev  # local tiles computed last


@pytest.mark.parametrize("B,H,KV,D,S", [(1, 4, 1, 32, 512), (2, 8, 2, 64, 1024),
                                        (2, 8, 8, 32, 768)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, H, KV, D, S, dtype):
    q = jax.random.normal(RNG, (B, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, D), jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, D), jnp.float32).astype(dtype)
    length = S - 7
    o = ops.decode_attention(q, k, v, jnp.int32(length), bs=256,
                             interpret=True)
    o_ref = ref.decode_attention_ref(q, k, v, length)
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(o_ref, np.float32), **_tol(dtype)
    )


def test_decode_attention_respects_length_mask():
    B, H, KV, D, S = 1, 2, 1, 16, 256
    q = jax.random.normal(RNG, (B, H, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, D), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, D), jnp.float32)
    o_small = ops.decode_attention(q, k, v, jnp.int32(10), bs=64,
                                   interpret=True)
    # garbage beyond the length must not affect the result
    k2 = k.at[:, 10:].set(99.0)
    v2 = v.at[:, 10:].set(-99.0)
    o_small2 = ops.decode_attention(q, k2, v2, jnp.int32(10), bs=64,
                                    interpret=True)
    np.testing.assert_allclose(o_small, o_small2, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("shape", [(4, 128), (2, 33, 256), (1, 7, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(shape, dtype):
    x = jax.random.normal(RNG, shape, jnp.float32).astype(dtype)
    g = jax.random.normal(jax.random.PRNGKey(1), (shape[-1],), jnp.float32) * 0.2
    y = ops.rmsnorm(x, g, br=32, interpret=True)
    np.testing.assert_allclose(
        np.asarray(y, np.float32),
        np.asarray(ref.rmsnorm_ref(x, g), np.float32),
        **_tol(dtype),
    )
