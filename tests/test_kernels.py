"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode executes the kernel bodies in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.attention.flash import flash_decode, flash_prefill
from repro.kernels.attention.ref import decode_ref, mha_ref
from repro.kernels.ssd.ref import ssd_ref
from repro.kernels.ssd.ssd import ssd_pallas

KEY = jax.random.key(0)


# --------------------------------------------------------- flash attention
@pytest.mark.parametrize("b,sq,skv,hq,hkv,d,causal,window,dtype", [
    (2, 128, 128, 4, 2, 32, True, 0, jnp.float32),
    (2, 128, 128, 4, 1, 32, True, 48, jnp.float32),
    (1, 256, 256, 8, 8, 64, True, 0, jnp.bfloat16),
    (2, 128, 128, 4, 4, 32, False, 0, jnp.float32),
    (1, 64, 128, 2, 2, 16, False, 0, jnp.float32),   # cross-attn shape
])
def test_flash_prefill_sweep(b, sq, skv, hq, hkv, d, causal, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, sq, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, skv, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, skv, hkv, d), dtype)
    ref = mha_ref(q, k, v, causal=causal, window=window)
    out = flash_prefill(q, k, v, causal=causal, window=window, block_q=64,
                        block_k=64, interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


@pytest.mark.parametrize("b,s,hq,hkv,d,pos,window,dtype", [
    (2, 256, 8, 2, 32, 255, 0, jnp.float32),
    (2, 256, 8, 2, 32, 100, 0, jnp.float32),
    (2, 256, 4, 1, 64, 200, 64, jnp.bfloat16),
    (1, 128, 16, 16, 32, 64, 0, jnp.float32),
])
def test_flash_decode_sweep(b, s, hq, hkv, d, pos, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, 1, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    ref = decode_ref(q, k, v, position=pos, window=window)
    out = flash_decode(q, k, v, position=pos, window=window, block_k=64,
                       interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol)


# ------------------------------------------------------------------- SSD
@pytest.mark.parametrize("B,S,H,P,G,N,Q,dtype", [
    (2, 64, 4, 16, 1, 32, 16, jnp.float32),
    (1, 128, 4, 32, 2, 16, 32, jnp.float32),
    (2, 64, 2, 16, 1, 16, 64, jnp.float32),
    (1, 128, 8, 32, 1, 64, 32, jnp.bfloat16),
])
def test_ssd_kernel_sweep(B, S, H, P, G, N, Q, dtype):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(
        jnp.float32)
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    b = jax.random.normal(ks[3], (B, S, G, N), dtype)
    c = jax.random.normal(ks[4], (B, S, G, N), dtype)
    yr, sr = ssd_ref(x, dt, a, b, c, Q)
    yp, sp = ssd_pallas(x, dt, a, b, c, chunk=Q, interpret=True)
    scale = max(1.0, float(np.max(np.abs(np.asarray(yr, np.float32)))))
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    assert np.max(np.abs(np.asarray(yr, np.float32)
                         - np.asarray(yp, np.float32))) / scale < tol
    assert np.max(np.abs(np.asarray(sr, np.float32)
                         - np.asarray(sp, np.float32))) < tol * 10


def test_ssd_kernel_vs_recurrence():
    """Kernel must agree with the token-by-token recurrence, not just the
    chunked oracle (guards against shared bugs)."""
    from repro.models.ssm import ssd_decode_step
    B, S, H, P, G, N, Q = 1, 32, 2, 8, 1, 8, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    b = jax.random.normal(ks[3], (B, S, G, N), jnp.float32)
    c = jax.random.normal(ks[4], (B, S, G, N), jnp.float32)
    yp, sp = ssd_pallas(x, dt, a, b, c, chunk=Q, interpret=True)
    state = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(S):
        yt, state = ssd_decode_step(state, x[:, t], dt[:, t], a, b[:, t],
                                    c[:, t])
        ys.append(yt)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(y_seq), atol=2e-4)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(state), atol=2e-4)
