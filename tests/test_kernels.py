"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret
mode (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.fused_adam import fused_adam
from repro.kernels.selective_scan import selective_scan_fwd


@pytest.mark.parametrize("B,H,S,hd", [
    (1, 1, 128, 64), (2, 4, 256, 64), (1, 2, 512, 128), (2, 1, 384, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, H, S, hd, dtype, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), dtype)
    k = jax.random.normal(ks[1], (B, H, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, H, S, hd), dtype)
    out = flash_attention_fwd(q, k, v, causal=causal, block_q=128, block_k=128,
                              interpret=True)
    want = ref.ref_attention(q, k, v, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("blocks", [(64, 64), (128, 32), (256, 128)])
def test_flash_attention_block_shapes(blocks):
    """Result must be independent of the BlockSpec tiling."""
    bq, bk = blocks
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    out = flash_attention_fwd(q, k, v, block_q=bq, block_k=bk,
                              interpret=True)
    want = ref.ref_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("B,S,di,st", [
    (1, 64, 128, 8), (2, 64, 256, 16), (1, 128, 512, 16), (2, 96, 384, 4),
])
def test_selective_scan_sweep(B, S, di, st):
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    x = jax.random.normal(ks[0], (B, S, di)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di)) * 0.2)
    A = -jnp.exp(jax.random.normal(ks[2], (di, st)) * 0.3)
    Bc = jax.random.normal(ks[3], (B, S, st))
    Cc = jax.random.normal(ks[4], (B, S, st))
    D = jnp.ones((di,))
    y, h = selective_scan_fwd(x, dt, A, Bc, Cc, D, block_d=128, block_t=32,
                              interpret=True)
    yr, hr = ref.ref_selective_scan(x, dt, A, Bc, Cc, D)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-4)


def test_selective_scan_matches_model_scan():
    """Kernel agrees with the model's chunked lax.scan implementation."""
    from repro.models.mamba import selective_scan as model_scan
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    B, S, di, st = 2, 64, 256, 16
    x = jax.random.normal(ks[0], (B, S, di)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di)) * 0.2)
    A = -jnp.exp(jax.random.normal(ks[2], (di, st)) * 0.3)
    Bc = jax.random.normal(ks[3], (B, S, st))
    Cc = jax.random.normal(ks[4], (B, S, st))
    D = jnp.ones((di,))
    y1, h1 = selective_scan_fwd(x, dt, A, Bc, Cc, D, interpret=True)
    y2, h2 = model_scan(x, dt, A, Bc, Cc, D)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-4)


@pytest.mark.parametrize("n", [100, 1024, 4097, 65536])
@pytest.mark.parametrize("step", [1, 10])
def test_fused_adam_sweep(n, step):
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    p = jax.random.normal(ks[0], (n,), jnp.float32)
    m = jax.random.normal(ks[1], (n,)) * 0.1
    v = jnp.abs(jax.random.normal(ks[2], (n,))) * 0.01
    g = jax.random.normal(ks[3], (n,))
    p2, m2, v2, lp = fused_adam(p, m, v, g, step, lr=1e-2, interpret=True)
    pr, mr, vr = ref.ref_adam(p, m, v, g, step, lr=1e-2)
    np.testing.assert_allclose(np.asarray(p2), np.asarray(pr), atol=1e-6)
    np.testing.assert_allclose(np.asarray(m2), np.asarray(mr), atol=1e-7)
    np.testing.assert_allclose(np.asarray(v2), np.asarray(vr), atol=1e-7)
    np.testing.assert_allclose(np.asarray(lp, np.float32), np.asarray(pr),
                               atol=2e-2)  # bf16 low-precision copy


def test_fused_adam_partial_matches_two_stage():
    """Early [0,k) + late [k,n) kernel launches == one full launch —
    the α-delayed optimizer identity at kernel level."""
    n, k, step = 10_000, 6_000, 5
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    p = jax.random.normal(ks[0], (n,), jnp.float32)
    m = jnp.zeros((n,))
    v = jnp.zeros((n,))
    g = jax.random.normal(ks[3], (n,))
    pf, mf, vf, _ = fused_adam(p, m, v, g, step, lr=1e-2, interpret=True)
    p1, m1, v1, _ = fused_adam(p, m, v, g, step, lo=0, hi=k, lr=1e-2,
                             interpret=True)
    p2, m2, v2, _ = fused_adam(p1, m1, v1, g, step, lo=k, hi=n, lr=1e-2,
                             interpret=True)
    np.testing.assert_allclose(np.asarray(p2), np.asarray(pf), atol=1e-7)
    np.testing.assert_allclose(np.asarray(m2), np.asarray(mf), atol=1e-7)
    np.testing.assert_allclose(np.asarray(v2), np.asarray(vf), atol=1e-7)
