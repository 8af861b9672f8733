"""Per-kernel allclose vs pure-jnp oracles, swept over shapes & dtypes
(the module opts into the Pallas interpreter, which executes the kernel
bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.fixture(autouse=True, scope="module")
def _pallas_interpreter():
    """Kernels never pick the interpreter by backend; this module asks."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_PALLAS_INTERPRET", "1")
        yield


@pytest.mark.parametrize("n,d1,d2", [(64, 128, 128), (100, 257, 3),
                                     (33, 7, 17), (512, 64, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ridge_gram(n, d1, d2, dtype):
    from repro.kernels.ridge_gram import ops, ref
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d1), dtype)
    y = jax.random.normal(jax.random.PRNGKey(1), (n, d2), dtype)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(ops.gram(x, y), ref.gram(x, y),
                               rtol=tol, atol=tol * n)


@pytest.mark.parametrize("n,d", [(64, 256), (17, 33), (512, 16), (1, 8)])
@pytest.mark.parametrize("temp", [1.0, 2.0])
def test_kl_mutual(n, d, temp):
    from repro.kernels.kl_mutual import ops, ref
    x = jax.random.normal(jax.random.PRNGKey(0), (n, d)) * 3
    y = jax.random.normal(jax.random.PRNGKey(1), (n, d)) * 3
    got = ops.kl_loss(x, y, temperature=temp)
    want = jnp.mean(ref.kl_rows(x, y, temp))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert got >= -1e-6                      # KL >= 0
    same = ops.kl_loss(x, x, temperature=temp)
    np.testing.assert_allclose(same, 0.0, atol=1e-5)   # KL(p‖p) = 0


def test_kl_gradient_matches_ref():
    from repro.kernels.kl_mutual import ops, ref
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 64))
    y = jax.random.normal(jax.random.PRNGKey(1), (32, 64))
    g1 = jax.grad(lambda x: ops.kl_loss(x, y))(x)
    g2 = jax.grad(lambda x: jnp.mean(ref.kl_rows(x, y)))(x)
    np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("B,H,KV,S,D", [(2, 4, 2, 128, 64), (1, 8, 1, 256, 64),
                                        (2, 3, 3, 96, 32), (1, 2, 2, 64, 128)])
@pytest.mark.parametrize("window", [None, 64])
def test_flash_attention(B, H, KV, S, D, window):
    from repro.kernels.flash_attention import ops, ref
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, KV, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, KV, S, D), jnp.float32)
    o1 = ops.flash_attention(q, k, v, window=window)
    o2 = ref.attention(q, k, v, scale=1.0 / D ** 0.5, window=window)
    np.testing.assert_allclose(o1, o2, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    from repro.kernels.flash_attention import ops, ref
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 4, 128, 64), dtype)
    k = jax.random.normal(ks[1], (1, 2, 128, 64), dtype)
    v = jax.random.normal(ks[2], (1, 2, 128, 64), dtype)
    o1 = ops.flash_attention(q, k, v)
    o2 = ref.attention(q, k, v, scale=1.0 / 8.0)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(o1.astype(jnp.float32),
                               o2.astype(jnp.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("b,L,nh,N,P,chunk", [
    (2, 64, 3, 16, 32, 32), (1, 200, 2, 8, 16, 64), (1, 32, 1, 64, 64, 8)])
def test_mamba2_scan(b, L, nh, N, P, chunk):
    from repro.kernels.mamba2_scan import ops, ref
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    decay = jax.nn.sigmoid(jax.random.normal(ks[0], (b, L, nh))) * 0.6 + 0.35
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, L, nh)))
    B = jax.random.normal(ks[2], (b, L, N))
    C = jax.random.normal(ks[3], (b, L, N))
    x = jax.random.normal(ks[4], (b, L, nh, P))
    y1 = ops.mamba2_scan(decay, dt, B, C, x, chunk=chunk)
    y2 = ref.mamba2_scan(decay, dt, B, C, x)
    np.testing.assert_allclose(y1, y2, rtol=1e-3, atol=1e-3)


def test_mamba2_scan_strong_decay_stable():
    """Near-zero decay (long-context forgetting) must not overflow the
    log-space chunk math."""
    from repro.kernels.mamba2_scan import ops, ref
    b, L, nh, N, P = 1, 128, 2, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    decay = jnp.full((b, L, nh), 1e-4)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, L, nh)))
    B = jax.random.normal(ks[2], (b, L, N))
    C = jax.random.normal(ks[3], (b, L, N))
    x = jax.random.normal(ks[4], (b, L, nh, P))
    y1 = ops.mamba2_scan(decay, dt, B, C, x, chunk=64)
    y2 = ref.mamba2_scan(decay, dt, B, C, x)
    assert jnp.isfinite(y1).all()
    np.testing.assert_allclose(y1, y2, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("b,L,nh,P,chunk", [(2, 64, 2, 16, 32),
                                            (1, 100, 3, 32, 64),
                                            (1, 16, 1, 64, 16)])
def test_rwkv6_wkv(b, L, nh, P, chunk):
    from repro.kernels.rwkv6_wkv import ops, ref
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    r = jax.random.normal(ks[0], (b, L, nh, P))
    k = jax.random.normal(ks[1], (b, L, nh, P))
    v = jax.random.normal(ks[2], (b, L, nh, P))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, L, nh, P)))
    u = jax.random.normal(ks[4], (nh, P))
    y1 = ops.rwkv6_wkv(r, k, v, w, u, chunk=chunk)
    y2 = ref.rwkv6_wkv(r, k, v, w, u)
    np.testing.assert_allclose(y1, y2, rtol=1e-4, atol=1e-4)


def test_model_paths_use_kernels_consistently():
    """mamba2/rwkv6 forward with use_kernel=True must match the scan path."""
    from repro.configs.base import get_config
    from repro.models import mamba2, rwkv6
    cfg = get_config("zamba2-2.7b").reduced()
    p = mamba2.init_mamba2(jax.random.PRNGKey(0), cfg.d_model, cfg.ssm,
                           jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.d_model))
    y1 = mamba2.mamba2_forward(p, x, cfg.ssm, use_kernel=False)
    y2 = mamba2.mamba2_forward(p, x, cfg.ssm, use_kernel=True)
    np.testing.assert_allclose(y1, y2, rtol=2e-3, atol=2e-3)

    cfg = get_config("rwkv6-1.6b").reduced()
    p = rwkv6.init_rwkv6(jax.random.PRNGKey(0), cfg.d_model, cfg.d_ff,
                         cfg.ssm, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.d_model))
    y1 = rwkv6.rwkv6_time_mix(p, x, cfg.ssm, use_kernel=False)
    y2 = rwkv6.rwkv6_time_mix(p, x, cfg.ssm, use_kernel=True)
    np.testing.assert_allclose(y1, y2, rtol=2e-3, atol=2e-3)
