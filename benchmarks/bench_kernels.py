"""Pallas kernel microbenchmarks (the Pallas interpreter off the TPU — the
us_per_call numbers there are for regression tracking, not TPU
projections; `derived` carries the workload size)."""
from __future__ import annotations

import jax

from benchmarks.common import Row, pallas_interpreter_off_tpu, time_fn


def run(fast: bool = False):
    with pallas_interpreter_off_tpu():
        return _run()


def _run():
    rows: list[Row] = []
    key = jax.random.PRNGKey(0)

    from repro.kernels.ridge_gram import ops as rg
    n, d = (2048, 257)
    x = jax.random.normal(key, (n, d))
    us = time_fn(lambda: rg.gram(x, x))
    rows.append(("kernel_ridge_gram", us,
                 f"gflop={2 * n * d * d / 1e9:.3f}"))

    from repro.kernels.kl_mutual import ops as kl
    x = jax.random.normal(key, (4096, 256))
    y = jax.random.normal(jax.random.PRNGKey(1), (4096, 256))
    us = time_fn(lambda: kl.kl_loss(x, y, temperature=2.0))
    rows.append(("kernel_kl_mutual", us, "rows=4096;d=256"))

    from repro.kernels.flash_attention import ops as fa
    B, H, KV, S, D = 1, 4, 2, 512, 64
    q = jax.random.normal(key, (B, H, S, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, KV, S, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, KV, S, D))
    us = time_fn(lambda: fa.flash_attention(q, k, v))
    rows.append(("kernel_flash_attention", us,
                 f"gflop={4 * B * H * S * S * D / 1e9:.3f}"))

    from repro.kernels.mamba2_scan import ops as ms
    b, L, nh, N, P = 1, 512, 4, 64, 64
    ks = jax.random.split(key, 5)
    decay = jax.nn.sigmoid(jax.random.normal(ks[0], (b, L, nh))) * 0.5 + 0.45
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, L, nh)))
    Bm = jax.random.normal(ks[2], (b, L, N))
    C = jax.random.normal(ks[3], (b, L, N))
    xm = jax.random.normal(ks[4], (b, L, nh, P))
    us = time_fn(lambda: ms.mamba2_scan(decay, dt, Bm, C, xm))
    rows.append(("kernel_mamba2_scan", us, f"tokens={L};heads={nh}"))

    from repro.kernels.rwkv6_wkv import ops as rw
    r = jax.random.normal(ks[0], (1, 256, 4, 64))
    k2 = jax.random.normal(ks[1], (1, 256, 4, 64))
    v2 = jax.random.normal(ks[2], (1, 256, 4, 64))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (1, 256, 4, 64)))
    u = jax.random.normal(ks[4], (4, 64))
    us = time_fn(lambda: rw.rwkv6_wkv(r, k2, v2, w, u))
    rows.append(("kernel_rwkv6_wkv", us, "tokens=256;heads=4"))
    return rows
