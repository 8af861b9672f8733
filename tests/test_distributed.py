"""Distributed SplitMe/SFL rounds (shard_map) + MoE dispatch variants.

``make_splitme_round`` is now an engine adapter (the shard_map round lives
in ``repro.core.engine.build_sharded_round_fn``); the hand-written vanilla
SFL boundary-exchange round moved to ``repro.launch.fl_dryrun`` (dry-run
collective accounting only)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.splitme_dnn import DNN10
from repro.core import dnn
from repro.core.distributed import (make_distributed_inversion,
                                    make_splitme_round)
from repro.launch.fl_dryrun import make_sfl_round
from repro.launch.mesh import make_host_mesh


@pytest.fixture(scope="module")
def setup():
    mesh = make_host_mesh()
    w_c = dnn.init_client(jax.random.PRNGKey(0), DNN10)
    w_i = dnn.init_inverse_server(jax.random.PRNGKey(1), DNN10)
    w_s = dnn.init_server(jax.random.PRNGKey(2), DNN10)
    rng = np.random.default_rng(0)
    M, n = 4, 32
    x = jnp.asarray(rng.normal(size=(M, n, DNN10.n_features)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 3, (M, n)), jnp.int32)
    return mesh, w_c, w_i, w_s, x, y


def test_splitme_round_trains_and_aggregates(setup):
    mesh, w_c, w_i, _, x, y = setup
    y1 = jax.nn.one_hot(y, 3)
    rnd = make_splitme_round(DNN10, mesh, n_clients=4, samples_per_client=32,
                             E=3)
    wc2, wi2 = jax.jit(rnd)(w_c, w_i, x, y1, jax.random.PRNGKey(5))
    # params moved and stayed finite
    for a, b in zip(jax.tree.leaves(w_c), jax.tree.leaves(wc2)):
        assert a.shape == b.shape
        assert jnp.isfinite(b).all()
    delta = sum(float(jnp.abs(a - b).sum()) for a, b in
                zip(jax.tree.leaves(w_c), jax.tree.leaves(wc2)))
    assert delta > 0


def test_sfl_round_runs(setup):
    mesh, w_c, _, w_s, x, y = setup
    rnd = make_sfl_round(DNN10, mesh, n_clients=4, samples_per_client=32, E=2)
    wc2, ws2 = jax.jit(rnd)(w_c, w_s, x, y, jax.random.PRNGKey(6))
    assert all(jnp.isfinite(l).all() for l in jax.tree.leaves((wc2, ws2)))


def test_distributed_inversion_matches_local(setup):
    """shard_map Gram-psum inversion == single-host inversion on the same
    data (eq. 9's all-reduce is exact).

    Uses enough samples that Σ OᵀO is full-rank: with a rank-deficient Gram
    the tiny-γ ridge solve is op-order sensitive, and jit-fused math can
    legitimately differ from the eager path."""
    mesh, w_c, w_i, _, _, _ = setup
    rng = np.random.default_rng(3)
    M, n = 4, 160                                   # 640 samples > 257 dims
    x = jnp.asarray(rng.normal(size=(M, n, DNN10.n_features)), jnp.float32)
    y1 = jax.nn.one_hot(jnp.asarray(rng.integers(0, 3, (M, n))), 3)
    smashed = jax.vmap(lambda xm: dnn.client_forward(w_c, xm, DNN10))(x)
    # gamma=1.0: well-conditioned solve (tiny-gamma ridge on a near-singular
    # Gram is fp32 op-order sensitive; psum-exactness is covered separately
    # by test_inversion_allreduce_equivalence)
    dist = jax.jit(make_distributed_inversion(DNN10, mesh, gamma=1.0))(
        w_i, smashed, y1)
    from repro.core.inversion import invert_inverse_model
    local = invert_inverse_model(
        w_i, smashed.reshape(-1, smashed.shape[-1]), y1.reshape(-1, 3),
        DNN10, gamma=1.0)
    # weights may differ in the data null-space of deeper (rank-deficient)
    # layers; the recovered FUNCTION must agree on the data.
    flat = smashed.reshape(-1, smashed.shape[-1])
    out_d = dnn.server_forward(dist, flat, DNN10)
    out_l = dnn.server_forward(local, flat, DNN10)
    np.testing.assert_allclose(out_d, out_l, rtol=5e-2, atol=5e-2)
    assert float(jnp.mean(jnp.argmax(out_d, -1) == jnp.argmax(out_l, -1))) \
        > 0.99


def test_moe_local_dispatch_matches_global_when_no_drops():
    """With generous capacity (no token drops), per-example and global
    dispatch compute the same mixture output."""
    from repro.configs.base import MoEConfig
    from repro.models import moe
    cfg = MoEConfig(n_experts=4, top_k=2, d_ff_expert=32,
                    capacity_factor=8.0)
    p = moe.init_moe(jax.random.PRNGKey(0), 16, cfg, "swiglu", jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16))
    y_g, _ = moe.apply_moe(p, x, cfg, "swiglu", local_dispatch=False)
    y_l, _ = moe.apply_moe(p, x, cfg, "swiglu", local_dispatch=True)
    np.testing.assert_allclose(y_g, y_l, rtol=2e-4, atol=2e-4)
