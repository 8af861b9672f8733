"""Kernel-vs-reference parity for the dispatch layer (`repro.kernels.dispatch`)
and the kernelized training stack built on it.

Everything here runs the Pallas kernel BODIES through the Pallas
interpreter: the module opts into it with ``REPRO_PALLAS_INTERPRET=1``
(kernels are forced on per-op via explicit ``KernelPolicy`` bits), so the
suite stays green without a TPU.  ``scripts/ci.sh`` runs this module as its
kernel-parity stage: ``REPRO_PALLAS_INTERPRET=1 pytest -m kernels``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.splitme_dnn import DNN10, DNNConfig
from repro.core import dnn, mutual
from repro.core.inversion import invert_inverse_model
from repro.kernels import dispatch
from repro.kernels.dispatch import BF16, KernelPolicy

pytestmark = pytest.mark.kernels


@pytest.fixture(autouse=True, scope="module")
def _pallas_interpreter():
    """Kernels never pick the interpreter by backend; this module asks."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_PALLAS_INTERPRET", "1")
        yield

KERNEL_ON = KernelPolicy(kl_mutual=True, ridge_gram=True)
KERNEL_BF16_ON = KernelPolicy(kl_mutual=True, ridge_gram=True,
                              precision=BF16)


# ---------------------------------------------------------------------------
# Policy resolution
# ---------------------------------------------------------------------------

def test_policy_resolution(monkeypatch):
    """Auto bits resolve by backend: off on CPU, forced on by
    REPRO_PALLAS_INTERPRET=1 (read dynamically, not import-cached)."""
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    pol = dispatch.get_policy(None)
    on_tpu = jax.default_backend() == "tpu"
    assert pol.kl_mutual is on_tpu and pol.ridge_gram is on_tpu
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    pol = dispatch.get_policy("kernel")
    assert pol.kl_mutual is True and pol.ridge_gram is True
    # explicit bits always win over the environment
    assert dispatch.get_policy("reference").kl_mutual is False
    # the bf16 PRESET is an auto request: resolved per backend; an explicit
    # Precision in a custom policy is forced everywhere
    assert (dispatch.get_policy("kernel_bf16").precision.is_mixed
            is dispatch.mixed_precision_supported())
    assert dispatch.get_policy(
        KernelPolicy(precision=BF16)).precision.is_mixed
    with pytest.raises(KeyError):
        dispatch.get_policy("nope")


def test_round_builder_rejects_policy_mismatch():
    """The phase losses capture the policy at make_spec time, so the round
    builders refuse a different override (it could only half-apply)."""
    from repro.core import engine
    spec = engine.make_spec("fedavg", DNN10, policy="reference")
    with pytest.raises(ValueError, match="spec-bound"):
        engine.build_round_fn(spec, DNN10, e_max=2, policy=KERNEL_ON)
    # restating the bound policy is fine
    engine.build_round_fn(spec, DNN10, e_max=2,
                          policy=dispatch.get_policy("reference"))


# ---------------------------------------------------------------------------
# kl_mutual: value AND custom_vjp gradient vs mutual.kl_paper autodiff
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("temp", [1.0, 2.0])
def test_kl_loss_value_and_grad_vs_kl_paper(temp):
    x = jax.random.normal(jax.random.PRNGKey(0), (48, 40)) * 2
    y = jax.random.normal(jax.random.PRNGKey(1), (48, 40)) * 2

    got = dispatch.kl_loss(x, y, temperature=temp, policy=KERNEL_ON)
    want = mutual.kl_paper(x, y, temp)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    # the kernel's closed-form custom_vjp vs autodiff through kl_paper
    g_kernel = jax.grad(lambda a: dispatch.kl_loss(
        a, y, temperature=temp, policy=KERNEL_ON))(x)
    g_ref = jax.grad(lambda a: mutual.kl_paper(a, y, temp))(x)
    np.testing.assert_allclose(g_kernel, g_ref, rtol=1e-5, atol=1e-6)

    # the reference branch of the dispatcher is the same graph as kl_paper
    got_ref = dispatch.kl_loss(x, y, temperature=temp, policy="reference")
    np.testing.assert_allclose(got_ref, want, rtol=0, atol=0)


def test_kl_loss_vmapped_over_clients():
    """The engine calls the dispatched loss inside a client-axis vmap."""
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 32, 24))
    y = jax.random.normal(jax.random.PRNGKey(1), (6, 32, 24))
    got = jax.vmap(lambda a, b: dispatch.kl_loss(
        a, b, temperature=2.0, policy=KERNEL_ON))(x, y)
    want = jax.vmap(lambda a, b: mutual.kl_paper(a, b, 2.0))(x, y)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    g1 = jax.grad(lambda a: jnp.sum(jax.vmap(lambda p, q: dispatch.kl_loss(
        p, q, temperature=2.0, policy=KERNEL_ON))(a, y)))(x)
    g2 = jax.grad(lambda a: jnp.sum(jax.vmap(
        lambda p, q: mutual.kl_paper(p, q, 2.0))(a, y)))(x)
    np.testing.assert_allclose(g1, g2, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# ridge_gram: vs OᵀZ, under vmap, and under the 1-device shard_map psum path
# ---------------------------------------------------------------------------

def test_gram_kernel_under_vmap():
    o = jax.random.normal(jax.random.PRNGKey(0), (5, 96, 18))
    z = jax.random.normal(jax.random.PRNGKey(1), (5, 96, 3))
    got = jax.vmap(lambda a, b: dispatch.gram(a, b, policy=KERNEL_ON))(o, z)
    want = jnp.einsum("mnd,mnc->mdc", o, z)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_gram_kernel_under_shard_map_psum():
    """Per-shard kernel Grams + psum == single-shot OᵀZ (the Step-4
    all-reduce is exact with the kernel in the shard body)."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    o = jax.random.normal(jax.random.PRNGKey(0), (128, 18))
    z = jax.random.normal(jax.random.PRNGKey(1), (128, 3))
    f = jax.shard_map(
        lambda a, b: jax.lax.psum(
            dispatch.gram(a, b, policy=KERNEL_ON), "data"),
        mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P(),
        check_vma=False)
    np.testing.assert_allclose(jax.jit(f)(o, z), o.T @ z,
                               rtol=1e-5, atol=1e-4)


def test_inversion_kernel_matches_reference_incl_shard_map():
    """invert_inverse_model with the gram kernel == reference, plain and
    under the 1-device shard_map bundled-psum path (per-layer Gram psum
    preserved with the kernel in the body).

    Compared is what the inversion determines: the recovered server
    model's layer outputs on the smashed data.  Its weights are not
    determined that tightly: layer 2's ridge Gram here has condition
    number ~3.5e5 at gamma=1e-3, so the ~2e-7 relative difference between
    the kernel's blocked accumulation and one reference matmul moves its
    weights by ~2.5e-2 along directions the data barely excites, while
    every layer output stays within ~2e-6."""
    from jax.sharding import Mesh, PartitionSpec as P

    cfg = DNNConfig(n_features=6, hidden=(12, 8), split_index=1, n_classes=3)
    inv = dnn.init_inverse_server(jax.random.PRNGKey(0), cfg)
    o = jax.random.normal(jax.random.PRNGKey(1), (120, 12))
    y1 = jax.nn.one_hot(
        jax.random.randint(jax.random.PRNGKey(2), (120,), 0, 3), 3)

    def outputs(server):
        return dnn.mlp_activations(server, o, cfg.activation)

    want = outputs(invert_inverse_model(inv, o, y1, cfg, policy="reference"))
    got = outputs(invert_inverse_model(inv, o, y1, cfg, policy=KERNEL_ON))
    for a, b in zip(want, got):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    sharded = jax.shard_map(
        lambda w, s, y: invert_inverse_model(w, s, y, cfg, axis_name="data",
                                             policy=KERNEL_ON),
        mesh=mesh, in_specs=(P(), P("data"), P("data")), out_specs=P(),
        check_vma=False)
    for a, b in zip(want, outputs(jax.jit(sharded)(inv, o, y1))):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Mixed precision: bf16 activations / f32 accumulators
# ---------------------------------------------------------------------------

def test_mixed_precision_forward_close_and_f32_grads():
    layers = dnn.init_mlp(jax.random.PRNGKey(0), (10, 32, 16, 3))
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 10))
    full = dnn.mlp_forward(layers, x)
    mixed = dnn.mlp_forward(layers, x, precision=BF16)
    assert mixed.dtype == jnp.float32          # accumulators / logits f32
    np.testing.assert_allclose(mixed, full, rtol=5e-2, atol=5e-2)
    # master params stay f32: gradients come back f32 through the casts
    g = jax.grad(lambda w: jnp.sum(
        dnn.mlp_forward(w, x, precision=BF16)))(layers)
    assert all(l.dtype == jnp.float32 for l in jax.tree.leaves(g))


# ---------------------------------------------------------------------------
# End-to-end: scanned SplitMe campaign, kernelized and mixed-precision
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_data():
    from repro.data import oran
    X, y = oran.generate(n_per_class=300, seed=0)
    (Xtr, ytr), (Xte, yte) = oran.train_test_split(X, y)
    cd = oran.partition_non_iid(Xtr, ytr, 12, samples_per_client=32, seed=0)
    return cd, (Xte, yte)


def _campaign(small_data, policy):
    from repro.core.cost import SystemParams
    from repro.launch import campaign
    cd, test = small_data
    return campaign.run_campaign(
        "splitme", DNN10, SystemParams(M=12, seed=0), cd, rounds=3,
        seeds=(0, 1), test_data=test, e_initial=6, policy=policy)


def test_splitme_campaign_kernel_policy_matches_reference(small_data):
    """A whole scanned campaign through the f32 kernel policy (fused KL
    kernel in every local step, gram kernel in the fused Step-4 eval)
    reproduces the reference path at 1e-5."""
    ref = _campaign(small_data, "reference")
    ker = _campaign(small_data, KERNEL_ON)
    np.testing.assert_allclose(ker.losses, ref.losses, atol=1e-5, rtol=0)
    for a, b in zip(jax.tree.leaves(ref.params), jax.tree.leaves(ker.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   rtol=0)
    # accuracy is a discrete argmax metric downstream of an ill-conditioned
    # ridge solve — identical to fp noise, so only sanity-bounded here
    assert np.all(ker.accuracy > 0.3)


def test_splitme_campaign_bf16_policy_close(small_data):
    """The bf16-activation policy stays within 1e-3 of reference losses and
    parameters over a short campaign (f32 accumulators + master params keep
    the SGD trajectory from drifting)."""
    ref = _campaign(small_data, "reference")
    bf = _campaign(small_data, KERNEL_BF16_ON)
    np.testing.assert_allclose(bf.losses, ref.losses, atol=1e-3, rtol=0)
    for a, b in zip(jax.tree.leaves(ref.params), jax.tree.leaves(bf.params)):
        # master params are f32 and every step's update error is bounded by
        # the bf16 activation rounding
        assert np.asarray(a).dtype == np.float32
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3,
                                   rtol=0)
    assert np.all(bf.accuracy > 0.3)
