"""CommQuant wire formats: quantized masked-FedAvg aggregation.

Pins (a) the error-feedback telescoping invariant of the int8 stochastic
rounding, (b) round/campaign parity of the quantized paths against f32
within the DOCUMENTED tolerances (bf16: 2e-2 on params over 3 rounds;
int8+EF: 5e-2), (c) the sharded psum path still lowering to EXACTLY one
all-reduce with quantization on (and matching the single-device quantized
round bit-for-bit on a 1-shard mesh), and (d) the fl_dryrun collective
accounting counting the quantized payload width — bf16 halves the
reported comm_bits — instead of hardcoded f32.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.splitme_dnn import DNN10
from repro.core import engine, quantcomm
from repro.core.cost import SystemParams
from repro.core.quantcomm import CommQuant
from repro.launch import campaign
from repro.roofline.analysis import parse_collectives

SEED_DATA = dict(n_clients=12, samples_per_client=32)


@pytest.fixture(scope="module")
def small_data():
    from repro.data import oran
    X, y = oran.generate(n_per_class=300, seed=0)
    (Xtr, ytr), (Xte, yte) = oran.train_test_split(X, y)
    cd = oran.partition_non_iid(Xtr, ytr, SEED_DATA["n_clients"],
                                samples_per_client=32, seed=0)
    return cd, (Xte, yte)


def _leaves_delta(got, want):
    return max(float(np.max(np.abs(np.asarray(g) - np.asarray(w))))
               for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


# ---------------------------------------------------------------------------
# Policy resolution + unit-level quantizer properties
# ---------------------------------------------------------------------------

def test_quant_resolution():
    assert quantcomm.quant_names() == ("none", "bf16", "int8")
    assert quantcomm.get_quant(None).mode == "none"
    assert quantcomm.get_quant("bf16").wire_bits == 16
    assert quantcomm.get_quant("int8").wire_bits == 8
    assert quantcomm.get_quant("int8").stateful
    assert not CommQuant("int8", error_feedback=False).stateful
    assert not quantcomm.get_quant("bf16").stateful
    assert quantcomm.get_quant("bf16").wire_scale == 0.5
    with pytest.raises(KeyError):
        quantcomm.get_quant("fp4")
    with pytest.raises(KeyError):
        CommQuant("fp4")


def test_error_feedback_telescopes():
    """The defining EF invariant, over multiple rounds: each round
    ``deq + ef_new == value + ef_old`` exactly, so the total transmitted
    payload plus the final residual equals the total true payload."""
    quant = quantcomm.INT8
    tree = {0: [jnp.zeros((6, 5)), jnp.zeros((5,))]}
    state = jax.tree.map(jnp.zeros_like, tree)
    rng = np.random.default_rng(0)
    total_v = jax.tree.map(jnp.zeros_like, tree)
    total_deq = jax.tree.map(jnp.zeros_like, tree)
    for t in range(5):
        v = jax.tree.map(
            lambda z: jnp.asarray(rng.normal(size=z.shape), jnp.float32),
            tree)
        old_state = state
        deq, state = quantcomm.fake_quant_int8(
            v, state, jax.random.PRNGKey(t), quant)
        # per-round telescoping: deq + ef_new == v + ef_old
        for d, e_new, vv, e_old in zip(*(jax.tree.leaves(x) for x in
                                         (deq, state, v, old_state))):
            np.testing.assert_allclose(np.asarray(d + e_new),
                                       np.asarray(vv + e_old),
                                       atol=1e-6, rtol=0)
        total_v = jax.tree.map(jnp.add, total_v, v)
        total_deq = jax.tree.map(jnp.add, total_deq, deq)
    # over the campaign: sum(wire) + residual == sum(true values)
    for s, d, e in zip(*(jax.tree.leaves(x) for x in
                         (total_v, total_deq, state))):
        np.testing.assert_allclose(np.asarray(d + e), np.asarray(s),
                                   atol=1e-5, rtol=0)
        # the residual never exceeds one grid step of the last round
        assert float(jnp.max(jnp.abs(e))) < 0.2


def test_int8_stochastic_rounding_unbiased():
    """Without error feedback, averaging the wire values over many draws
    recovers the true payload (stochastic rounding is unbiased)."""
    quant = CommQuant("int8", error_feedback=False)
    v = jnp.asarray(np.random.default_rng(1).normal(size=(64,)), jnp.float32)
    deqs = [quantcomm.fake_quant_int8(v, (), jax.random.PRNGKey(k), quant)[0]
            for k in range(256)]
    mean = np.mean(np.stack(deqs), axis=0)
    scale = float(jnp.max(jnp.abs(v))) / quant.levels
    # SR error per draw is U(-scale, scale)-ish; the mean of 256 draws
    # concentrates well inside a quarter grid step
    np.testing.assert_allclose(mean, v, atol=scale / 4, rtol=0)


# ---------------------------------------------------------------------------
# Engine rounds: parity within documented tolerances, EF across rounds
# ---------------------------------------------------------------------------

def _run_rounds(spec, x, y, rounds=4, e=3):
    rf = engine.build_round_fn(spec, DNN10, x, y, e_max=e, donate=False)
    params = spec.init_fn(jax.random.PRNGKey(3))
    qstate = engine.init_quant_state(spec, params)
    key = jax.random.PRNGKey(7)
    a = jnp.ones(x.shape[0], jnp.float32)
    for _ in range(rounds):
        key, sub = jax.random.split(key)
        params, losses, qstate = rf(params, a, jnp.asarray(e), sub, qstate)
    return params, losses


def test_quantized_rounds_close_to_f32(small_data):
    """Documented tolerances over 4 full-participation rounds: bf16 within
    2e-2, int8 (+EF) within 5e-2 of the f32 parameters."""
    cd, _ = small_data
    x = jnp.asarray(cd["x"])
    y = jnp.asarray(cd["y"])
    ref, ref_losses = _run_rounds(engine.make_spec("fedavg", DNN10), x, y)
    for q, tol in (("bf16", 2e-2), ("int8", 5e-2)):
        got, losses = _run_rounds(
            engine.make_spec("fedavg", DNN10, quant=q), x, y)
        assert _leaves_delta(got, ref) < tol, q
        assert np.isfinite([float(l) for l in losses]).all()


def test_error_feedback_reduces_multiround_error(small_data):
    """With the accumulator the int8 aggregation error telescopes instead
    of compounding: over 6 rounds the EF run lands closer to the f32
    trajectory than the EF-off run (fixed seeds, deterministic)."""
    cd, _ = small_data
    x = jnp.asarray(cd["x"])
    y = jnp.asarray(cd["y"])
    ref, _ = _run_rounds(engine.make_spec("fedavg", DNN10), x, y, rounds=6)
    with_ef, _ = _run_rounds(
        engine.make_spec("fedavg", DNN10, quant="int8"), x, y, rounds=6)
    without_ef, _ = _run_rounds(
        engine.make_spec("fedavg", DNN10,
                         quant=CommQuant("int8", error_feedback=False)),
        x, y, rounds=6)
    d_ef, d_no = _leaves_delta(with_ef, ref), _leaves_delta(without_ef, ref)
    assert d_ef < d_no, (d_ef, d_no)


# ---------------------------------------------------------------------------
# Sharded psum path: one all-reduce, 1-shard parity
# ---------------------------------------------------------------------------

def _one_device_mesh():
    from repro.launch.mesh import make_client_mesh
    return make_client_mesh(1)


@pytest.mark.parametrize("quant", ["bf16", "int8"])
def test_sharded_quantized_round_one_all_reduce(quant):
    """Quantize-before-psum keeps the one-communication-per-round
    invariant: the lowered sharded round still contains EXACTLY one
    all-reduce (the int8 scales are per-shard local, no extra
    collective)."""
    mesh = _one_device_mesh()
    spec = engine.make_spec("splitme", DNN10, masked_loss_metric=True,
                            quant=quant)
    M, n = 8, 16
    rf = engine.build_sharded_round_fn(spec, DNN10, mesh, n_clients=M,
                                       e_max=2, jit=False, donate=False)
    params = spec.init_fn(jax.random.PRNGKey(0))
    qstate = engine.init_quant_state(spec, params, n_shards=1)
    x = jnp.zeros((M, n, DNN10.n_features))
    y = jnp.zeros((M, n), jnp.int32)
    args = (params, x, y, jnp.ones(M), jnp.asarray(2),
            jax.random.PRNGKey(1), qstate)
    with mesh:
        txt = jax.jit(rf).lower(*args).compile().as_text()
    counts = {}
    for c in parse_collectives(txt):
        counts[c.kind] = counts.get(c.kind, 0) + 1
    assert counts == {"all-reduce": 1}, counts


@pytest.mark.parametrize("quant", ["bf16", "int8"])
def test_sharded_quantized_round_matches_single_device(quant, small_data):
    """On a 1-shard mesh the quantized sharded round reproduces the
    single-device quantized round exactly (same scale domain, same
    per-shard quantization stream)."""
    cd, _ = small_data
    x = jnp.asarray(cd["x"])
    y = jnp.asarray(cd["y"])
    M = x.shape[0]
    spec = engine.make_spec("fedavg", DNN10, quant=quant)
    params = spec.init_fn(jax.random.PRNGKey(3))
    a = jnp.ones(M, jnp.float32)
    key = jax.random.PRNGKey(7)
    single = engine.build_round_fn(spec, DNN10, x, y, e_max=3, donate=False)
    p1, l1, _ = single(params, a, jnp.asarray(3), key,
                       engine.init_quant_state(spec, params))
    mesh = _one_device_mesh()
    sharded = engine.build_sharded_round_fn(spec, DNN10, mesh, n_clients=M,
                                            e_max=3, donate=False)
    p2, l2, _ = sharded(params, x, y, a, jnp.asarray(3), key,
                        engine.init_quant_state(spec, params, n_shards=1))
    assert _leaves_delta(p1, p2) < 1e-6
    for g, h in zip(l1, l2):
        assert abs(float(g) - float(h)) < 1e-6


# ---------------------------------------------------------------------------
# fl_dryrun collective accounting: quantized payload width, not f32
# ---------------------------------------------------------------------------

def test_dryrun_comm_bits_counts_quantized_width():
    """Regression: the dry-run used to report ``collective_bytes`` off the
    HLO dtype — always f32 on CPU, where XLA hoists the bf16 converts out
    of the all-reduce.  ``comm_bits`` counts elements × wire width, so
    bf16 halves it and int8 quarters it, with the one-all-reduce structure
    intact."""
    from repro.launch.fl_dryrun import lower_round
    mesh = _one_device_mesh()
    base = lower_round("splitme", mesh, 8, 16, 1)
    bf16 = lower_round("splitme", mesh, 8, 16, 1, quant="bf16")
    int8 = lower_round("splitme", mesh, 8, 16, 1, quant="int8")
    assert base["counts"] == {"all-reduce": 1}
    assert bf16["counts"] == {"all-reduce": 1}
    assert int8["counts"] == {"all-reduce": 1}
    assert base["comm_bits"] > 0
    np.testing.assert_allclose(bf16["comm_bits"], 0.5 * base["comm_bits"],
                               rtol=1e-12)
    np.testing.assert_allclose(int8["comm_bits"], 0.25 * base["comm_bits"],
                               rtol=1e-12)


# ---------------------------------------------------------------------------
# Campaigns: comm accounting + quantized training end-to-end
# ---------------------------------------------------------------------------

def test_campaign_comm_bits_reflect_wire_format(small_data):
    """FedAvg's fixed-K schedule is payload-independent, so the reported
    comm_bits scale EXACTLY with the wire width."""
    cd, _ = small_data
    base = campaign.run_campaign("fedavg", DNN10, SystemParams(M=12, seed=0),
                                 cd, rounds=2, seeds=(0,), K=4, E=5)
    for q, scale in (("bf16", 0.5), ("int8", 0.25)):
        res = campaign.run_campaign("fedavg", DNN10,
                                    SystemParams(M=12, seed=0), cd,
                                    rounds=2, seeds=(0,), K=4, E=5, quant=q)
        for r in range(2):
            np.testing.assert_allclose(
                res.metrics[r].comm_bits,
                scale * base.metrics[r].comm_bits, rtol=1e-12)
        # latency follows the narrower payload too (eq. 18/19)
        assert res.metrics[0].sim_time < base.metrics[0].sim_time


def test_splitme_campaign_quantized_trains(small_data):
    """A scanned SplitMe campaign under each quantized wire format stays
    within the documented tolerance of the f32 campaign's parameters when
    the schedules agree, and still reaches useful accuracy (the P2
    schedule itself may admit MORE clients under quantization — that is
    the intended joint-optimization response)."""
    cd, test = small_data
    ref = campaign.run_campaign("splitme", DNN10, SystemParams(M=12, seed=0),
                                cd, rounds=3, seeds=(0, 1), test_data=test)
    for q, tol in (("bf16", 2e-2), ("int8", 6e-2)):
        res = campaign.run_campaign("splitme", DNN10,
                                    SystemParams(M=12, seed=0), cd,
                                    rounds=3, seeds=(0, 1), test_data=test,
                                    quant=q)
        assert np.isfinite(res.losses).all()
        # above 3-class chance; bf16 seed-1 lands on exactly 0.35 here
        assert np.all(res.accuracy >= 0.35), (q, res.accuracy)
        same_sched = (res.schedule.E.tolist() == ref.schedule.E.tolist()
                      and np.array_equal(res.schedule.a, ref.schedule.a))
        if same_sched:
            assert _leaves_delta(res.params, ref.params) < tol, q


def test_all_frameworks_train_quantized(small_data):
    """Acceptance: run_campaign trains every registered framework (the
    paper's four + fedora + ecofl) with CommQuant in {none, bf16, int8} —
    here the cheapest non-trivial slice: every framework × int8."""
    cd, _ = small_data
    assert set(engine.framework_names()) == {
        "splitme", "fedavg", "sfl", "oranfed", "fedora", "ecofl"}
    for fw in engine.framework_names():
        res = campaign.run_campaign(fw, DNN10, SystemParams(M=12, seed=0),
                                    cd, rounds=2, seeds=(0,), K=4, E=3,
                                    e_initial=4, quant="int8")
        assert np.isfinite(res.losses).all(), fw
        assert all(m.comm_bits > 0 for m in res.metrics), fw
