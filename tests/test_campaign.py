"""Vmapped multi-seed campaign == serial engine-trainer runs.

The campaign runner batches independent seeds through one compiled
scan-over-rounds; each seed's trajectory must match the serial engine
trainer with the same seed (same schedule, same RNG chain).
"""
import warnings

import jax
import numpy as np
import pytest

from repro.configs.splitme_dnn import DNN10
from repro.core.baselines import FedAvgTrainer, ORANFedTrainer
from repro.core.cost import SystemParams
from repro.core.splitme import SplitMeTrainer
from repro.launch import campaign

SEEDS = (0, 1, 2, 3)
ROUNDS = 3


@pytest.fixture(scope="module")
def small_data():
    from repro.data import oran
    X, y = oran.generate(n_per_class=300, seed=0)
    (Xtr, ytr), (Xte, yte) = oran.train_test_split(X, y)
    cd = oran.partition_non_iid(Xtr, ytr, 12, samples_per_client=32, seed=0)
    return cd, (Xte, yte)


def _leaves_close(got, want, atol):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=atol,
                                   rtol=0)


def test_oranfed_campaign_matches_serial(small_data):
    """O-RANFed's schedule is deterministic (no selection randomness), so a
    4-seed vmapped campaign must reproduce 4 serial trainer runs exactly."""
    cd, test = small_data
    res = campaign.run_campaign("oranfed", DNN10, SystemParams(M=12, seed=0),
                                cd, rounds=ROUNDS, seeds=SEEDS, E=5)
    assert res.losses.shape == (len(SEEDS), ROUNDS, 1)
    for i, s in enumerate(SEEDS):
        tr = ORANFedTrainer(DNN10, SystemParams(M=12, seed=0), cd, test,
                            E=5, seed=s)
        serial_losses = [tr.run_round().client_loss for _ in range(ROUNDS)]
        np.testing.assert_allclose(res.losses[i, :, 0], serial_losses,
                                   atol=1e-5, rtol=0)
        # batched (vmapped) matmuls reassociate fp sums; the tiny per-step
        # difference amplifies through SGD, so params get a looser bound
        _leaves_close(res.params_for(i)[0], tr.params, atol=2e-3)
        # schedule bookkeeping matches the trainer's history
        for r in range(ROUNDS):
            assert res.metrics[r].n_selected == tr.history[r].n_selected
            np.testing.assert_allclose(res.metrics[r].comm_bits,
                                       tr.history[r].comm_bits)


def test_splitme_campaign_matches_serial(small_data):
    """The campaign scans only max(schedule E) steps and reports the
    masked-mean loss, but the trained PARAMETERS must match the serial
    trainer (masked updates are exact no-ops)."""
    cd, test = small_data
    res = campaign.run_campaign("splitme", DNN10, SystemParams(M=12, seed=0),
                                cd, rounds=ROUNDS, seeds=(0, 1))
    assert res.losses.shape == (2, ROUNDS, 2)      # client + server phases
    assert np.isfinite(res.losses).all()
    for i, s in enumerate((0, 1)):
        tr = SplitMeTrainer(DNN10, SystemParams(M=12, seed=0), cd, test,
                            seed=s)
        for r in range(ROUNDS):
            m = tr.run_round()
            assert res.metrics[r].E == m.E
            assert res.metrics[r].n_selected == m.n_selected
        w_c, w_s_inv = res.params_for(i)
        _leaves_close(w_c, tr.w_c, atol=2e-3)
        _leaves_close(w_s_inv, tr.w_s_inv, atol=2e-3)


def test_fedavg_campaign_matches_serial_for_policy_seed(small_data):
    """FedAvg's client selection is itself random; the campaign's shared
    schedule equals the serial trainer whose seed == policy_seed."""
    cd, test = small_data
    res = campaign.run_campaign("fedavg", DNN10, SystemParams(M=12, seed=0),
                                cd, rounds=ROUNDS, seeds=(0,), K=4, E=5,
                                test_data=test)
    tr = FedAvgTrainer(DNN10, SystemParams(M=12, seed=0), cd, test, K=4,
                       E=5, seed=0)
    serial = [tr.run_round().client_loss for _ in range(ROUNDS)]
    np.testing.assert_allclose(res.losses[0, :, 0], serial, atol=1e-5,
                               rtol=0)
    assert res.accuracy is not None and res.accuracy.shape == (1,)
    np.testing.assert_allclose(res.accuracy[0], tr.evaluate(), atol=1e-6)


def test_campaign_seeds_differ(small_data):
    """Different seeds actually train different models."""
    cd, _ = small_data
    res = campaign.run_campaign("fedavg", DNN10, SystemParams(M=12, seed=0),
                                cd, rounds=2, seeds=(0, 1), K=4, E=5)
    (params,) = res.params
    w0 = jax.tree.leaves(jax.tree.map(lambda p: p[0], params))
    w1 = jax.tree.leaves(jax.tree.map(lambda p: p[1], params))
    delta = sum(float(np.abs(np.asarray(a) - np.asarray(b)).sum())
                for a, b in zip(w0, w1))
    assert delta > 0


def test_splitme_campaign_evaluates(small_data):
    """Step-4 inversion evaluation works on campaign results."""
    cd, test = small_data
    res = campaign.run_campaign("splitme", DNN10, SystemParams(M=12, seed=0),
                                cd, rounds=4, seeds=(0,), test_data=test)
    assert res.accuracy.shape == (1,)
    assert res.accuracy[0] > 0.4          # 3 classes, chance = 1/3


def test_scanned_campaign_single_host_transfer(small_data, monkeypatch):
    """The scanned campaign pulls metrics device→host EXACTLY once, and its
    device phase performs zero d2h transfers (hard-enforced by
    ``strict_transfers``, which arms jax's transfer guard)."""
    cd, test = small_data
    calls = []
    real = campaign._host_fetch
    monkeypatch.setattr(campaign, "_host_fetch",
                        lambda tree: (calls.append(1), real(tree))[1])
    res = campaign.run_campaign(
        "splitme", DNN10, SystemParams(M=12, seed=0), cd, rounds=ROUNDS,
        seeds=(0, 1), test_data=test, strict_transfers=True)
    assert len(calls) == 1
    assert np.isfinite(res.losses).all()


@pytest.mark.parametrize("runner", ["campaign", "sweep", "population"])
def test_scanned_campaign_captures_no_constants(small_data, runner):
    """The client and test data reach the compiled scan as ARGUMENTS.  A
    device array closed over instead is baked into the program as a
    constant, read back to the host at lowering: on a TPU that transfer
    breaks ``strict_transfers`` (the CPU, whose arrays are host memory,
    never shows it), so JAX's own captured-constants warning stands guard.
    The segment cache is cleared first, so the campaign's scans trace."""
    campaign.clear_segment_cache()
    cd, test = small_data
    prev = jax.config.jax_captured_constants_warn_bytes
    jax.config.update("jax_captured_constants_warn_bytes", 1)
    try:
        with warnings.catch_warnings():
            warnings.filterwarnings("error", message=".*constants were "
                                    "captured")
            if runner == "campaign":
                campaign.run_campaign(
                    "splitme", DNN10, SystemParams(M=12, seed=0), cd,
                    rounds=ROUNDS, seeds=(0, 1), test_data=test,
                    eval_every=2, strict_transfers=True)
            elif runner == "sweep":
                campaign.run_config_sweep(
                    "splitme", DNN10, [SystemParams(M=12, seed=0)] * 2, cd,
                    rounds=2, seeds=(0,), test_data=test,
                    strict_transfers=True)
            else:
                from repro.core.population import Population
                pool = (cd["x"].reshape(-1, cd["x"].shape[-1]),
                        cd["y"].reshape(-1))
                campaign.run_population_campaign(
                    "splitme", DNN10, Population(size=40, seed=0), pool,
                    rounds=ROUNDS, seeds=(0, 1), cohort=8,
                    samples_per_client=16, test_data=test, eval_every=2,
                    strict_transfers=True)
    finally:
        jax.config.update("jax_captured_constants_warn_bytes", prev)


def test_sharded_campaign_matches_gathered(small_data):
    """mesh= mode (scan over shard_map rounds, seeds vmapped) reproduces the
    single-device gathered campaign."""
    from repro.launch.mesh import make_host_mesh
    cd, test = small_data
    mesh = make_host_mesh()
    res_m = campaign.run_campaign("splitme", DNN10, SystemParams(M=12, seed=0),
                                  cd, rounds=ROUNDS, seeds=(0, 1), mesh=mesh,
                                  test_data=test)
    res_g = campaign.run_campaign("splitme", DNN10, SystemParams(M=12, seed=0),
                                  cd, rounds=ROUNDS, seeds=(0, 1),
                                  test_data=test)
    np.testing.assert_allclose(res_m.losses, res_g.losses, atol=1e-5, rtol=0)
    for i in range(2):
        _leaves_close(res_m.params_for(i), res_g.params_for(i), atol=1e-5)
    np.testing.assert_allclose(res_m.accuracy, res_g.accuracy, atol=1e-6)


def test_config_sweep_vmapped_matches_serial(small_data, monkeypatch):
    """One compiled scan over (variant, seed) pairs == per-variant campaigns,
    with a single host transfer for the whole sweep."""
    cd, test = small_data
    sps = [SystemParams(M=12, seed=0), SystemParams(M=12, seed=0, B=5e8)]
    calls = []
    real = campaign._host_fetch
    monkeypatch.setattr(campaign, "_host_fetch",
                        lambda tree: (calls.append(1), real(tree))[1])
    sweep = campaign.run_config_sweep("oranfed", DNN10, sps, cd,
                                      rounds=ROUNDS, seeds=(0, 1), E=5,
                                      test_data=test)
    assert len(calls) == 1
    serial = campaign.run_config_sweep("oranfed", DNN10, sps, cd,
                                       rounds=ROUNDS, seeds=(0, 1), E=5,
                                       test_data=test, vmap_configs=False)
    assert len(sweep) == len(serial) == 2
    for v in range(2):
        np.testing.assert_allclose(sweep[v].losses, serial[v].losses,
                                   atol=1e-5, rtol=0)
        np.testing.assert_allclose(sweep[v].accuracy, serial[v].accuracy,
                                   atol=1e-6)
        for r in range(ROUNDS):
            np.testing.assert_allclose(sweep[v].metrics[r].comm_bits,
                                       serial[v].metrics[r].comm_bits)
        for i in range(2):
            _leaves_close(sweep[v].params_for(i), serial[v].params_for(i),
                          atol=2e-3)
