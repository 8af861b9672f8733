"""Fault-tolerant campaign runtime (repro.launch.resilience).

Pins the PR's acceptance behaviors:

* a checkpointed campaign interrupted at a segment boundary and resumed
  equals the uninterrupted run EXACTLY (f32 reference path) — params,
  losses, metrics;
* resume works under a 1-device mesh through the NamedSharding restore
  path, and the int8 error-feedback qstate + per-seed RNG chains
  round-trip through a checkpoint bit-exactly;
* a ``faults:p`` campaign completes with finite params, nonzero
  ``skipped_rounds``, and ONE device→host transfer with the guards armed
  (the transfer guard turns any stray pull into a hard error);
* the quorum guard degrades to hold-rounds, the norm clip bounds wire
  corruption, and the fault traces are deterministic in the scenario seed.
"""
import jax
import numpy as np
import pytest

from repro.configs.splitme_dnn import DNNConfig
from repro.core import scenario as scen
from repro.core.cost import SystemParams
from repro.core.engine import RoundGuards
from repro.launch import campaign, resilience, spans

CFG = DNNConfig(name="resilience-dnn", n_features=30, n_classes=3,
                hidden=(16, 16, 8), split_index=1)
M = 8
SEEDS = (0, 1)


@pytest.fixture(scope="module")
def clients():
    from repro.data import oran
    X, y = oran.generate(n_per_class=120, seed=0)
    (Xtr, ytr), _ = oran.train_test_split(X, y)
    return oran.partition_non_iid(Xtr, ytr, M, samples_per_client=16, seed=0)


def _run(name="splitme", rounds=12, **kw):
    kw.setdefault("K", 4)
    kw.setdefault("E", 3)
    return campaign.run_campaign(name, CFG, SystemParams(M=M, seed=0),
                                 kw.pop("clients"), rounds=rounds,
                                 seeds=SEEDS, **kw)


def _abort_after(round_cursor):
    def hook(r):
        if r >= round_cursor:
            raise resilience.CampaignAborted(f"test abort at round {r}")
    return hook


def _assert_params_equal(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_resume_matches_uninterrupted_exactly(clients, tmp_path):
    """Kill-at-segment-boundary resume == the plain uninterrupted campaign,
    bit-exactly: params, losses, and every per-round metric."""
    ref = _run(clients=clients)
    with pytest.raises(resilience.CampaignAborted):
        _run(clients=clients, checkpoint_every=3, checkpoint_dir=tmp_path,
             _checkpoint_hook=_abort_after(6))
    found = resilience.latest_checkpoint(tmp_path)
    assert found is not None and found.name == "ckpt-r000006"
    res = resilience.resume_campaign(
        "splitme", CFG, SystemParams(M=M, seed=0), clients,
        checkpoint_dir=tmp_path, checkpoint_every=3, rounds=12, seeds=SEEDS,
        K=4, E=3)
    _assert_params_equal(res.params, ref.params)
    np.testing.assert_array_equal(res.losses, ref.losses)
    for mr, mf in zip(res.metrics, ref.metrics):
        assert repr(mr) == repr(mf)


def test_mesh_resume_with_int8_qstate_roundtrip(clients, tmp_path):
    """Resume under a 1-device mesh (the NamedSharding restore path) with
    the int8 error-feedback accumulator and the per-seed RNG chains riding
    through the checkpoint — still bit-exact."""
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh()
    kw = dict(clients=clients, name="fedavg", rounds=8, mesh=mesh,
              quant="int8")
    ref = _run(**kw)
    with pytest.raises(resilience.CampaignAborted):
        _run(**kw, checkpoint_every=4, checkpoint_dir=tmp_path,
             _checkpoint_hook=_abort_after(4))
    res = resilience.resume_campaign(
        "fedavg", CFG, SystemParams(M=M, seed=0), clients,
        checkpoint_dir=tmp_path, checkpoint_every=4, rounds=8, seeds=SEEDS,
        K=4, E=3, mesh=mesh, quant="int8")
    _assert_params_equal(res.params, ref.params)
    np.testing.assert_array_equal(res.losses, ref.losses)


def test_qstate_rng_checkpoint_roundtrip_single_device(clients, tmp_path):
    """int8 EF state + RNG chains round-trip without a mesh too."""
    kw = dict(clients=clients, name="fedavg", rounds=8, quant="int8")
    ref = _run(**kw)
    with pytest.raises(resilience.CampaignAborted):
        _run(**kw, checkpoint_every=4, checkpoint_dir=tmp_path,
             _checkpoint_hook=_abort_after(4))
    res = resilience.resume_campaign(
        "fedavg", CFG, SystemParams(M=M, seed=0), clients,
        checkpoint_dir=tmp_path, checkpoint_every=4, rounds=8, seeds=SEEDS,
        K=4, E=3, quant="int8")
    _assert_params_equal(res.params, ref.params)
    np.testing.assert_array_equal(res.losses, ref.losses)


def test_faults_campaign_guarded_one_transfer(clients):
    """The faults:p smoke: guards auto-arm, the campaign survives NaN
    poisoning / crashes / wire corruption with finite params, counts its
    skipped rounds, and still performs exactly ONE host transfer."""
    before = spans.counts["host_transfers"]
    res = _run(clients=clients, scenario="faults:0.3", scenario_seed=1,
               rounds=8, strict_transfers=True)
    assert spans.counts["host_transfers"] - before == 1
    for leaf in jax.tree.leaves(res.params):
        assert np.isfinite(np.asarray(leaf)).all()
    assert res.skipped_rounds > 0
    trace = scen.get_trace("faults:0.3", 8, M, seed=1)
    assert res.crashed_rounds == int((trace.crash > 0).sum())
    # the metrics surface the guard accounting (bench/gate satellite)
    assert sum(m.skipped for m in res.metrics) > 0
    assert any(m.crashed for m in res.metrics) == (res.crashed_rounds > 0)
    # crash rounds record no server-side loss
    crashed = np.asarray(trace.crash) > 0
    assert np.isnan(res.losses[:, crashed, 0]).all()
    assert np.isfinite(res.losses[:, ~crashed, 0]).all()


def test_faults_guards_off_diverges(clients):
    """Control for the rollback guard: the same poisoned campaign with the
    guards forced OFF lets NaN reach the aggregated params."""
    res = _run(clients=clients, scenario="faults:0.9", scenario_seed=3,
               rounds=8, guards=False)
    assert not all(np.isfinite(np.asarray(leaf)).all()
                   for leaf in jax.tree.leaves(res.params))


def test_quorum_guard_holds_rounds(clients):
    """min_clients above the cohort size degrades every round to a hold:
    params never move, so 4- and 8-round campaigns end identically."""
    kw = dict(clients=clients, name="fedavg",
              guards=RoundGuards(min_clients=M + 1))
    a = _run(rounds=4, **kw)
    b = _run(rounds=8, **kw)
    _assert_params_equal(a.params, b.params)
    assert a.quorum_rounds == 4 * len(SEEDS)
    assert b.quorum_rounds == 8 * len(SEEDS)
    assert a.skipped_rounds == 0


def test_clip_norm_bounds_wire_corruption(clients):
    """A finite ±2^12 wire corruption is bounded by the per-client norm
    clip: the clipped run stays closer to the clean run than the
    unclipped one, and nothing is rolled back (corruption is finite)."""
    wire = np.ones((8, M))
    wire[2, :] = scen.WIRE_FLIP_GAIN        # round 2's uploads corrupted
    # (every client, so the randomized K=4 cohort can't dodge it)
    ones = np.ones((8, M))
    trace = scen.ScenarioTrace(name="wireflip", seed=0, gain=ones,
                               qc_scale=ones, qs_scale=ones, avail=ones,
                               drop=ones, deadline_scale=ones,
                               wire_gain=wire)
    clean = _run(clients=clients, name="fedavg", rounds=8)
    clipped = _run(clients=clients, name="fedavg", rounds=8, scenario=trace,
                   guards=RoundGuards(clip_norm=1.0))
    unclipped = _run(clients=clients, name="fedavg", rounds=8,
                     scenario=trace, guards=RoundGuards())
    assert clipped.skipped_rounds == 0

    def dist(a, b):
        return sum(float(np.abs(np.asarray(x) - np.asarray(y)).sum())
                   for x, y in zip(jax.tree.leaves(a.params),
                                   jax.tree.leaves(b.params)))
    d_clip, d_raw = dist(clipped, clean), dist(unclipped, clean)
    assert 0 < d_clip < d_raw


def test_fault_trace_deterministic():
    t1 = scen.get_trace("faults:0.4", 16, M, seed=7)
    t2 = scen.get_trace("faults:0.4", 16, M, seed=7)
    t3 = scen.get_trace("faults:0.4", 16, M, seed=8)
    np.testing.assert_array_equal(t1.poison, t2.poison)
    np.testing.assert_array_equal(t1.crash, t2.crash)
    np.testing.assert_array_equal(t1.wire_gain, t2.wire_gain)
    assert t1.has_faults()
    assert not (np.array_equal(t1.poison, t3.poison)
                and np.array_equal(t1.crash, t3.crash)
                and np.array_equal(t1.wire_gain, t3.wire_gain))


def test_fingerprint_mismatch_refuses_resume(clients, tmp_path):
    _run(clients=clients, name="fedavg", rounds=8, checkpoint_every=4,
         checkpoint_dir=tmp_path)
    with pytest.raises(ValueError, match="fingerprint"):
        resilience.resume_campaign(
            "fedavg", CFG, SystemParams(M=M, seed=0), clients,
            checkpoint_dir=tmp_path, checkpoint_every=4, rounds=8,
            seeds=(0, 2), K=4, E=3)


def test_checkpointing_excludes_strict_transfers(clients, tmp_path):
    with pytest.raises(ValueError, match="strict_transfers"):
        _run(clients=clients, checkpoint_every=3, checkpoint_dir=tmp_path,
             strict_transfers=True)
