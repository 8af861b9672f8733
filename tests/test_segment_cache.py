"""The campaign driver's process cache of compiled segment scans
(``campaign._segment_exec``).

A later ``run_campaign`` whose segment programs are the same calls the
jitted scans it already holds: it counts ``segment_hits``, never
``segment_builds``, and computes bit for bit what a cold cache computes.
Any part of the key that changes, a rebinding of the engine code the
trace looks up among them, misses.  Every test starts from a cleared cache.
"""
import jax
import numpy as np
import pytest

from repro.configs.splitme_dnn import DNNConfig
from repro.core import engine
from repro.core.cost import SystemParams
from repro.kernels import dispatch
from repro.launch import campaign, resilience, spans

CFG = DNNConfig(name="cache-dnn", n_features=30, n_classes=3,
                hidden=(16, 16, 8), split_index=1)
M, ROUNDS, SEEDS = 8, 3, (0, 1)
FRAMEWORKS = ("splitme", "fedavg")


@pytest.fixture(scope="module")
def data():
    from repro.data import oran
    X, y = oran.generate(n_per_class=120, seed=0)
    (Xtr, ytr), test = oran.train_test_split(X, y)
    return oran.partition_non_iid(Xtr, ytr, M, samples_per_client=16,
                                  seed=0), test


@pytest.fixture(autouse=True)
def cold_cache():
    campaign.clear_segment_cache()
    yield
    campaign.clear_segment_cache()


def _run(data, framework, seeds=SEEDS, **kw):
    clients, test = data
    kw.setdefault("test_data", test)
    kw.setdefault("eval_every", 2)
    if framework == "fedavg":
        kw.setdefault("K", 4)
        kw.setdefault("E", 3)
    return campaign.run_campaign(framework, CFG, SystemParams(M=M, seed=0),
                                 clients, rounds=ROUNDS, seeds=seeds, **kw)


def _run_population(data, framework, seeds=SEEDS):
    """A population campaign whose cohorts draw from the clients' pooled
    samples."""
    from repro.core.population import Population
    clients, test = data
    pool = (clients["x"].reshape(-1, clients["x"].shape[-1]),
            clients["y"].reshape(-1))
    kw = {"K": 4, "E": 3} if framework == "fedavg" else {}
    return campaign.run_population_campaign(
        framework, CFG, Population(size=40, seed=0), pool, rounds=ROUNDS,
        seeds=seeds, cohort=6, samples_per_client=16, test_data=test,
        eval_every=2, **kw)


def _counted(fn):
    """``fn()``'s result, its counters and its ``segment`` spans."""
    before = spans.counts.copy()
    with spans.record() as recorded:
        out = fn()
    return (out, spans.counts - before,
            [s for s in recorded if s.name == "segment"])


def _assert_same(got, want):
    for g, w in zip(jax.tree.leaves(got.params), jax.tree.leaves(want.params)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    np.testing.assert_array_equal(got.losses, want.losses)
    if want.accuracy_per_round is not None:
        np.testing.assert_array_equal(got.accuracy_per_round,
                                      want.accuracy_per_round)


@pytest.mark.parametrize("framework", FRAMEWORKS)
def test_second_campaign_hits_every_segment(data, framework):
    _, first, segs = _counted(lambda: _run(data, framework))
    assert first["segment_builds"] == len(segs) >= 1
    assert first["segment_hits"] == 0
    _, second, segs = _counted(lambda: _run(data, framework, seeds=(7, 8)))
    assert second["segment_builds"] == 0
    assert second["segment_hits"] == len(segs) >= 1
    assert not any(s.attrs["built"] for s in segs)
    # nothing is traced or lowered again, so JAX compiles nothing either
    assert second["executables_compiled"] == 0
    assert not any(s.counts.get("trace_s") for s in segs)


@pytest.mark.parametrize("framework", FRAMEWORKS)
def test_warm_campaign_is_bit_identical_to_cold(data, framework):
    _run(data, framework, seeds=(5, 6))                     # warms the cache
    warm, counted, _ = _counted(lambda: _run(data, framework))
    assert counted["segment_builds"] == 0
    campaign.clear_segment_cache()
    cold, counted, _ = _counted(lambda: _run(data, framework))
    assert counted["segment_hits"] == 0
    _assert_same(warm, cold)


@pytest.mark.parametrize("framework", FRAMEWORKS)
def test_second_population_campaign_hits_and_matches_cold(data, framework):
    """run_population_campaign's segments live in the same cache."""
    cold, first, segs = _counted(lambda: _run_population(data, framework))
    assert first["segment_builds"] == len(segs) >= 1
    warm, second, segs = _counted(lambda: _run_population(data, framework))
    assert second["segment_builds"] == 0
    assert second["segment_hits"] == len(segs) >= 1
    assert second["executables_compiled"] == 0
    _assert_same(warm, cold)


# one part of the key changed from the base run's
CHANGES = {
    "eval_gamma": lambda fw: {"eval_gamma": 1.0},
    "policy": lambda fw: {"policy": dispatch.KernelPolicy(
        precision=dispatch.BF16)},
    "quant": lambda fw: {"quant": "bf16"},
    "hyper_lr": lambda fw: {"lr_c" if fw == "splitme" else "lr": 0.01},
    "guards": lambda fw: {"guards": engine.RoundGuards()},
    "seed_count": lambda fw: {"seeds": (0, 1, 2)},
    "test_set": lambda fw: {"test_data": None, "eval_every": None},
}


@pytest.mark.parametrize("part", sorted(CHANGES))
@pytest.mark.parametrize("framework", FRAMEWORKS)
def test_a_changed_key_part_misses(data, framework, part):
    _run(data, framework)
    _, counted, segs = _counted(
        lambda: _run(data, framework, **CHANGES[part](framework)))
    assert counted["segment_hits"] == 0
    assert counted["segment_builds"] == len(segs) >= 1
    assert all(s.attrs["built"] for s in segs)


def test_rebound_round_core_misses_and_runs(data, monkeypatch):
    base = _run(data, "fedavg")
    init = campaign._init_state(engine.make_spec("fedavg", CFG), SEEDS)[0]
    orig = engine._round_core

    def held(spec, runners, params, *a, **k):
        return (params,) + tuple(orig(spec, runners, params, *a, **k)[1:])

    with monkeypatch.context() as mp:
        mp.setattr(engine, "_round_core", held)
        faulted, counted, segs = _counted(lambda: _run(data, "fedavg"))
    assert counted["segment_hits"] == 0
    assert counted["segment_builds"] == len(segs) >= 1
    # the patched code ran: every round handed its state back unchanged
    for g, w in zip(jax.tree.leaves(faulted.params), jax.tree.leaves(init)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # the original binding is back, and so is its cached program
    again, counted, _ = _counted(lambda: _run(data, "fedavg"))
    assert counted["segment_builds"] == 0
    _assert_same(again, base)


def _abort_after(round_cursor):
    def hook(r):
        if r >= round_cursor:
            raise resilience.CampaignAborted(f"test abort at round {r}")
    return hook


def _killed_and_resumed(data, ckpt_dir):
    clients, test = data
    kw = dict(rounds=6, seeds=SEEDS, test_data=test, checkpoint_every=2,
              checkpoint_dir=ckpt_dir)
    with pytest.raises(resilience.CampaignAborted):
        campaign.run_campaign("splitme", CFG, SystemParams(M=M, seed=0),
                              clients, _checkpoint_hook=_abort_after(4),
                              **kw)
    return resilience.resume_campaign("splitme", CFG,
                                      SystemParams(M=M, seed=0), clients,
                                      **kw)


def test_resume_on_a_warm_cache_matches_a_cold_one(data, tmp_path):
    cold, counted, _ = _counted(
        lambda: _killed_and_resumed(data, tmp_path / "cold"))
    assert counted["segment_builds"] >= 1
    warm, counted, segs = _counted(
        lambda: _killed_and_resumed(data, tmp_path / "warm"))
    assert counted["segment_builds"] == 0
    assert counted["segment_hits"] == len(segs) >= 2
    _assert_same(warm, cold)
    for mw, mc in zip(warm.metrics, cold.metrics):
        assert repr(mw) == repr(mc)


def test_lru_evicts_past_its_bound(monkeypatch):
    monkeypatch.setattr(campaign, "SEGMENT_CACHE_SIZE", 3)
    spec = engine.make_spec("fedavg", CFG)
    data = {"x": np.zeros((M, 16, 30), np.float32),
            "y": np.zeros((M, 16), np.int32)}
    base = campaign._segment_key(spec, CFG, {}, data, 2, 1e-3, None, None,
                                 False, False)
    keys = [base._replace(kb=k) for k in range(1, 5)]
    before = spans.counts.copy()
    for k in keys[:3]:
        campaign._segment_exec(k)
    campaign._segment_exec(keys[0])              # now the most recent
    campaign._segment_exec(keys[3])              # evicts keys[1]
    assert list(campaign._segments) == [keys[2], keys[0], keys[3]]
    campaign._segment_exec(keys[1])              # built again
    counted = spans.counts - before
    assert counted["segment_builds"] == 5 and counted["segment_hits"] == 1
    assert len(campaign._segments) == 3
