"""The plain reference against the program's own reference-policy
campaign, at a tiny size on the CPU: both are float32 there, so they agree
to rounding."""
import json

import jax
import pytest

from conftest import add_config, run_tiny  # noqa: F401
import compare
import reference


@pytest.mark.parametrize("base", ["splitme-dnn10-m50", "fedavg-dnn10-m50"])
def test_reference_matches_run_campaign(tiny_root, base):
    import run as harness
    name = base.split("-")[0] + "-tiny.mini"
    c = harness.load_cell(tiny_root, name)
    system = harness.System(tiny_root, c, 3, jax.devices())
    seeds = system.next_seeds()
    got = system.host_view(system.run(seeds, policy="reference"))
    ref = reference.run_campaign(system.kind, system.config, system.clients,
                                 system.test, rounds=4, seeds=seeds)
    r = compare.readings(got, ref, reference.accuracy(
        system.kind, system.config, system.clients, system.test,
        got["params"]))
    assert r["schedule_mismatch"] == 0
    assert r["loss_gap"] < 1e-5
    assert r["acc_gap"] == 0.0
    assert r["param_gap"] < 1e-5


def test_planner_matches_at_the_papers_fleet():
    """Alg. 1 + P2 of the reference against the program's planner for the
    whole 30-round SplitMe campaign of M=50."""
    from repro.configs.splitme_dnn import DNN10
    from repro.core.cost import SystemParams
    from repro.launch import campaign
    import run as harness
    from conftest import BENCH, ROOT
    cfg = json.loads((BENCH / "configs" / "splitme-dnn10-m50.json")
                     .read_text())
    kind = harness.load_kind(ROOT, harness.kind_of(cfg))
    a, E = reference.plan(kind, cfg, 30, [0])
    _, sched = campaign.plan_schedule("splitme", SystemParams(M=50, seed=0),
                                      DNN10, 30, n_samples_per_client=96)
    assert (a == sched.a).all() and (E == sched.E).all()
