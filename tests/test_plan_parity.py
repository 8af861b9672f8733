"""The campaign planner's schedules are bitwise those of the scalar P2
solver the batched one replaced (the frozen copy in test_allocation.py),
at 150 rounds: A_t feeds back through the realised uplink time, so one
ulp of b in one round could move a later selection."""
import numpy as np
import pytest

from repro.configs.splitme_dnn import DNN10
from repro.core import engine, population as popn
from repro.core.cost import SystemParams
from repro.launch import campaign, spans
from test_allocation import frozen_solve_bandwidth, frozen_solve_p2

ROUNDS = 150
STEP_CAP = 200 * 20          # the scalar sweep's bisection steps a round


def _plan(framework, M, **kw):
    before = spans.counts.copy()
    _, sched = campaign.plan_schedule(framework, SystemParams(M=M, seed=0),
                                      DNN10, ROUNDS, n_samples_per_client=96,
                                      **kw)
    return sched, (spans.counts - before)["p2_bisect_steps"]


@pytest.mark.parametrize("framework, M, kw", [
    ("splitme", 50, {}),
    ("splitme", 48, {}),
    ("splitme", 50, {"scenario": "fading"}),
    ("splitme", 50, {"quant": "int8"}),
    ("oranfed", 50, {}),
    ("fedora", 50, {}),
], ids=["splitme-m50", "splitme-m48", "splitme-fading", "splitme-int8",
        "oranfed", "fedora"])
def test_plan_bitwise_equals_scalar_solver(monkeypatch, framework, M, kw):
    sched, steps = _plan(framework, M, **kw)
    with monkeypatch.context() as m:
        m.setattr(engine, "solve_bandwidth", frozen_solve_bandwidth)
        m.setattr(engine, "solve_p2", frozen_solve_p2)
        want, _ = _plan(framework, M, **kw)
    for k in ("a", "b", "E"):
        assert np.array_equal(getattr(sched, k), getattr(want, k)), k
    assert steps > 0
    if framework == "splitme":
        # the early exit engages: far fewer steps than 200 for each E
        assert steps < STEP_CAP * ROUNDS
        assert len(set(sched.E.tolist())) > 1      # E adapts over the plan


def test_fixed_k_plan_counts_no_bisection():
    _, steps = _plan("fedavg", 50)
    assert steps == 0


def test_population_plan_counts_bisection_steps():
    before = spans.counts.copy()
    campaign.plan_population_schedule(
        "splitme", popn.Population(size=1000, seed=0), DNN10, rounds=3,
        cohort=16, n_samples_per_client=16)
    steps = (spans.counts - before)["p2_bisect_steps"]
    assert 0 < steps < STEP_CAP * 3
