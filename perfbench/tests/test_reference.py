"""The plain reference against the program's own reference-policy
campaign, at a tiny size on the CPU: both are float32 there, so they agree
to rounding.  Its blocked path (a kind's ``client_block`` and
``sample_block``) against its default one."""
import functools
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import BENCH, ROOT, TINY, add_config, run_tiny  # noqa: F401
import calibrate
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


# ---------------------------------------------------------------------------
# The blocked path: a kind that states ``client_block``/``sample_block``
# ---------------------------------------------------------------------------

def tiny(base: str, M: int = TINY["M"]):
    """The mlp kind, a tiny copy of a committed configuration, and its
    data."""
    import run as harness
    cfg = json.loads((BENCH / "configs" / f"{base}.json").read_text())
    cfg["fleet"].update(M=M, samples_per_client=TINY["samples_per_client"])
    cfg["data"]["n_per_class"] = TINY["n_per_class"]
    if cfg["framework"] == "fedavg":
        cfg["hyper"].update(K=3, E=3)
    kind = harness.load_kind(ROOT, "mlp")
    clients, test = kind.make_data(cfg["data"], M,
                                   cfg["fleet"]["samples_per_client"], 3)
    return kind, cfg, clients, test


_BLOCKED = {}


def blocked(kind, client_block, sample_block):
    """``kind`` with the two block sizes set, one module per pair (the
    reference's jits take the kind as a static argument)."""
    key = (kind.__name__, client_block, sample_block)
    if key not in _BLOCKED:
        mod = types.ModuleType(f"{kind.__name__}_blocked")
        mod.__dict__.update({k: v for k, v in vars(kind).items()
                             if not k.startswith("__")})
        mod.client_block, mod.sample_block = client_block, sample_block
        _BLOCKED[key] = mod
    return _BLOCKED[key]


@functools.lru_cache(maxsize=None)
def today(base: str, variant: str):
    """The default path's campaign, once per configuration and variant:
    two rounds, the first of which selects 5 of SplitMe's 6 RICs (so a
    block of 3 is padded) and the second all 6.  From the third on, one
    ReLU near its kink turns the blocked sums' last-digit round-off into
    gaps of 1e-6 (SplitMe, on the CPU)."""
    kind, cfg, clients, test = tiny(base)
    return reference.run_campaign(kind, cfg, clients, test, rounds=2,
                                  seeds=[3, 4], **VARIANTS[variant])


VARIANTS = {"plain": {},
            "train_mask": {"train_mask": calibrate.first_half},
            "fp8": {"compute_dtype": calibrate.control_dtype()}}


def assert_same(got: dict, want: dict, tol: float = 1e-6):
    """Each parameter leaf within ``tol`` of today's by its norm, the
    losses within ``tol`` of the campaign's largest, and the same accuracy.
    A loss is a KL, the small difference of two cross-entropies: its last
    digits are round-off of those, so it is not held to its own size."""
    for g, w in zip(jax.tree.leaves(got["params"]),
                    jax.tree.leaves(want["params"])):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        assert np.linalg.norm(g - w) <= tol * np.linalg.norm(w)
    lg, lw = np.asarray(got["losses"]), np.asarray(want["losses"])
    assert np.max(np.abs(lg - lw)) <= tol * np.max(np.abs(lw))
    np.testing.assert_array_equal(got["acc"][-1], want["acc"][-1])
    np.testing.assert_array_equal(got["init"][0][0]["w"],
                                  want["init"][0][0]["w"])


@pytest.mark.parametrize("sample_block", [7, None])
@pytest.mark.parametrize("client_block", [1, 3])
@pytest.mark.parametrize("base", ["splitme-dnn10-m50", "fedavg-dnn10-m50"])
def test_blocked_reference_equals_todays_path(base, client_block,
                                              sample_block):
    """A round of A_t's clients, ``client_block`` at a time (3 does not
    divide the 6 RICs), and forwards of ``sample_block`` samples give
    today's parameters, losses and accuracy."""
    kind, cfg, clients, test = tiny(base)
    got = reference.run_campaign(blocked(kind, client_block, sample_block),
                                 cfg, clients, test, rounds=2, seeds=[3, 4])
    assert_same(got, today(base, "plain"))


@pytest.mark.parametrize("variant", ["train_mask", "fp8"])
@pytest.mark.parametrize("base", ["splitme-dnn10-m50", "fedavg-dnn10-m50"])
def test_blocked_reference_keeps_faults_and_control(base, variant):
    """``calibrate.py``'s half-cohort ``train_mask`` and its fp8 control
    read the same on the blocked path as on today's."""
    kind, cfg, clients, test = tiny(base)
    got = reference.run_campaign(blocked(kind, 1, 7), cfg, clients, test,
                                 rounds=2, seeds=[3, 4], **VARIANTS[variant])
    assert_same(got, today(base, variant))


@pytest.mark.parametrize("base", ["splitme-dnn10-m50", "fedavg-dnn10-m50"])
def test_unselected_nan_stays_out(base):
    """A NaN in the data of a client no round trains: the blocked round
    never reads it; today's masked average takes 0 * NaN and is lost."""
    kind, cfg, clients, test = tiny(base)
    clients = dict(clients, x=np.array(clients["x"]))
    clients["x"][-1, 0, 0] = np.nan

    def first_three(a):
        out = np.zeros_like(a)
        out[:, :3] = 1.0
        return out

    kw = dict(rounds=2, seeds=[3], train_mask=first_three)
    for client_block in (1, 3):
        got = reference.run_campaign(blocked(kind, client_block, None), cfg,
                                     clients, test, **kw)
        for leaf in jax.tree.leaves(got["params"]):
            assert np.isfinite(leaf).all()
        assert np.isfinite(got["losses"]).all()
    lost = reference.run_campaign(kind, cfg, clients, test, **kw)
    assert not all(np.isfinite(l).all()
                   for l in jax.tree.leaves(lost["params"]))


def round_temp_bytes(kind, base: str, M: int) -> int:
    """Temporary bytes of one compiled reference round of a fleet of M."""
    _, cfg, clients, test = tiny(base, M)
    fw = cfg["framework"]
    a = np.zeros((1, M))
    a[0, ::2] = 1.0
    data = {"x": jnp.asarray(clients["x"]), "y": jnp.asarray(clients["y"]),
            "x_test": jnp.asarray(test[0]), "y_test": jnp.asarray(test[1])}
    params = jax.tree.map(lambda l: l[None], reference.init_params(
        kind, fw, cfg["model"], 0))
    hp = tuple(sorted((k, v) for k, v in cfg["hyper"].items()
                      if k in ("lr", "lr_c", "lr_s", "temperature",
                               "batch_size")))
    args = (params, jnp.asarray(a[0], jnp.float32), jnp.int32(5),
            jax.random.PRNGKey(0)[None], data, kind, fw, hp,
            kind.sizes(cfg["model"])["n_classes"], None)
    if getattr(kind, "client_block", None) is None:
        lowered = reference._round.lower(*args)
    else:
        sel, n_sel = reference.selected(a, kind.client_block)
        lowered = reference._round_blocked.lower(
            *args, sel=jnp.asarray(sel[0]), n_sel=jnp.int32(n_sel[0]))
    return lowered.compile().memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize("base", ["splitme-dnn10-m50", "fedavg-dnn10-m50"])
def test_blocked_round_memory_does_not_grow_with_the_fleet(base):
    """With ``client_block`` 1 a round's temporaries stay put when M
    doubles; today's round holds a trained copy per client and grows."""
    kind = tiny(base)[0]
    one = blocked(kind, 1, None)
    small, large = (round_temp_bytes(one, base, M) for M in (8, 16))
    assert large < 1.1 * small, (small, large)
    small, large = (round_temp_bytes(kind, base, M) for M in (8, 16))
    assert large > 1.5 * small, (small, large)
