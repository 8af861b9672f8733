"""The harness's run with the timed path broken underneath comes out
``correct: false``, once for each fault these cells can have.  (No cell
spans chips yet, so the fault of a left-out exchange has no case.)"""
import jax.numpy as jnp
import pytest

from conftest import run_tiny


def _state_unchanged(orig):
    def core(spec, runners, params, *a, **k):
        out = orig(spec, runners, params, *a, **k)
        return (params,) + tuple(out[1:])
    return core


def _half_cohort(orig):
    def core(spec, runners, params, ctx_c, a_mask, *a, **k):
        m = a_mask.shape[0]
        keep = (jnp.arange(m) < max(m // 2, 1)).astype(a_mask.dtype)
        return orig(spec, runners, params, ctx_c, a_mask * keep, *a, **k)
    return core


def _accuracy_altered(orig):
    def build(*a, **k):
        acc = orig(*a, **k)
        return lambda params: acc(params) + 0.1
    return build


FAULTS = {
    "state_unchanged": ("_round_core", _state_unchanged),
    "half_cohort": ("_round_core", _half_cohort),
    "answer_altered": ("build_eval_fn", _accuracy_altered),
}


@pytest.mark.parametrize("cell", ["splitme-tiny.mini", "fedavg-tiny.mini"])
def test_sound_run_is_correct(tiny_root, cell):
    assert run_tiny(tiny_root, cell)["correct"] is True


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", ["splitme-tiny.mini", "fedavg-tiny.mini"])
def test_fault_is_incorrect(tiny_root, monkeypatch, cell, fault):
    from repro.core import engine
    name, make = FAULTS[fault]
    monkeypatch.setattr(engine, name, make(getattr(engine, name)))
    result = run_tiny(tiny_root, cell)
    assert result["correct"] is False, result["checks"]
