"""Faults planted under the timed path: each rebinds one function of the
program's engine, so a run through the cell's own entry computes a wrong
result.  ``calibrate.py`` reads them on the chip (the upper end of a
limit), ``tests/test_faults.py`` sees each come out not correct.

* ``state_unchanged``: every round returns the parameters it was given;
* ``half_cohort``: every round trains and averages the first half of its
  clients only (of each shard's, on a mesh);
* ``answer_altered``: the eval adds 0.1 to every accuracy it reports;
* ``exchange_left_out`` (a cell on a mesh): the round's one cross-chip
  psum (``engine.psum_bundle``) returns each shard's own sums.
"""
from __future__ import annotations

import contextlib


def _state_unchanged(orig):
    def core(spec, runners, params, *a, **k):
        out = orig(spec, runners, params, *a, **k)
        return (params,) + tuple(out[1:])
    return core


def _half_cohort(orig):
    import jax.numpy as jnp

    def core(spec, runners, params, ctx_c, a_mask, *a, **k):
        m = a_mask.shape[0]
        keep = (jnp.arange(m) < max(m // 2, 1)).astype(a_mask.dtype)
        return orig(spec, runners, params, ctx_c, a_mask * keep, *a, **k)
    return core


def _answer_altered(orig):
    def build(*a, **k):
        acc = orig(*a, **k)
        return lambda params: acc(params) + 0.1
    return build


def _exchange_left_out(orig):
    def psum_bundle(tree, axis_names, wire_dtype=None):
        return tree
    return psum_bundle


FAULTS = {
    "state_unchanged": ("_round_core", _state_unchanged),
    "half_cohort": ("_round_core", _half_cohort),
    "answer_altered": ("build_eval_fn", _answer_altered),
}
MESH_FAULTS = dict(FAULTS,
                   exchange_left_out=("psum_bundle", _exchange_left_out))


@contextlib.contextmanager
def planted(fault: str):
    """Inside, the engine runs with ``fault``.  The driver's cache of
    compiled segment scans is emptied on the way in and out: its key holds
    some of the engine's functions but not ``psum_bundle``."""
    from repro.core import engine
    from repro.launch import campaign
    name, make = MESH_FAULTS[fault]
    orig = getattr(engine, name)
    setattr(engine, name, make(orig))
    campaign.clear_segment_cache()
    try:
        yield
    finally:
        setattr(engine, name, orig)
        campaign.clear_segment_cache()
