"""The stand-in kind of ``standin.py`` (the reference's blocked path on a
model too large to copy per client; on the chip at 755 M parameters,
PERF.md) runs here at small widths, and gives the default path's
campaign."""
import jax
import numpy as np
import pytest

import reference
import standin
from test_reference import assert_same


@pytest.mark.parametrize("width,depth", [(16, 2), (48, 3)])
def test_standin_equals_the_default_path(width, depth):
    res = standin.fits(width=width, depth=depth, fleet=8, k=4, rounds=2)
    assert res["finite"] and res["client_params"] > 0
    cfg = standin.config(width, depth, 8)
    clients, test = standin.make_data(cfg["data"], 8,
                                      cfg["fleet"]["samples_per_client"], 0)
    kw = dict(rounds=2, seeds=[0], train_mask=standin.trained(4, 0))
    mlp = standin._mlp
    got = reference.run_campaign(standin, cfg, clients, test, **kw)
    want = reference.run_campaign(mlp, cfg, clients, test, **kw)
    assert_same(got, want)
    assert np.asarray(want["a"]).shape == (2, 8)
    assert jax.tree.structure(got["params"]) == jax.tree.structure(
        want["params"])
