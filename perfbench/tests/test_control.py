"""The control of ``correct``: the reference put in the program's place in
the nearest precision below the configuration's comes out not correct
under each committed configuration's limits.  On the chip it ran at the
cells' own size (``calibrate.py``; readings in PERF.md); here at a size a
test run holds, on the CPU."""
import json

import jax
import pytest

from conftest import BENCH
import calibrate
import compare
import reference


@pytest.mark.parametrize("base", ["splitme-dnn10-m50", "fedavg-dnn10-m50",
                                  "splitme-dnn10-m48-mesh4"])
def test_control_fails_the_limits(tiny_root, base):
    import run as harness
    limits = json.loads((BENCH / "configs" / f"{base}.json").read_text())[
        "limits"]
    c = harness.load_cell(tiny_root, base.split("-")[0] + "-tiny.mini")
    system = harness.System(tiny_root, c, 11, jax.devices())
    seeds = system.next_seeds()
    kw = dict(rounds=4, seeds=seeds)
    ref = reference.run_campaign(system.kind, system.config, system.clients,
                                 system.test, **kw)
    control = reference.run_campaign(system.kind, system.config,
                                     system.clients, system.test, **kw,
                                     compute_dtype=calibrate.control_dtype())
    ref_acc = reference.accuracy(system.kind, system.config, system.clients,
                                 system.test, control["params"])
    correct, rows = compare.judge(compare.readings(control, ref, ref_acc),
                                  limits)
    assert not correct, rows
