"""A configuration, a traffic mix, a per-layer metric and a model kind
added as new files plus new BENCHMARK.json entries run without an edit to
any file the benchmark already has."""
import json

import pytest

from conftest import MIX, add_cell, add_config, run_tiny


@pytest.fixture
def cpu_peaks(monkeypatch):
    import run as harness
    monkeypatch.setattr(harness, "device_peaks",
                        lambda root, kind: {"bf16_tflops": 197.0,
                                            "hbm_gb_s": 819.0})


def test_new_config_mix_and_metric_by_name(tiny_root, cpu_peaks):
    add_config(tiny_root, "splitme-dummy", "splitme-dnn10-m50",
               eval_gamma=0.01)
    name = add_cell(tiny_root, "splitme-dummy", "dummy",
                    dict(MIX, rounds=3, seeds_per_campaign=1, eval_every=3))
    reader = tiny_root / "perfbench" / "metrics" / "dummy_campaigns.py"
    reader.write_text("def read(ctx):\n    return float(ctx['campaigns'])\n")
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "dummy_campaigns", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "test",
        "moves": "seed_rounds_per_s", "workloads": [name]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))

    result = run_tiny(tiny_root, name, trace=1)
    assert result["correct"] is True
    assert result["metrics"]["dummy_campaigns"]["value"] >= 1.0
    # readers of the committed metrics that find something run too
    assert result["metrics"]["plan_s_per_campaign"]["value"] > 0
    assert result["metrics"]["rebuild_s_per_campaign"]["value"] > 0
    # no TPU trace on the CPU: the device readers stay silent, never 0
    for silent in ("round_device_ms", "idle_share", "kl_mutual_roofline",
                   "ridge_gram_roofline"):
        assert silent not in result["metrics"]
    assert list(result)[-1] == "checks"


def test_untraced_result_line(tiny_root):
    result = run_tiny(tiny_root, "fedavg-tiny.mini")
    assert set(result["metrics"]) == {"seed_rounds_per_s", "setup_s"}
    assert result["metrics"]["seed_rounds_per_s"]["unit"] == "seed-rounds/s"
    assert result["device"]["count"] >= 1
    assert result["correct"] is True and result["attempted"] >= 1


def test_population_entry_runs(tiny_root):
    """The ``entry`` of a configuration may name the population runner."""
    import jax
    import run as harness
    add_config(tiny_root, "fedavg-pop", "fedavg-dnn10-m50",
               entry="run_population_campaign",
               population={"size": 1000, "seed": 0}, cohort=6)
    name = add_cell(tiny_root, "fedavg-pop", "mini")
    system = harness.System(tiny_root, harness.load_cell(tiny_root, name), 3,
                            jax.devices())
    res = system.run(system.next_seeds())
    assert res.losses.shape == (2, 4, 1)
    assert res.accuracy_per_round.shape == (4, 2)


# A model kind of its own: the mlp kind's functions, loaded from its file
# apart from the harness's copy, with every call counted.
PROBE_KIND = """
import collections
import importlib.util
import pathlib

_spec = importlib.util.spec_from_file_location(
    "probe_base", pathlib.Path(__file__).with_name("mlp.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)
CALLS = collections.Counter()


def _counted(name):
    fn = getattr(_base, name)

    def call(*a, **k):
        CALLS[name] += 1
        return fn(*a, **k)
    return call


for _name in ("program_model", "make_data", "init_params", "client_forward",
              "full_forward", "sizes", "forward_flops", "backward_flops"):
    globals()[_name] = _counted(_name)
"""


def test_new_model_kind_by_name(tiny_root, cpu_peaks):
    import run as harness
    models = tiny_root / "perfbench" / "models"
    (models / "probe.py").write_text(PROBE_KIND)
    add_config(tiny_root, "splitme-probe", "splitme-dnn10-m50")
    cfg_path = tiny_root / "perfbench" / "configs" / "splitme-probe.json"
    cfg = json.loads(cfg_path.read_text())
    cfg["model"]["kind"] = "probe"
    cfg_path.write_text(json.dumps(cfg))
    name = add_cell(tiny_root, "splitme-probe", "mini", MIX)

    result = run_tiny(tiny_root, name, trace=1)
    assert result["correct"] is True, result["checks"]
    assert result["metrics"]["train_mfu"]["value"] > 0
    calls = harness.load_kind(tiny_root, "probe").CALLS
    for fn in ("program_model", "make_data", "init_params", "client_forward",
               "sizes", "forward_flops", "backward_flops"):
        assert calls[fn] > 0, fn
    # the harness never loaded this checkout's mlp kind
    assert str(models / "mlp.py") not in harness._KINDS


# The probe kind, stating the reference's two block sizes, with the rows of
# every client forward recorded.
BLOCKED_KIND = PROBE_KIND + """
client_block = 2
sample_block = 5
ROWS = []


def client_forward(params, x, dt=None):
    ROWS.append(x.shape[0])
    return _base.client_forward(params, x, dt)
"""


def test_new_kind_with_blocks(tiny_root, monkeypatch):
    """A kind added as a file only, with ``client_block`` and
    ``sample_block``: the reference trains its rounds two clients at a
    time and never runs a client forward over more than a batch."""
    import reference
    import run as harness
    blocks = []
    orig = reference._blocked_average

    def spy(*a):
        blocks.append(a[-1])
        return orig(*a)

    monkeypatch.setattr(reference, "_blocked_average", spy)
    (tiny_root / "perfbench" / "models" / "blocked.py").write_text(
        BLOCKED_KIND)
    add_config(tiny_root, "splitme-blocked", "splitme-dnn10-m50")
    cfg_path = tiny_root / "perfbench" / "configs" / "splitme-blocked.json"
    cfg = json.loads(cfg_path.read_text())
    cfg["model"]["kind"] = "blocked"
    cfg_path.write_text(json.dumps(cfg))
    name = add_cell(tiny_root, "splitme-blocked", "mini", MIX)

    result = run_tiny(tiny_root, name)
    assert result["correct"] is True, result["checks"]
    assert blocks and set(blocks) == {2}
    rows = harness.load_kind(tiny_root, "blocked").ROWS
    batch = cfg["hyper"]["batch_size"]
    assert 5 in rows and max(rows) <= batch, sorted(set(rows))
