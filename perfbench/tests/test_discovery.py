"""A configuration, a traffic mix and a per-layer metric added as new
files plus new BENCHMARK.json entries run without an edit to any file the
benchmark already has."""
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
