"""Fixtures of the benchmark's own tests (run on the CPU):

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests

``tiny_root`` is a checkout in a temporary directory: the committed
``BENCHMARK.json`` and ``perfbench/``, the program's ``src`` beside them,
and, added as files and entries only, a small SplitMe and FedAvg
configuration (6 RICs x 32 samples) under a 4-round mix.  The harness runs
there in-process with its look for a chip skipped.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

TINY = {"M": 6, "samples_per_client": 32, "n_per_class": 200}
MIX = {"rounds": 4, "seeds_per_campaign": 2, "eval_every": 2,
       "scenario": "static"}


def add_config(root: Path, name: str, base: str, **changes) -> None:
    """A configuration file made from ``base`` and its BENCHMARK.json
    entry."""
    cfg = json.loads((root / "perfbench" / "configs"
                      / f"{base}.json").read_text())
    cfg["name"] = name
    cfg["fleet"]["M"] = TINY["M"]
    cfg["fleet"]["samples_per_client"] = TINY["samples_per_client"]
    cfg["data"]["n_per_class"] = TINY["n_per_class"]
    if cfg["framework"] == "fedavg":
        cfg["hyper"].update(K=3, E=3)
    for k, v in changes.items():
        cfg[k] = v
    path = f"perfbench/configs/{name}.json"
    (root / path).write_text(json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": name, "source": "test", "file": path,
                             "reduced": ["fleet"], "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def add_cell(root: Path, config: str, traffic: str, mix=None) -> str:
    if mix is not None:
        (root / "perfbench" / "mixes" / f"{traffic}.json").write_text(
            json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    name = f"{config}.{traffic}"
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": traffic, "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return name


@pytest.fixture
def tiny_root(tmp_path) -> Path:
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(BENCH, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(ROOT / "src", root / "src")
    add_config(root, "splitme-tiny", "splitme-dnn10-m50")
    add_config(root, "fedavg-tiny", "fedavg-dnn10-m50")
    add_cell(root, "splitme-tiny", "mini", MIX)
    add_cell(root, "fedavg-tiny", "mini")
    return root


def run_tiny(root: Path, workload: str, seed: int = 5, trace: int = 0):
    """One harness run in this process, on the CPU."""
    import run as harness
    args = argparse.Namespace(workload=workload, seed=seed, seconds=0.0,
                              trace=trace, keep_trace=None)
    return harness.run(args, root=root, require_chip=False,
                       t_start=time.perf_counter())
