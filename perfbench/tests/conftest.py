"""Fixtures of the benchmark's own tests (run on the CPU):

    JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests

``tiny_root`` is a checkout in a temporary directory: the committed
``BENCHMARK.json`` and ``perfbench/``, the program's ``src`` beside them,
and, added as files and entries only, a small SplitMe and FedAvg
configuration (6 RICs x 32 samples) under a 4-round mix.  The harness runs
there in-process with its look for a chip skipped; ``run_on_devices`` runs
it in a child process that sees several CPU devices, for a mesh cell.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
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


def add_cell(root: Path, config: str, traffic: str, mix=None,
             chips: int = 1) -> str:
    if mix is not None:
        (root / "perfbench" / "mixes" / f"{traffic}.json").write_text(
            json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    name = f"{config}.{traffic}"
    bench["workloads"].append({"name": name, "config": config,
                               "traffic": traffic, "chips": chips,
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


def add_mesh_cell(root: Path) -> str:
    """A tiny copy of the committed mesh configuration: 8 RICs over a
    data=4 mesh, 2 per device, under the 4-round mix."""
    add_config(root, "mesh-tiny", "splitme-dnn10-m48-mesh4",
               fleet={"M": 8, "seed": 0,
                      "samples_per_client": TINY["samples_per_client"]})
    return add_cell(root, "mesh-tiny", "mini", chips=4)


CHILD = """
import json, sys
from pathlib import Path
sys.path.insert(0, {tests!r})
import contextlib
from conftest import run_tiny
from faults import planted
fault = {fault!r}
with planted(fault) if fault else contextlib.nullcontext():
    result = run_tiny(Path({root!r}), {workload!r}, seed={seed!r})
print(json.dumps(result))
"""


def run_on_devices(root: Path, workload: str, devices: int, seed: int = 5,
                   fault=None) -> dict:
    """One harness run in a child process on ``devices`` CPU devices, with
    ``fault`` (a name of ``faults.MESH_FAULTS``) planted if given."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    code = CHILD.format(tests=str(Path(__file__).resolve().parent),
                        root=str(root), workload=workload, seed=seed,
                        fault=fault)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])
