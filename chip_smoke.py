"""Chip smoke test: the scanned federated campaign on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the client-sharded mesh campaign only

Drives the system's main path, ``repro.launch.campaign.run_campaign``, at
the paper's full width: the DNN10 split model (30-256-256 | 128-128-64-64-
32-32-16-3, split after layer 2), the paper's fleet (``SystemParams()``,
M=50 near-RT-RICs with 96 samples each) and the default kernel policy,
which on a TPU runs the Pallas ``kl_mutual`` and ``ridge_gram`` kernels.
Weights are random, drawn from the seeds.

One chip (no arguments):

1. SplitMe, 2 seeds x 6 rounds, ``eval_every=3`` so the fused Step-4
   inversion (and with it ``ridge_gram``) runs inside the scan, under
   ``strict_transfers`` (one device-to-host transfer per campaign),
2. the same campaign under ``policy="reference"`` on the chip (kernel
   parity on the device),
3. the same reference campaign on the host CPU in this process (the
   chip's accuracy against the CPU at the same seeds),
4. FedAvg, 2 seeds x 4 rounds, on the chip and on the CPU (the shared
   engine's full-model path).

``--chips 4``: SplitMe over a (data=4, model=1) mesh with M=48 clients
sharded over the four chips, against the same campaign on one chip.

Each phase prints what it measured; the checks compare against the
tolerances below.  Any failed phase exits non-zero.  The last line of a
passing run is exactly one JSON object naming the device.  Without a TPU
the script fails and prints no result; it never falls back to the CPU.
Times printed are from one run, compile included, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

SEEDS = (0, 1)
SPLITME_ROUNDS = 6
EVAL_EVERY = 3
FEDAVG_ROUNDS = 4
MESH_CLIENTS = 48           # divisible by the 4 client shards

# Tolerances, each set from the gap measured on TPU v5e (quoted beside it;
# see CHANGES.md).  Losses are per-round phase losses of every seed;
# accuracies are the final test accuracies per seed (1200 samples, so one
# flipped prediction moves accuracy by 8.3e-4).  The chip's default f32
# matmul is one bf16 pass, so chip and CPU training drift apart a little.
KERNEL_LOSS_ATOL = 1e-3     # kernel vs reference policy on the chip: 1.1e-4
KERNEL_ACC_ATOL = 2e-2      # 2.5e-3
CPU_LOSS_ATOL = 2e-2        # chip vs CPU: 2.9e-4 SplitMe, 5.1e-3 FedAvg
CPU_ACC_ATOL = 2e-2         # 2.5e-3 SplitMe, 0 FedAvg
MESH_LOSS_ATOL = 1e-3       # 4-chip mesh vs one chip: 1.0e-5
MESH_ACC_ATOL = 2e-2        # 4.2e-3
MIN_ACCURACY = 0.5          # three classes: chance is 1/3


class CompileCounter:
    """Counts the XLA executables JAX builds (compiled, or loaded from the
    persistent cache) and their seconds, through ``jax.monitoring``."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def reset(self):
        self.executables, self.seconds, self.cache_hits = 0, 0.0, 0

    def _on_time(self, event, duration, **_):
        if event == self.BUILD:
            self.executables += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == self.CACHE_HIT:
            self.cache_hits += 1


class Checks:
    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str) -> None:
        print(f"[{'PASS' if ok else 'FAIL'}] {name}: {detail}", flush=True)
        if not ok:
            self.failed.append(name)


def _data(n_clients: int):
    from repro.data import oran
    X, y = oran.generate(n_per_class=2000, seed=0)
    (Xtr, ytr), test = oran.train_test_split(X, y)
    clients = oran.partition_non_iid(Xtr, ytr, n_clients,
                                     samples_per_client=96, seed=0)
    return clients, test


def _campaign(counter, framework, clients, test, *, device=None, **kw):
    """One ``run_campaign`` under strict transfers, with its transfer
    count, executables built and wall time."""
    import jax
    from repro.configs.splitme_dnn import DNN10
    from repro.core.cost import SystemParams
    from repro.launch import campaign

    before = campaign.HOST_TRANSFERS
    counter.reset()
    where = (jax.default_device(device) if device is not None
             else contextlib.nullcontext())
    t0 = time.perf_counter()
    with where:
        res = campaign.run_campaign(
            framework, DNN10, SystemParams(M=len(clients["y"]), seed=0),
            clients, seeds=SEEDS, test_data=test, strict_transfers=True,
            **kw)
    stats = {"wall_s": time.perf_counter() - t0,
             "transfers": campaign.HOST_TRANSFERS - before,
             "executables": counter.executables,
             "compile_s": counter.seconds,
             "cache_hits": counter.cache_hits}
    return res, stats


def _report(label, res, stats):
    import numpy as np
    last = res.losses[:, -1, :]
    print(f"{label}: accuracy per seed {res.accuracy.tolist()}; "
          f"last-round loss per seed/phase {last.tolist()}; "
          f"host transfers {stats['transfers']}; executables built "
          f"{stats['executables']} ({stats['cache_hits']} from the "
          f"persistent cache) in {stats['compile_s']:.2f} s; wall "
          f"{stats['wall_s']:.2f} s (one run, compile included); "
          f"finite losses {bool(np.isfinite(res.losses).all())}",
          flush=True)


def _parity(check, name, a, b, loss_atol, acc_atol):
    """Max absolute gaps of two campaigns' per-round losses and final
    accuracies, against their tolerances."""
    import numpy as np
    loss_gap = float(np.max(np.abs(a.losses - b.losses)))
    acc_gap = float(np.max(np.abs(a.accuracy - b.accuracy)))
    check(name, loss_gap <= loss_atol and acc_gap <= acc_atol,
          f"max |loss gap| {loss_gap:.3e} (tol {loss_atol}), "
          f"max |accuracy gap| {acc_gap:.3e} (tol {acc_atol})")


def _sane(check, label, res, stats, *, learns=True):
    """One transfer, finite values of the expected shapes and, for SplitMe
    (FedAvg on the one-class-per-client split hovers near chance for
    dozens of rounds), an accuracy well above chance."""
    import numpy as np
    check(f"{label} one host transfer", stats["transfers"] == 1,
          f"{stats['transfers']} transfer(s)")
    n_phases = 2 if res.framework == "splitme" else 1
    shapes = (res.losses.shape == (len(SEEDS), res.schedule.rounds, n_phases)
              and res.accuracy.shape == (len(SEEDS),))
    check(f"{label} finite", shapes and bool(
        np.isfinite(res.losses).all() and np.isfinite(res.accuracy).all()),
          f"losses shape {res.losses.shape}, accuracy shape "
          f"{res.accuracy.shape}")
    if learns:
        check(f"{label} learns", bool(np.all(res.accuracy > MIN_ACCURACY)),
              f"accuracy {res.accuracy.tolist()} > {MIN_ACCURACY}")


def _kernels_in_compiled_splitme(clients, test):
    """Whether the compiled SplitMe round (kl_mutual) and fused eval
    (ridge_gram) under the default policy hold a Mosaic kernel call."""
    import jax
    import jax.numpy as jnp
    from repro.configs.splitme_dnn import DNN10
    from repro.core import engine

    spec = engine.make_spec("splitme", DNN10, masked_loss_metric=True)
    rnd = engine.build_round_fn(spec, DNN10, jnp.asarray(clients["x"]),
                                jnp.asarray(clients["y"]), e_max=20,
                                gather=True)
    params = spec.init_fn(jax.random.PRNGKey(0))
    cohort = 16
    round_text = rnd.lower(
        params, jnp.arange(cohort), jnp.ones(cohort), jnp.int32(20),
        jax.random.PRNGKey(1), engine.init_quant_state(spec, params)
    ).compile().as_text()
    ev = engine.build_eval_fn(spec, DNN10, *test, client_data=clients)
    eval_text = ev.lower(params).compile().as_text()
    return "tpu_custom_call" in round_text, "tpu_custom_call" in eval_text


def one_chip(check, counter, cpu) -> None:
    from repro.kernels import dispatch

    pol = dispatch.get_policy(None)
    print(f"resolved kernel policy: {pol}", flush=True)
    check("policy turns both kernels on", pol.kl_mutual and pol.ridge_gram,
          f"kl_mutual={pol.kl_mutual} ridge_gram={pol.ridge_gram}")

    clients, test = _data(50)
    in_round, in_eval = _kernels_in_compiled_splitme(clients, test)
    check("tpu_custom_call in the compiled SplitMe round", in_round,
          f"round={in_round} fused eval={in_eval}")
    check("tpu_custom_call in the compiled SplitMe eval", in_eval,
          f"fused eval={in_eval}")

    sm = dict(rounds=SPLITME_ROUNDS, eval_every=EVAL_EVERY)
    runs = {}
    for label, kw in [("splitme kernel/chip", {}),
                      ("splitme reference/chip", {"policy": "reference"}),
                      ("splitme reference/cpu", {"policy": "reference",
                                                 "device": cpu})]:
        res, stats = _campaign(counter, "splitme", clients, test, **sm, **kw)
        _report(label, res, stats)
        _sane(check, label, res, stats)
        runs[label] = res

    _parity(check, "splitme kernel vs reference on the chip",
            runs["splitme kernel/chip"], runs["splitme reference/chip"],
            KERNEL_LOSS_ATOL, KERNEL_ACC_ATOL)
    _parity(check, "splitme chip vs cpu (reference policy)",
            runs["splitme reference/chip"], runs["splitme reference/cpu"],
            CPU_LOSS_ATOL, CPU_ACC_ATOL)

    fa = dict(rounds=FEDAVG_ROUNDS, K=10, E=10)
    chip, stats = _campaign(counter, "fedavg", clients, test, **fa)
    _report("fedavg default/chip", chip, stats)
    _sane(check, "fedavg default/chip", chip, stats, learns=False)
    host, stats = _campaign(counter, "fedavg", clients, test, device=cpu,
                            policy="reference", **fa)
    _report("fedavg reference/cpu", host, stats)
    _sane(check, "fedavg reference/cpu", host, stats, learns=False)
    _parity(check, "fedavg chip vs cpu", chip, host,
            CPU_LOSS_ATOL, CPU_ACC_ATOL)


def four_chips(check, counter) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import engine
    from repro.launch.mesh import make_client_mesh

    mesh = make_client_mesh(4)
    clients, test = _data(MESH_CLIENTS)
    shard = NamedSharding(mesh, P(engine.client_axes(mesh)))
    sharded = {k: jax.device_put(v, shard) for k, v in clients.items()}
    spans = len(sharded["x"].sharding.device_set)
    check("client data sharded over 4 devices", spans == 4,
          f"x {sharded['x'].shape} over {spans} device(s): "
          f"{sharded['x'].sharding}")

    sm = dict(rounds=SPLITME_ROUNDS, eval_every=EVAL_EVERY)
    mesh_res, stats = _campaign(counter, "splitme", sharded, test,
                                mesh=mesh, **sm)
    _report("splitme mesh data=4", mesh_res, stats)
    _sane(check, "splitme mesh data=4", mesh_res, stats)
    one, stats = _campaign(counter, "splitme", clients, test, **sm)
    _report("splitme one chip", one, stats)
    _sane(check, "splitme one chip", one, stats)
    _parity(check, "splitme mesh vs one chip", mesh_res, one,
            MESH_LOSS_ATOL, MESH_ACC_ATOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the client-sharded mesh campaign and "
                         "its one-chip comparison")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    # keep the host CPU reachable for the CPU reference next to the chip
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devices[0].platform} "
              f"devices only); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 1
    print(f"cache: {enable_compile_cache()}", flush=True)
    print(f"device: {devices[0].device_kind} x {len(devices)}", flush=True)

    check, counter = Checks(), CompileCounter()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(check, counter)
    else:
        one_chip(check, counter, jax.devices("cpu")[0])
    print(f"total wall {time.perf_counter() - t0:.2f} s", flush=True)
    if check.failed:
        print(f"chip_smoke: FAILED: {', '.join(check.failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
