"""Chip benchmark of the federated campaign driver.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell of ``BENCHMARK.json`` names a configuration
(``perfbench/configs/<name>.json``: framework, hyper-parameters, kernel
policy, wire format, model, fleet, entry, chips, limits) and a traffic mix
(``perfbench/mixes/<name>.json``: rounds, seeds per campaign, eval cadence,
scenario).  The configuration's model names its kind (``"kind"``, default
``mlp``), and ``perfbench/models/<kind>.py`` gives everything that depends
on the model: the program's model argument, the data, and the reference's
forward passes, sizes and operation counts.  A run:

1. set-up: makes the client and test data from ``--seed`` and runs one
   warm-up campaign of the mix, which compiles every shape the window uses
   or loads it from JAX's persistent cache in ``<checkout>/.jax_cache``;
   ``setup_s`` ends there;
2. window: calls the entry (``run_campaign``) back to back, each call a
   whole campaign with fresh training seeds drawn from ``--seed``, and
   stops at the first campaign that ends after ``--seconds``;
   ``seed_rounds_per_s`` is every seed-round of every campaign over the
   whole window;
3. with ``--trace 1``: host spans and JAX's compile events are counted
   over the window, then one more campaign runs under the JAX profiler,
   and the per-layer readers (``perfbench/metrics/<name>.py``) reduce the
   counters and the trace;
4. check: a campaign of the window drawn from the seed is run again by the
   plain reference (``reference.py``) and compared (``compare.py``)
   against the limits in the configuration.

The last line of standard output is the result as one JSON object; the
numbers compared, each beside its limit, close standard error.  Without a
TPU, or with fewer chips than the cell asks for, the run exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# The cell, by name
# ---------------------------------------------------------------------------

def load_cell(root: Path, name: str) -> dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((root / entry["file"]).read_text())
    mix = json.loads((root / "perfbench" / "mixes"
                      / f"{cell['traffic']}.json").read_text())

    def mine(m):
        return name in m.get("workloads", [name])

    return {"bench": bench, "cell": cell, "config": config, "mix": mix,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


_KINDS: dict = {}


def load_kind(root: Path, name: str):
    """The model kind ``perfbench/models/<name>.py``, loaded once per path:
    the reference's jitted functions take it as a static argument."""
    path = root / "perfbench" / "models" / f"{name}.py"
    if str(path) not in _KINDS:
        spec = importlib.util.spec_from_file_location(f"model_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _KINDS[str(path)] = mod
    return _KINDS[str(path)]


def kind_of(config: dict) -> str:
    return config["model"].get("kind", "mlp")


def load_reader(root: Path, metric: str):
    path = root / "perfbench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def chips_for(cell: dict):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {len(devs)} {devs[0].platform} "
                     f"device(s)")
    if len(devs) < cell["chips"]:
        raise NoChip(f"the cell asks for {cell['chips']} chips, JAX sees "
                     f"{len(devs)}")
    return devs


# ---------------------------------------------------------------------------
# Host counters: the harness's spans and JAX's compile events
# ---------------------------------------------------------------------------

REBUILD_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class Counters:
    """Executables built and loaded from the persistent cache, and the
    seconds of tracing, lowering and compiling or loading (the union of
    their spans, so nested events count once)."""

    def __init__(self):
        import jax
        self.reset()
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_time_span_listener(self._span)

    def reset(self):
        self.built = self.loaded = 0
        self.spans = []

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.loaded += 1

    def _span(self, event, start, end, **_):
        if event in REBUILD_EVENTS:
            self.spans.append((start, end))
            if event == REBUILD_EVENTS[2]:
                self.built += 1

    def rebuild_s(self) -> float:
        total, last = 0.0, -np.inf
        for s, e in sorted(self.spans):
            total += max(0.0, e - max(s, last))
            last = max(last, e)
        return total


def wrap_timed(module, name, store, label):
    """Replace ``module.name`` by a wrapper that adds its host seconds to
    ``store[label]`` and writes a profiler span called ``label``; returns
    what puts the original back."""
    import jax
    orig = getattr(module, name)

    def timed(*a, **k):
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(label):
            out = orig(*a, **k)
        store[label] = store.get(label, 0.0) + time.perf_counter() - t
        return out

    setattr(module, name, timed)
    return lambda: setattr(module, name, orig)


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------

class System:
    """The program's entry, its data and one call per campaign."""

    def __init__(self, root: Path, c: dict, seed: int, devices):
        sys.path.insert(0, str(root / "src"))
        sys.path.insert(0, str(root / "perfbench"))
        from repro.core.cost import SystemParams
        from repro.launch import campaign
        self.campaign = campaign
        self.config, self.mix = c["config"], c["mix"]
        cfg, fl = self.config, self.config["fleet"]
        self.kind = load_kind(root, kind_of(cfg))
        self.model = self.kind.program_model(cfg["model"])
        self.sp = lambda: SystemParams(M=fl["M"], seed=fl["seed"])
        self.clients, self.test = self.kind.make_data(
            cfg["data"], fl["M"], fl["samples_per_client"], seed)
        self.data = self.clients
        self.mesh = None
        if cfg.get("mesh"):
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.core import engine
            from repro.launch.mesh import make_client_mesh
            self.mesh = make_client_mesh(cfg["mesh"]["data"])
            sh = NamedSharding(self.mesh, P(engine.client_axes(self.mesh)))
            self.data = {k: jax.device_put(v, sh)
                         for k, v in self.clients.items()}
        self.devices = devices[:c["cell"]["chips"]]
        self.seed_stream = np.random.default_rng([seed, 1])
        self.per_campaign = self.mix["seeds_per_campaign"]

    def next_seeds(self):
        return [int(s) for s in self.seed_stream.integers(
            0, 2 ** 30, self.per_campaign)]

    def kwargs(self, seeds) -> dict:
        cfg, mix = self.config, self.mix
        kw = dict(cfg["hyper"])
        kw.update(rounds=mix["rounds"], seeds=seeds, test_data=self.test,
                  eval_every=mix["eval_every"], eval_gamma=cfg["eval_gamma"],
                  policy=cfg["policy"], quant=cfg["quant"],
                  scenario=mix["scenario"])
        return kw

    def run(self, seeds, policy=None):
        kw = self.kwargs(seeds)
        if policy is not None:
            kw["policy"] = policy
        entry = self.config["entry"]
        if entry == "run_campaign":
            return self.campaign.run_campaign(
                self.config["framework"], self.model, self.sp(), self.data,
                mesh=self.mesh, **kw)
        if entry == "run_population_campaign":
            from repro.core.population import Population
            pop = Population(**self.config["population"])
            x = self.clients["x"]
            X = x.reshape((-1,) + x.shape[2:])
            y = self.clients["y"].reshape(-1)
            return self.campaign.run_population_campaign(
                self.config["framework"], self.model, pop, (X, y),
                cohort=self.config["cohort"],
                samples_per_client=x.shape[1], **kw)
        raise KeyError(f"unknown entry {entry!r}")

    @staticmethod
    def host_view(res) -> dict:
        """What the comparison reads of a campaign, as host arrays."""
        import jax
        return {"a": res.schedule.a, "E": res.schedule.E,
                "losses": res.losses, "acc": res.accuracy_per_round,
                "params": jax.device_get(res.params)}


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def run(args, root: Path = ROOT, require_chip: bool = True,
        t_start: float = T_START) -> dict:
    c = load_cell(root, args.workload)
    import jax
    devices = chips_for(c["cell"]) if require_chip else jax.devices()
    system = System(root, c, args.seed, devices)
    rounds, per = system.mix["rounds"], system.per_campaign
    counters = Counters()

    system.run(system.next_seeds())                       # warm-up
    setup_s = time.perf_counter() - t_start
    print(f"setup: {setup_s:.3f} s; executables built {counters.built}, "
          f"{counters.loaded} of them loaded from the persistent cache",
          flush=True)

    host = {"plan": [], "rebuild": [], "built": [], "loaded": []}
    store, restore = {}, []
    if args.trace:
        restore = [wrap_timed(system.campaign, name, store, label)
                   for name, label in (("plan_schedule", "plan"),
                                       ("_host_fetch", "fetch"),
                                       ("_run_rounds_scan", "rounds"))]
    runs = []
    try:
        t0 = time.perf_counter()
        while True:
            counters.reset()
            store.clear()
            seeds = system.next_seeds()
            runs.append((seeds, system.run(seeds)))
            host["rebuild"].append(counters.rebuild_s())
            host["built"].append(counters.built)
            host["loaded"].append(counters.loaded)
            host["plan"].append(store.get("plan", 0.0))
            if time.perf_counter() - t0 >= args.seconds:
                break
        window_s = time.perf_counter() - t0
        trace = traced_campaign(system, root, args) if args.trace else None
    finally:
        for undo in restore:
            undo()
    rate = len(runs) * per * rounds / window_s
    print(f"window: {len(runs)} campaigns of {per} seeds x {rounds} rounds "
          f"in {window_s:.3f} s; rebuild per campaign "
          f"{[round(r, 4) for r in host['rebuild']]}; executables built per "
          f"campaign {host['built']}, loaded from the persistent cache "
          f"{host['loaded']}", flush=True)

    peak = memory_peak(system.devices)

    # the check: one campaign of the window, drawn from the seed
    pick = int(np.random.default_rng([args.seed, 2]).integers(len(runs)))
    seeds, res = runs[pick]
    prog = System.host_view(res)
    runs.clear()
    del res
    import compare
    import reference
    ref = reference.run_campaign(system.kind, system.config, system.clients,
                                 system.test, rounds=rounds, seeds=seeds)
    values = compare.readings(prog, ref, reference.accuracy(
        system.kind, system.config, system.clients, system.test,
        prog["params"]))
    correct, rows = compare.judge(values, system.config["limits"])

    dev0 = system.devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    if args.trace:
        metrics = per_layer(root, c, system, host, trace, window_s,
                            len(host["rebuild"]))
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s
    else:
        units = {m["name"]: m["unit"] for m in c["end_to_end"]}
        metrics = {"seed_rounds_per_s": {"value": rate,
                                         "unit": units["seed_rounds_per_s"]},
                   "setup_s": {"value": setup_s, "unit": units["setup_s"]}}
    result = {"correct": bool(correct), "attempted": len(host["rebuild"]),
              "failed": 0 if correct else 1, "metrics": metrics,
              "device": device}
    if args.trace:
        result["breakdown"] = {"device_ops": trace.top_ops(),
                               "idle_gaps": trace.idle_gaps()}
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in rows}
    return result


def traced_campaign(system, root: Path, args):
    """One more campaign of the mix under the JAX profiler, inside a
    ``campaign`` span; the trace is read and then deleted."""
    import jax
    import tracereduce
    log_dir = root / ".perfbench_trace" / args.workload
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    seeds = system.next_seeds()
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tracereduce.WINDOW_SPAN):
            res = system.run(seeds)
            jax.block_until_ready(res.params)
    finally:
        jax.profiler.stop_trace()
    path = tracereduce.find_xplane(str(log_dir))
    trace = tracereduce.read(path, len(system.devices))
    trace.schedule = (res.schedule.a, res.schedule.E)
    if args.keep_trace:
        os.makedirs(args.keep_trace, exist_ok=True)
        shutil.copy(path, Path(args.keep_trace) / f"{args.workload}.xplane.pb")
    shutil.rmtree(log_dir, ignore_errors=True)
    return trace


def device_peaks(root: Path, kind: str) -> dict:
    peaks = json.loads((root / "perfbench" / "peaks.json").read_text())
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


def per_layer(root, c, system, host, trace, window_s, runs_n) -> dict:
    import flops
    ctx = {"config": system.config, "kind": system.kind, "mix": system.mix,
           "host": host, "trace": trace, "window_s": window_s,
           "campaigns": runs_n, "chips": len(system.devices), "flops": flops,
           "peaks": device_peaks(root, system.devices[0].device_kind)}
    out = {}
    for m in c["per_layer"]:
        value = load_reader(root, m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def configure_jax() -> None:
    """The persistent compile cache at a fixed path in the checkout, with
    every executable written to it: the warm-up's campaign writes what the
    window's campaigns rebuild, so they load it and compile nothing."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced campaign's .xplane.pb here")
    args = ap.parse_args(argv)
    configure_jax()
    try:
        result = run(args)
    except NoChip as e:
        print(f"perfbench: {e}; nothing was run", file=sys.stderr)
        return 1
    for name, chk in result["checks"].items():
        print(f"check {name}: {chk['value']!r} limit {chk['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
