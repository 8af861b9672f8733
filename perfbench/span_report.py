"""The program's spans, counters and named scopes over a cell's campaigns.

    python3 perfbench/span_report.py --workload <cell> --seed <n> \
        --campaigns <k> [--out <dir>]

Set-up as ``run.py`` (data from the seed, one warm-up campaign), then
``2k`` campaigns back to back, every other one under ``spans.record()``
(recording's cost is the gap between the two halves' mean campaign
seconds), then one campaign under the JAX profiler, as ``run.py --trace 1``
runs it.  Reports, per recorded campaign: each program span's host
seconds, self seconds and JAX trace/lower/compile-or-load seconds (the
union, ``rebuild_s``, beside the harness's own ``rebuild_s_per_campaign``
reading) and counters; for the traced campaign: the device's self time by
named scope (``spanreduce.scope_seconds``) against the busy union, and the
device's idle time by innermost program span.  The traced campaign's
``.xplane.pb`` is kept in ``--out``.  The last line of standard output is
the report as one JSON object.  Without a TPU the run exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

import run as harness

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def by_span(recorded) -> dict:
    """Host seconds, self seconds and counts summed by span name."""
    kids: dict = {}
    for s in recorded:
        kids[s.parent] = kids.get(s.parent, 0.0) + s.seconds
    out: dict = {}
    for s in recorded:
        row = out.setdefault(s.name, {"n": 0, "s": 0.0, "self_s": 0.0})
        row["n"] += 1
        row["s"] += s.seconds
        row["self_s"] += s.seconds - kids.get(s.id, 0.0)
        for k, v in s.counts.items():
            row[k] = row.get(k, 0) + v
    return out


def report(args, root: Path = ROOT, require_chip: bool = True) -> dict:
    c = harness.load_cell(root, args.workload)
    import jax
    devices = (harness.chips_for(c["cell"]) if require_chip
               else jax.devices())
    system = harness.System(root, c, args.seed, devices)
    from repro.launch import spans
    import spanreduce
    counters = harness.Counters()
    system.run(system.next_seeds())                        # warm-up

    seconds = {"off": [], "on": []}
    campaigns = []
    for i in range(2 * args.campaigns):
        on = i % 2 == 1
        counters.reset()
        before = spans.counts.copy()
        t0 = time.perf_counter()
        if on:
            with spans.record() as recorded:
                system.run(system.next_seeds())
        else:
            system.run(system.next_seeds())
        seconds["on" if on else "off"].append(time.perf_counter() - t0)
        if on:
            campaigns.append({
                "seconds": seconds["on"][-1],
                "harness_rebuild_s": counters.rebuild_s(),
                "harness_built": counters.built,
                "harness_loaded": counters.loaded,
                "counted": dict(spans.counts - before),
                "spans": by_span(recorded)})

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    traced_args = argparse.Namespace(workload=args.workload,
                                     keep_trace=str(out_dir))
    with spans.record() as recorded:
        trace = harness.traced_campaign(system, root, traced_args)
    ops = spanreduce.read_ops(str(out_dir / f"{args.workload}.xplane.pb"),
                              len(system.devices))
    mix = system.mix
    evals = -(-mix["rounds"] // (mix["eval_every"] or mix["rounds"]))
    scopes = spanreduce.scope_seconds(ops, trace.window)
    idle = spanreduce.idle_by_span(trace, spans.NAMES)
    traced = {
        "window_s": trace.window_s, "busy_s": trace.busy_s(),
        "rounds": mix["rounds"], "evals": evals,
        "scope_s": scopes,
        "scope_sum_over_busy": (sum(scopes.values()) / trace.busy_s()
                                if scopes and trace.busy_s() else None),
        "idle_s": {str(k): v for k, v in idle.items()},
        "idle_unattributed_share": spanreduce.idle_unattributed_share(
            trace, spans.NAMES, spans.ROOTS),
        "spans": by_span(recorded)}
    mean = {k: statistics.fmean(v) for k, v in seconds.items()}
    return {"workload": args.workload, "seed": args.seed,
            "device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind},
            "campaign_s": seconds, "campaign_s_mean": mean,
            "recording_cost": mean["on"] / mean["off"] - 1.0,
            "campaigns": campaigns, "traced": traced}


def summary(rep: dict) -> str:
    lines = [f"{rep['workload']}: campaign seconds off "
             f"{rep['campaign_s']['off']} on {rep['campaign_s']['on']}; "
             f"recording costs {100 * rep['recording_cost']:+.2f}%"]
    for i, cam in enumerate(rep["campaigns"]):
        lines.append(f"recorded campaign {i}: {cam['seconds']:.3f} s; harness "
                     f"rebuild {cam['harness_rebuild_s']:.3f} s, built "
                     f"{cam['harness_built']}, loaded {cam['harness_loaded']};"
                     f" counted {cam['counted']}")
        for name, row in cam["spans"].items():
            lines.append(f"  {name}: " + ", ".join(
                f"{k} {v:.4f}" if isinstance(v, float) else f"{k} {v}"
                for k, v in row.items()))
    t = rep["traced"]
    lines.append(f"traced: window {t['window_s']:.4f} s, busy "
                 f"{t['busy_s']:.6f} s; scopes over busy "
                 f"{t['scope_sum_over_busy']}")
    for k, v in (t["scope_s"] or {}).items():
        per = t["evals"] if k == "eval" else t["rounds"]
        lines.append(f"  scope {k}: {v:.6f} s, {1e3 * v / per:.4f} ms per "
                     f"{'eval' if k == 'eval' else 'round'}")
    idle = sum(t["idle_s"].values())
    for k, v in sorted(t["idle_s"].items(), key=lambda kv: -kv[1]):
        lines.append(f"  idle under {k}: {v:.4f} s ({100 * v / idle:.2f}%)")
    lines.append(f"  idle_unattributed_share {t['idle_unattributed_share']}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--campaigns", type=int, default=2)
    ap.add_argument("--out", default=str(ROOT / ".perfbench_trace" / "spans"))
    args = ap.parse_args(argv)
    harness.configure_jax()
    try:
        rep = report(args)
    except harness.NoChip as e:
        print(f"span_report: {e}; nothing was run", file=sys.stderr)
        return 1
    print(summary(rep), flush=True)
    (Path(args.out) / f"{args.workload}.spans.json").write_text(
        json.dumps(rep, indent=1))
    print(json.dumps(rep), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
