"""Paper Figures 3a/3b/4a/4b — the framework registry on the O-RAN slice
data (the paper's four plus the FedORA / EcoFL resource-allocation
baselines).

One training campaign per framework produces all four paper artifacts:
  Fig 3a: number of selected trainers per round
  Fig 3b: accumulated communication volume (MB)
  Fig 4a: test accuracy vs (simulated) total training time
  Fig 4b: accumulated communication resource cost vs time
All frameworks run through the unified engine (repro.core.engine); a
final section measures the vmapped multi-seed campaign runner
(repro.launch.campaign) against the same number of serial single-seed runs,
and the kernel-policy section writes the six-framework sweep + CommQuant
wire-format accounting + the time-varying scenario sweep
(``repro.core.scenario``: six frameworks × {static, fading, straggler,
noniid} planned metrics, plus trained SplitMe campaigns per scenario) to
the top-level BENCH_fl.json (the CI bench regression gate reads its
``modes`` and per-framework ``rounds_per_sec`` blocks).
Results are also dumped to benchmarks/results/fl_frameworks.json for the
EXPERIMENTS.md tables.
"""
import copy
import json
import time
from pathlib import Path

import numpy as np

from benchmarks.common import Row
from repro.configs.splitme_dnn import DNN10
from repro.core.baselines import (EcoFLTrainer, FedAvgTrainer, FedORATrainer,
                                  ORANFedTrainer, SFLTrainer)
from repro.core.cost import SystemParams
from repro.core.splitme import SplitMeTrainer
from repro.data import oran

RESULTS = Path(__file__).resolve().parent / "results"

# paper: SplitMe needs 30 rounds; baselines recorded for 150.  CPU budget:
# baselines get 60 rounds here (trend is established; see EXPERIMENTS.md).
ROUNDS = {"splitme": 30, "fedavg": 60, "sfl": 60, "oranfed": 60,
          "fedora": 60, "ecofl": 60}


def run(fast: bool = False):
    rounds = {k: (8 if fast else v) for k, v in ROUNDS.items()}
    X, y = oran.generate(n_per_class=2000, seed=0)
    (Xtr, ytr), (Xte, yte) = oran.train_test_split(X, y)
    cd = oran.partition_non_iid(Xtr, ytr, 50, samples_per_client=96, seed=0)

    makers = {
        "splitme": lambda sp: SplitMeTrainer(DNN10, sp, copy.deepcopy(cd),
                                             (Xte, yte), seed=0),
        "fedavg": lambda sp: FedAvgTrainer(DNN10, sp, copy.deepcopy(cd),
                                           (Xte, yte), K=10, E=10, seed=0),
        "sfl": lambda sp: SFLTrainer(DNN10, sp, copy.deepcopy(cd),
                                     (Xte, yte), K=20, E=14, seed=0),
        "oranfed": lambda sp: ORANFedTrainer(DNN10, sp, copy.deepcopy(cd),
                                             (Xte, yte), E=10, seed=0),
        "fedora": lambda sp: FedORATrainer(DNN10, sp, copy.deepcopy(cd),
                                           (Xte, yte), E=10, seed=0),
        "ecofl": lambda sp: EcoFLTrainer(DNN10, sp, copy.deepcopy(cd),
                                         (Xte, yte), K=10, E=10, seed=0),
    }
    rows: list[Row] = []
    summary = {}
    for name, make in makers.items():
        tr = make(SystemParams(seed=0))
        # round 0 is the warmup: compiles the round AND eval functions, so
        # the timed window (and the per-framework CI regression gate fed
        # from it) measures steady-state throughput, not jit compile
        tr.run_round(eval_acc=True)
        timed_rounds = max(rounds[name] - 1, 1)
        t0 = time.perf_counter()
        for k in range(1, rounds[name]):
            tr.run_round(eval_acc=(k % 5 == 4 or k == rounds[name] - 1))
        # async serial trainers buffer device-array metrics; resolve them
        # in ONE device→host transfer after the round loop
        tr.fetch_history()
        wall_us = (time.perf_counter() - t0) / timed_rounds * 1e6
        h = tr.history
        acc = tr.evaluate()
        total_mb = sum(m.comm_bits for m in h) / 8e6
        total_time = sum(m.sim_time for m in h)
        total_cost = sum(m.cost for m in h)
        summary[name] = {
            "rounds": rounds[name],
            "timed_rounds": timed_rounds,
            "final_accuracy": acc,
            # steady-state serial-trainer throughput (round-0 compile
            # excluded; the per-framework CI regression gate in
            # scripts/check_bench_regression.py compares this between
            # baseline and fresh runs of the SAME round count)
            "rounds_per_sec": 1e6 / wall_us,
            "selected_per_round": [m.n_selected for m in h],
            "comm_mb_cumulative": float(np.cumsum(
                [m.comm_bits / 8e6 for m in h])[-1]),
            "sim_time_s": total_time,
            "resource_cost": total_cost,
            "energy_j": float(sum(m.energy for m in h)),
            "accuracy_curve": [(m.round, m.accuracy) for m in h
                               if m.accuracy == m.accuracy],
            "E_per_round": [m.E for m in h],
            "skipped_rounds": float(sum(m.skipped for m in h)),
            "quorum_rounds": float(sum(m.quorum_held for m in h)),
        }
        rows.append((f"fig3a_selected_{name}", wall_us,
                     f"mean_sel={np.mean([m.n_selected for m in h]):.1f}"))
        rows.append((f"fig3b_commvol_{name}", wall_us,
                     f"total_MB={total_mb:.1f}"))
        rows.append((f"fig4a_accuracy_{name}", wall_us,
                     f"acc={acc:.3f};sim_time_s={total_time:.2f}"))
        rows.append((f"fig4b_cost_{name}", wall_us,
                     f"resource_cost={total_cost:.1f}"))
    # ------------------------------------------------------------------
    # Multi-seed campaign execution modes:
    #   serial           : PR-1 serial engine trainers, one per seed (the
    #                      per-round float() metric pulls included)
    #   scanned          : lax.scan over rounds, device-resident metric
    #                      buffers, ONE host transfer per campaign
    #   scanned+sharded  : the same scan over shard_map engine rounds
    #                      (clients sharded over the mesh data axes)
    # Each mode reports rounds/sec (aggregate seed-rounds) and the number of
    # device→host metric transfers it performed.
    # ------------------------------------------------------------------
    import jax

    from repro.launch import campaign as camp, spans
    from repro.launch.mesh import make_host_mesh

    n_seeds = 4
    camp_rounds = 8 if fast else 12
    run_rounds = n_seeds * camp_rounds
    # one kwargs dict per framework, shared by the serial trainers and the
    # campaign so the two paths always train the same workload
    camp_specs = (("fedavg", FedAvgTrainer, {"K": 10, "E": 10}),
                  ("splitme", SplitMeTrainer, {}))
    for name, cls, kw in camp_specs:
        t0 = time.perf_counter()
        for s in range(n_seeds):
            # interactive=True keeps this baseline's documented semantics:
            # the PR-1 serial loop with a float() metric pull EVERY round
            tr = cls(DNN10, SystemParams(seed=0), copy.deepcopy(cd),
                     (Xte, yte), seed=s, interactive=True, **kw)
            for _ in range(camp_rounds):
                tr.run_round()
        serial_s = time.perf_counter() - t0

        modes = {"scanned": {}, "scanned_sharded": {"mesh": make_host_mesh()}}
        mode_stats = {}
        res = None
        for mode, mkw in modes.items():
            before = spans.counts["host_transfers"]
            t0 = time.perf_counter()
            res = camp.run_campaign(name, DNN10, SystemParams(seed=0), cd,
                                    rounds=camp_rounds,
                                    seeds=tuple(range(n_seeds)), **kw, **mkw)
            jax.block_until_ready(res.params)
            dt = time.perf_counter() - t0
            mode_stats[mode] = {
                "s": dt,
                "rounds_per_sec": run_rounds / dt,
                "host_transfers": spans.counts["host_transfers"] - before,
            }
        scanned_speedup = serial_s / mode_stats["scanned"]["s"]
        summary[f"campaign_{name}"] = {
            "seeds": n_seeds, "rounds": camp_rounds,
            "serial_python_loop_s": serial_s,
            "serial_rounds_per_sec": run_rounds / serial_s,
            "serial_host_transfers_per_round": 1,   # float() pull each round
            "modes": mode_stats,
            "scanned_speedup_vs_serial_python_loop": scanned_speedup,
            "final_loss_per_seed": res.losses[:, -1, 0].tolist(),
            # guard accounting (0 here — no faults scenario): surfaced so
            # the regression gate can spot a guarded-vs-unguarded mismatch
            "skipped_rounds": res.skipped_rounds,
            "quorum_rounds": res.quorum_rounds,
            "crashed_rounds": res.crashed_rounds,
        }
        rows.append((f"campaign_serial{n_seeds}_{name}",
                     serial_s / run_rounds * 1e6,
                     f"{n_seeds}x{camp_rounds} rounds serial python loop"))
        for mode, st in mode_stats.items():
            rows.append((f"campaign_{mode}{n_seeds}_{name}",
                         st["s"] / run_rounds * 1e6,
                         f"rounds_per_sec={st['rounds_per_sec']:.2f};"
                         f"host_transfers={st['host_transfers']}"))
        rows.append((f"campaign_scan_speedup_{name}",
                     mode_stats["scanned"]["s"] / run_rounds * 1e6,
                     f"scanned_vs_python_loop={scanned_speedup:.2f}x"))

    # ------------------------------------------------------------------
    # Kernel-dispatch / precision policy modes (the engine hot path through
    # repro.kernels.dispatch):
    #   reference   — kernels forced OFF, pure-jnp f32
    #   kernel      — auto per-op dispatch (Pallas on TPU; on CPU auto
    #                 resolves to the reference impls — interpret mode is
    #                 for parity, not speed — so this mode measures the
    #                 dispatch layer's overhead, which must be ~zero)
    #   kernel_bf16 — auto dispatch + bf16 activations / f32 accumulators
    # One scanned SplitMe campaign per mode; rounds/sec + steps/sec land in
    # the top-level BENCH_fl.json as the perf trajectory baseline.
    # ------------------------------------------------------------------
    from repro.kernels import dispatch

    pol_rounds = 4 if fast else 12      # timed steady-state rounds / repeat
    warmup = 2                          # compile + first dispatch excluded
    pol_modes = ("reference", "kernel", "kernel_bf16")
    trainers = {}
    for mode in pol_modes:
        tr = SplitMeTrainer(DNN10, SystemParams(seed=0), copy.deepcopy(cd),
                            (Xte, yte), seed=0, kernel_policy=mode)
        for _ in range(warmup):
            tr.run_round()
        jax.block_until_ready(tr.w_c)
        trainers[mode] = tr
    # repeats INTERLEAVED across the modes, alternating the within-cycle
    # order (A/B/C then C/B/A) so ambient-load drift cancels instead of
    # systematically taxing whichever mode runs last.  SplitMe's adaptive
    # policy shrinks E/|A_t| across the windows, but every mode executes
    # the identical schedule, so aggregate totals stay comparable.
    n_reps = 4
    times = {mode: [] for mode in pol_modes}
    for r in range(n_reps):
        order = pol_modes if r % 2 == 0 else tuple(reversed(pol_modes))
        for mode in order:
            tr = trainers[mode]
            t0 = time.perf_counter()
            for _ in range(pol_rounds):
                tr.run_round()
            jax.block_until_ready(tr.w_c)
            times[mode].append(time.perf_counter() - t0)
    mode_stats = {}
    for mode, tr in trainers.items():
        # aggregate executed local-SGD steps over ALL timed windows: E_t
        # per selected client per round, two mutual-learning phases
        # (E/n_selected are schedule-side ints — no device sync).  Total
        # steps / total time is the noise-robust throughput: every mode
        # executes the identical schedule and the interleaving spreads
        # ambient load evenly across modes.
        timed = tr.history[warmup:warmup + n_reps * pol_rounds]
        steps = sum(m.E * m.n_selected for m in timed) * 2
        dt = sum(times[mode])
        tr.fetch_history()
        pol = dispatch.get_policy(mode)
        mode_stats[mode] = {
            "s": dt,
            "rounds_per_sec": n_reps * pol_rounds / dt,
            "steps_per_sec": steps / dt,
            "skipped_rounds": float(sum(m.skipped for m in timed)),
            "resolved": {"kl_mutual": bool(pol.kl_mutual),
                         "ridge_gram": bool(pol.ridge_gram),
                         "compute_dtype": pol.precision.compute},
        }
        rows.append((f"round_policy_{mode}_splitme",
                     dt / (n_reps * pol_rounds) * 1e6,
                     f"rounds_per_sec={mode_stats[mode]['rounds_per_sec']:.2f};"
                     f"steps_per_sec={mode_stats[mode]['steps_per_sec']:.0f}"))
    # ------------------------------------------------------------------
    # Six-framework sweep + CommQuant wire-format accounting for the
    # top-level BENCH_fl.json: per-framework serial summary (measured
    # above) and, per framework × {none, bf16, int8}, the total schedule
    # comm bits — the schedule is re-planned per wire format, so the
    # deadline/energy selection's response to quantization is part of the
    # number (host-side only, no extra training).
    # ------------------------------------------------------------------
    from repro.launch.campaign import plan_schedule
    from repro.core import engine as _engine

    frameworks_block = {
        name: {
            "rounds": summary[name]["rounds"],
            "timed_rounds": summary[name]["timed_rounds"],
            "final_accuracy": summary[name]["final_accuracy"],
            "rounds_per_sec": summary[name]["rounds_per_sec"],
            "comm_mb": summary[name]["comm_mb_cumulative"],
            "sim_time_s": summary[name]["sim_time_s"],
            "resource_cost": summary[name]["resource_cost"],
            "energy_j": summary[name]["energy_j"],
            # guarded-run accounting: a baseline whose skipped_rounds
            # differs from the fresh run trained a different effective
            # round count, so the gate treats the row as informational
            "skipped_rounds": summary[name]["skipped_rounds"],
            "quorum_rounds": summary[name]["quorum_rounds"],
        } for name in makers
    }
    n_per_client = int(cd["x"].shape[1])    # same partition as the runs
    quant_comm_bits = {}
    for name in makers:
        quant_comm_bits[name] = {}
        for qm in ("none", "bf16", "int8"):
            sp_q, sched_q = plan_schedule(
                name, SystemParams(seed=0), DNN10, rounds[name],
                n_samples_per_client=n_per_client, quant=qm)
            spec_q = _engine.make_spec(name, DNN10, quant=qm)
            total = float(np.sum(np.atleast_1d(
                spec_q.comm_model(sched_q.a, sched_q.E, sp_q))))
            quant_comm_bits[name][qm] = {
                "total_comm_bits": total,
                "mean_selected": float(sched_q.a.sum(axis=1).mean()),
            }
        base_bits = quant_comm_bits[name]["none"]["total_comm_bits"]
        for qm in ("bf16", "int8"):
            quant_comm_bits[name][qm]["vs_f32"] = (
                quant_comm_bits[name][qm]["total_comm_bits"] / base_bits)

    # ------------------------------------------------------------------
    # Time-varying scenario sweep (repro.core.scenario): per framework ×
    # {static, fading, straggler, noniid}, the planned schedule's realized
    # cohort / comm / latency / cost / energy (host-side trace × schedule,
    # no extra training), plus one scanned SplitMe TRAINING campaign per
    # scenario — the noniid row trains on the Dirichlet(α) partition — so
    # BENCH_fl.json carries accuracy under dynamic RAN state too.
    # ------------------------------------------------------------------
    from repro.core import scenario as scen_mod
    from repro.core.cost import schedule_metrics

    scen_names = ("static", "fading", "straggler", "noniid", "faults:0.3")
    scenario_plans = {}
    for name in makers:
        scenario_plans[name] = {}
        for sc in scen_names:
            sp_s, sched_s = plan_schedule(
                name, SystemParams(seed=0), DNN10, rounds[name],
                n_samples_per_client=n_per_client, scenario=sc)
            spec_s = _engine.make_spec(name, DNN10)
            comm_s = float(np.sum(np.atleast_1d(
                spec_s.comm_model(sched_s.a, sched_s.E, sp_s))))
            sim_s, cost_s, energy_s = schedule_metrics(
                sched_s.a, sched_s.b, sched_s.E, sp_s, trace=sched_s.trace)
            scenario_plans[name][sc] = {
                "mean_selected": float(sched_s.a.sum(axis=1).mean()),
                "mean_E": float(np.mean(sched_s.E)),
                "comm_mb": comm_s / 8e6,
                "sim_time_s": float(np.sum(sim_s)),
                "resource_cost": float(np.sum(cost_s)),
                "energy_j": float(np.sum(energy_s)),
            }
    scen_rounds = 4 if fast else 10
    scenario_trained = {}
    for sc in scen_names:
        trace = scen_mod.get_trace(sc, scen_rounds, 50, seed=0)
        cd_s = scen_mod.partition_for(trace, Xtr, ytr, 50,
                                      samples_per_client=96, seed=0)
        t0 = time.perf_counter()
        res = camp.run_campaign("splitme", DNN10, SystemParams(seed=0),
                                cd_s, rounds=scen_rounds, seeds=(0, 1),
                                test_data=(Xte, yte), scenario=trace)
        jax.block_until_ready(res.params)
        dt = time.perf_counter() - t0
        scenario_trained[sc] = {
            "rounds": scen_rounds,
            "final_accuracy_mean": float(res.accuracy.mean()),
            "mean_selected": float(np.mean(
                [m.n_selected for m in res.metrics])),
            "rounds_per_sec": 2 * scen_rounds / dt,
            "data_alpha": trace.data_alpha,
            # in-scan guard accounting (nonzero only for the faults:p
            # family, whose trace auto-arms RoundGuards)
            "skipped_rounds": res.skipped_rounds,
            "quorum_rounds": res.quorum_rounds,
            "crashed_rounds": res.crashed_rounds,
        }
        rows.append((f"scenario_{sc}_splitme", dt / scen_rounds * 1e6,
                     f"acc={scenario_trained[sc]['final_accuracy_mean']:.3f};"
                     f"mean_sel={scenario_trained[sc]['mean_selected']:.1f}"))

    # ------------------------------------------------------------------
    # Population scale-out (repro.core.population): one scanned SplitMe
    # campaign over a MILLION virtual clients, sampling an O(cohort)
    # cohort per round under population churn.  The block records the
    # host peak memory of the whole plan+run (tracemalloc) next to the
    # bytes a materialized run would need just to HOLD the population
    # (SystemParams rows + data shards), plus rounds/sec against a
    # materialized campaign of the same cohort-scale workload.
    # ------------------------------------------------------------------
    import tracemalloc

    from repro.core import population as popn

    pop_M = 1_000_000        # the headline number IS the point — both modes
    pop_cohort = 16
    pop_rounds = 4 if fast else 8
    pop_seeds = (0, 1)
    pop = popn.Population(size=pop_M, seed=0)
    tracemalloc.start()
    t0 = time.perf_counter()
    res_pop = camp.run_population_campaign(
        "splitme", DNN10, pop, (Xtr, ytr), rounds=pop_rounds,
        seeds=pop_seeds, cohort=pop_cohort, samples_per_client=96,
        test_data=(Xte, yte), scenario="churn:0.5")
    jax.block_until_ready(res_pop.params)
    pop_dt = time.perf_counter() - t0
    _, pop_peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    # a materialized run's floor: the per-client SystemParams rows (Q_C,
    # Q_S, t_round, S_m, G_m, avail — float64) plus the stacked f32/i32
    # data shards, before any training state
    mat_bytes = pop_M * (6 * 8 + 96 * (DNN10.n_features * 4 + 4))
    mat_t0 = time.perf_counter()
    res_mat = camp.run_campaign(
        "splitme", DNN10, SystemParams(seed=0), cd, rounds=pop_rounds,
        seeds=pop_seeds, test_data=(Xte, yte))
    jax.block_until_ready(res_mat.params)
    mat_dt = time.perf_counter() - mat_t0
    population_block = {
        "population": pop_M,
        "cohort": pop_cohort,
        "rounds": pop_rounds,
        "seeds": len(pop_seeds),
        "scenario": "churn:0.5",
        "final_accuracy_mean": float(res_pop.accuracy.mean()),
        "mean_selected": float(np.mean(
            [m.n_selected for m in res_pop.metrics])),
        "registered_clients_per_round":
            res_pop.schedule.m_t.astype(int).tolist(),
        "rounds_per_sec": len(pop_seeds) * pop_rounds / pop_dt,
        "peak_host_bytes": int(pop_peak),
        "materialized_bytes_est": int(mat_bytes),
        "memory_ratio_vs_materialized": float(pop_peak / mat_bytes),
        "materialized_M50_rounds_per_sec":
            len(pop_seeds) * pop_rounds / mat_dt,
        "note": "peak_host_bytes = tracemalloc peak over plan+run of the "
                "population campaign (O(rounds x cohort) by construction); "
                "materialized_bytes_est = bytes needed just to HOLD the "
                "population's SystemParams rows + data shards if "
                "materialized.  rounds_per_sec compares against a "
                "materialized M=50 campaign of the same rounds/seeds "
                "(the device work per round is cohort-sized in both).",
    }
    rows.append((f"population_{pop_M}_splitme",
                 pop_dt / (len(pop_seeds) * pop_rounds) * 1e6,
                 f"peak_MB={pop_peak / 1e6:.1f};"
                 f"mat_GB={mat_bytes / 1e9:.1f};"
                 f"acc={population_block['final_accuracy_mean']:.3f}"))

    import os
    import platform

    bench_fl = {
        "backend": jax.default_backend(),
        # environment fingerprint: scripts/check_bench_regression.py only
        # HARD-gates rounds/sec when baseline and fresh run come from the
        # same environment (absolute throughput is machine-specific; a
        # baseline committed from a dev box must not brick a slower CI
        # runner — there the comparison is reported informationally)
        "env": {
            "platform": platform.platform(),
            "machine": platform.machine(),
            "cpu_count": os.cpu_count(),
            "backend": jax.default_backend(),
        },
        "framework": "splitme",
        "timed_rounds": pol_rounds,
        "warmup_rounds": warmup,
        "frameworks": frameworks_block,
        "scenarios": {
            "planned": scenario_plans,
            "splitme_trained": scenario_trained,
            "note": "planned = host-side trace × schedule sweep (realized "
                    "cohort/comm/latency/cost/energy per framework × "
                    "scenario, same round counts as the serial runs); "
                    "splitme_trained = scanned multi-seed campaigns per "
                    "scenario (noniid trains on the Dirichlet partition)",
        },
        "population": population_block,
        "quant_comm_bits": quant_comm_bits,
        "quant_note": "total_comm_bits re-plans the schedule per wire "
                      "format: fixed-K frameworks (fedavg/sfl/ecofl) scale "
                      "exactly by wire_bits/32, while deadline-driven "
                      "schedules (splitme/oranfed/fedora) may admit MORE "
                      "clients under quantization (see mean_selected) — "
                      "the joint-optimization response, so vs_f32 can "
                      "exceed 1 while per-client bits still shrink",
        "note": "aggregate throughput over 4 order-alternating interleaved "
                "timed windows per mode, compile/warmup excluded; every "
                "mode executes the identical adaptive schedule.  On CPU "
                "the auto kernel "
                "policy resolves to the reference impls, so 'kernel' "
                "measures dispatch overhead — the kernel win itself is a "
                "TPU property",
        "modes": mode_stats,
        # when a mode's RESOLVED policy equals reference's (all of them on
        # CPU), the compiled programs are identical and the true speedup is
        # 1.0 by construction — the measured ratio shows the estimator's
        # noise floor
        "resolves_same_as_reference": {
            m: dispatch.get_policy(m) == dispatch.get_policy("reference")
            for m in pol_modes},
        "kernel_bf16_vs_reference_speedup":
            mode_stats["kernel_bf16"]["steps_per_sec"]
            / mode_stats["reference"]["steps_per_sec"],
    }
    (Path(__file__).resolve().parents[1] / "BENCH_fl.json").write_text(
        json.dumps(bench_fl, indent=1))
    summary["round_policy_modes_splitme"] = bench_fl

    RESULTS.mkdir(exist_ok=True, parents=True)
    (RESULTS / "fl_frameworks.json").write_text(json.dumps(summary, indent=1))
    return rows
