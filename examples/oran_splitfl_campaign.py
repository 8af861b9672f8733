"""End-to-end O-RAN SplitFL campaign — the paper's full experiment.

    PYTHONPATH=src python examples/oran_splitfl_campaign.py [--rounds 30]
        [--baselines] [--ckpt-dir /tmp/splitme] [--seeds 4] [--quant bf16]
        [--scenario fading] [--checkpoint-every 10] [--resume]

Trains SplitMe to convergence on the COMMAG-style slice data (30 rounds, as
in §V-B), checkpoints (w_C, w_S⁻¹) every 10 rounds, performs the final
analytic inversion, and (optionally) runs the baseline frameworks for the
same wall-clock comparison the paper plots in Fig. 4.

The framework registry (``repro.core.engine``) holds SIX frameworks: the
paper's four — splitme, fedavg, sfl, oranfed — plus two resource-allocation
baselines from the related work, fedora (arXiv 2505.19211: RIC
deadline-feasible cohort allocation) and ecofl (arXiv 2507.21698:
energy-first selection).  ``--baselines`` runs all five non-SplitMe
frameworks.

``--quant {none,bf16,int8}`` selects the CommQuant wire format of the
masked-FedAvg aggregation payload: bf16 halves and int8 quarters every
upload (int8 adds stochastic rounding with an f32 error-feedback
accumulator), and comm volume, latency, cost and the deadline/energy
selection policies all account the narrower format.

``--scenario NAME`` runs against a time-varying O-RAN trace from the
``repro.core.scenario`` registry — ``static`` (all-ones, identical to no
scenario), ``fading`` (AR(1) log-normal channel + compute fade, deadline
jitter), ``straggler`` (persistent slow cohort, Markov availability
blackouts, mid-round dropouts), ``noniid`` (static RAN, Dirichlet(α)
client partition replacing the one-class-per-client split).  A name may
carry a level suffix: ``fading:0.8`` (fade σ), ``straggler:0.4``
(blackout prob), ``noniid:0.1`` (α).  Selection/allocation re-solve per
round against the round-t trace; with ``--seeds N`` the whole trace-driven
campaign still runs as compiled scans with one host transfer
(``--scenario-seed`` varies the trace draw).  ``faults:p`` injects
failures — NaN-poisoned client updates, server-crash rounds, bit-flipped
wire payloads — and auto-arms the in-scan guards (non-finite rollback,
quorum hold); the run reports skipped/quorum/crashed round counts.

Campaign runs are fault-tolerant (``repro.launch.resilience``):
``--checkpoint-every K`` persists the full campaign carry to
``--checkpoint-dir`` every K rounds with atomic manifests, and
``--resume`` restores the newest committed checkpoint and continues
bit-exactly — rerun the identical command line after a crash.

With ``--seeds N`` (N > 1) the run goes through the scanned multi-seed
campaign runner instead: N independent seeds train through one compiled
lax.scan-over-rounds per shape bucket, all metrics (and the fused
evaluation — ``--eval-every K`` evaluates every K rounds inside the scan)
stay on the device until ONE final host transfer, and the per-seed final
accuracies are reported (mean ± std) — the multi-seed error bars the paper
omits.

``--population M`` switches to the POPULATION campaign
(``repro.launch.campaign.run_population_campaign``): M virtual clients —
millions are fine — described by a parameterized ``Population``
distribution; each round samples a ``--cohort C`` cohort and lazily
realizes only those C clients' SystemParams rows, trace channels and data
shards, so memory stays O(cohort) instead of O(M).  Combine with
``--scenario churn:0.5`` to let the registered population size itself vary
round to round.  Requires --seeds N > 1 (population mode is scanned-only).
"""
import argparse
import copy
import time

import numpy as np

from repro.checkpoint import io as ckpt
from repro.configs.splitme_dnn import DNN10
from repro.core.baselines import (EcoFLTrainer, FedAvgTrainer, FedORATrainer,
                                  ORANFedTrainer, SFLTrainer)
from repro.core.cost import SystemParams
from repro.core.splitme import SplitMeTrainer
from repro.data import oran
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser(
        description="O-RAN SplitFL campaign over the six-framework registry "
                    "(splitme, fedavg, sfl, oranfed, fedora, ecofl)",
        epilog="CommQuant: --quant bf16|int8 narrows the aggregation wire "
               "format (comm volume, latency, cost and deadline/energy "
               "selection all respond); int8 uses stochastic rounding with "
               "an f32 error-feedback accumulator.")
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--baseline-rounds", type=int, default=60)
    ap.add_argument("--baselines", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/splitme_ckpt")
    ap.add_argument("--seeds", type=int, default=1,
                    help="N>1: scanned multi-seed campaign instead of one "
                         "serial run")
    ap.add_argument("--eval-every", type=int, default=None,
                    help="campaign mode: fuse an eval round into the scan "
                         "every K rounds (accuracy curve, zero extra host "
                         "syncs)")
    ap.add_argument("--policy", default=None,
                    choices=["reference", "kernel", "kernel_bf16"],
                    help="kernel dispatch / precision policy (default: "
                         "auto by backend — Pallas kernels on TPU, "
                         "reference jnp on CPU)")
    ap.add_argument("--quant", default=None,
                    choices=["none", "bf16", "int8"],
                    help="CommQuant wire format of the masked-FedAvg "
                         "aggregation payload (default none/f32; bf16 = "
                         "deterministic 16-bit rounding, int8 = stochastic "
                         "rounding + f32 error feedback; comm_bits/latency/"
                         "cost and the selection policies account it)")
    ap.add_argument("--scenario", default=None,
                    help="time-varying scenario from the repro.core.scenario "
                         "registry: static | fading | straggler | noniid, "
                         "optionally with a level suffix (fading:0.8, "
                         "noniid:0.1); default: the frozen network snapshot")
    ap.add_argument("--scenario-seed", type=int, default=0,
                    help="seed of the scenario trace draw")
    ap.add_argument("--checkpoint-every", type=int, default=None,
                    help="campaign mode: persist the full campaign carry "
                         "(params/RNG/EF state/metric buffers) every K "
                         "rounds to --checkpoint-dir (atomic manifests)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="campaign checkpoint directory (default: "
                         "<--ckpt-dir>/campaign)")
    ap.add_argument("--resume", action="store_true",
                    help="resume the campaign from the newest committed "
                         "checkpoint in --checkpoint-dir (bit-exact; "
                         "fresh start when the directory is empty)")
    ap.add_argument("--population", type=int, default=None,
                    help="population mode: train over M virtual clients "
                         "(millions are fine) sampling a --cohort per "
                         "round; memory is O(cohort), not O(M)")
    ap.add_argument("--cohort", type=int, default=32,
                    help="population mode: clients sampled per round "
                         "(default 32)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.population is not None and args.seeds <= 1:
        ap.error("--population needs the scanned campaign runner "
                 "(--seeds N with N > 1)")
    if (args.resume or args.checkpoint_every) and args.seeds <= 1:
        ap.error("--checkpoint-every/--resume need the scanned campaign "
                 "runner (--seeds N with N > 1)")
    if args.resume and not args.checkpoint_every:
        ap.error("--resume needs --checkpoint-every (the resumed run "
                 "replans the same segment boundaries)")
    ckpt_dir = args.checkpoint_dir or f"{args.ckpt_dir}/campaign"

    X, y = oran.generate(n_per_class=2000, seed=0)
    (Xtr, ytr), (Xte, yte) = oran.train_test_split(X, y)
    sp = SystemParams()
    # the scenario decides the client partition (Dirichlet α for noniid,
    # the paper's one-class-per-client split otherwise); serial trainers
    # take a concrete pre-drawn trace, so build one long enough for the
    # longest loop below
    horizon = max(args.rounds, args.baseline_rounds)
    trace = None
    if args.scenario is not None:
        from repro.core import scenario as scen
        trace = scen.make_trace(args.scenario, horizon, sp.M,
                                seed=args.scenario_seed)
        clients = scen.partition_for(trace, Xtr, ytr, sp.M,
                                     samples_per_client=96, seed=0)
    else:
        clients = oran.partition_non_iid(Xtr, ytr, sp.M,
                                         samples_per_client=96, seed=0)

    if args.population is not None:
        from repro.core import population as popn
        from repro.launch import campaign

        seeds = tuple(range(args.seeds))
        pop = popn.Population(size=args.population, seed=0)
        t0 = time.time()
        res = campaign.run_population_campaign(
            "splitme", DNN10, pop, (Xtr, ytr), rounds=args.rounds,
            seeds=seeds, cohort=args.cohort, samples_per_client=96,
            test_data=(Xte, yte), eval_every=args.eval_every,
            policy=args.policy, quant=args.quant, scenario=args.scenario,
            scenario_seed=args.scenario_seed,
            checkpoint_every=args.checkpoint_every,
            checkpoint_dir=(f"{ckpt_dir}/population"
                            if args.checkpoint_every else None),
            resume=args.resume)
        acc = res.accuracy
        print(f"[splitme/pop] {args.population:,} clients, cohort "
              f"{args.cohort}, {len(seeds)} seeds x {args.rounds} rounds: "
              f"acc={acc.mean():.3f}±{acc.std():.3f} "
              f"comm={sum(m.comm_bits for m in res.metrics) / 8e6:.1f}MB "
              f"wall={time.time() - t0:.0f}s")
        return

    if args.seeds > 1:
        from repro.launch import campaign

        seeds = tuple(range(args.seeds))
        for name, kw in [("splitme", {})] + ([
                ("fedavg", {"K": 10, "E": 10}),
                ("sfl", {"K": 20, "E": 14}),
                ("oranfed", {"E": 10}),
                ("fedora", {"E": 10}),
                ("ecofl", {"K": 10, "E": 10}),
        ] if args.baselines else []):
            rounds = args.rounds if name == "splitme" else args.baseline_rounds
            t0 = time.time()
            # per-framework checkpoint subdir: each plan has its own
            # schedule fingerprint, so checkpoints must not interleave
            res = campaign.run_campaign(name, DNN10, SystemParams(seed=0),
                                        clients, rounds=rounds, seeds=seeds,
                                        test_data=(Xte, yte),
                                        eval_every=args.eval_every,
                                        policy=args.policy,
                                        quant=args.quant, scenario=trace,
                                        checkpoint_every=args.checkpoint_every,
                                        checkpoint_dir=(f"{ckpt_dir}/{name}"
                                                        if args.checkpoint_every
                                                        else None),
                                        resume=args.resume,
                                        **kw)
            acc = res.accuracy
            print(f"[{name}] {len(seeds)} seeds x {rounds} rounds: "
                  f"acc={acc.mean():.3f}±{acc.std():.3f} "
                  f"(per-seed {np.round(acc, 3).tolist()}) "
                  f"comm={sum(m.comm_bits for m in res.metrics) / 8e6:.1f}MB "
                  f"sim_time={sum(m.sim_time for m in res.metrics):.2f}s "
                  f"wall={time.time() - t0:.0f}s")
            if res.skipped_per_round is not None or res.crashed_rounds:
                print(f"[{name}] guards: skipped_rounds="
                      f"{res.skipped_rounds} quorum_rounds="
                      f"{res.quorum_rounds} crashed_rounds="
                      f"{res.crashed_rounds}")
            if args.eval_every:
                curve = [(m.round, round(m.accuracy, 3))
                         for m in res.metrics if m.accuracy == m.accuracy]
                print(f"[{name}] fused-eval accuracy curve: {curve}")
        return

    tr = SplitMeTrainer(DNN10, sp, clients, (Xte, yte), seed=0,
                        kernel_policy=args.policy, comm_quant=args.quant,
                        scenario=trace, interactive=True)
    t0 = time.time()
    for k in range(args.rounds):
        m = tr.run_round(eval_acc=(k % 5 == 4))
        if k % 5 == 4:
            print(f"[splitme] round {k}: sel={m.n_selected} E={m.E} "
                  f"acc={m.accuracy:.3f} cum_comm="
                  f"{sum(h.comm_bits for h in tr.history) / 8e6:.1f}MB")
        if (k + 1) % 10 == 0:
            ckpt.save(f"{args.ckpt_dir}/round{k + 1}",
                      {"w_c": tr.w_c, "w_s_inv": tr.w_s_inv},
                      metadata={"round": k + 1})
    w_server = tr.finalize()
    acc = tr.evaluate(w_server)
    total_time = sum(m.sim_time for m in tr.history)
    print(f"[splitme] FINAL acc={acc:.3f} rounds={args.rounds} "
          f"sim_time={total_time:.2f}s wall={time.time() - t0:.0f}s")

    if args.baselines:
        for name, cls, kw in [
            ("fedavg", FedAvgTrainer, {"K": 10, "E": 10}),
            ("sfl", SFLTrainer, {"K": 20, "E": 14}),
            ("oranfed", ORANFedTrainer, {"E": 10}),
            ("fedora", FedORATrainer, {"E": 10}),
            ("ecofl", EcoFLTrainer, {"K": 10, "E": 10}),
        ]:
            b = cls(DNN10, SystemParams(seed=0), copy.deepcopy(clients),
                    (Xte, yte), comm_quant=args.quant, scenario=trace, **kw)
            for _ in range(args.baseline_rounds):
                b.run_round()
            print(f"[{name}] acc={b.evaluate():.3f} "
                  f"rounds={args.baseline_rounds} "
                  f"sim_time={sum(m.sim_time for m in b.history):.2f}s "
                  f"comm={sum(m.comm_bits for m in b.history) / 8e6:.1f}MB")


if __name__ == "__main__":
    main()
