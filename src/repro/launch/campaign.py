"""Scanned, vmapped multi-seed / multi-config campaign runner.

Batches many independent training runs — different model-init / batching
RNG seeds over the same data — through shared compiled round functions,
``vmap``-ed over the seed axis, and runs ALL ROUNDS of a campaign as
``lax.scan``s on the device: per-round losses and fused-eval accuracies
land in device-resident metric buffers that transfer to host ONCE per
campaign (``_host_fetch``), never once per round, while the
schedule-derived metrics (comm_bits, selected-count, latency, cost) are
vectorized over the whole precomputed schedule up front — so no per-round
host arithmetic ever depends on a device pull.

This works because the system-side trajectory (A_t, b_t, E_t) of every §V
framework is independent of the learned parameters — Alg. 1 / P2 depend
only on SystemParams and realized comm times — so it is precomputed
host-side once (`plan_schedule`) and shared by all seeds, exactly matching
what each serial trainer would have done.  Knowing the schedule up front
buys exact optimizations the serial trainers cannot apply (a varying cohort
would recompile every round): each round gathers only its selected client
cohort (the engine round's cohort index) and scans exactly E_t local
steps, skipping unselected clients and the frozen scan tail entirely; the
precomputed A_t/b_t/E_t arrays become scan operands; and evaluation is
fused into the scanned round behind a per-round ``do_eval`` mask
(``lax.cond``), so training never leaves the device between rounds.
Rounds sharing a
(cohort-bucket, E-bucket) shape form contiguous scan segments that share
one compiled scan (segment lengths are bucketed too; padded rounds carry a
``live=0`` flag and are exact no-ops), and a compiled scan outlives its
campaign: a later ``run_campaign`` in the process whose segment program is
the same calls it again without tracing or lowering (``_segment_exec``).
Trained parameters are numerically identical to serial engine-trainer runs
(tests/test_campaign.py).

Execution modes — one round builder (``engine.build_round_fn``) and one
scanned segment path (``_run_rounds_scan`` over ``_build_segment``), whose
scan rows come in three kinds:

* a gathered cohort of the fleet (the default): each round's cohort
  index, mask and fault channels;
* ``mesh=...`` — the full mask over the fleet: clients shard over the mesh
  ``data``/``pod`` axes and the masked-FedAvg psum is the round's only
  collective, while seeds stay vmapped and rounds stay scanned
  (scan-over-shard_map-over-vmap);
* a population cohort (``run_population_campaign``): each round's own
  cohort data and mask.

Multi-config campaigns: ``run_config_sweep`` vmaps over SystemParams
variants sharing one (rounds, M) schedule shape — one compiled scan trains
every (variant, seed) pair and the whole sweep performs a single host
transfer.

Time-varying scenarios (``repro.core.scenario``) slot straight into this
architecture because traces, like schedules, are parameter-independent and
precomputable: ``plan_schedule(scenario=...)`` re-selects each round
against the round-t trace, the realized masks/E become the scan operands,
and latency/cost/energy vectorize over trace × schedule — a fading or
straggler campaign is still one compiled scan with one host transfer.

Fault tolerance (``repro.launch.resilience`` documents the failure model
and checkpoint layout): a ``faults:p`` scenario's poison/wire-corruption
channels become extra scan operands feeding the engine round's fault
injection, its server-crash channel holds the round in the scan body, and
``RoundGuards`` (auto-armed whenever the trace injects faults) roll back
non-finite aggregates in-scan — still one compiled program, one transfer.
``checkpoint_every``/``checkpoint_dir``/``resume`` split the scan at
checkpoint boundaries and persist/restore the full campaign carry so a
SIGKILLed campaign resumes bit-exactly.

Population mode (``repro.core.population``): ``run_population_campaign``
trains against a parameterized ``Population`` of up to millions of
virtual clients with O(cohort) memory — per-round cohorts are sampled
from the scenario seed, their SystemParams rows / trace channels / data
shards generated lazily for the sampled ids only, and the scan's operands
are cohort-shaped rows of the same segment path (the checkpoint carry
stays O(cohort) too).  Sampling the whole population as the cohort
reproduces the materialized ``run_campaign`` exactly (test-pinned at
1e-5).
"""
from __future__ import annotations

import collections
import contextlib
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.splitme_dnn import DNNConfig
from repro.core import (allocation, engine, population as popn,
                        scenario as scen)
from repro.core.cost import SystemParams, schedule_metrics
from repro.core.engine import RoundMetrics
from repro.kernels.common import use_interpret
from repro.launch import spans


def _host_fetch(tree):
    """The single device→host transfer point for campaign metrics.  Every
    metrics pull in this module comes here, so the counter
    ``spans.counts["host_transfers"]`` counts them (one per campaign)."""
    with spans.span("host_fetch"):
        spans.count("host_transfers")
        return jax.device_get(tree)


def _init_state(spec, seeds, mesh=None):
    """Each seed's initial params, round-key chain and error-feedback
    state, stacked over seeds; the init keys mirror the serial trainers."""
    with spans.span("init_state"):
        init_keys = jnp.stack([jax.random.PRNGKey(s + spec.init_key_offset)
                               for s in seeds])
        key_arr = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
        params = jax.vmap(spec.init_fn)(init_keys)
        return params, key_arr, _init_qstate(spec, params, mesh)


def _init_qstate(spec, params, mesh=None):
    """Seed-stacked CommQuant error-feedback accumulator: ``zeros_like`` on
    the seed-stacked params gives the per-seed state directly; the sharded
    round additionally keeps one residual per client shard (axis 1, after
    the seed axis)."""
    qstate = engine.init_quant_state(spec, params)
    if mesh is not None and spec.quant.stateful:
        n_shards = engine.n_client_shards(mesh)
        qstate = jax.tree.map(
            lambda z: jnp.zeros((z.shape[0], n_shards) + z.shape[1:],
                                z.dtype), qstate)
    return qstate


@dataclass
class RoundSchedule:
    """Precomputed system-side trajectory, shared by every seed.

    With a scenario, ``a`` is the REALIZED per-round mask — the policy's
    selection (made against the round-t trace) times the mid-round survival
    mask — and ``trace`` carries the trace the metrics vectorize over."""
    a: np.ndarray      # (R, M) binary selection masks (trace-realized)
    b: np.ndarray      # (R, M) bandwidth fractions
    E: np.ndarray      # (R,)   local-update counts
    trace: Optional[scen.ScenarioTrace] = None

    @property
    def rounds(self) -> int:
        return len(self.E)


@dataclass
class CampaignResult:
    framework: str
    seeds: Tuple[int, ...]
    schedule: RoundSchedule
    params: Any               # params tuple, each leaf stacked over seeds
    losses: np.ndarray        # (n_seeds, rounds, n_phases)
    metrics: List[RoundMetrics]   # system metrics per round (seed-invariant)
    accuracy: Optional[np.ndarray] = None   # (n_seeds,) if test_data given
    accuracy_per_round: Optional[np.ndarray] = None  # (rounds, n_seeds), NaN
    # off eval rounds (scan mode with test_data / eval_every)
    # Guarded-campaign accounting (None when guards are off; see
    # repro.launch.resilience for the failure model):
    skipped_per_round: Optional[np.ndarray] = None  # (R, S) 0/1 non-finite
    # rollbacks, quorum holds, and (R,) server-crash injections
    quorum_per_round: Optional[np.ndarray] = None   # (R, S)
    crashed_per_round: Optional[np.ndarray] = None  # (R,)

    def params_for(self, i: int):
        """The i-th seed's params tuple (unstacked)."""
        return jax.tree.map(lambda p: p[i], self.params)

    @property
    def skipped_rounds(self) -> int:
        """Total non-finite round rollbacks across all seeds."""
        return (0 if self.skipped_per_round is None
                else int(self.skipped_per_round.sum()))

    @property
    def quorum_rounds(self) -> int:
        """Total quorum hold-rounds across all seeds."""
        return (0 if self.quorum_per_round is None
                else int(self.quorum_per_round.sum()))

    @property
    def crashed_rounds(self) -> int:
        """Rounds lost to injected server crashes (seed-invariant)."""
        return (0 if self.crashed_per_round is None
                else int(self.crashed_per_round.sum()))


def plan_schedule(framework: str, sp: SystemParams, cfg: DNNConfig,
                  rounds: int, *, policy_seed: int = 0, K: int = 10,
                  E: int = 10, e_initial: int = 20,
                  n_samples_per_client: Optional[int] = None,
                  quant=None, scenario: scen.ScenarioLike = None,
                  scenario_seed: int = 0
                  ) -> Tuple[SystemParams, RoundSchedule]:
    """Run the framework's host-side policy for `rounds` rounds.

    Returns the framework's derived SystemParams copy and the schedule.
    ``quant`` (a ``CommQuant`` / mode name) scales the wire payloads the
    policy optimizes over, so deadline/energy selection responds to the
    quantized format.

    ``scenario`` (None / a registry name like ``"fading"`` /
    ``"straggler:0.4"`` / a ``ScenarioTrace``) makes the plan TIME-VARYING:
    each round the trace's channel gains, compute scales, deadline jitter
    and availability are written into the derived copy before the policy
    re-selects, and the recorded mask is the REALIZED one (selection ×
    mid-round survival).  The returned SystemParams carries the
    round-invariant base values (the schedule's trace rides on
    ``RoundSchedule.trace``).
    """
    sp, policy = engine.make_policy(
        framework, sp, cfg, seed=policy_seed, K=K, E=E, e_initial=e_initial,
        n_samples_per_client=n_samples_per_client, quant=quant)
    trace = scen.get_trace(scenario, rounds, sp.M, seed=scenario_seed)
    # an all-ones trace (e.g. "static", or "noniid" whose action is purely
    # data-side) needs no per-round SystemParams rewrites
    dynamic = trace is not None and not trace.is_static()
    base = scen.capture_base(sp) if dynamic else None
    steps0 = allocation.bisect_steps
    a_l, b_l, e_l = [], [], []
    for t in range(rounds):
        if dynamic:
            scen.apply_round(sp, base, trace, t)
        a, b, e = policy.step()
        if dynamic:
            a = scen.realized_mask(a, trace, t)
        a_l.append(a), b_l.append(b), e_l.append(e)
    if dynamic:
        scen.restore_base(sp, base)
    _count_bisect_steps(steps0)
    return sp, RoundSchedule(a=np.stack(a_l), b=np.stack(b_l),
                             E=np.asarray(e_l, np.int32), trace=trace)


def _count_bisect_steps(since: int) -> None:
    """Count the P2 bisection steps a plan took (``p2_bisect_steps``)."""
    steps = allocation.bisect_steps - since
    if steps:
        spans.count("p2_bisect_steps", steps)


def _bucket_cohorts(values, cap: int, max_exact: int = 8) -> Dict[int, int]:
    """Map each schedule value (cohort size, E, or scan-segment length) to a
    compile-shape bucket.

    Few distinct values → exact shapes (one compile each); many → round up
    to powers of two (bounds the number of compilations at log2(cap))."""
    distinct = sorted(set(int(c) for c in values))
    if len(distinct) <= max_exact:
        return {k: k for k in distinct}
    buckets, b = [], 1
    while b < cap:
        buckets.append(b)
        b *= 2
    buckets.append(cap)
    return {k: next(x for x in buckets if x >= k) for k in distinct}


def _schedule_system_metrics(spec, sched: RoundSchedule, sp: SystemParams):
    """All schedule-derived metrics for every round in one vectorized pass
    over trace × schedule — comm_bits via the spec's stacked-schedule
    comm_model, latency/cost/energy via ``cost.schedule_metrics`` (which
    reads the schedule's ScenarioTrace, if any) — so no per-round host
    arithmetic (and nothing here) ever depends on a device pull."""
    comm = np.atleast_1d(np.asarray(
        spec.comm_model(sched.a, sched.E, sp), np.float64))
    nsel = sched.a.sum(axis=1).astype(int)
    sim, cost, energy = schedule_metrics(sched.a, sched.b, sched.E, sp,
                                         trace=sched.trace)
    return comm, nsel, sim, cost, energy


def _plan_segments(kb_r: Sequence[int], eb_r: Sequence[int]
                   ) -> List[Tuple[int, int, int, int]]:
    """Contiguous maximal runs of rounds sharing a (cohort, E) shape bucket:
    [(kb, eb, start, length)] in round order."""
    segs, start = [], 0
    R = len(kb_r)
    for r in range(1, R + 1):
        if r == R or (kb_r[r], eb_r[r]) != (kb_r[start], eb_r[start]):
            segs.append((kb_r[start], eb_r[start], start, r - start))
            start = r
    return segs


def _split_at_checkpoints(segs, every: Optional[int]
                          ) -> List[Tuple[int, int, int, int]]:
    """Additionally split the (kb, eb, start, length) runs at global rounds
    divisible by ``every``, so every checkpoint boundary lands exactly on a
    segment edge.  Numerically free: per-round computation depends only on
    the (kb, eb) shape buckets, which splitting leaves untouched."""
    if not every:
        return segs
    out = []
    for kb, eb, start, length in segs:
        r, end = start, start + length
        while r < end:
            nxt = min(end, (r // every + 1) * every)
            out.append((kb, eb, r, nxt - r))
            r = nxt
    return out


def _make_metrics(sched, comm, nsel, sim, cost, energy, losses, acc_rounds,
                  skipped=None, quorum=None, crashed=None
                  ) -> List[RoundMetrics]:
    metrics = []
    for r in range(sched.rounds):
        acc_r = float("nan")
        if acc_rounds is not None and np.isfinite(acc_rounds[r]).any():
            acc_r = float(np.nanmean(acc_rounds[r]))
        metrics.append(RoundMetrics(
            round=r, n_selected=int(nsel[r]), E=int(sched.E[r]),
            comm_bits=float(comm[r]), sim_time=float(sim[r]),
            cost=float(cost[r]), energy=float(energy[r]), accuracy=acc_r,
            client_loss=float(losses[:, r, 0].mean()),
            server_loss=float(losses[:, r, 1].mean())
            if losses.shape[-1] > 1 else float("nan"),
            skipped=float(skipped[r].mean()) if skipped is not None else 0.0,
            quorum_held=float(quorum[r].mean()) if quorum is not None
            else 0.0,
            crashed=float(crashed[r]) if crashed is not None else 0.0))
    return metrics


def run_campaign(framework: str, cfg: DNNConfig, sp: SystemParams,
                 client_data: Dict[str, np.ndarray], *, rounds: int,
                 seeds: Sequence[int], test_data=None,
                 K: int = 10, E: int = 10, e_initial: int = 20,
                 policy_seed: Optional[int] = None, mesh=None,
                 eval_every: Optional[int] = None,
                 eval_gamma: float = 1e-3, strict_transfers: bool = False,
                 policy=None, quant=None,
                 scenario: scen.ScenarioLike = None,
                 scenario_seed: int = 0, guards=None,
                 checkpoint_every: Optional[int] = None,
                 checkpoint_dir=None, resume: bool = False,
                 _checkpoint_hook=None, **hyper) -> CampaignResult:
    """Train `len(seeds)` independent runs of `framework` in one compiled
    scan-over-rounds, vmapped over the seed axis.

    The per-seed RNG chains mirror the serial trainers exactly
    (PRNGKey(seed [+ init offset]) for init, the same split chain per
    round), so seed s here equals a serial run of the engine-backed trainer
    with seed=s.  The single A_t/b_t/E_t schedule is shared by all seeds;
    for frameworks whose selection is itself randomized (FedAvg/SFL) it is
    drawn from ``policy_seed`` (default: min(seeds)).  ``hyper`` forwards
    to the framework spec factory (lr / lr_c / lr_s / temperature /
    batch_size).

    The whole campaign runs on-device (see module docstring): one host
    transfer for all per-round metrics, evaluation fused behind a
    ``do_eval`` mask on the final round (plus every ``eval_every`` rounds).
    ``mesh`` switches the round bodies to the shard_map engine round
    (clients sharded over the mesh data axes).  ``strict_transfers=True``
    wraps the device phase in ``jax.transfer_guard_device_to_host(
    "disallow")``, turning any stray per-round pull into a hard error (used
    by the transfer-counting test).  ``policy`` (None / ``"reference"`` /
    ``"kernel"`` / ``"kernel_bf16"`` / a
    ``repro.kernels.dispatch.KernelPolicy``) selects
    the kernel dispatch + precision for every round AND the fused eval, so
    the whole scanned campaign runs kernelized end-to-end.

    ``quant`` (None / "none" / "bf16" / "int8" /
    ``repro.core.quantcomm.CommQuant``) narrows the wire format of the
    masked-FedAvg aggregation payload: the rounds quantize-before-psum
    (int8 carries a per-seed error-feedback accumulator through the scan),
    and comm_bits / latency / cost / the schedule's selection all account
    the quantized bits.

    ``scenario`` (None / a ``repro.core.scenario`` registry name like
    ``"fading"`` / ``"straggler:0.4"`` / a ``ScenarioTrace``) runs the
    campaign against a TIME-VARYING RAN: the schedule is planned round by
    round against the trace (selection/allocation see the round-t channel
    gains, compute scales, deadline jitter and availability; mid-round
    dropouts zero the realized mask), and comm_bits / latency / cost /
    energy vectorize over trace × schedule.  The trace-realized per-round
    masks/E become the ``lax.scan`` operands of the scanned campaign, so a
    scenario campaign still compiles to the same scans with ONE host
    transfer (``strict_transfers`` holds with scenarios on).  Note the
    caller partitions ``client_data`` — for a ``noniid`` scenario build it
    with ``scenario.partition_for`` (Dirichlet α rides on the trace).

    Fault tolerance (``repro.launch.resilience``): a ``faults:p``
    scenario's poison / wire-corruption / server-crash channels are
    injected inside the scan, and ``guards`` (an ``engine.RoundGuards``;
    ``None`` auto-arms the defaults whenever the trace injects faults,
    ``False`` forces them off) adds the in-scan non-finite rollback,
    quorum hold and optional per-client norm clip — the campaign stays one
    compiled program with one host transfer.  ``checkpoint_every`` +
    ``checkpoint_dir`` persist the full campaign carry every that-many
    rounds (atomic manifests; each save is an explicit extra device pull,
    so it excludes ``strict_transfers``); ``resume=True`` restores the
    newest committed checkpoint from ``checkpoint_dir`` (validated against
    the replanned schedule's fingerprint) and re-enters the scan at the
    next segment, bit-exactly.  ``_checkpoint_hook(round_cursor)``, if
    given, runs after each committed save (crash-injection drivers and
    tests hang their abort/kill timing on it).
    """
    with spans.span("run_campaign", framework=framework, rounds=rounds,
                    seeds=len(seeds)):
        spans.count("campaigns")
        x = jnp.asarray(client_data["x"])
        y = jnp.asarray(client_data["y"])
        if x.shape[0] != sp.M:
            # the gathered round would silently clamp out-of-range client
            # indices under jit; fail loudly instead
            raise ValueError(f"client_data has {x.shape[0]} clients but "
                             f"SystemParams.M={sp.M}")
        n_m = int(x.shape[1])
        if policy_seed is None:
            policy_seed = min(seeds)
        with spans.span("plan_schedule", rounds=rounds):
            sp, sched = plan_schedule(
                framework, sp, cfg, rounds, K=K, E=E, e_initial=e_initial,
                policy_seed=policy_seed, n_samples_per_client=n_m, quant=quant,
                scenario=scenario, scenario_seed=scenario_seed)
        # masked_loss_metric: average losses over the executed steps only, so a
        # round's scan can be exactly E_t steps long.  Trained params are
        # identical to the serial trainers (masked updates are exact no-ops);
        # only SplitMe's *loss metric* differs from the seed quirk of averaging
        # over the full E_max scan.
        spec_kw = dict(hyper, masked_loss_metric=True)
        spec = engine.make_spec(framework, cfg, policy=policy, quant=quant,
                                **spec_kw)
        system = _schedule_system_metrics(spec, sched, sp)
        guards = _resolve_guards(guards, sched.trace)
        _check_checkpoint_args(checkpoint_every, checkpoint_dir, resume,
                               strict_transfers)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            csh = NamedSharding(mesh, P(engine.client_axes(mesh)))
            x, y = jax.device_put(x, csh), jax.device_put(y, csh)
        do_eval = _eval_mask(rounds, test_data, eval_every)
        ckpt = _checkpoint_plan(framework, seeds, sched, do_eval, spec,
                                checkpoint_every, checkpoint_dir, resume,
                                _checkpoint_hook)
        params, host = _fetched(strict_transfers, lambda: _run_rounds_scan(
            spec, cfg, sp, sched, _scan_data(x, y, test_data), seeds,
            do_eval, eval_gamma, mesh, guards=guards, ckpt=ckpt,
            spec_kw=spec_kw))
        return _campaign_result(framework, seeds, sched, params, host,
                                system, test_data, guards)


def _resolve_guards(guards, trace):
    """The rounds' ``RoundGuards``: ``None`` arms the defaults when the
    trace injects faults (a population trace carries none), ``False``
    forces them off."""
    if (guards is None and isinstance(trace, scen.ScenarioTrace)
            and trace.has_faults()):
        return engine.RoundGuards()
    return None if guards is False else guards


def _fault_channels(trace):
    """The trace's (poison, wire_gain, crash) channels; no trace and a
    population trace carry none."""
    if isinstance(trace, scen.ScenarioTrace):
        return trace.poison, trace.wire_gain, trace.crash
    return None, None, None


def _check_checkpoint_args(checkpoint_every, checkpoint_dir, resume,
                           strict_transfers) -> None:
    if checkpoint_every or checkpoint_dir or resume:
        if not (checkpoint_every and checkpoint_dir is not None):
            raise ValueError("checkpointing needs BOTH checkpoint_every "
                             "and checkpoint_dir (resume implies both)")
        if strict_transfers:
            raise ValueError("checkpoint_every is incompatible with "
                             "strict_transfers: each segment save is an "
                             "explicit device→host pull")


def _eval_mask(rounds: int, test_data, eval_every: Optional[int]):
    """The per-round ``do_eval`` mask: with a test set, the final round
    plus every ``eval_every``-th."""
    do_eval = np.zeros(rounds, np.float32)
    if test_data is not None:
        if eval_every:
            do_eval[eval_every - 1::eval_every] = 1.0
        do_eval[rounds - 1] = 1.0
    return do_eval


def _checkpoint_plan(framework, seeds, sched, do_eval, spec,
                     checkpoint_every, checkpoint_dir, resume, hook,
                     extra=()):
    """The ``ckpt`` dict ``_run_rounds_scan`` saves and resumes by (None
    without checkpointing).  ``extra`` joins the schedule fingerprint; a
    resume refuses a checkpoint written under another fingerprint."""
    if not checkpoint_every:
        return None
    from repro.launch import resilience
    fp = resilience.schedule_fingerprint(
        framework, seeds, sched, do_eval=do_eval,
        quant_mode=spec.quant.mode, checkpoint_every=checkpoint_every,
        extra=extra)
    resume_from = None
    if resume:
        resume_from = resilience.latest_checkpoint(checkpoint_dir)
        if resume_from is not None:
            meta = resilience.load_checkpoint_meta(resume_from)
            if meta.get("fingerprint") != fp:
                raise ValueError(
                    f"checkpoint {resume_from} was written by a "
                    f"different campaign plan (schedule fingerprint "
                    f"mismatch); refusing to resume")
    return {"dir": checkpoint_dir, "every": int(checkpoint_every),
            "fingerprint": fp, "resume_from": resume_from, "hook": hook,
            "framework": framework, "n_seeds": len(seeds)}


def _fetched(strict_transfers: bool, run):
    """``run()``'s ``(params, device buffers)``, the buffers then fetched
    in THE per-campaign transfer.  ``strict_transfers`` runs it under
    ``jax.transfer_guard_device_to_host("disallow")``."""
    guard = (jax.transfer_guard_device_to_host("disallow")
             if strict_transfers else contextlib.nullcontext())
    with guard:
        params, buffers = run()
    return params, _host_fetch(buffers)


def _campaign_result(framework, seeds, sched, params, host, system,
                     test_data, guards) -> CampaignResult:
    """A scanned campaign's result from its fetched buffers; ``system`` is
    the schedule's (comm, nsel, sim, cost, energy)."""
    rounds = sched.rounds
    live = host["live"] > 0
    losses = np.transpose(host["loss"][live], (1, 0, 2))   # (S, R, n_ph)
    acc_rounds = (np.asarray(host["acc"][live])            # (R, S)
                  if test_data is not None else None)
    skipped = quorum = crashed = None
    if guards is not None:
        skipped = np.asarray(host["skipped"][live])        # (R, S)
        quorum = np.asarray(host["quorum"][live])          # (R, S)
    crash = _fault_channels(sched.trace)[2]
    if crash is not None:
        crashed = (np.asarray(crash[:rounds]) > 0).astype(np.float64)
    result = CampaignResult(
        framework=framework, seeds=tuple(seeds), schedule=sched,
        params=params, losses=losses,
        metrics=_make_metrics(sched, *system, losses, acc_rounds,
                              skipped=skipped, quorum=quorum,
                              crashed=crashed),
        accuracy_per_round=acc_rounds, skipped_per_round=skipped,
        quorum_per_round=quorum, crashed_per_round=crashed)
    if acc_rounds is not None:
        result.accuracy = acc_rounds[rounds - 1]
    return result


def _concat(ys_all):
    """The scan segments' metric buffers, joined along the round axis."""
    return {k: (jnp.concatenate([ys[k] for ys in ys_all], axis=0)
                if len(ys_all) > 1 else ys_all[0][k])
            for k in ys_all[0]}


def _save_checkpoint(ckpt, end: int, rounds: int, carry, ys_all) -> None:
    """Persist the campaign carry and the buffers of rounds [0, end)."""
    from repro.launch import resilience
    with spans.span("checkpoint_save", round=end):
        resilience.save_checkpoint(
            ckpt["dir"], end, carry, _concat(ys_all),
            fingerprint=ckpt["fingerprint"], rounds=rounds,
            framework=ckpt["framework"], n_seeds=ckpt["n_seeds"])
    if ckpt["hook"] is not None:
        ckpt["hook"](end)


def _scan_data(x, y, test_data):
    """Every array a campaign's compiled scan reads, as one pytree passed
    to it as an ARGUMENT.  Closed over instead, a device array is embedded
    in the program as a constant, which reads it back to the host first:
    on an accelerator that is a device-to-host transfer per compile (and
    ``strict_transfers`` refuses it)."""
    data = {"x": x, "y": y}
    if test_data is not None:
        data["x_test"], data["y_test"] = map(jnp.asarray, test_data)
    return data


def _fused_eval(spec, cfg, data, gamma, mesh=None):
    """The per-round eval over the scan's traced ``data`` (``_scan_data``);
    None without a test set.  SplitMe's Step-4 Grams read ``x``/``y``;
    with the clients sharded over ``mesh`` each shard builds its own Grams
    and one psum per layer sums them (the paper's all-reduce).  That is
    also the only place a Pallas Gram kernel can run on a multi-chip
    program: Mosaic kernels are never partitioned automatically."""
    if "x_test" not in data:
        return None
    if spec.name != "splitme" or mesh is None:
        return engine.build_eval_fn(
            spec, cfg, data["x_test"], data["y_test"], gamma=gamma,
            jit=False, client_data=data if spec.name == "splitme" else None)
    from jax.sharding import PartitionSpec as P
    axes = engine.client_axes(mesh)

    def local(params, x, y, x_test, y_test):
        return engine.build_eval_fn(
            spec, cfg, x_test, y_test, client_data={"x": x, "y": y},
            gamma=gamma, jit=False, axis_name=axes)(params)

    sharded = jax.shard_map(local, mesh=mesh,
                            in_specs=(P(), P(axes), P(axes), P(), P()),
                            out_specs=P(), check_vma=False)
    return lambda params: sharded(params, data["x"], data["y"],
                                  data["x_test"], data["y_test"])


# Segment scans compiled in this process, least recently used first.  A
# later campaign whose segment program is the same calls the jitted scan it
# already holds: no trace, no lowering, no load from the persistent cache.
SEGMENT_CACHE_SIZE = 64
_segments: "collections.OrderedDict[_SegmentKey, Any]" = \
    collections.OrderedDict()


class _SegmentKey(NamedTuple):
    """Everything the trace of one segment scan reads, so one entry is one
    executable.  ``code`` holds the engine functions the trace looks up
    (strong references): rebinding one, by a reload or a test's fault,
    misses."""
    framework: str
    cfg: DNNConfig
    spec_kw: tuple        # make_spec's keywords (resolved), sorted, typed
    eval_gamma: float
    mesh: Any
    guards: Optional[engine.RoundGuards]
    with_faults: bool
    robust: bool
    population: bool      # rows carry their cohort's data (xc, yc)
    data: tuple           # (name, shape, dtype) of each scan-data leaf
    n_seeds: int
    # what the trace reads of the environment: the default matmul
    # precision and device, and whether Pallas interprets
    settings: tuple
    code: tuple
    kb: int = 0
    eb: int = 0
    lb: int = 0


def clear_segment_cache() -> None:
    """Forget every compiled segment scan of this process."""
    _segments.clear()


def _segment_key(spec, cfg, spec_kw, data, n_seeds, eval_gamma, mesh,
                 guards, with_faults, robust,
                 population: bool = False) -> _SegmentKey:
    """The key of a campaign's segments, their (kb, eb, lb) left at 0."""
    kw = dict(spec_kw, policy=spec.policy, quant=spec.quant)
    return _SegmentKey(
        framework=spec.name, cfg=cfg,
        spec_kw=tuple(sorted((k, type(v), v) for k, v in kw.items())),
        eval_gamma=eval_gamma, mesh=mesh, guards=guards,
        with_faults=with_faults, robust=robust, population=population,
        data=tuple((k, v.shape, str(v.dtype))
                   for k, v in sorted(data.items())),
        n_seeds=n_seeds,
        settings=(jax.config.jax_default_matmul_precision,
                  jax.config.jax_default_device, use_interpret()),
        code=(engine._round_core, engine.build_round_fn,
              engine.build_eval_fn))


def _segment_exec(key: _SegmentKey):
    """The jitted scan of ``key``: the cached one (a ``segment_hits``), or
    a new one (a ``segment_builds``; it traces at its first call)."""
    fn = _segments.get(key)
    if fn is not None:
        spans.count("segment_hits")
        _segments.move_to_end(key)
        return fn
    spans.count("segment_builds")
    fn = _segments[key] = _build_segment(key)
    if len(_segments) > SEGMENT_CACHE_SIZE:
        _segments.popitem(last=False)
    return fn


def _round_caller(spec, cfg, mesh, eb: int, guards, with_faults: bool,
                  data):
    """One seed-vmapped round over the scan row ``xr``, which comes in one
    of three kinds: a gathered cohort of ``data`` (``idx``, ``mask`` and
    the fault channels gathered by ``idx``), the full mask over ``data``'s
    clients on ``mesh`` (``mask``), or a population cohort with its own
    data (``xc``, ``yc``, ``mask``)."""
    raw = engine.build_round_fn(spec, cfg, e_max=max(1, eb), mesh=mesh,
                                jit=False, guards=guards,
                                with_faults=with_faults)
    vround = jax.vmap(raw, in_axes=(0, None, None, None, None, 0, 0, None,
                                    None))

    def call_round(params, xr, subs, qstate):
        x, y = ((xr["xc"], xr["yc"]) if "xc" in xr
                else (data["x"], data["y"]))
        faults = ({"poison": xr["poison"], "wire_gain": xr["wire"]}
                  if with_faults else None)
        return vround(params, x, y, xr["mask"], xr["e"], subs, qstate,
                      faults, xr.get("idx"))
    return call_round


def _build_segment(key: _SegmentKey):
    """The jitted scan of one segment shape.  It closes over parts of
    ``key`` only: the carry, the scan rows and the data are arguments."""
    cfg, mesh, guards, robust = key.cfg, key.mesh, key.guards, key.robust
    spec = engine.make_spec(key.framework, cfg,
                            **{k: v for k, _, v in key.spec_kw})

    def seg(params, key_arr, qstate, xs, data):
        # the round and eval close over the traced data, never over
        # device arrays (see _scan_data)
        call_round = _round_caller(spec, cfg, mesh, key.eb, guards,
                                   key.with_faults, data)
        eval_fn = _fused_eval(spec, cfg, data, key.eval_gamma, mesh)
        nan_row = jnp.full((key_arr.shape[0],), jnp.nan, jnp.float32)

        def body(carry, xr):
            params, keys, qstate = carry
            ks = jax.vmap(jax.random.split)(keys)
            nkeys, subs = ks[:, 0], ks[:, 1]
            out = call_round(params, xr, subs, qstate)
            if guards is not None:
                nparams, phase_losses, nqstate, flags = out
            else:
                nparams, phase_losses, nqstate = out
                flags = None
            live = xr["live"] > 0
            # a crash round is lost server-side: params/EF hold, clients
            # still advanced their RNG (they did train), losses are NaN
            ran = (jnp.logical_and(live, xr["crash"] <= 0) if robust
                   else live)
            params = jax.tree.map(lambda n, o: jnp.where(ran, n, o),
                                  nparams, params)
            qstate = jax.tree.map(lambda n, o: jnp.where(ran, n, o),
                                  nqstate, qstate)
            keys = jnp.where(live, nkeys, keys)
            loss_row = jnp.where(ran, jnp.stack(phase_losses, -1), jnp.nan)
            if eval_fn is None:
                acc = nan_row
            else:
                acc = jax.lax.cond(
                    jnp.logical_and(xr["do_eval"] > 0, live),
                    jax.vmap(eval_fn), lambda p: nan_row, params)
            ys = {"loss": loss_row, "acc": acc, "live": xr["live"]}
            if guards is not None:
                ys["skipped"] = jnp.where(ran, flags["skipped"], 0.0)
                ys["quorum"] = jnp.where(ran, flags["quorum"], 0.0)
            return (params, keys, qstate), ys

        return jax.lax.scan(body, (params, key_arr, qstate), xs)

    return jax.jit(seg, donate_argnums=(0, 1, 2))


def _run_rounds_scan(spec, cfg, sp, sched, data, seeds, do_eval, eval_gamma,
                     mesh, *, spec_kw, guards=None, ckpt=None, cohorts=None):
    """Scan all rounds on-device; returns (params, device metric buffers).

    The buffers carry everything that EXISTS on the device — per-round
    per-seed losses and fused-eval accuracies (plus the live mask; under
    guards also the per-seed skipped/quorum flags); the remaining
    per-round metrics (comm_bits, selected-count, latency, cost) are
    schedule constants already precomputed host-side and never touch the
    device.

    Rounds sharing a (cohort-bucket, E-bucket) shape form contiguous scan
    segments; segment lengths are bucketed as well, padded with ``live=0``
    no-op rounds, so the number of compiled scans is bounded even for
    adaptive-E / varying-cohort schedules.  The compiled scans outlive the
    call (``_segment_exec``): ``spec_kw``, the keywords ``spec`` was made
    with, keys them with everything else their trace reads.

    ``cohorts`` (``(xc, yc)``, every round's population cohort data, from
    ``run_population_campaign``) makes the cohort data scan rows: each
    round trains its ``C`` cohort positions under ``sched.a``'s mask, and
    the device never holds more than one segment's cohorts.  Without it a
    round gathers its selected clients of ``data`` (or, on ``mesh``,
    trains the full masked fleet).

    ``guards`` (engine.RoundGuards) and the schedule trace's fault
    channels arm the robust scan body: poison/wire-corruption rows become
    extra scan operands feeding the round's fault injection, a crash round
    holds params/qstate (clients still advance their RNG — they trained;
    the server lost the aggregate), and the round's guard flags land in
    the buffers.  ``ckpt`` (``_checkpoint_plan``'s dict: dir / every /
    fingerprint / resume_from / hook) splits segments at checkpoint
    boundaries, persists the carry after each boundary via
    ``repro.launch.resilience`` and, on resume, restores it and skips the
    completed segments."""
    rounds = sched.rounds
    n_seeds = len(seeds)
    counts = sched.a.sum(axis=1).astype(int)
    e_of = _bucket_cohorts(sched.E, int(sp.E_max))
    gather = mesh is None and cohorts is None
    if gather:
        size_of = _bucket_cohorts(counts, sp.M)
        kb_r = [size_of[int(c)] for c in counts]
    else:
        # a sharded round trains the full masked M axis (a gather would
        # break the static client sharding), a population round its cohort
        kb_r = [int(sched.a.shape[1])] * rounds
    eb_r = [e_of[int(e)] for e in sched.E]
    segs = _split_at_checkpoints(_plan_segments(kb_r, eb_r),
                                 ckpt["every"] if ckpt else None)
    len_of = _bucket_cohorts([l for *_ , l in segs],
                             max(l for *_, l in segs))

    poison, wire, crash = _fault_channels(sched.trace)
    with_faults = poison is not None or wire is not None
    has_crash = crash is not None and bool(np.any(np.asarray(crash) > 0))
    robust = guards is not None or with_faults or has_crash
    M = int(sp.M)
    p_arr = (np.zeros((rounds, M), np.float32) if poison is None
             else np.asarray(poison, np.float32))
    w_arr = (np.ones((rounds, M), np.float32) if wire is None
             else np.asarray(wire, np.float32))

    base = _segment_key(spec, cfg, spec_kw, data, n_seeds, eval_gamma, mesh,
                        guards, with_faults, robust,
                        population=cohorts is not None)
    params, key_arr, qstate = _init_state(spec, seeds, mesh)
    ys_all = []
    start_round = 0
    if ckpt is not None and ckpt["resume_from"] is not None:
        from repro.checkpoint import io
        path = ckpt["resume_from"]
        like = {"params": params, "keys": key_arr, "qstate": qstate}
        if mesh is not None:
            # the acceptance-pinned mesh resume: params land replicated
            # through the checkpoint layer's shardings= path
            from jax.sharding import NamedSharding, PartitionSpec as P
            rep = jax.tree.map(lambda _: NamedSharding(mesh, P()), like)
            state = io.restore(path, like, shardings=rep)
        else:
            state = io.restore(path, like)
        params, key_arr, qstate = \
            state["params"], state["keys"], state["qstate"]
        buf = io.load_arrays(Path(path).with_name(Path(path).name
                                                  + "-buffers"))
        ys_all.append({k: jnp.asarray(v) for k, v in buf.items()})
        start_round = int(
            io.manifest(path)["metadata"]["round_cursor"])
    for kb, eb, start, length in segs:
        if start + length <= start_round:
            continue                       # restored from the checkpoint
        lb = len_of[length]
        end = start + length
        key = base._replace(kb=kb, eb=eb, lb=lb)
        with spans.span("segment", kb=kb, eb=eb, lb=lb, start=start,
                        length=length, built=key not in _segments):
            xs = {"e": _pad(sched.E[start:end], lb, np.int32),
                  "live": _pad(np.ones(length), lb),
                  "do_eval": _pad(do_eval[start:end], lb)}
            if robust:
                xs["crash"] = _pad(crash[start:end] if has_crash
                                   else np.zeros(length), lb)
            if gather:
                idx = np.zeros((lb, kb), np.int32)
                mask = np.zeros((lb, kb), np.float32)
                for i, r in enumerate(range(start, end)):
                    k_r = int(counts[r])
                    idx[i, :k_r] = np.nonzero(sched.a[r])[0]  # pads: client
                    mask[i, :k_r] = 1.0                       # 0, weight 0
                xs["idx"], xs["mask"] = idx, mask
                if with_faults:
                    # the fault channels gathered by the same cohort index;
                    # pads stay neutral (poison 0, gain 1 — and carry mask 0)
                    r_i = np.arange(start, end)[:, None]
                    sel = mask[:length] > 0
                    xs["poison"] = _pad(np.where(
                        sel, p_arr[r_i, idx[:length]], 0.0), lb)
                    xs["wire"] = _pad(np.where(
                        sel, w_arr[r_i, idx[:length]], 1.0), lb, fill=1.0)
            else:
                xs["mask"] = _pad(sched.a[start:end], lb)
                if with_faults:
                    xs["poison"] = _pad(p_arr[start:end], lb)
                    xs["wire"] = _pad(w_arr[start:end], lb, fill=1.0)
            if cohorts is not None:
                xs["xc"] = _pad(cohorts[0][start:end], lb)
                xs["yc"] = _pad(cohorts[1][start:end], lb, np.int32)
            (params, key_arr, qstate), ys = _segment_exec(key)(
                params, key_arr, qstate, xs, data)
        ys_all.append(ys)
        if ckpt is not None and (end % ckpt["every"] == 0 or end == rounds):
            _save_checkpoint(ckpt, end, rounds, {"params": params,
                                                 "keys": key_arr,
                                                 "qstate": qstate}, ys_all)
    return params, _concat(ys_all)


def _pad(rows, lb: int, dtype=np.float32, fill=0.0):
    """``rows`` along a segment's round axis, padded to its ``lb`` rounds
    with ``fill``."""
    rows = np.asarray(rows, dtype)
    out = np.full((lb,) + rows.shape[1:], fill, dtype)
    out[:len(rows)] = rows
    return out


# ---------------------------------------------------------------------------
# Population mode: O(cohort) campaigns over millions of virtual clients
# ---------------------------------------------------------------------------

@dataclass
class PopulationSchedule:
    """Precomputed system-side trajectory of a POPULATION campaign.

    Everything is cohort-shaped: round t touches the ``cohort_sizes[t]``
    distinct clients in ``ids[t]`` (pads repeat ``ids[t, 0]`` and are never
    selectable), and ``a``/``b`` index cohort POSITIONS, not client ids.
    ``rows`` carries the REALIZED per-round Q_C/Q_S/gain of the sampled
    clients (framework derivation and trace channels applied) — the
    absolute values ``cost.schedule_metrics(rows=...)`` vectorizes over,
    since a round-invariant base doesn't exist when every round samples a
    different cohort."""
    ids: np.ndarray           # (R, C) int64 sampled client ids
    a: np.ndarray             # (R, C) realized selection over positions
    b: np.ndarray             # (R, C) bandwidth fractions
    E: np.ndarray             # (R,)   local-update counts
    m_t: np.ndarray           # (R,)   registered population per round
    cohort_sizes: np.ndarray  # (R,)   distinct sampled ids (<= C)
    rows: Dict[str, np.ndarray]           # {"q_c","q_s","gain"} each (R, C)
    trace: Optional[popn.PopulationTrace] = None

    @property
    def rounds(self) -> int:
        return len(self.E)


def plan_population_schedule(framework: str, population: popn.Population,
                             cfg: DNNConfig, rounds: int, *, cohort: int,
                             policy_seed: int = 0, K: int = 10, E: int = 10,
                             e_initial: int = 20,
                             n_samples_per_client: Optional[int] = None,
                             quant=None, scenario=None,
                             scenario_seed: int = 0,
                             stratified: bool = False
                             ) -> Tuple[SystemParams, PopulationSchedule]:
    """Run the framework's host-side policy over per-round SAMPLED cohorts.

    The cohort pipeline per round t: sample ``min(cohort, m_t)`` distinct
    ids from the round's registered population (uniform or stratified by
    anchor class; deterministic in ``(scenario_seed, t)`` alone, so a
    resume replans identically) → evaluate the sampled clients' rows and
    the trace's lazy channels → write them into the framework's derived
    SystemParams copy → ``policy.step()`` selects/allocates within the
    cohort — the existing deadline/energy policies run UNCHANGED, they
    just see cohort-sized arrays.  Memory is O(R × cohort); the population
    size only enters through the samplers.

    With ``scenario=None`` and ``cohort >= population.size`` every round's
    cohort is the whole population in id order and the planned schedule
    equals ``plan_schedule`` on ``population.system_params(arange(size))``
    (the parity the population tests pin)."""
    ptrace = popn.get_population_trace(scenario, rounds, population.size,
                                       seed=scenario_seed)
    m_t = (ptrace.m_t if ptrace is not None
           else np.full(rounds, population.size, np.int64))
    C = int(min(cohort, population.size))
    if C < 1:
        raise ValueError(f"cohort must be >= 1, got {cohort}")
    ids = np.zeros((rounds, C), np.int64)
    csize = np.zeros(rounds, np.int64)
    for t in range(rounds):
        got = popn.sample_cohort(scenario_seed, t, m_t[t], C,
                                 stratified=stratified)
        csize[t] = got.size
        ids[t, :got.size] = got
        if got.size < C:
            ids[t, got.size:] = got[0]     # pads: real data, never selected
    sp, policy = engine.make_policy(
        framework, population.system_params(ids[0]), cfg, seed=policy_seed,
        K=K, E=E, e_initial=e_initial,
        n_samples_per_client=n_samples_per_client, quant=quant)
    fold_offload = framework == "oranfed"  # make_policy folded Q_S into Q_C
    pos = np.arange(C)
    steps0 = allocation.bisect_steps
    a_l, b_l, e_l = [], [], []
    q_c_all = np.zeros((rounds, C))
    q_s_all = np.zeros((rounds, C))
    gain_all = np.zeros((rounds, C))
    for t in range(rounds):
        r = population.rows(ids[t])
        ch = ptrace.channels(t, ids[t]) if ptrace is not None else None
        q_c = r["Q_C"] * (ch["qc_scale"] if ch is not None else 1.0)
        q_s = r["Q_S"] * (ch["qs_scale"] if ch is not None else 1.0)
        if fold_offload:
            q_c, q_s = q_c + q_s, np.zeros_like(q_s)
        gain = r["G_m"] * (ch["gain"] if ch is not None else 1.0)
        pad_live = (pos < csize[t]).astype(np.float64)
        # the policies read sp's arrays on every step(); S_m / omega /
        # d_model_bits are cohort-invariant under every derivation, so only
        # the per-client rows are rewritten round to round
        sp.Q_C, sp.Q_S, sp.G_m = q_c, q_s, gain
        sp.t_round = r["t_round"] * (ch["deadline_scale"] if ch is not None
                                     else 1.0)
        sp.avail = (ch["avail"] if ch is not None else 1.0) * pad_live
        a, b, e = policy.step()
        if ch is not None:
            a_real = a * ch["drop"]
            if a_real.sum() == 0 and a.sum() > 0:   # never stall
                a_real = np.zeros_like(a)
                a_real[np.argmax(a > 0)] = 1.0
            a = a_real
        a_l.append(a), b_l.append(b), e_l.append(e)
        q_c_all[t], q_s_all[t], gain_all[t] = q_c, q_s, gain
    _count_bisect_steps(steps0)
    sched = PopulationSchedule(
        ids=ids, a=np.stack(a_l), b=np.stack(b_l),
        E=np.asarray(e_l, np.int32), m_t=np.asarray(m_t, np.int64),
        cohort_sizes=csize,
        rows={"q_c": q_c_all, "q_s": q_s_all, "gain": gain_all},
        trace=ptrace)
    return sp, sched


def run_population_campaign(framework: str, cfg: DNNConfig,
                            population: popn.Population, data, *,
                            rounds: int, seeds: Sequence[int], cohort: int,
                            samples_per_client: int = 64, test_data=None,
                            K: int = 10, E: int = 10, e_initial: int = 20,
                            policy_seed: Optional[int] = None,
                            eval_every: Optional[int] = None,
                            eval_gamma: float = 1e-3,
                            strict_transfers: bool = False, policy=None,
                            quant=None, scenario=None,
                            scenario_seed: int = 0,
                            stratified: bool = False, guards=None,
                            checkpoint_every: Optional[int] = None,
                            checkpoint_dir=None, resume: bool = False,
                            _checkpoint_hook=None, **hyper
                            ) -> CampaignResult:
    """The scanned campaign over a ``Population`` — O(cohort) in memory.

    ``data`` is the raw ``(X, y)`` sample pool; each round's cohort draws
    its clients' lazy shards from it (``Population.sample_shards``), and
    the stacked per-round cohort data become scan operands — the runner
    holds O(rounds × cohort × samples) host bytes and O(cohort) device
    bytes, NEVER O(population).  Everything else is ``run_campaign``'s
    segment path: one compiled scan per (E-bucket, length-bucket), kept
    in the same process cache (``_segment_exec``), one host transfer
    (``strict_transfers`` enforceable), fused eval behind ``do_eval``,
    CommQuant wire formats, ``RoundGuards``, and checkpoint/resume with
    the cohort plan hashed into the schedule fingerprint.  Fault-injection
    scenarios are materialized-only (population traces carry no fault
    channels, so ``scenario="faults:p"`` is rejected by the trace
    registry).

    SplitMe's fused/post-hoc evaluation needs client data for the Step-4
    Gram sums; population campaigns use the FINAL round's cohort shards —
    with ``cohort >= population.size`` that is the full materialized
    dataset, keeping the parity contract exact."""
    with spans.span("run_population_campaign", framework=framework,
                    rounds=rounds, seeds=len(seeds)):
        spans.count("campaigns")
        X = np.asarray(data[0])
        y = np.asarray(data[1])
        if policy_seed is None:
            policy_seed = min(seeds)
        with spans.span("plan_schedule", rounds=rounds):
            sp, sched = plan_population_schedule(
                framework, population, cfg, rounds, cohort=cohort,
                policy_seed=policy_seed, K=K, E=E, e_initial=e_initial,
                n_samples_per_client=samples_per_client, quant=quant,
                scenario=scenario, scenario_seed=scenario_seed,
                stratified=stratified)
        spec_kw = dict(hyper, masked_loss_metric=True)
        spec = engine.make_spec(framework, cfg, policy=policy, quant=quant,
                                **spec_kw)
        comm = np.atleast_1d(np.asarray(
            spec.comm_model(sched.a, sched.E, sp), np.float64))
        nsel = sched.a.sum(axis=1).astype(int)
        sim, cost, energy = schedule_metrics(sched.a, sched.b, sched.E, sp,
                                             rows=sched.rows)

        # per-round cohort shards, drawn lazily for the sampled ids only
        alpha = "population"
        if sched.trace is not None and sched.trace.data_alpha is not None:
            alpha = sched.trace.data_alpha
        C = sched.ids.shape[1]
        xc_all = np.zeros((rounds, C, samples_per_client, X.shape[1]),
                          np.float32)
        yc_all = np.zeros((rounds, C, samples_per_client), np.int32)
        for t in range(rounds):
            sh = population.sample_shards(X, y, sched.ids[t],
                                          samples_per_client, alpha=alpha)
            xc_all[t], yc_all[t] = sh["x"], sh["y"]

        guards = _resolve_guards(guards, sched.trace)
        _check_checkpoint_args(checkpoint_every, checkpoint_dir, resume,
                               strict_transfers)
        do_eval = _eval_mask(rounds, test_data, eval_every)
        ckpt = _checkpoint_plan(framework, seeds, sched, do_eval, spec,
                                checkpoint_every, checkpoint_dir, resume,
                                _checkpoint_hook,
                                extra=(sched.ids, sched.m_t))
        params, host = _fetched(strict_transfers, lambda: _run_rounds_scan(
            spec, cfg, sp, sched,
            _scan_data(jnp.asarray(xc_all[-1]), jnp.asarray(yc_all[-1]),
                       test_data), seeds, do_eval, eval_gamma, None,
            spec_kw=spec_kw, guards=guards, ckpt=ckpt,
            cohorts=(xc_all, yc_all)))
        return _campaign_result(framework, seeds, sched, params, host,
                                (comm, nsel, sim, cost, energy), test_data,
                                guards)


def run_config_sweep(framework: str, cfg: DNNConfig,
                     system_params: Sequence[SystemParams],
                     client_data, *, rounds: int, seeds: Sequence[int],
                     test_data=None, vmap_configs: bool = True,
                     K: int = 10, E: int = 10, e_initial: int = 20,
                     policy_seed: Optional[int] = None,
                     eval_gamma: float = 1e-3,
                     eval_every: Optional[int] = None, mesh=None,
                     strict_transfers: bool = False, policy=None,
                     quant=None, scenario: scen.ScenarioLike = None,
                     scenario_seed: int = 0, **hyper) -> List[CampaignResult]:
    """Multi-config campaign over SystemParams variants.

    With ``vmap_configs=True`` (default) every variant's schedule shares
    one (rounds, M) shape, so ALL (variant, seed) pairs train through one
    compiled scan-over-rounds: full-M masked rounds (exact — masked updates
    are no-ops), E_max = the sweep-wide maximum, schedules stacked as scan
    operands, evaluation fused behind the ``do_eval`` mask (final round +
    every ``eval_every`` rounds), and a single host transfer for the entire
    sweep.  Set ``vmap_configs=False`` for the serial per-variant loop (one
    scanned campaign each); ``mesh`` (sharded rounds) is only available on
    that path — per-variant masks can't share one static client sharding."""
    if not vmap_configs:
        return [run_campaign(framework, cfg, sp, client_data, rounds=rounds,
                             seeds=seeds, test_data=test_data, K=K, E=E,
                             e_initial=e_initial, policy_seed=policy_seed,
                             eval_gamma=eval_gamma, eval_every=eval_every,
                             mesh=mesh, strict_transfers=strict_transfers,
                             policy=policy, quant=quant, scenario=scenario,
                             scenario_seed=scenario_seed, **hyper)
                for sp in system_params]
    if mesh is not None:
        raise ValueError("mesh (sharded rounds) requires vmap_configs=False")

    x = jnp.asarray(client_data["x"])
    y = jnp.asarray(client_data["y"])
    n_m = int(x.shape[1])
    if policy_seed is None:
        policy_seed = min(seeds)
    planned = [plan_schedule(framework, sp, cfg, rounds, K=K, E=E,
                             e_initial=e_initial, policy_seed=policy_seed,
                             n_samples_per_client=n_m, quant=quant,
                             scenario=scenario, scenario_seed=scenario_seed)
               for sp in system_params]
    for sp_d, _ in planned:
        if sp_d.M != x.shape[0]:
            raise ValueError(f"all SystemParams variants must have "
                             f"M={x.shape[0]} to share one schedule shape")
    sps = [sp_d for sp_d, _ in planned]
    scheds = [sch for _, sch in planned]
    for sch in scheds:
        if sch.trace is not None and sch.trace.has_faults():
            raise ValueError("fault-injection scenarios are not supported "
                             "by the vmapped config sweep; use "
                             "vmap_configs=False (per-variant campaigns)")
    V, S = len(planned), len(seeds)
    a_all = np.stack([sch.a for sch in scheds]).astype(np.float32)  # (V,R,M)
    e_all = np.stack([sch.E for sch in scheds]).astype(np.int32)    # (V,R)
    e_max = max(1, int(e_all.max()))

    spec = engine.make_spec(framework, cfg, masked_loss_metric=True,
                            policy=policy, quant=quant, **hyper)
    do_eval = _eval_mask(rounds, test_data, eval_every)

    def sweep(init_keys, key_arr, xs, data):
        # round and eval over the traced data (see _scan_data)
        raw = engine.build_round_fn(spec, cfg, e_max=e_max, jit=False)
        eval_fn = _fused_eval(spec, cfg, data, eval_gamma)
        params_s = jax.vmap(spec.init_fn)(init_keys)          # (S, …)
        params = jax.tree.map(
            lambda p: jnp.broadcast_to(p[None], (V,) + p.shape), params_s)
        # per-(variant, seed) error-feedback accumulator ((V, S, …) zeros)
        qstate = engine.init_quant_state(spec, params)

        def body(carry, xr):
            params, keys, qstate = carry          # keys (S, 2): the seed
            ks = jax.vmap(jax.random.split)(keys)  # chain is variant-free
            nkeys, subs = ks[:, 0], ks[:, 1]
            nparams, phase_losses, nqstate = jax.vmap(
                lambda pv, av, ev, qv: jax.vmap(
                    raw, in_axes=(0, None, None, None, None, 0, 0))(
                    pv, data["x"], data["y"], av, ev, subs, qv))(
                params, xr["a"], xr["e"], qstate)
            loss_row = jnp.stack(phase_losses, -1)        # (V, S, n_ph)
            if eval_fn is None:
                acc = jnp.full((V, S), jnp.nan, jnp.float32)
            else:
                acc = jax.lax.cond(
                    xr["do_eval"] > 0,
                    jax.vmap(jax.vmap(eval_fn)),
                    lambda p: jnp.full((V, S), jnp.nan, jnp.float32),
                    nparams)
            return (nparams, nkeys, nqstate), {"loss": loss_row, "acc": acc}

        (params, _, _), ys = jax.lax.scan(body, (params, key_arr, qstate),
                                          xs)
        return params, ys

    def run():
        init_keys = jnp.stack([jax.random.PRNGKey(s + spec.init_key_offset)
                               for s in seeds])
        key0 = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
        xs = {"a": a_all.transpose(1, 0, 2), "e": e_all.T,
              "do_eval": do_eval}
        return jax.jit(sweep)(init_keys, key0, xs,
                              _scan_data(x, y, test_data))

    params, host = _fetched(strict_transfers, run)   # ONE transfer

    results = []
    for v in range(V):
        losses = np.transpose(host["loss"][:, v], (1, 0, 2))  # (S, R, n_ph)
        acc_rounds = np.asarray(host["acc"][:, v])            # (R, S)
        comm, nsel, sim, cost, energy = _schedule_system_metrics(
            spec, scheds[v], sps[v])
        res = CampaignResult(
            framework=framework, seeds=tuple(seeds), schedule=scheds[v],
            params=jax.tree.map(lambda p: p[v], params), losses=losses,
            metrics=_make_metrics(
                sched=scheds[v], comm=comm, nsel=nsel, sim=sim, cost=cost,
                energy=energy, losses=losses,
                acc_rounds=acc_rounds if test_data is not None else None),
            accuracy_per_round=(acc_rounds if test_data is not None
                                else None))
        if test_data is not None:
            res.accuracy = acc_rounds[rounds - 1]
        results.append(res)
    return results
