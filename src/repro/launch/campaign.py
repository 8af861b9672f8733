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
cohort (engine ``gather`` mode) and scans exactly E_t local steps, skipping
unselected clients and the frozen scan tail entirely; the precomputed
A_t/b_t/E_t arrays become scan operands; and evaluation is fused into the
scanned round behind a per-round ``do_eval`` mask (``lax.cond``), so
training never leaves the device between rounds.  Rounds sharing a
(cohort-bucket, E-bucket) shape form contiguous scan segments that share
one compiled scan (segment lengths are bucketed too; padded rounds carry a
``live=0`` flag and are exact no-ops).  Trained parameters are numerically
identical to serial engine-trainer runs (tests/test_campaign.py).

Execution modes:

* ``scan=True`` (default) — the scanned campaign described above.
* ``scan=False`` — the legacy per-round python loop (one dispatch and,
  eventually, one host transfer per round); kept as the benchmark baseline.
* ``mesh=...`` — rounds run through ``engine.build_sharded_round_fn``:
  clients shard over the mesh ``data``/``pod`` axes and the masked-FedAvg
  psum is the round's only collective, while seeds stay vmapped and rounds
  stay scanned (scan-over-shard_map-over-vmap).

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
are cohort-shaped (the checkpoint carry stays O(cohort) too).  Sampling
the whole population as the cohort reproduces the materialized
``run_campaign`` exactly (test-pinned at 1e-5).
"""
from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.splitme_dnn import DNNConfig
from repro.core import engine, population as popn, scenario as scen
from repro.core.cost import SystemParams, schedule_metrics
from repro.core.engine import RoundMetrics

# Device→host transfer accounting: every metrics pull in this module goes
# through _host_fetch, so tests/benchmarks can count transfers per campaign
# (scanned: exactly 1; python loop: 1 per round).
HOST_TRANSFERS = 0


def _host_fetch(tree):
    """The single device→host transfer point for campaign metrics."""
    global HOST_TRANSFERS
    HOST_TRANSFERS += 1
    return jax.device_get(tree)


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
    return sp, RoundSchedule(a=np.stack(a_l), b=np.stack(b_l),
                             E=np.asarray(e_l, np.int32), trace=trace)


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
                 policy_seed: Optional[int] = None, scan: bool = True,
                 mesh=None, eval_every: Optional[int] = None,
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

    ``scan=True`` runs the whole campaign on-device (see module docstring):
    one host transfer for all per-round metrics, evaluation fused behind a
    ``do_eval`` mask on the final round (plus every ``eval_every`` rounds).
    ``scan=False`` is the legacy per-round python loop.  ``mesh`` switches
    the round bodies to the shard_map engine round (clients sharded over
    the mesh data axes).  ``strict_transfers=True`` wraps the device phase
    in ``jax.transfer_guard_device_to_host("disallow")``, turning any
    stray per-round pull into a hard error (used by the transfer-counting
    test).  ``policy`` (None / ``"reference"`` / ``"kernel"`` /
    ``"kernel_bf16"`` / a ``repro.kernels.dispatch.KernelPolicy``) selects
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
    sp, sched = plan_schedule(framework, sp, cfg, rounds, K=K, E=E,
                              e_initial=e_initial, policy_seed=policy_seed,
                              n_samples_per_client=n_m, quant=quant,
                              scenario=scenario, scenario_seed=scenario_seed)
    # masked_loss_metric: average losses over the executed steps only, so a
    # round's scan can be exactly E_t steps long.  Trained params are
    # identical to the serial trainers (masked updates are exact no-ops);
    # only SplitMe's *loss metric* differs from the seed quirk of averaging
    # over the full E_max scan.
    spec = engine.make_spec(framework, cfg, masked_loss_metric=True,
                            policy=policy, quant=quant, **hyper)
    comm, nsel, sim, cost, energy = _schedule_system_metrics(spec, sched, sp)

    trace = sched.trace
    has_faults = trace is not None and trace.has_faults()
    if guards is None and has_faults:
        guards = engine.RoundGuards()       # faults auto-arm the defaults
    elif guards is False or guards is None:
        guards = None
    if checkpoint_every or checkpoint_dir or resume:
        if not (checkpoint_every and checkpoint_dir is not None):
            raise ValueError("checkpointing needs BOTH checkpoint_every "
                             "and checkpoint_dir (resume implies both)")
        if not scan:
            raise ValueError("checkpoint/resume requires scan=True (the "
                             "python loop has no segment boundaries)")
        if strict_transfers:
            raise ValueError("checkpoint_every is incompatible with "
                             "strict_transfers: each segment save is an "
                             "explicit device→host pull")

    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        csh = NamedSharding(mesh, P(engine.client_axes(mesh)))
        x, y = jax.device_put(x, csh), jax.device_put(y, csh)

    if not scan:
        if mesh is not None:
            raise ValueError("mesh (sharded rounds) requires scan=True")
        if eval_every:
            raise ValueError("eval_every (fused per-round eval) requires "
                             "scan=True; the python loop only evaluates "
                             "post-hoc")
        if has_faults or guards is not None:
            raise ValueError("fault injection / RoundGuards require "
                             "scan=True (the guards live inside the scan)")
        losses, params = _run_rounds_loop(spec, cfg, sp, sched, x, y, seeds)
        result = CampaignResult(
            framework=framework, seeds=tuple(seeds), schedule=sched,
            params=params, losses=losses,
            metrics=_make_metrics(sched, comm, nsel, sim, cost, energy,
                                  losses, None))
        if test_data is not None:
            result.accuracy = evaluate_campaign(
                result, cfg, test_data, client_data=client_data,
                gamma=eval_gamma, policy=spec.policy)
        return result

    do_eval = np.zeros(rounds, np.float32)
    if test_data is not None:
        if eval_every:
            do_eval[eval_every - 1::eval_every] = 1.0
        do_eval[rounds - 1] = 1.0

    ckpt = None
    if checkpoint_every:
        from repro.launch import resilience
        fp = resilience.schedule_fingerprint(
            framework, seeds, sched, do_eval=do_eval,
            quant_mode=spec.quant.mode, checkpoint_every=checkpoint_every)
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
        ckpt = {"dir": checkpoint_dir, "every": int(checkpoint_every),
                "fingerprint": fp, "resume_from": resume_from,
                "hook": _checkpoint_hook, "framework": framework,
                "n_seeds": len(seeds)}

    guard = (jax.transfer_guard_device_to_host("disallow")
             if strict_transfers else contextlib.nullcontext())
    with guard:
        params, buffers = _run_rounds_scan(
            spec, cfg, sp, sched, _scan_data(x, y, test_data), seeds,
            do_eval, eval_gamma, mesh, guards=guards, ckpt=ckpt)
    host = _host_fetch(buffers)            # THE per-campaign transfer

    live = host["live"] > 0
    losses = np.transpose(host["loss"][live], (1, 0, 2))   # (S, R, n_ph)
    acc_rounds = np.asarray(host["acc"][live])             # (R, S)
    skipped = quorum = crashed = None
    if guards is not None:
        skipped = np.asarray(host["skipped"][live])        # (R, S)
        quorum = np.asarray(host["quorum"][live])          # (R, S)
    if trace is not None and trace.crash is not None:
        crashed = (np.asarray(trace.crash[:rounds]) > 0).astype(np.float64)
    result = CampaignResult(
        framework=framework, seeds=tuple(seeds), schedule=sched,
        params=params, losses=losses,
        metrics=_make_metrics(sched, comm, nsel, sim, cost, energy, losses,
                              acc_rounds if test_data is not None else None,
                              skipped=skipped, quorum=quorum,
                              crashed=crashed),
        accuracy_per_round=acc_rounds if test_data is not None else None,
        skipped_per_round=skipped, quorum_per_round=quorum,
        crashed_per_round=crashed)
    if test_data is not None:
        result.accuracy = acc_rounds[rounds - 1]
    return result


def _run_rounds_loop(spec, cfg, sp, sched, x, y, seeds):
    """Legacy per-round python loop (the PR-1 hot path, kept as benchmark
    baseline): one dispatch per round, one host transfer per round when the
    loss rows are pulled."""
    rounds = sched.rounds
    counts = sched.a.sum(axis=1).astype(int)
    size_of = _bucket_cohorts(counts, sp.M)
    e_of = _bucket_cohorts(sched.E, int(sp.E_max))
    fns: Dict[Tuple[int, int], Any] = {}

    def round_exec(k_bucket: int, e_bucket: int):
        if (k_bucket, e_bucket) not in fns:
            raw = engine.build_round_fn(spec, cfg, x, y,
                                        e_max=max(1, e_bucket),
                                        jit=False, gather=True)
            fns[k_bucket, e_bucket] = jax.jit(
                jax.vmap(raw, in_axes=(0, None, None, None, 0, 0)),
                donate_argnums=(0, 5))
        return fns[k_bucket, e_bucket]

    init_keys = jnp.stack([jax.random.PRNGKey(s + spec.init_key_offset)
                           for s in seeds])
    key_arr = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
    params = jax.vmap(spec.init_fn)(init_keys)
    # per-seed error-feedback accumulator (zeros_like on the seed-stacked
    # params gives the stacked state directly; () when stateless)
    qstate = engine.init_quant_state(spec, params)
    loss_rows = []
    for r in range(rounds):
        k_r, e_r = int(counts[r]), int(sched.E[r])
        kb = size_of[k_r]
        idx = np.zeros(kb, np.int32)
        mask = np.zeros(kb, np.float32)
        idx[:k_r] = np.nonzero(sched.a[r])[0]   # pads index client 0 and
        mask[:k_r] = 1.0                        # carry mask weight 0
        # per-seed key chains advance exactly like the serial trainers
        ks = jax.vmap(jax.random.split)(key_arr)
        key_arr, subs = ks[:, 0], ks[:, 1]
        params, loss_r, qstate = round_exec(kb, e_of[e_r])(
            params, jnp.asarray(idx), jnp.asarray(mask), jnp.asarray(e_r),
            subs, qstate)
        loss_rows.append(loss_r)

    losses = np.stack(
        [np.stack(_host_fetch(row), axis=-1) for row in loss_rows],
        axis=1)                                   # (S, R, n_phases)
    return losses, params


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


def _run_rounds_scan(spec, cfg, sp, sched, data, seeds, do_eval, eval_gamma,
                     mesh, guards=None, ckpt=None):
    """Scan all rounds on-device; returns (params, device metric buffers).

    The buffers carry everything that EXISTS on the device — per-round
    per-seed losses and fused-eval accuracies (plus the live mask; under
    guards also the per-seed skipped/quorum flags); the remaining
    per-round metrics (comm_bits, selected-count, latency, cost) are
    schedule constants already precomputed host-side by
    ``_schedule_system_metrics`` and never touch the device.

    Rounds sharing a (cohort-bucket, E-bucket) shape form contiguous scan
    segments; segment lengths are bucketed as well, padded with ``live=0``
    no-op rounds, so the number of compiled scans is bounded even for
    adaptive-E / varying-cohort schedules.

    ``guards`` (engine.RoundGuards) and the schedule trace's fault
    channels arm the robust scan body: poison/wire-corruption rows become
    extra scan operands feeding the round's fault injection, a crash round
    holds params/qstate (clients still advance their RNG — they trained;
    the server lost the aggregate), and the round's guard flags land in
    the buffers.  ``ckpt`` (dict from ``run_campaign``: dir / every /
    fingerprint / resume_from / hook) splits segments at checkpoint
    boundaries, persists the carry after each boundary via
    ``repro.launch.resilience`` and, on resume, restores it and skips the
    completed segments."""
    rounds = sched.rounds
    n_seeds = len(seeds)
    counts = sched.a.sum(axis=1).astype(int)
    e_of = _bucket_cohorts(sched.E, int(sp.E_max))
    if mesh is None:
        size_of = _bucket_cohorts(counts, sp.M)
        kb_r = [size_of[int(c)] for c in counts]
    else:
        kb_r = [int(sp.M)] * rounds       # sharded rounds train the full
        # masked M axis (a gather would break the static client sharding)
    eb_r = [e_of[int(e)] for e in sched.E]
    segs = _split_at_checkpoints(_plan_segments(kb_r, eb_r),
                                 ckpt["every"] if ckpt else None)
    len_of = _bucket_cohorts([l for *_ , l in segs],
                             max(l for *_, l in segs))

    trace = sched.trace
    poison = trace.poison if trace is not None else None
    wire = trace.wire_gain if trace is not None else None
    crash = trace.crash if trace is not None else None
    with_faults = poison is not None or wire is not None
    has_crash = crash is not None and bool(np.any(np.asarray(crash) > 0))
    robust = guards is not None or with_faults or has_crash
    M = int(sp.M)
    p_arr = (np.zeros((rounds, M), np.float32) if poison is None
             else np.asarray(poison, np.float32))
    w_arr = (np.ones((rounds, M), np.float32) if wire is None
             else np.asarray(wire, np.float32))

    n_ph = len(spec.phases)
    fns: Dict[Tuple[int, int, int], Any] = {}

    def round_caller(eb: int, x, y):
        if mesh is None:
            raw = engine.build_round_fn(spec, cfg, x, y, e_max=max(1, eb),
                                        jit=False, gather=True,
                                        guards=guards,
                                        with_faults=with_faults)

            def call_round(params, xr, subs, qstate):
                if not with_faults:
                    return jax.vmap(
                        raw, in_axes=(0, None, None, None, 0, 0))(
                        params, xr["idx"], xr["mask"], xr["e"], subs,
                        qstate)
                faults = {"poison": xr["poison"], "wire_gain": xr["wire"]}
                return jax.vmap(
                    raw, in_axes=(0, None, None, None, 0, 0, None))(
                    params, xr["idx"], xr["mask"], xr["e"], subs, qstate,
                    faults)
        else:
            raw = engine.build_sharded_round_fn(
                spec, cfg, mesh, n_clients=M, e_max=max(1, eb),
                jit=False, guards=guards, with_faults=with_faults)

            def call_round(params, xr, subs, qstate):
                if not with_faults:
                    return jax.vmap(
                        raw, in_axes=(0, None, None, None, None, 0, 0))(
                        params, x, y, xr["mask"], xr["e"], subs, qstate)
                faults = {"poison": xr["poison"], "wire_gain": xr["wire"]}
                return jax.vmap(
                    raw, in_axes=(0, None, None, None, None, 0, 0, None))(
                    params, x, y, xr["mask"], xr["e"], subs, qstate,
                    faults)
        return call_round

    def seg_exec(kb: int, eb: int, lb: int):
        if (kb, eb, lb) in fns:
            return fns[kb, eb, lb]
        fns[kb, eb, lb] = jax.jit(functools.partial(seg, eb),
                                  donate_argnums=(0, 1, 2))
        return fns[kb, eb, lb]

    def seg(eb, params, key_arr, qstate, xs, data):
        # the round and eval close over the traced data, never over
        # device arrays (see _scan_data)
        call_round = round_caller(eb, data["x"], data["y"])
        eval_fn = _fused_eval(spec, cfg, data, eval_gamma, mesh)
        nan_row = jnp.full((n_seeds,), jnp.nan, jnp.float32)

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

    init_keys = jnp.stack([jax.random.PRNGKey(s + spec.init_key_offset)
                           for s in seeds])
    key_arr = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
    params = jax.vmap(spec.init_fn)(init_keys)
    qstate = _init_qstate(spec, params, mesh)
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
        xs = {
            "e": np.zeros(lb, np.int32),
            "live": np.zeros(lb, np.float32),
            "do_eval": np.zeros(lb, np.float32),
        }
        xs["e"][:length] = sched.E[start:start + length]
        xs["live"][:length] = 1.0
        xs["do_eval"][:length] = do_eval[start:start + length]
        if robust:
            xs["crash"] = np.zeros(lb, np.float32)
            if has_crash:
                xs["crash"][:length] = crash[start:start + length]
        if mesh is None:
            idx = np.zeros((lb, kb), np.int32)
            mask = np.zeros((lb, kb), np.float32)
            for i, r in enumerate(range(start, start + length)):
                k_r = int(counts[r])
                idx[i, :k_r] = np.nonzero(sched.a[r])[0]  # pads: client 0,
                mask[i, :k_r] = 1.0                       # mask weight 0
            xs["idx"], xs["mask"] = idx, mask
            if with_faults:
                # gather the fault channels by the same cohort index;
                # pads stay neutral (poison 0, gain 1 — and carry mask 0)
                pz = np.zeros((lb, kb), np.float32)
                wg = np.ones((lb, kb), np.float32)
                for i, r in enumerate(range(start, start + length)):
                    k_r = int(counts[r])
                    pz[i, :k_r] = p_arr[r, idx[i, :k_r]]
                    wg[i, :k_r] = w_arr[r, idx[i, :k_r]]
                xs["poison"], xs["wire"] = pz, wg
        else:
            mask = np.zeros((lb, M), np.float32)
            mask[:length] = sched.a[start:start + length]
            xs["mask"] = mask
            if with_faults:
                pz = np.zeros((lb, M), np.float32)
                wg = np.ones((lb, M), np.float32)
                pz[:length] = p_arr[start:start + length]
                wg[:length] = w_arr[start:start + length]
                xs["poison"], xs["wire"] = pz, wg
        (params, key_arr, qstate), ys = seg_exec(kb, eb, lb)(
            params, key_arr, qstate, xs, data)
        ys_all.append(ys)
        end = start + length
        if ckpt is not None and (end % ckpt["every"] == 0 or end == rounds):
            from repro.launch import resilience
            done = {k: (jnp.concatenate([ys[k] for ys in ys_all], axis=0)
                        if len(ys_all) > 1 else ys_all[0][k])
                    for k in ys_all[0]}
            resilience.save_checkpoint(
                ckpt["dir"], end,
                {"params": params, "keys": key_arr, "qstate": qstate},
                done, fingerprint=ckpt["fingerprint"], rounds=rounds,
                framework=ckpt["framework"], n_seeds=ckpt["n_seeds"])
            if ckpt["hook"] is not None:
                ckpt["hook"](end)

    buffers = {k: (jnp.concatenate([ys[k] for ys in ys_all], axis=0)
                   if len(ys_all) > 1 else ys_all[0][k])
               for k in ys_all[0]}
    return params, buffers


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
    bytes, NEVER O(population).  Everything else matches ``run_campaign``:
    one compiled scan per (E-bucket, length-bucket), one host transfer
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
    X = np.asarray(data[0])
    y = np.asarray(data[1])
    if policy_seed is None:
        policy_seed = min(seeds)
    sp, sched = plan_population_schedule(
        framework, population, cfg, rounds, cohort=cohort,
        policy_seed=policy_seed, K=K, E=E, e_initial=e_initial,
        n_samples_per_client=samples_per_client, quant=quant,
        scenario=scenario, scenario_seed=scenario_seed,
        stratified=stratified)
    spec = engine.make_spec(framework, cfg, masked_loss_metric=True,
                            policy=policy, quant=quant, **hyper)
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

    if guards is False:
        guards = None
    if checkpoint_every or checkpoint_dir or resume:
        if not (checkpoint_every and checkpoint_dir is not None):
            raise ValueError("checkpointing needs BOTH checkpoint_every "
                             "and checkpoint_dir (resume implies both)")
        if strict_transfers:
            raise ValueError("checkpoint_every is incompatible with "
                             "strict_transfers: each segment save is an "
                             "explicit device→host pull")

    do_eval = np.zeros(rounds, np.float32)
    if test_data is not None:
        if eval_every:
            do_eval[eval_every - 1::eval_every] = 1.0
        do_eval[rounds - 1] = 1.0

    ckpt = None
    if checkpoint_every:
        from repro.launch import resilience
        fp = resilience.schedule_fingerprint(
            framework, seeds, sched, do_eval=do_eval,
            quant_mode=spec.quant.mode, checkpoint_every=checkpoint_every,
            extra=(sched.ids, sched.m_t))
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
        ckpt = {"dir": checkpoint_dir, "every": int(checkpoint_every),
                "fingerprint": fp, "resume_from": resume_from,
                "hook": _checkpoint_hook, "framework": framework,
                "n_seeds": len(seeds)}

    guard = (jax.transfer_guard_device_to_host("disallow")
             if strict_transfers else contextlib.nullcontext())
    with guard:
        params, buffers = _run_population_scan(
            spec, cfg, sp, sched, xc_all, yc_all, seeds, do_eval,
            _scan_data(jnp.asarray(xc_all[-1]), jnp.asarray(yc_all[-1]),
                       test_data), eval_gamma, guards=guards, ckpt=ckpt)
    host = _host_fetch(buffers)            # THE per-campaign transfer

    live = host["live"] > 0
    losses = np.transpose(host["loss"][live], (1, 0, 2))   # (S, R, n_ph)
    acc_rounds = np.asarray(host["acc"][live])             # (R, S)
    skipped = quorum = None
    if guards is not None:
        skipped = np.asarray(host["skipped"][live])
        quorum = np.asarray(host["quorum"][live])
    result = CampaignResult(
        framework=framework, seeds=tuple(seeds), schedule=sched,
        params=params, losses=losses,
        metrics=_make_metrics(sched, comm, nsel, sim, cost, energy, losses,
                              acc_rounds if test_data is not None else None,
                              skipped=skipped, quorum=quorum),
        accuracy_per_round=acc_rounds if test_data is not None else None,
        skipped_per_round=skipped, quorum_per_round=quorum)
    if test_data is not None:
        result.accuracy = acc_rounds[rounds - 1]
    return result


def _run_population_scan(spec, cfg, sp, sched: PopulationSchedule, xc_all,
                         yc_all, seeds, do_eval, data, eval_gamma,
                         guards=None, ckpt=None):
    """Scan all rounds of a population campaign on-device.

    The structure mirrors ``_run_rounds_scan`` with one inversion: instead
    of gathering cohorts out of a fixed closed-over dataset, the per-round
    cohort DATA are scan operands (``xc``/``yc``) feeding
    ``engine.build_cohort_round_fn`` — the device never holds more than
    one segment's cohorts.  The cohort width C is constant, so segments
    split only on (E-bucket, length-bucket) and checkpoint boundaries;
    the carry ({params, keys, qstate}) is population-size-free and
    persists/restores through the same resilience layer."""
    rounds = sched.rounds
    n_seeds = len(seeds)
    C = int(sched.ids.shape[1])
    e_of = _bucket_cohorts(sched.E, int(sp.E_max))
    eb_r = [e_of[int(e)] for e in sched.E]
    segs = _split_at_checkpoints(_plan_segments([C] * rounds, eb_r),
                                 ckpt["every"] if ckpt else None)
    len_of = _bucket_cohorts([l for *_, l in segs],
                             max(l for *_, l in segs))
    n_ph = len(spec.phases)
    fns: Dict[Tuple[int, int], Any] = {}

    def seg_exec(eb: int, lb: int):
        if (eb, lb) in fns:
            return fns[eb, lb]
        raw = engine.build_cohort_round_fn(spec, cfg, e_max=max(1, eb),
                                           jit=False, guards=guards)
        fns[eb, lb] = jax.jit(functools.partial(seg, raw),
                              donate_argnums=(0, 1, 2))
        return fns[eb, lb]

    def seg(raw, params, key_arr, qstate, xs, data):
        # the SplitMe eval's client data and the test set arrive traced
        # (see _scan_data)
        eval_fn = _fused_eval(spec, cfg, data, eval_gamma)
        nan_row = jnp.full((n_seeds,), jnp.nan, jnp.float32)

        def body(carry, xr):
            params, keys, qstate = carry
            ks = jax.vmap(jax.random.split)(keys)
            nkeys, subs = ks[:, 0], ks[:, 1]
            out = jax.vmap(raw, in_axes=(0, None, None, None, None, 0, 0))(
                params, xr["xc"], xr["yc"], xr["mask"], xr["e"], subs,
                qstate)
            if guards is not None:
                nparams, phase_losses, nqstate, flags = out
            else:
                nparams, phase_losses, nqstate = out
                flags = None
            live = xr["live"] > 0
            params = jax.tree.map(lambda n, o: jnp.where(live, n, o),
                                  nparams, params)
            qstate = jax.tree.map(lambda n, o: jnp.where(live, n, o),
                                  nqstate, qstate)
            keys = jnp.where(live, nkeys, keys)
            loss_row = jnp.where(live, jnp.stack(phase_losses, -1), jnp.nan)
            if eval_fn is None:
                acc = nan_row
            else:
                acc = jax.lax.cond(
                    jnp.logical_and(xr["do_eval"] > 0, live),
                    jax.vmap(eval_fn), lambda p: nan_row, params)
            ys = {"loss": loss_row, "acc": acc, "live": xr["live"]}
            if guards is not None:
                ys["skipped"] = jnp.where(live, flags["skipped"], 0.0)
                ys["quorum"] = jnp.where(live, flags["quorum"], 0.0)
            return (params, keys, qstate), ys

        return jax.lax.scan(body, (params, key_arr, qstate), xs)

    init_keys = jnp.stack([jax.random.PRNGKey(s + spec.init_key_offset)
                           for s in seeds])
    key_arr = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
    params = jax.vmap(spec.init_fn)(init_keys)
    qstate = _init_qstate(spec, params)
    ys_all = []
    start_round = 0
    if ckpt is not None and ckpt["resume_from"] is not None:
        from repro.checkpoint import io
        path = ckpt["resume_from"]
        like = {"params": params, "keys": key_arr, "qstate": qstate}
        state = io.restore(path, like)
        params, key_arr, qstate = \
            state["params"], state["keys"], state["qstate"]
        buf = io.load_arrays(Path(path).with_name(Path(path).name
                                                  + "-buffers"))
        ys_all.append({k: jnp.asarray(v) for k, v in buf.items()})
        start_round = int(io.manifest(path)["metadata"]["round_cursor"])
    n_samples = xc_all.shape[2]
    for _, eb, start, length in segs:
        if start + length <= start_round:
            continue                       # restored from the checkpoint
        lb = len_of[length]
        xs = {
            "e": np.zeros(lb, np.int32),
            "live": np.zeros(lb, np.float32),
            "do_eval": np.zeros(lb, np.float32),
            "mask": np.zeros((lb, C), np.float32),
            "xc": np.zeros((lb, C, n_samples, xc_all.shape[3]), np.float32),
            "yc": np.zeros((lb, C, n_samples), np.int32),
        }
        end = start + length
        xs["e"][:length] = sched.E[start:end]
        xs["live"][:length] = 1.0
        xs["do_eval"][:length] = do_eval[start:end]
        xs["mask"][:length] = sched.a[start:end]
        xs["xc"][:length] = xc_all[start:end]
        xs["yc"][:length] = yc_all[start:end]
        (params, key_arr, qstate), ys = seg_exec(eb, lb)(
            params, key_arr, qstate, xs, data)
        ys_all.append(ys)
        if ckpt is not None and (end % ckpt["every"] == 0 or end == rounds):
            from repro.launch import resilience
            done = {k: (jnp.concatenate([ys[k] for ys in ys_all], axis=0)
                        if len(ys_all) > 1 else ys_all[0][k])
                    for k in ys_all[0]}
            resilience.save_checkpoint(
                ckpt["dir"], end,
                {"params": params, "keys": key_arr, "qstate": qstate},
                done, fingerprint=ckpt["fingerprint"], rounds=rounds,
                framework=ckpt["framework"], n_seeds=ckpt["n_seeds"])
            if ckpt["hook"] is not None:
                ckpt["hook"](end)

    buffers = {k: (jnp.concatenate([ys[k] for ys in ys_all], axis=0)
                   if len(ys_all) > 1 else ys_all[0][k])
               for k in ys_all[0]}
    return params, buffers


def evaluate_campaign(result: CampaignResult, cfg: DNNConfig, test_data,
                      client_data=None, gamma: float = 1e-3,
                      policy=None) -> np.ndarray:
    """Per-seed test accuracy of a finished campaign (post-hoc; the scanned
    campaign fuses the same jitted evaluation into its round scan).

    Full-model frameworks evaluate the aggregated MLP directly; SplitMe
    first recovers each seed's server model via the one-shot analytic
    inversion (Step 4), which needs the client data for the Gram sums.
    Both paths are the engine's jitted ``build_eval_fn``, vmapped over the
    seed axis; ``policy`` selects kernels/precision for them."""
    spec = engine.make_spec(result.framework, cfg, policy=policy)
    if result.framework == "splitme" and client_data is None:
        raise ValueError("splitme evaluation needs client_data for Step 4")
    eval_fn = engine.build_eval_fn(
        spec, cfg, *test_data, gamma=gamma, jit=False,
        client_data=client_data if result.framework == "splitme" else None)
    acc = _host_fetch(jax.jit(jax.vmap(eval_fn))(result.params))
    return np.asarray(acc, dtype=np.float64)


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
    do_eval = np.zeros(rounds, np.float32)
    if test_data is not None:
        if eval_every:
            do_eval[eval_every - 1::eval_every] = 1.0
        do_eval[rounds - 1] = 1.0

    def sweep(init_keys, key_arr, xs, data):
        # round and eval over the traced data (see _scan_data)
        raw = engine.build_round_fn(spec, cfg, data["x"], data["y"],
                                    e_max=e_max, jit=False, gather=False)
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
                    raw, in_axes=(0, None, None, 0, 0))(
                    pv, av, ev, subs, qv))(params, xr["a"], xr["e"], qstate)
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

    guard = (jax.transfer_guard_device_to_host("disallow")
             if strict_transfers else contextlib.nullcontext())
    with guard:
        init_keys = jnp.stack([jax.random.PRNGKey(s + spec.init_key_offset)
                               for s in seeds])
        key0 = jnp.stack([jax.random.PRNGKey(s) for s in seeds])
        xs = {"a": a_all.transpose(1, 0, 2), "e": e_all.T,
              "do_eval": do_eval}
        params, ys = jax.jit(sweep)(init_keys, key0, xs,
                                    _scan_data(x, y, test_data))
    host = _host_fetch(ys)                 # ONE transfer for the sweep

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
