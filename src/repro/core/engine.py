"""Unified federated round engine for the framework registry — the
paper's four §V frameworks plus the FedORA / EcoFL resource-allocation
baselines — single-device, sharded (shard_map), and scanned execution
from ONE round core.

A framework contributes only what actually differs, as a ``FrameworkSpec``:

* one or more ``PhaseSpec``s — a pure per-batch ``local_step`` loss plus how
  the phase's per-client inputs and targets derive from the round state
  (SplitMe is two coupled phases: the server phase's targets are the smashed
  activations of the client phase's *updated* per-client weights),
* a ``comm_model`` — bits on the wire per round (Fig. 3b/4b input).  Comm
  models are vectorized over a whole precomputed schedule: ``comm(a, E, sp)``
  accepts a single round ((M,), int) or a stacked schedule ((R, M), (R,)),
  so campaign metrics never do per-round host arithmetic,
* a host-side selection/allocation ``Policy`` (Alg. 1 / P2 / fixed-K).

The engine owns the hot path once, for every execution mode:

* replication of the global parameters onto the vmapped client axis,
* the masked E_max-step local-SGD scan — E is a *traced* operand and the
  scan length is static, so adaptive local-update counts (SplitMe's P2)
  never trigger recompilation,
* masked FedAvg aggregation over the selected set A_t,
* per-phase loss metrics,
* ``donate_argnums`` on the carried parameters, so round k+1 reuses round
  k's parameter buffers instead of reallocating them,
* RNG pre-split once per round into per-phase × per-client keys before the
  vmapped scan (no per-step host splitting).

Execution modes over that core:

* ``build_round_fn`` — single-device jitted round (optionally ``gather``
  mode: train only a fixed-size selected cohort, numerically exact),
* ``build_sharded_round_fn`` — the same round under ``shard_map`` with the
  client axis sharded over the mesh ``data``/``pod`` axes.  Aggregation
  becomes per-shard masked partial sums + one cross-client ``psum`` — the
  paper's "one communication per round" as a real collective.  This is the
  production pattern ``repro.core.distributed`` used to hand-write for
  SplitMe only; that module is now a thin adapter over this builder,
* ``build_eval_fn`` — jitted, vmap-able test-set evaluation (full-model
  argmax accuracy, or SplitMe's Step-4 analytic inversion + stitched
  forward), fused into the scanned campaign via a per-round ``do_eval``
  mask so training never leaves the device between rounds.

Numerics are governed by a ``repro.kernels.dispatch.KernelPolicy`` bound
into the spec at ``make_spec(policy=...)`` time: the mutual-KL phase losses
and the Step-4 Gram products dispatch to the Pallas kernels per the policy
(auto: kernels on TPU, reference jnp elsewhere), and its ``Precision``
casts the forwards to bf16 activations with f32 accumulators/master params
— loss reductions and the masked aggregation stay f32.

The WIRE format of the aggregation is a second, independent knob: a
``repro.core.quantcomm.CommQuant`` bound at ``make_spec(quant=...)`` time
narrows the masked-FedAvg payload to bf16 or int8 (stochastic rounding +
f32 error feedback, threaded through the round functions as ``qstate``)
at the quantize-before-psum point, preserving the one-all-reduce-per-round
invariant; ``make_policy(quant=...)`` scales the derived SystemParams so
comm volume, latency, cost and deadline/energy selection all count the
quantized bits.

``make_policy`` also prepares a private copy of the caller's
``SystemParams`` — the seed trainers mutated the shared instance in place,
which silently corrupted sequential framework runs; the engine never writes
to the caller's object.

``repro.core.splitme`` and ``repro.core.baselines`` are thin adapters over
this engine; tests/test_engine_parity.py pins them to the seed trainers'
exact numerics and pins the sharded round to the single-device round at
1e-5.  ``repro.launch.campaign`` scans whole campaigns (all rounds, all
seeds, fused eval) through compiled round functions built here, with one
device→host metrics transfer per campaign.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.splitme_dnn import DNNConfig
from repro.core import dnn, quantcomm
from repro.core.allocation import solve_bandwidth, solve_p2
from repro.core.cost import SystemParams, uplink_time
from repro.core.inversion import invert_inverse_model
from repro.core.quantcomm import CommQuant
from repro.core.selection import (SelectionState, initial_state,
                                  select_trainers, update_state)
from repro.kernels import dispatch
from repro.kernels.dispatch import KernelPolicy

Params = Any                     # pytree of arrays
ParamsTuple = Tuple[Params, ...]

# fold_in salt deriving the quantization RNG stream from the round key
# WITHOUT advancing the per-client split chain (quant=none numerics stay
# byte-identical to the pre-quantcomm engine)
_QSALT = 0x5157


@dataclass(frozen=True)
class RoundGuards:
    """In-scan fault guards for a round (``repro.launch.resilience``).

    ``nonfinite``   — detect NaN/Inf in the aggregated update and ROLL THE
                      ROUND BACK (hold the previous params and EF qstate;
                      the round counts toward ``skipped_rounds``),
    ``min_clients`` — quorum: when the realized cohort |A_t| falls below
                      this, degrade to a hold-round instead of averaging
                      over a near-empty set (counts toward
                      ``quorum_rounds``),
    ``clip_norm``   — optional robust aggregation: clip each client's
                      update to this global L2 norm at the
                      quantize-before-psum point (bounds finite wire
                      corruption; NaN updates pass through to the
                      non-finite rollback).

    All three run INSIDE the compiled round, so guarded campaigns stay one
    compiled program with one host transfer."""
    nonfinite: bool = True
    min_clients: int = 1
    clip_norm: Optional[float] = None


@dataclass
class RoundMetrics:
    round: int
    n_selected: int
    E: int
    comm_bits: float          # uplink volume this round (all selected)
    sim_time: float           # eq. 18 latency (s)
    cost: float               # eq. 20
    energy: float = float("nan")   # EcoFL round energy (J), cost.round_energy
    # accuracy / losses may hold 0-d DEVICE arrays while a serial trainer
    # runs non-interactively (no per-round host sync); ``fetch_history``
    # resolves them to floats in one transfer at campaign end.
    accuracy: float = float("nan")
    client_loss: float = float("nan")
    server_loss: float = float("nan")
    # guarded-campaign accounting (0 everywhere when guards are off):
    # fraction of seeds whose round was rolled back on a non-finite
    # aggregate / held for quorum, and whether the round was a server-crash
    # injection — the bench summaries surface these so a guarded run is
    # never silently compared against an unguarded baseline.
    skipped: float = 0.0
    quorum_held: float = 0.0
    crashed: float = 0.0


def fetch_history(history) -> list:
    """Resolve any buffered device-array metrics in a trainer's history to
    python floats with ONE device→host transfer (the serial trainers'
    async-metrics counterpart of the campaign runner's ``_host_fetch``)."""
    vals = jax.device_get([(m.client_loss, m.server_loss, m.accuracy)
                           for m in history])
    for m, (c, s, a) in zip(history, vals):
        m.client_loss, m.server_loss, m.accuracy = \
            float(c), float(s), float(a)
    return history


# ---------------------------------------------------------------------------
# Framework specs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class PhaseSpec:
    """One masked local-SGD phase of a round.

    ``loss_fn(w, x_batch, target_batch)`` is the pure per-batch local_step
    loss; ``data_key`` picks the per-client input array from the round
    context ({"x", "y", "y1"}); ``target_fn(params, updated, ctx)`` builds
    the (M, n, …) per-client targets, where ``updated`` maps param indices
    to the *per-client* (stacked) weights already trained by earlier phases
    this round.
    """
    name: str
    param_idx: int
    lr: float
    loss_fn: Callable[[Params, jax.Array, jax.Array], jax.Array]
    data_key: str
    target_fn: Callable[[ParamsTuple, Dict[int, Params], Dict[str, jax.Array]],
                        jax.Array]
    # False → mean loss over all E_max scan steps (the seed SplitMe metric);
    # True → mean over the executed (unmasked) steps only.
    loss_over_mask: bool = True


@dataclass(frozen=True)
class FrameworkSpec:
    name: str
    init_fn: Callable[[jax.Array], ParamsTuple]
    phases: Tuple[PhaseSpec, ...]
    comm_model: Callable[[np.ndarray, int, SystemParams], float]
    batch_size: int
    # PRNGKey(seed + offset) initializes the parameters (the seed baselines
    # used seed+1 for init and seed for the round chain).
    init_key_offset: int = 0
    # The RESOLVED kernel-dispatch/precision policy the phase losses were
    # built with (``make_spec`` binds it; the builders and ``build_eval_fn``
    # read it so one spec means one numerics everywhere).
    policy: Optional[KernelPolicy] = None
    # Wire format of the masked-FedAvg aggregation payload
    # (quantize-before-psum / dequantize-after inside the round core; the
    # comm models count the quantized bits via the make_policy-scaled
    # SystemParams).
    quant: CommQuant = quantcomm.NONE


# ---------------------------------------------------------------------------
# The engine: build one jitted round function from a spec
# ---------------------------------------------------------------------------

def client_axes(mesh) -> Tuple[str, ...]:
    """The mesh axes the client dimension shards over (shard_map rounds and
    the Step-4 distributed inversion agree on this)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def n_client_shards(mesh) -> int:
    """Number of client shards on `mesh` — the leading-axis length of the
    per-shard CommQuant error-feedback state (``init_quant_state``'s
    ``n_shards``), shared by every caller that sizes that state."""
    return int(np.prod([mesh.shape[a] for a in client_axes(mesh)]))


def replicate(params: Params, m: int) -> Params:
    """Broadcast global params onto the client axis (no copy until donated)."""
    return jax.tree.map(lambda p: jnp.broadcast_to(p, (m,) + p.shape), params)


def psum_bundle(tree, axis_names, wire_dtype=None):
    """psum a whole pytree as ONE all-reduce: ravel + concatenate the
    leaves, cross the mesh once, split back.  ``jax.lax.psum`` on a pytree
    emits one all-reduce per leaf and not every backend re-combines them;
    bundling makes "one communication per round" a structural property of
    the lowered HLO (fl_dryrun counts it).  Elementwise sums are unchanged,
    so this is numerically exact.

    ``wire_dtype`` narrows the wire format (the bf16 ``CommQuant`` mode):
    the bundled vector is rounded to that dtype before the all-reduce and
    widened back after — still exactly one collective.  (XLA's CPU passes
    promote narrow all-reduces back to f32 in the lowered HLO, so comm
    accounting counts ``CommQuant.wire_bits`` analytically rather than
    trusting the HLO byte widths; see ``repro.launch.fl_dryrun``.)"""
    flat, treedef = jax.tree.flatten(tree)
    sizes = [l.size for l in flat]
    vec = jnp.concatenate([l.ravel() for l in flat]) if len(flat) > 1 \
        else flat[0].ravel()
    if wire_dtype is not None:
        out_dtype = vec.dtype
        vec = jax.lax.psum(vec.astype(wire_dtype), axis_names) \
            .astype(out_dtype)
    else:
        vec = jax.lax.psum(vec, axis_names)
    parts = jnp.split(vec, list(np.cumsum(sizes[:-1])))
    return jax.tree.unflatten(
        treedef, [p.reshape(l.shape) for p, l in zip(parts, flat)])


def _phase_runner(phase: PhaseSpec, n: int, batch_size: int, e_max: int,
                  unroll: bool = False):
    """Per-client masked E_max-scan of SGD on the phase's local_step loss.

    ``unroll=True`` python-unrolls the step loop (the fl_dryrun collective
    accounting needs unrolled bodies so any per-step collectives appear
    E times in the lowered HLO)."""
    def run(w, data_m, target_m, e_steps, key_m):
        steps = jnp.arange(e_max)

        def step(carry, i):
            w, k = carry
            k, sk = jax.random.split(k)
            idx = jax.random.randint(sk, (batch_size,), 0, n)
            loss, g = jax.value_and_grad(phase.loss_fn)(
                w, data_m[idx], target_m[idx])
            do = (i < e_steps).astype(jnp.float32)
            w = jax.tree.map(lambda p, gg: p - phase.lr * do * gg, w, g)
            return (w, k), loss

        if unroll:
            carry, loss_l = (w, key_m), []
            for i in range(e_max):
                carry, l = step(carry, jnp.asarray(i))
                loss_l.append(l)
            w, losses = carry[0], jnp.stack(loss_l)
        else:
            (w, _), losses = jax.lax.scan(step, (w, key_m), steps)
        if phase.loss_over_mask:
            mask = (steps < e_steps).astype(jnp.float32)
            loss = jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        else:
            loss = jnp.mean(losses)
        return w, loss

    return run


def _round_core(spec: FrameworkSpec, runners, params: ParamsTuple, ctx_c,
                a_mask, e_steps, keys, qstate=(), qkey=None,
                axis_names: Optional[Tuple[str, ...]] = None,
                faults=None, guards: Optional[RoundGuards] = None):
    """One masked round over a client cohort (the full M axis, a gathered
    cohort, or one device's shard — ``axis_names`` turns the aggregation
    sums into cross-shard psums).

    ``spec.quant`` narrows the wire format of the aggregation payload at
    the point where it would cross the mesh: int8 stochastically rounds
    the partial masked-FedAvg sums (error feedback carried in ``qstate``)
    BEFORE the psum, bf16 narrows the bundled all-reduce itself — either
    way the round still performs exactly one collective.

    ``faults`` (optional dict, per-cohort slices of the scenario's fault
    channels) injects failures into the UPLOADED per-client updates before
    aggregation: ``"poison"`` (m,) NaN-poisons a selected client's update,
    ``"wire_gain"`` (m,) multiplies it (exponent-bit-flip corruption).

    ``guards`` (a ``RoundGuards``) arms the in-scan protections: per-client
    norm clipping of the update payload, then — after the aggregate exists
    — non-finite rollback and the quorum hold.  With guards the return
    grows a 4th element, ``flags = {"skipped", "quorum"}`` (f32 scalars);
    without guards the return is the classic 3-tuple and the compiled
    program is byte-identical to the pre-resilience engine."""
    m = ctx_c["x"].shape[0]                 # (local) client-cohort axis
    updated: Dict[int, Params] = {}
    phase_losses = []
    for pi, ph in enumerate(spec.phases):
        tgt = ph.target_fn(params, updated, ctx_c)
        w_rep = replicate(params[ph.param_idx], m)
        w_new, loss_m = jax.vmap(runners[pi], in_axes=(0, 0, 0, None, 0))(
            w_rep, ctx_c[ph.data_key], tgt, e_steps, keys[pi])
        updated[ph.param_idx] = w_new
        phase_losses.append(loss_m)
    # Fault injection + robust aggregation act on the per-client UPDATE
    # (delta from the round-start globals) — the payload a client uploads —
    # right before it would cross the wire.
    clip = guards.clip_norm if guards is not None else None
    if faults is not None or clip is not None:
        poison = faults.get("poison") if faults is not None else None
        wire = faults.get("wire_gain") if faults is not None else None
        for i, u in updated.items():
            delta = jax.tree.map(lambda wn, wo: wn - wo[None], u, params[i])
            if wire is not None:
                delta = quantcomm.apply_client_gain(delta, wire)
            if poison is not None:
                # only SELECTED clients poison the aggregate: a NaN on a
                # mask-0 client would leak through 0 * NaN in the masked sum
                bad = jnp.logical_and(poison > 0, a_mask > 0)
                delta = quantcomm.apply_client_gain(
                    delta, jnp.where(bad, jnp.nan, 1.0))
            if clip is not None:
                delta = quantcomm.clip_client_norm(delta, clip)
            updated[i] = jax.tree.map(lambda d, wo: wo[None] + d,
                                      delta, params[i])
    # Masked-FedAvg numerators, the |A_t| count and the loss sums all cross
    # the mesh in ONE fused psum — the paper's "one communication per round"
    # is literally one all-reduce in the lowered HLO (fl_dryrun pins this).
    weighted = {i: jax.tree.map(lambda p: jnp.tensordot(a_mask, p, axes=1), u)
                for i, u in updated.items()}
    msum = jnp.sum(a_mask)
    loss_sums = tuple(jnp.sum(l * a_mask) for l in phase_losses)
    quant = spec.quant
    old_qstate = qstate
    if quant.stochastic:
        weighted, qstate = quantcomm.fake_quant_int8(
            weighted, qstate, qkey, quant)
    if axis_names is not None:
        weighted, msum, loss_sums = psum_bundle(
            (weighted, msum, loss_sums), axis_names,
            wire_dtype=jnp.bfloat16 if quant.mode == "bf16" else None)
    elif quant.mode == "bf16":
        # no psum to carry the narrow format — simulate the identical
        # rounding so the single-device round matches the sharded wire
        weighted, msum, loss_sums = quantcomm.simulate_cast(
            (weighted, msum, loss_sums), jnp.bfloat16)
    wsum = jnp.maximum(msum, 1.0)
    new_params = tuple(
        jax.tree.map(lambda p: p / wsum, weighted[i]) if i in weighted
        else params[i]
        for i in range(len(params)))
    losses = tuple(s / wsum for s in loss_sums)
    if guards is None:
        return new_params, losses, qstate
    # In-scan guards on the AGGREGATED update (post-psum, so every shard
    # takes the identical decision): non-finite → roll the whole round back
    # (params and EF state hold), |A_t| < quorum → hold-round.
    finite = jnp.asarray(True)
    if guards.nonfinite:
        for i in updated:
            for leaf in jax.tree.leaves(new_params[i]):
                finite = jnp.logical_and(finite,
                                         jnp.all(jnp.isfinite(leaf)))
    quorum_ok = (msum >= guards.min_clients if guards.min_clients > 1
                 else jnp.asarray(True))
    apply = jnp.logical_and(finite, quorum_ok)
    new_params = jax.tree.map(lambda n, o: jnp.where(apply, n, o),
                              new_params, params)
    qstate = jax.tree.map(lambda n, o: jnp.where(apply, n, o),
                          qstate, old_qstate)
    flags = {
        "skipped": 1.0 - finite.astype(jnp.float32),
        "quorum": finite.astype(jnp.float32)
        * (1.0 - quorum_ok.astype(jnp.float32)),
    }
    return new_params, losses, qstate, flags


def init_quant_state(spec: FrameworkSpec, params: Params,
                     n_shards: Optional[int] = None):
    """Fresh error-feedback accumulator for ``spec``'s quantized rounds:
    one zero tree per trained param index, matching the aggregation
    payload's shapes.  ``()`` when the spec's quant mode carries no state
    (none / bf16 / int8 without error feedback), so callers can thread it
    unconditionally.

    For the SHARDED round pass ``n_shards``: each device shard keeps its
    own residual (it quantizes its own partial sums), so every leaf gains
    a leading shard axis to shard alongside the client data."""
    if not spec.quant.stateful:
        return ()
    state = {ph.param_idx: jax.tree.map(jnp.zeros_like, params[ph.param_idx])
             for ph in spec.phases}
    if n_shards is not None:
        state = jax.tree.map(
            lambda z: jnp.zeros((n_shards,) + z.shape, z.dtype), state)
    return state


def _spec_policy(spec: FrameworkSpec,
                 policy: Optional[KernelPolicy]) -> KernelPolicy:
    """The policy a builder should honor: an explicit override, else the
    one bound into the spec at ``make_spec`` time, else auto."""
    return dispatch.get_policy(policy if policy is not None else spec.policy)


def _bound_policy(spec: FrameworkSpec,
                  policy: Optional[KernelPolicy]) -> KernelPolicy:
    """Like ``_spec_policy`` for the ROUND builders, where the phase-loss
    closures already captured the spec's policy at ``make_spec`` time: a
    different ``policy`` here could only half-apply (dataset cast without
    matching losses), so a mismatch is an error — rebuild the spec with
    ``make_spec(..., policy=...)`` instead."""
    bound = dispatch.get_policy(spec.policy)
    if policy is not None and dispatch.get_policy(policy) != bound:
        raise ValueError(
            "round builders cannot override the spec-bound kernel policy "
            f"(spec has {bound}); rebuild via make_spec(..., policy=...)")
    return bound


def build_round_fn(spec: FrameworkSpec, cfg: DNNConfig,
                   x: jax.Array, y: jax.Array, *, e_max: int,
                   donate: bool = True, jit: bool = True,
                   gather: bool = False,
                   policy: Optional[KernelPolicy] = None,
                   guards: Optional[RoundGuards] = None,
                   with_faults: bool = False):
    """Compile one federated round for `spec` over the fixed client dataset.

    Returns ``round_fn(params_tuple, a_mask, e_steps, key, qstate) ->
    (params_tuple, per_phase_losses, qstate)``.  ``qstate`` is the
    ``CommQuant`` error-feedback accumulator (``init_quant_state``; the
    empty tuple whenever the spec's wire format carries no state — thread
    it through unconditionally).  ``e_max`` is the static scan
    length; ``e_steps`` (traced) masks the tail, so frameworks with adaptive
    E compile once with ``e_max = sp.E_max`` while fixed-E frameworks pass
    ``e_max = E`` for an exact-length scan.  With ``jit=False`` the pure
    function is returned for embedding in a larger program (the campaign
    runner's whole-training scan).

    ``gather=True`` changes the signature to ``round_fn(params, sel_idx,
    sel_mask, e_steps, key, qstate)``: only the gathered client cohort
    ``sel_idx``
    (a fixed-size, possibly padded index vector; pads carry mask 0) is
    trained.  This is numerically EXACT relative to the full masked round —
    unselected clients contribute nothing to the masked aggregation or the
    loss, and the RNG streams are the full per-client split gathered by
    index — but skips their computation entirely.  The serial trainers keep
    the full-M round (a varying cohort size would recompile every round);
    the campaign runner knows the whole schedule up front and exploits it.

    The kernel/precision policy is the one BOUND into the spec at
    ``make_spec`` time (``policy`` may restate it, but a different value
    raises — the phase losses already captured the bound policy).  The
    engine-owned application here: under a mixed-precision policy the
    CLIENT DATASET is cast to the compute dtype once per campaign, instead
    of once per batch inside the loss (halves the x-gather traffic of
    every local step).

    ``guards`` (a ``RoundGuards``) arms the in-scan fault guards; the
    returned function then yields ``(params, losses, qstate, flags)`` —
    see ``_round_core``.  ``with_faults=True`` appends a trailing
    ``faults`` argument (dict of per-cohort fault-channel slices) for the
    fault-injection scenarios.  Both default off, leaving the signature,
    numerics and compiled program untouched.
    """
    pol = _bound_policy(spec, policy)
    if pol.precision.is_mixed:
        x = x.astype(pol.precision.compute_dtype)
    M, n = x.shape[0], x.shape[1]
    y1 = jax.nn.one_hot(y, cfg.n_classes)
    ctx = {"x": x, "y": y, "y1": y1}
    runners = [_phase_runner(ph, n, spec.batch_size, e_max)
               for ph in spec.phases]
    n_ph = len(spec.phases)

    if gather:
        def round_fn(params: ParamsTuple, sel_idx, sel_mask, e_steps, key,
                     qstate=(), faults=None):
            # full per-client key split, gathered: stream m is the same
            # whether or not the other clients are computed
            keys = jax.random.split(key, n_ph * M).reshape(
                n_ph, M, -1)[:, sel_idx]
            qkey = _quant_key(spec, key)
            ctx_c = {k: v[sel_idx] for k, v in ctx.items()}
            return _round_core(spec, runners, params, ctx_c, sel_mask,
                               e_steps, keys, qstate, qkey,
                               faults=faults if with_faults else None,
                               guards=guards)
        donate_args = (0, 5)
    else:
        def round_fn(params: ParamsTuple, a_mask, e_steps, key, qstate=(),
                     faults=None):
            keys = jax.random.split(key, n_ph * M).reshape(n_ph, M, -1)
            qkey = _quant_key(spec, key)
            return _round_core(spec, runners, params, ctx, a_mask, e_steps,
                               keys, qstate, qkey,
                               faults=faults if with_faults else None,
                               guards=guards)
        donate_args = (0, 4)

    if not jit:
        return round_fn
    return jax.jit(round_fn, donate_argnums=donate_args if donate else ())


def build_cohort_round_fn(spec: FrameworkSpec, cfg: DNNConfig, *,
                          e_max: int, donate: bool = True, jit: bool = True,
                          policy: Optional[KernelPolicy] = None,
                          guards: Optional[RoundGuards] = None):
    """Compile one federated round whose client DATA ARRIVE AS ARGUMENTS —
    the population-mode round (``repro.core.population``), where the
    cohort changes every round so no fixed dataset can be closed over.

    Returns ``round_fn(params_tuple, xc, yc, a_mask, e_steps, key, qstate)
    -> (params_tuple, per_phase_losses, qstate)`` with ``xc`` a ``(C, n,
    d)`` cohort batch, ``yc`` ``(C, n)`` labels and ``a_mask`` the ``(C,)``
    selection mask over cohort POSITIONS.  Numerically this is exactly
    ``build_round_fn(gather=False)`` over the same ``(C, n)`` data: the
    per-position RNG streams are the identical ``n_phases × C`` split of
    the round key, the masked aggregation and the quantize-before-psum
    point are the shared ``_round_core``.  When the cohort IS the whole
    population in id order, position == client id and the round reproduces
    the materialized campaign bit-for-bit (the population parity test pins
    this through whole campaigns).

    ``guards`` arms the same in-scan protections as ``build_round_fn``
    (the return grows the ``flags`` element); fault-channel injection is
    materialized-only — population traces carry no fault channels."""
    pol = _bound_policy(spec, policy)
    n_ph = len(spec.phases)

    def round_fn(params: ParamsTuple, xc, yc, a_mask, e_steps, key,
                 qstate=()):
        if pol.precision.is_mixed:
            xc = xc.astype(pol.precision.compute_dtype)
        C, n = xc.shape[0], xc.shape[1]
        runners = [_phase_runner(ph, n, spec.batch_size, e_max)
                   for ph in spec.phases]
        ctx_c = {"x": xc, "y": yc, "y1": jax.nn.one_hot(yc, cfg.n_classes)}
        keys = jax.random.split(key, n_ph * C).reshape(n_ph, C, -1)
        qkey = _quant_key(spec, key)
        return _round_core(spec, runners, params, ctx_c, a_mask, e_steps,
                           keys, qstate, qkey, guards=guards)

    if not jit:
        return round_fn
    return jax.jit(round_fn, donate_argnums=(0, 6) if donate else ())


def _quant_key(spec: FrameworkSpec, key):
    """Quantization RNG stream, derived by fold_in so the per-client split
    chain (and hence quant=none numerics) is untouched.  The trailing
    fold_in(0) matches shard 0 of the sharded round, so a 1-shard mesh
    reproduces the single-device quantized round exactly."""
    if not spec.quant.stochastic:
        return None
    return jax.random.fold_in(jax.random.fold_in(key, _QSALT), 0)


def build_sharded_round_fn(spec: FrameworkSpec, cfg: DNNConfig, mesh, *,
                           n_clients: int, e_max: int, donate: bool = True,
                           jit: bool = True, unroll_steps: bool = False,
                           policy: Optional[KernelPolicy] = None,
                           guards: Optional[RoundGuards] = None,
                           with_faults: bool = False):
    """Compile one federated round for `spec` with the CLIENT AXIS SHARDED
    over the mesh ``data``/``pod`` axes via ``shard_map``.

    Returns ``round_fn(params_tuple, x, y, a_mask, e_steps, key, qstate)
    -> (params_tuple, per_phase_losses, qstate)``.  ``qstate`` is the
    per-shard ``CommQuant`` error-feedback accumulator
    (``init_quant_state(spec, params, n_shards=...)`` — each shard
    quantizes its own partial sums, so each keeps its own residual; the
    empty tuple for stateless wire formats).  Unlike ``build_round_fn`` the
    client dataset is an argument (shard it once with
    ``NamedSharding(mesh, P(client_axes(mesh)))`` and every round reuses the
    placement).  Each device trains only its M/|shards| client slab; the
    ONLY cross-device communication is the masked-FedAvg ``psum`` of the
    per-shard (weighted params, mask count, losses) partial sums — the
    paper's "one communication per round" as a real collective, exactly the
    pattern ``core/distributed.py`` used to hand-write for SplitMe.

    The RNG is the full ``n_phases × M`` per-client split computed from the
    round key *before* shard_map, sharded alongside the data, so every
    client sees the identical stream as the single-device round: results
    match ``build_round_fn`` to fp-reassociation error (pinned at 1e-5 by
    tests/test_engine_parity.py, including a multi-device CPU case).

    ``unroll_steps`` python-unrolls the local-SGD loop for the fl_dryrun
    collective accounting (per-step collectives — none for the engine's
    frameworks — would appear E times in the lowered HLO).

    The kernel/precision policy rides on the spec (``policy`` may only
    restate it; a mismatch raises): the phase losses inside the shard_map
    body already dispatch per the spec-bound policy, and under a
    mixed-precision policy each device's client-data slab is cast to the
    compute dtype before the shard_map so the cast is sharded too.
    """
    from jax.sharding import PartitionSpec as P

    pol = _bound_policy(spec, policy)
    axes = client_axes(mesh)
    axis_sizes = [int(mesh.shape[a]) for a in axes]
    n_shards = n_client_shards(mesh)
    M = n_clients
    if M % n_shards:
        raise ValueError(f"n_clients={M} not divisible by the "
                         f"{n_shards} client shards of mesh axes {axes}")
    n_ph = len(spec.phases)

    def shard_index():
        idx = jax.lax.axis_index(axes[0])
        for a, size in zip(axes[1:], axis_sizes[1:]):
            idx = idx * size + jax.lax.axis_index(a)
        return idx

    guarded = guards is not None or with_faults

    def local_round(params, x_s, y_s, a_s, e_steps, keys_s, qstate_s, qkey,
                    faults_s=None):
        n = x_s.shape[1]
        runners = [_phase_runner(ph, n, spec.batch_size, e_max, unroll_steps)
                   for ph in spec.phases]
        ctx_c = {"x": x_s, "y": y_s, "y1": jax.nn.one_hot(y_s, cfg.n_classes)}
        # strip the shard axis from this shard's EF block; each shard draws
        # its own quantization stream (fold_in by shard index)
        qstate = jax.tree.map(lambda l: l[0], qstate_s)
        if spec.quant.stochastic:
            qkey = jax.random.fold_in(qkey, shard_index())
        out = _round_core(
            spec, runners, params, ctx_c, a_s, e_steps, keys_s, qstate,
            qkey, axis_names=axes,
            faults=faults_s if with_faults else None, guards=guards)
        new_params, losses, qstate = out[:3]
        qstate = jax.tree.map(lambda l: l[None], qstate)
        if guards is not None:
            # flags derive from post-psum values, so every shard returns
            # the identical (replicated) decision
            return new_params, losses, qstate, out[3]
        return new_params, losses, qstate

    c_spec = P(axes)
    in_specs = (P(), c_spec, c_spec, c_spec, P(), P(None, axes), c_spec, P())
    out_specs = (P(), P(), c_spec)
    if guarded:
        in_specs = in_specs + (c_spec,)       # faults dict (per-client)
    if guards is not None:
        out_specs = out_specs + (P(),)        # flags (replicated scalars)
    sharded = jax.shard_map(local_round, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)

    ones_faults = {"poison": jnp.zeros((M,), jnp.float32),
                   "wire_gain": jnp.ones((M,), jnp.float32)}

    def round_fn(params: ParamsTuple, x, y, a_mask, e_steps, key, qstate=(),
                 faults=None):
        if pol.precision.is_mixed:
            x = x.astype(pol.precision.compute_dtype)
        keys = jax.random.split(key, n_ph * M).reshape(n_ph, M, -1)
        # the fold_in is dead (DCE'd) unless the spec's wire format is
        # stochastic; passing it unconditionally keeps one shard_map arity
        qkey = jax.random.fold_in(key, _QSALT)
        if not guarded:
            return sharded(params, x, y, a_mask, e_steps, keys, qstate, qkey)
        return sharded(params, x, y, a_mask, e_steps, keys, qstate, qkey,
                       faults if faults is not None else ones_faults)

    if not jit:
        return round_fn
    return jax.jit(round_fn, donate_argnums=(0, 6) if donate else ())


# ---------------------------------------------------------------------------
# Host-side selection / allocation policies (Alg. 1, P2, fixed-K)
# ---------------------------------------------------------------------------

class FixedKPolicy:
    """FedAvg / vanilla SFL: K uniformly random clients, uniform bandwidth.

    Scenario availability (``sp.avail``) bounds the draw: only available
    clients are candidates, and the cohort shrinks below K when fewer are
    up.  The all-available case consumes the identical RNG stream as the
    pre-scenario policy (parity-pinned)."""

    def __init__(self, sp: SystemParams, K: int, E: int, seed: int):
        self.sp, self.K, self.E = sp, K, E
        self.rng = np.random.default_rng(seed)

    def step(self) -> Tuple[np.ndarray, np.ndarray, int]:
        cand = np.flatnonzero(self.sp.avail > 0)
        a = np.zeros(self.sp.M)
        if cand.size == self.sp.M:
            # population cohorts can be smaller than K; clamping leaves the
            # RNG stream untouched whenever K <= M (the parity-pinned case)
            k = min(self.K, self.sp.M)
            a[self.rng.choice(self.sp.M, k, replace=False)] = 1.0
        else:
            if cand.size == 0:            # total blackout: never stall
                cand = np.arange(self.sp.M)
            k = min(self.K, cand.size)
            a[self.rng.choice(cand, k, replace=False)] = 1.0
        b = np.where(a > 0, 1.0 / k, 0.0)
        return a, b, self.E


class DeadlineFixedEPolicy:
    """O-RANFed: deadline-aware selection + min-max bandwidth, fixed E."""

    def __init__(self, sp: SystemParams, state: SelectionState, E: int):
        self.sp, self.state, self.E = sp, state, E

    def step(self) -> Tuple[np.ndarray, np.ndarray, int]:
        a = select_trainers(self.E, self.sp, self.state)
        b = solve_bandwidth(a, self.E, self.sp)
        self.state = update_state(self.state, a, b, self.sp)
        return a, b, self.E


class SplitMeAdaptivePolicy:
    """SplitMe: Alg. 1 selection + P2 bandwidth/adaptive-E (never increases)."""

    def __init__(self, sp: SystemParams, state: SelectionState, e_initial: int):
        self.sp, self.state, self.E = sp, state, e_initial

    def step(self) -> Tuple[np.ndarray, np.ndarray, int]:
        a = select_trainers(self.E, self.sp, self.state)
        b, self.E, _ = solve_p2(a, self.E, self.sp)
        self.state = update_state(self.state, a, b, self.sp)
        return a, b, self.E


class FedORAPolicy:
    """FedORA (arXiv 2505.19211): the RIC admits trainers by explicit
    resource allocation — clients are considered fastest-first and admitted
    while the exact min-max bandwidth allocation keeps EVERY admitted
    client's realized round time inside its slice deadline.  Unlike
    O-RANFed's Alg.-1 estimate (an EMA of past uplink maxima) the RIC
    re-solves the allocation for each candidate set, so admission responds
    immediately to payload size — including the quantized wire format.
    Fixed E, deterministic."""

    def __init__(self, sp: SystemParams, E: int):
        self.sp, self.E = sp, E

    def step(self) -> Tuple[np.ndarray, np.ndarray, int]:
        sp, E = self.sp, self.E
        order = np.argsort(E * (sp.Q_C + sp.Q_S), kind="stable")
        # the RIC only considers clients it can reach this round (scenario
        # availability); all-available keeps the original candidate order
        order = order[sp.avail[order] > 0]
        if order.size == 0:
            order = np.argsort(E * (sp.Q_C + sp.Q_S), kind="stable")
        a = np.zeros(sp.M)
        b = np.zeros(sp.M)
        for m in order:
            a[m] = 1.0
            b_try = solve_bandwidth(a, E, sp)
            t = E * (sp.Q_C + sp.Q_S) + uplink_time(a, b_try, sp)
            if np.all((a == 0) | (t <= sp.t_round)):
                b = b_try
            else:
                # admitted sets are nested along the fastest-first order
                # and feasibility shrinks monotonically with cohort size
                a[m] = 0.0
                break
        if a.sum() == 0:                       # never stall
            a[order[0]] = 1.0
            b = solve_bandwidth(a, E, sp)
        return a, b, self.E


class EcoFLPolicy:
    """EcoFL (arXiv 2507.21698): energy-first selection — the K clients
    with the lowest estimated per-round energy (transmit power × uplink
    time under a uniform K-share bandwidth estimate + compute power × the
    E local updates) — then the exact min-max bandwidth allocation over
    the selected set.  ``repro.core.cost.round_energy`` accounts the
    realized energy of the resulting schedule.  Fixed E, deterministic."""

    def __init__(self, sp: SystemParams, K: int, E: int):
        self.sp, self.K, self.E = sp, K, E

    def step(self) -> Tuple[np.ndarray, np.ndarray, int]:
        sp = self.sp
        t_up_est = (sp.S_m + sp.omega * sp.d_model_bits) \
            / ((sp.B / self.K) * sp.G_m)
        energy = (sp.p_tx_w * t_up_est
                  + sp.p_cpu_w * self.E * (sp.Q_C + sp.Q_S))
        # unavailable clients rank last (scenario availability); the cohort
        # shrinks below K when fewer are up, and a total blackout falls back
        # to the plain energy ranking (never stall)
        if np.any(sp.avail > 0):
            energy = np.where(sp.avail > 0, energy, np.inf)
        k = max(1, min(self.K, int(np.sum(np.isfinite(energy)))))
        a = np.zeros(sp.M)
        a[np.argsort(energy, kind="stable")[:k]] = 1.0
        b = solve_bandwidth(a, self.E, sp)
        return a, b, self.E


# ---------------------------------------------------------------------------
# Per-framework SystemParams derivation (on a private copy)
# ---------------------------------------------------------------------------

def _derive_splitme(sp: SystemParams, cfg: DNNConfig, n_m: int,
                    wire_bits: float = 32.0) -> None:
    """Smashed-data size, split-model bits and omega from the actual DNN.
    ``wire_bits`` is the CommQuant payload width — the boundary activations
    (S_m) and the uploaded split-model halves ship in the quantized wire
    format, so cost/latency and the P2 deadline selection respond to it."""
    d_split = dnn.client_dims(cfg)[-1]
    pc_c = dnn.param_count_dims(dnn.client_dims(cfg))
    pc_i = dnn.param_count_dims(dnn.inverse_server_dims(cfg))
    sp.S_m = np.full(sp.M, n_m * d_split * wire_bits)
    sp.d_model_bits = wire_bits * (pc_c + pc_i)
    sp.omega = pc_c / (pc_c + pc_i)


def _derive_full_model(sp: SystemParams) -> None:
    """Full-model FL upload: whole model, no smashed data."""
    sp.omega = 1.0
    sp.S_m = np.zeros(sp.M)


def _derive_no_offload(sp: SystemParams) -> None:
    """O-RANFed: the client computes BOTH halves locally."""
    _derive_full_model(sp)
    sp.Q_C = sp.Q_C + sp.Q_S
    sp.Q_S = np.zeros(sp.M)


def make_policy(name: str, sp: SystemParams, cfg: DNNConfig, *,
                seed: int = 0, K: int = 10, E: int = 10,
                e_initial: int = 20,
                n_samples_per_client: Optional[int] = None,
                quant: "quantcomm.QuantLike" = None
                ) -> Tuple[SystemParams, Any]:
    """Copy `sp`, apply the framework's parameter derivation to the copy,
    and build its selection/allocation policy.

    The initialization ORDER replicates the seed trainers exactly (the
    parity tests pin it): SplitMe seeds Alg. 1's pessimistic t_max^0 from
    the caller's generic S_m/omega BEFORE deriving the real sizes, while
    O-RANFed derives first and seeds the estimate from the derived values.

    ``quant`` (the spec's ``CommQuant``) scales every wire payload in the
    derived copy — S_m and d_model_bits — by ``wire_bits/32``, so the comm
    models count quantized bits and the latency/cost curves AND the
    deadline-driven selection policies (Alg. 1, P2, FedORA's RIC
    allocation, EcoFL's energy ranking) all respond to the narrower
    format.  ``quant=None``/"none" leaves the copy byte-identical to the
    pre-quantcomm derivation.
    """
    sp = sp.copy()
    q = quantcomm.get_quant(quant)
    wire = float(q.wire_bits)
    if q.mode != "none":
        # generic (pre-derivation) payload sizes: sfl keeps these, and
        # SplitMe's pessimistic t_max^0 estimate reads them
        sp.S_m = sp.S_m * q.wire_scale
        sp.d_model_bits = sp.d_model_bits * q.wire_scale
    if name == "splitme":
        if n_samples_per_client is None:
            raise ValueError("splitme needs n_samples_per_client for S_m")
        state = initial_state(sp)
        _derive_splitme(sp, cfg, n_samples_per_client, wire_bits=wire)
        return sp, SplitMeAdaptivePolicy(sp, state, e_initial)
    if name == "fedavg":
        _derive_full_model(sp)
        return sp, FixedKPolicy(sp, K, E, seed)
    if name == "sfl":
        return sp, FixedKPolicy(sp, K, E, seed)
    if name == "oranfed":
        _derive_no_offload(sp)
        return sp, DeadlineFixedEPolicy(sp, initial_state(sp), E)
    if name == "fedora":
        _derive_full_model(sp)
        return sp, FedORAPolicy(sp, E)
    if name == "ecofl":
        _derive_full_model(sp)
        return sp, EcoFLPolicy(sp, K, E)
    raise KeyError(f"unknown framework {name!r}; have {framework_names()}")


# ---------------------------------------------------------------------------
# Spec factories (the registry)
# ---------------------------------------------------------------------------

def _ce_step(cfg: DNNConfig, pol: KernelPolicy):
    prec = pol.precision

    def loss(w, x_b, y_b):
        # forward in the policy's compute dtype; logits land in the accum
        # dtype (f32), so the log_softmax + NLL reduction is pinned f32
        logits = dnn.mlp_forward(w, x_b, cfg.activation, precision=prec)
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(logp, y_b[:, None], axis=1))
    return loss


def _mlp_spec(name: str, cfg: DNNConfig, comm_model, *, lr: float,
              batch_size: int, pol: KernelPolicy,
              quant: CommQuant) -> FrameworkSpec:
    phase = PhaseSpec(
        name="local", param_idx=0, lr=lr, loss_fn=_ce_step(cfg, pol),
        data_key="x", target_fn=lambda params, updated, ctx: ctx["y"])
    return FrameworkSpec(
        name=name,
        init_fn=lambda key: (dnn.init_mlp(key, cfg.layer_dims),),
        phases=(phase,), comm_model=comm_model, batch_size=batch_size,
        init_key_offset=1, policy=pol, quant=quant)


def _as_float(x: np.ndarray):
    """Scalar float for a single round, ndarray for a stacked schedule."""
    x = np.asarray(x, np.float64)
    return float(x) if x.ndim == 0 else x


def _full_model_comm(a, E, sp):
    """Whole-model upload per selected client (fedavg / oranfed / fedora /
    ecofl).  ``sp.d_model_bits`` already carries the CommQuant wire scale
    (``make_policy`` derives it), so quantized campaigns count quantized
    bits with no extra factor here."""
    # a: (M,) or a stacked-schedule (R, M); E: int or (R,)
    return _as_float(np.sum(a, axis=-1) * sp.d_model_bits)


def _make_fedavg(cfg: DNNConfig, *, lr: float = 0.05, batch_size: int = 32,
                 policy: Optional[KernelPolicy] = None,
                 quant: CommQuant = quantcomm.NONE, **_) -> FrameworkSpec:
    return _mlp_spec("fedavg", cfg, _full_model_comm, lr=lr,
                     batch_size=batch_size, pol=dispatch.get_policy(policy),
                     quant=quant)


def _make_sfl(cfg: DNNConfig, *, lr: float = 0.05, batch_size: int = 32,
              policy: Optional[KernelPolicy] = None,
              quant: CommQuant = quantcomm.NONE, **_) -> FrameworkSpec:
    # per local step: smashed up + boundary grads down, one batch each —
    # the boundary tensors ship in the CommQuant wire format too
    boundary_bits = (2 * batch_size * dnn.client_dims(cfg)[-1]
                     * float(quant.wire_bits))

    def comm(a, E, sp):
        return _as_float(np.sum(a, axis=-1)
                         * (np.asarray(E, np.float64) * boundary_bits
                            + sp.omega * sp.d_model_bits))
    return _mlp_spec("sfl", cfg, comm, lr=lr, batch_size=batch_size,
                     pol=dispatch.get_policy(policy), quant=quant)


def _make_oranfed(cfg: DNNConfig, *, lr: float = 0.05, batch_size: int = 32,
                  policy: Optional[KernelPolicy] = None,
                  quant: CommQuant = quantcomm.NONE, **_) -> FrameworkSpec:
    return _mlp_spec("oranfed", cfg, _full_model_comm, lr=lr,
                     batch_size=batch_size, pol=dispatch.get_policy(policy),
                     quant=quant)


def _make_fedora(cfg: DNNConfig, *, lr: float = 0.05, batch_size: int = 32,
                 policy: Optional[KernelPolicy] = None,
                 quant: CommQuant = quantcomm.NONE, **_) -> FrameworkSpec:
    """FedORA [arXiv 2505.19211]: full-model FL whose cohort is set by the
    RIC's per-round resource allocation (``FedORAPolicy``); same local
    training and wire payload as FedAvg — a new comm/selection pair over
    the unified engine, zero new training code."""
    return _mlp_spec("fedora", cfg, _full_model_comm, lr=lr,
                     batch_size=batch_size, pol=dispatch.get_policy(policy),
                     quant=quant)


def _make_ecofl(cfg: DNNConfig, *, lr: float = 0.05, batch_size: int = 32,
                policy: Optional[KernelPolicy] = None,
                quant: CommQuant = quantcomm.NONE, **_) -> FrameworkSpec:
    """EcoFL [arXiv 2507.21698]: full-model FL with energy-first client
    selection (``EcoFLPolicy``); per-round energy of the realized schedule
    is ``repro.core.cost.round_energy``."""
    return _mlp_spec("ecofl", cfg, _full_model_comm, lr=lr,
                     batch_size=batch_size, pol=dispatch.get_policy(policy),
                     quant=quant)


def _make_splitme(cfg: DNNConfig, *, lr_c: float = 0.05, lr_s: float = 0.02,
                  temperature: float = 2.0, batch_size: int = 32,
                  masked_loss_metric: bool = False,
                  policy: Optional[KernelPolicy] = None,
                  quant: CommQuant = quantcomm.NONE, **_) -> FrameworkSpec:
    """SplitMe spec.  ``masked_loss_metric=False`` reproduces the seed
    trainer's loss metric (mean over the full E_max scan, frozen tail
    included) and requires ``e_max = sp.E_max``; ``True`` averages over the
    executed steps only, which lets the campaign runner scan exactly
    ``max(schedule E)`` steps.  The trained parameters are identical either
    way (masked updates are exact no-ops).

    Both mutual-KL phase losses go through the kernel dispatch layer
    (``dispatch.kl_loss``): the policy picks the fused online-softmax
    Pallas kernel (closed-form custom_vjp) or the reference
    ``mutual.kl_paper`` graph, and its precision casts the forwards to the
    compute dtype (loss reductions stay f32 either way)."""
    tau = temperature
    pol = dispatch.get_policy(policy)
    prec = pol.precision

    def client_step(w, x_b, t_b):
        # f_C = D_KL(c(X) ‖ sg[s⁻¹(Y)])  (eq. 5, client side)
        feat = dnn.client_forward(w, x_b, cfg, precision=prec)
        return dispatch.kl_loss(feat, t_b, temperature=tau, policy=pol)

    def server_step(w, y1_b, t_b):
        # f_S = D_KL(s⁻¹(Y) ‖ sg[c(X)])  (eq. 5, server side)
        inv = dnn.inverse_server_forward(w, y1_b, cfg, precision=prec)
        return dispatch.kl_loss(inv, t_b, temperature=tau, policy=pol)

    def client_targets(params, updated, ctx):
        # Step 1: download s⁻¹(Y_m) once — fixed targets for the round
        return jax.vmap(
            lambda y1m: dnn.inverse_server_forward(params[1], y1m, cfg,
                                                   precision=prec)
        )(ctx["y1"])

    def server_targets(params, updated, ctx):
        # Step 3: upload c(X_m) once, from the UPDATED per-client weights
        smashed = jax.vmap(
            lambda w, xm: dnn.client_forward(w, xm, cfg, precision=prec)
        )(updated[0], ctx["x"])
        return jax.lax.stop_gradient(smashed)

    def init(key):
        k1, k2 = jax.random.split(key)
        return (dnn.init_client(k1, cfg), dnn.init_inverse_server(k2, cfg))

    def comm(a, E, sp):
        return _as_float(np.sum(a * (sp.S_m + sp.omega * sp.d_model_bits),
                                axis=-1))

    return FrameworkSpec(
        name="splitme", init_fn=init,
        phases=(
            PhaseSpec("client", 0, lr_c, client_step, "x", client_targets,
                      loss_over_mask=masked_loss_metric),
            PhaseSpec("server", 1, lr_s, server_step, "y1", server_targets,
                      loss_over_mask=masked_loss_metric),
        ),
        comm_model=comm, batch_size=batch_size, policy=pol, quant=quant)


_REGISTRY: Dict[str, Callable[..., FrameworkSpec]] = {
    "splitme": _make_splitme,
    "fedavg": _make_fedavg,
    "sfl": _make_sfl,
    "oranfed": _make_oranfed,
    "fedora": _make_fedora,
    "ecofl": _make_ecofl,
}


def framework_names() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


def make_spec(name: str, cfg: DNNConfig, *,
              policy: "dispatch.PolicyLike" = None,
              quant: "quantcomm.QuantLike" = None, **hyper) -> FrameworkSpec:
    """Build a framework spec.  ``policy`` (None / preset name /
    ``KernelPolicy``) selects kernels and precision for the phase losses;
    ``quant`` (None / "none" / "bf16" / "int8" / ``CommQuant``) selects
    the wire format of the aggregation payload.  Both are resolved once
    here and bound into the spec, so every builder downstream (round fns,
    eval fn, campaign) shares one numerics — pass the same ``quant`` to
    ``make_policy`` so the comm/cost models count the same wire format."""
    try:
        factory = _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown framework {name!r}; have {framework_names()}") from None
    return factory(cfg, policy=dispatch.get_policy(policy),
                   quant=quantcomm.get_quant(quant), **hyper)


# ---------------------------------------------------------------------------
# Jitted test-set evaluation (vmap-able; fused into the scanned campaign)
# ---------------------------------------------------------------------------

def build_eval_fn(spec: FrameworkSpec, cfg: DNNConfig, x_test, y_test, *,
                  client_data: Optional[Dict[str, Any]] = None,
                  gamma: float = 1e-3, jit: bool = True,
                  policy: Optional[KernelPolicy] = None,
                  axis_name=None):
    """Build ``accuracy(params_tuple) -> scalar`` for `spec`.

    Full-model frameworks evaluate the aggregated MLP directly.  SplitMe
    first recovers the server model via the one-shot analytic inversion
    (Step 4), which needs `client_data` for the Gram sums; inside a
    ``shard_map`` whose clients are sharded over ``axis_name``, the Gram
    sums are psum'd over it (``invert_inverse_model``).  The function is
    pure (jit/vmap/cond-safe), so trainers call it jitted, the campaign
    runner vmaps it over the seed axis, and the scanned campaign embeds it
    behind a per-round ``do_eval`` mask without leaving the device.

    The kernel/precision policy rides on the spec (``policy`` overrides):
    forwards run in the compute dtype and the Step-4 Gram products dispatch
    to the ridge_gram kernel per the policy; the Gram accumulation, ridge
    solve, the recovered server model's forward (f32 at HIGHEST matmul
    precision) and the accuracy reduction itself stay pinned f32.
    """
    pol = _spec_policy(spec, policy)
    prec = pol.precision
    x_test = jnp.asarray(x_test)
    y_test = jnp.asarray(y_test)
    if spec.name == "splitme":
        if client_data is None:
            raise ValueError("splitme evaluation needs client_data for the "
                             "Step-4 Gram sums")
        x = jnp.asarray(client_data["x"])
        y1 = jax.nn.one_hot(jnp.asarray(client_data["y"]), cfg.n_classes)
        flat_y = y1.reshape(-1, cfg.n_classes)

        def accuracy(params: ParamsTuple) -> jax.Array:
            w_c, w_s_inv = params
            smashed = jax.vmap(
                lambda xm: dnn.client_forward(w_c, xm, cfg, precision=prec)
            )(x)
            w_s = invert_inverse_model(
                w_s_inv, smashed.reshape(-1, smashed.shape[-1]), flat_y, cfg,
                gamma=gamma, axis_name=axis_name, policy=pol)
            h = dnn.client_forward(w_c, x_test, cfg, precision=prec)
            # the ridge-recovered server model is ill-conditioned: apply it
            # in f32 at HIGHEST, as the inversion fitted it.  A TPU's
            # default f32 dot (one bf16 pass) takes accuracy to chance.
            with jax.default_matmul_precision("highest"):
                logits = dnn.server_forward(w_s, h.astype(jnp.float32), cfg)
            return jnp.mean((jnp.argmax(logits, -1) == y_test)
                            .astype(jnp.float32))
    else:
        def accuracy(params: ParamsTuple) -> jax.Array:
            (w,) = params
            logits = dnn.mlp_forward(w, x_test, cfg.activation,
                                     precision=prec)
            return jnp.mean((jnp.argmax(logits, -1) == y_test)
                            .astype(jnp.float32))

    return jax.jit(accuracy) if jit else accuracy
