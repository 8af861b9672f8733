"""SplitMe with system optimization (paper Algorithm 2).

Per global round k:
  1. Algorithm 1 decides the participant set A_t (deadline-aware).
  2. P2 allocates bandwidth + adapts the local-update count E.
  3. Each selected xApp downloads (w_C, s⁻¹(Y_m)) and runs E local SGD steps
     on D_KL(c(X) ‖ s⁻¹(Y))  — *no* per-batch traffic to the server.
  4. Each rApp receives (w_C,m, c(X_m)) once and runs E SGD steps on
     D_KL(s⁻¹(Y) ‖ c(X)).
  5. The non-RT-RIC aggregates both sides (masked FedAvg over A_t).
  Final round: the server-side model is recovered analytically
  (repro.core.inversion) — one shot, one communication round.

The round hot path (replication, masked E_max-scan, masked FedAvg, RNG
pre-splitting, parameter-buffer donation) lives in ``repro.core.engine``;
this class is a thin adapter wiring the engine's "splitme" spec (two coupled
mutual-learning phases) to Alg. 1/P2 and the paper's metrics.  E adapts per
round, so the jitted round function is compiled with a *static* E_max-step
scan and a dynamic step mask (recompilation-free adaptive local updates).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.splitme_dnn import DNNConfig
from repro.core import dnn, engine, scenario as scen
from repro.core.cost import SystemParams, round_cost, round_energy, total_time
from repro.core.engine import RoundMetrics  # re-export (seed import path)
from repro.core.engine import fetch_history
from repro.core.inversion import invert_inverse_model

__all__ = ["RoundMetrics", "SplitMeTrainer"]


class SplitMeTrainer:
    """Runs the full Algorithm 2 over the partitioned O-RAN dataset."""

    def __init__(self, cfg: DNNConfig, sp: SystemParams,
                 client_data: Dict[str, np.ndarray],
                 test_data: Tuple[np.ndarray, np.ndarray],
                 lr_c: float = 0.05, lr_s: float = 0.02,
                 temperature: float = 2.0, batch_size: int = 32,
                 e_initial: int = 20, gamma: float = 1e-3, seed: int = 0,
                 kernel_policy=None, comm_quant=None, scenario=None,
                 interactive: bool = False):
        assert lr_c > lr_s, "Corollary 3: η_C > η_S (B_1 < B_2)"
        self.cfg = cfg
        self.x = jnp.asarray(client_data["x"])      # (M, n, d)
        self.y = jnp.asarray(client_data["y"])      # (M, n)
        self.x_test, self.y_test = map(jnp.asarray, test_data)
        self.gamma = gamma
        # interactive=True restores per-round float() metric pulls (each
        # run_round blocks on its losses).  The default keeps metrics as
        # device arrays so round k+1 (and any fused eval) dispatches while
        # round k's reductions are still in flight; fetch_history() pulls
        # everything host-side in ONE transfer at campaign end.
        self.interactive = interactive
        # private SystemParams copy + Alg. 1/P2 policy (never mutates `sp`);
        # comm_quant scales the wire payloads P2 optimizes over
        self.sp, self.policy = engine.make_policy(
            "splitme", sp, cfg, e_initial=e_initial,
            n_samples_per_client=int(self.x.shape[1]), quant=comm_quant)
        # scenario: a pre-built ScenarioTrace (repro.core.scenario); each
        # run_round rewrites the derived copy to the round-t RAN state
        # before Alg. 1 / P2 re-select and re-allocate
        if isinstance(scenario, str):
            raise TypeError(
                "SplitMeTrainer needs a concrete ScenarioTrace (the round "
                "horizon is open-ended): build one with scenario.make_trace("
                f"{scenario!r}, rounds, M) or run a scanned campaign")
        self._trace = scenario
        self._trace_base = (scen.capture_base(self.sp)
                            if scenario is not None else None)
        self.key = jax.random.PRNGKey(seed)
        self._spec = engine.make_spec(
            "splitme", cfg, lr_c=lr_c, lr_s=lr_s, temperature=temperature,
            batch_size=batch_size, policy=kernel_policy, quant=comm_quant)
        self.w_c, self.w_s_inv = self._spec.init_fn(self.key)
        self.E = e_initial
        self.history: List[RoundMetrics] = []
        self._round = 0
        self._qstate = engine.init_quant_state(self._spec,
                                               (self.w_c, self.w_s_inv))
        self._round_fn = engine.build_round_fn(
            self._spec, cfg, e_max=self.sp.E_max)
        # jitted Step-4-inversion + stitched-forward accuracy (one compile,
        # reused on every eval round instead of an eager per-call inversion)
        self._eval_fn = engine.build_eval_fn(
            self._spec, cfg, self.x_test, self.y_test,
            client_data={"x": self.x, "y": self.y}, gamma=gamma)

    # ------------------------------------------------------------------
    def _jit_round(self, w_c, w_s_inv, a_mask, e_steps, key):
        """Seed-compatible signature over the engine round (steps 3-5)."""
        (w_c, w_s_inv), (closs, sloss), self._qstate = self._round_fn(
            (w_c, w_s_inv), self.x, self.y, a_mask, e_steps, key,
            self._qstate)
        return w_c, w_s_inv, closs, sloss

    # ------------------------------------------------------------------
    def run_round(self, eval_acc: bool = False) -> RoundMetrics:
        sp = self.sp
        if self._trace is not None:
            scen.apply_round(sp, self._trace_base, self._trace, self._round)
        # P1 + P2: deadline-aware selection, bandwidth, adaptive E
        a, b, self.E = self.policy.step()
        if self._trace is not None:
            a = scen.realized_mask(a, self._trace, self._round)

        self.key, sub = jax.random.split(self.key)
        self.w_c, self.w_s_inv, closs, sloss = self._jit_round(
            self.w_c, self.w_s_inv, jnp.asarray(a, jnp.float32),
            jnp.asarray(self.E), sub)

        # metrics stay device arrays unless interactive: no float() sync in
        # the round loop, so the next round's dispatch overlaps this eval
        m = RoundMetrics(
            round=self._round, n_selected=int(a.sum()), E=self.E,
            comm_bits=self._spec.comm_model(a, self.E, sp),
            sim_time=total_time(a, b, self.E, sp),
            cost=round_cost(a, b, self.E, sp),
            energy=round_energy(a, b, self.E, sp),
            client_loss=float(closs) if self.interactive else closs,
            server_loss=float(sloss) if self.interactive else sloss)
        if eval_acc:
            acc = self._eval_fn((self.w_c, self.w_s_inv))
            m.accuracy = float(acc) if self.interactive else acc
        self._round += 1
        self.history.append(m)
        return m

    # ------------------------------------------------------------------
    def fetch_history(self) -> List[RoundMetrics]:
        """Resolve buffered device-array metrics to floats in ONE
        device→host transfer (call once at campaign end)."""
        return fetch_history(self.history)

    # ------------------------------------------------------------------
    def finalize(self, use_kernel: Optional[bool] = None) -> List[dict]:
        """Step 4: analytic inversion using all clients' smashed data.

        The Gram sums Σ OᵀO / Σ OᵀZ are the paper's all-reduce; here the sum
        over the stacked client axis is that all-reduce (it shards over the
        mesh `data` axis under pjit).  The Gram products dispatch per the
        trainer's kernel policy; ``use_kernel`` force-overrides.
        """
        cfg = self.cfg
        prec = self._spec.policy.precision     # same numerics as _eval_fn
        smashed = jax.vmap(
            lambda x: dnn.client_forward(self.w_c, x, cfg, precision=prec)
        )(self.x)
        y1 = jax.nn.one_hot(self.y, cfg.n_classes)
        flat_s = smashed.reshape(-1, smashed.shape[-1])
        flat_y = y1.reshape(-1, cfg.n_classes)
        return invert_inverse_model(self.w_s_inv, flat_s, flat_y, cfg,
                                    gamma=self.gamma, use_kernel=use_kernel,
                                    policy=self._spec.policy)

    def evaluate(self, w_server: Optional[List[dict]] = None) -> float:
        if w_server is not None:
            logits = dnn.full_forward(self.w_c, w_server, self.x_test,
                                      self.cfg,
                                      precision=self._spec.policy.precision)
            return float(jnp.mean(jnp.argmax(logits, -1) == self.y_test))
        return float(self._eval_fn((self.w_c, self.w_s_inv)))
