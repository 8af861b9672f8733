"""Baseline FL frameworks the paper compares against (§V-A), plus the two
resource-allocation baselines from PAPERS.md the registry grew beyond the
paper:

* **FedAvg** [6]      — K=10 fixed clients, E=10, full-model local training,
                        no splitting, no system optimization.
* **Vanilla SFL** [12]— K=20, E=14; per-batch smashed-data upload + boundary
                        gradient download (the communication pattern SplitMe
                        eliminates); client/server copies FedAvg-aggregated.
* **O-RANFed** [8]    — FedAvg + deadline-aware selection + bandwidth
                        allocation (system optimization, no splitting).
* **FedORA** (arXiv 2505.19211) — full-model FL; the RIC admits the largest
                        fastest-first cohort whose exact min-max bandwidth
                        allocation meets every admitted client's slice
                        deadline.
* **EcoFL** (arXiv 2507.21698) — full-model FL; energy-first selection (the
                        K lowest-energy clients) with min-max bandwidth;
                        per-round energy via ``repro.core.cost.round_energy``.

All of them run on the same non-IID O-RAN slice data and report the same
metrics (selected trainers, comm volume, simulated latency, cost, accuracy)
so benchmarks/ can reproduce the paper's figures.

The local-training hot path is the unified engine (``repro.core.engine``);
each class here only names its framework spec and selection policy.  Every
trainer derives omega/S_m/Q_* on a private SystemParams copy, so sequential
framework runs sharing one SystemParams no longer corrupt each other.
``comm_quant`` (None / "bf16" / "int8" / ``CommQuant``) narrows the wire
format of the aggregation payload; comm volume, latency, cost and the
deadline/energy selection policies all account the quantized bits.
``scenario`` (a ``repro.core.scenario.ScenarioTrace``) drives the round-t
time-varying RAN state through selection, allocation and metrics.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from repro.configs.splitme_dnn import DNNConfig
from repro.core import engine, scenario as scen
from repro.core.cost import SystemParams, round_cost, round_energy, total_time
from repro.core.engine import RoundMetrics


class _FLBase:
    """Thin adapter: engine round + host-side policy + paper metrics."""

    framework: str

    def __init__(self, cfg: DNNConfig, sp: SystemParams, client_data,
                 test_data, lr: float, E: int, batch_size: int, seed: int,
                 K: int = 10, kernel_policy=None, comm_quant=None,
                 scenario=None, interactive: bool = False):
        self.cfg, self.E = cfg, E
        self.x = jnp.asarray(client_data["x"])
        self.y = jnp.asarray(client_data["y"])
        self.x_test, self.y_test = map(jnp.asarray, test_data)
        # interactive=True restores the per-round float() metric pull; the
        # default buffers device arrays so eval overlaps the next round's
        # dispatch (fetch_history() syncs once at campaign end)
        self.interactive = interactive
        self.sp, self.policy = engine.make_policy(
            self.framework, sp, cfg, seed=seed, K=K, E=E, quant=comm_quant)
        # scenario: a pre-built ScenarioTrace (repro.core.scenario.make_trace
        # / get_trace — the trainer has no round horizon to generate from);
        # each run_round re-selects against the round-t trace
        if isinstance(scenario, str):
            raise TypeError(
                "serial trainers need a concrete ScenarioTrace (the round "
                "horizon is open-ended): build one with scenario.make_trace("
                f"{scenario!r}, rounds, M) or run a scanned campaign")
        self._trace = scenario
        self._trace_base = (scen.capture_base(self.sp)
                            if scenario is not None else None)
        self.key = jax.random.PRNGKey(seed)
        self._spec = engine.make_spec(self.framework, cfg, lr=lr,
                                      batch_size=batch_size,
                                      policy=kernel_policy, quant=comm_quant)
        (self.params,) = self._spec.init_fn(
            jax.random.PRNGKey(seed + self._spec.init_key_offset))
        self.history: List[RoundMetrics] = []
        self._round = 0
        # CommQuant error-feedback accumulator (empty when stateless)
        self._qstate = engine.init_quant_state(self._spec, (self.params,))
        # fixed E → exact-length scan (mask is all-ones, compiled once)
        self._round_fn = engine.build_round_fn(self._spec, cfg, e_max=E)
        # jitted test accuracy, compiled once and reused each eval round
        self._eval_fn = engine.build_eval_fn(self._spec, cfg, self.x_test,
                                             self.y_test)

    def run_round(self, eval_acc: bool = False) -> RoundMetrics:
        if self._trace is not None:
            # policy.sp IS self.sp (make_policy returns the shared derived
            # copy), so the rewrite reaches the selection directly
            scen.apply_round(self.sp, self._trace_base, self._trace,
                             self._round)
        a, b, self.E = self.policy.step()
        if self._trace is not None:
            a = scen.realized_mask(a, self._trace, self._round)
        self.key, sub = jax.random.split(self.key)
        (self.params,), (loss,), self._qstate = self._round_fn(
            (self.params,), self.x, self.y, jnp.asarray(a, jnp.float32),
            jnp.asarray(self.E), sub, self._qstate)
        return self._record(a, b, eval_acc,
                            float(loss) if self.interactive else loss)

    def evaluate(self) -> float:
        return float(self._eval_fn((self.params,)))

    def fetch_history(self):
        """Resolve buffered device-array metrics to floats in ONE
        device→host transfer (call once at campaign end)."""
        return engine.fetch_history(self.history)

    def _record(self, a, b, eval_acc, loss) -> RoundMetrics:
        acc = float("nan")
        if eval_acc:
            # device array in async mode — the next round's dispatch
            # overlaps this evaluation instead of blocking on float()
            acc = self._eval_fn((self.params,))
            if self.interactive:
                acc = float(acc)
        m = RoundMetrics(
            round=self._round, n_selected=int(a.sum()), E=self.E,
            comm_bits=self._spec.comm_model(a, self.E, self.sp),
            sim_time=total_time(a, b, self.E, self.sp),
            cost=round_cost(a, b, self.E, self.sp),
            energy=round_energy(a, b, self.E, self.sp),
            client_loss=loss, accuracy=acc)
        self._round += 1
        self.history.append(m)
        return m


class FedAvgTrainer(_FLBase):
    """K fixed random clients per round, uniform bandwidth."""

    framework = "fedavg"

    def __init__(self, cfg, sp, client_data, test_data, *, K: int = 10,
                 E: int = 10, lr: float = 0.05, batch_size: int = 32,
                 seed: int = 0, **kw):
        super().__init__(cfg, sp, client_data, test_data, lr, E, batch_size,
                         seed, K=K, **kw)
        self.K = K


class SFLTrainer(_FLBase):
    """Vanilla SplitFed: same joint gradients, but the boundary tensors move
    between xApp and rApp on EVERY local batch — counted in comm_bits."""

    framework = "sfl"

    def __init__(self, cfg, sp, client_data, test_data, *, K: int = 20,
                 E: int = 14, lr: float = 0.05, batch_size: int = 32,
                 seed: int = 0, **kw):
        super().__init__(cfg, sp, client_data, test_data, lr, E, batch_size,
                         seed, K=K, **kw)
        self.K = K


class ORANFedTrainer(_FLBase):
    """O-RANFed [8]: deadline-aware selection + min-max bandwidth allocation,
    full-model FL (no split)."""

    framework = "oranfed"

    def __init__(self, cfg, sp, client_data, test_data, *, E: int = 10,
                 lr: float = 0.05, batch_size: int = 32, seed: int = 0,
                 **kw):
        super().__init__(cfg, sp, client_data, test_data, lr, E, batch_size,
                         seed, **kw)


class FedORATrainer(_FLBase):
    """FedORA (arXiv 2505.19211): full-model FL, cohort set per round by
    the RIC's deadline-feasible min-max resource allocation."""

    framework = "fedora"

    def __init__(self, cfg, sp, client_data, test_data, *, E: int = 10,
                 lr: float = 0.05, batch_size: int = 32, seed: int = 0,
                 **kw):
        super().__init__(cfg, sp, client_data, test_data, lr, E, batch_size,
                         seed, **kw)


class EcoFLTrainer(_FLBase):
    """EcoFL (arXiv 2507.21698): full-model FL, the K lowest-energy clients
    per round (transmit + compute power), min-max bandwidth over them."""

    framework = "ecofl"

    def __init__(self, cfg, sp, client_data, test_data, *, K: int = 10,
                 E: int = 10, lr: float = 0.05, batch_size: int = 32,
                 seed: int = 0, **kw):
        super().__init__(cfg, sp, client_data, test_data, lr, E, batch_size,
                         seed, K=K, **kw)
        self.K = K
