"""Pins the unified engine refactor to the SEED trainers' numerics.

The reference classes below are direct transcriptions of the pre-engine
(seed) trainers' jitted round implementations and run_round policy chains.
The engine-backed trainers must reproduce their per-round client/server
losses (within 1e-5) and parameters over 3 rounds from a fixed seed.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.splitme_dnn import DNN10
from repro.core import dnn, mutual
from repro.core.allocation import solve_bandwidth, solve_p2
from repro.core.baselines import FedAvgTrainer, ORANFedTrainer, SFLTrainer
from repro.core.cost import SystemParams
from repro.core.selection import initial_state, select_trainers, update_state
from repro.core.splitme import SplitMeTrainer

ROUNDS = 3


@pytest.fixture(scope="module")
def small_data():
    from repro.data import oran
    X, y = oran.generate(n_per_class=300, seed=0)
    (Xtr, ytr), (Xte, yte) = oran.train_test_split(X, y)
    cd = oran.partition_non_iid(Xtr, ytr, 12, samples_per_client=32, seed=0)
    return cd, (Xte, yte)


# ---------------------------------------------------------------------------
# Seed-trainer transcriptions (reference implementations)
# ---------------------------------------------------------------------------

class _SeedSplitMe:
    """Transcription of the seed SplitMeTrainer (init + round + policy)."""

    def __init__(self, cfg, sp, client_data, lr_c=0.05, lr_s=0.02,
                 temperature=2.0, batch_size=32, e_initial=20, seed=0):
        self.cfg, self.sp = cfg, sp
        self.x = jnp.asarray(client_data["x"])
        self.y = jnp.asarray(client_data["y"])
        self.lr_c, self.lr_s, self.tau = lr_c, lr_s, temperature
        self.bs = batch_size
        self.key = jax.random.PRNGKey(seed)
        k1, k2 = jax.random.split(self.key)
        self.w_c = dnn.init_client(k1, cfg)
        self.w_s_inv = dnn.init_inverse_server(k2, cfg)
        self.E = e_initial
        self.sel_state = initial_state(sp)
        d_split = dnn.client_dims(cfg)[-1]
        n_m = self.x.shape[1]
        sp.S_m = np.full(sp.M, n_m * d_split * 32.0)
        d_bits = 32.0 * (dnn.param_count(self.w_c)
                         + dnn.param_count(self.w_s_inv))
        sp.d_model_bits = d_bits
        sp.omega = dnn.param_count(self.w_c) / (d_bits / 32.0)
        self._jit_round = jax.jit(functools.partial(self._round_impl))

    def _round_impl(self, w_c, w_s_inv, a_mask, e_steps, key):
        cfg, tau = self.cfg, self.tau
        M, n, d = self.x.shape
        y_onehot = jax.nn.one_hot(self.y, cfg.n_classes)

        def client_local(w, x_m, target_m, key_m):
            def step(carry, i):
                w, k = carry
                k, sk = jax.random.split(k)
                idx = jax.random.randint(sk, (self.bs,), 0, n)
                def loss_fn(w):
                    feat = dnn.client_forward(w, x_m[idx], cfg)
                    return mutual.client_loss(feat, target_m[idx], tau)
                loss, g = jax.value_and_grad(loss_fn)(w)
                do = (i < e_steps).astype(jnp.float32)
                w = jax.tree.map(lambda p, gg: p - self.lr_c * do * gg, w, g)
                return (w, k), loss
            (w, _), losses = jax.lax.scan(step, (w, key_m),
                                          jnp.arange(self.sp.E_max))
            return w, jnp.mean(losses)

        def server_local(w, y1_m, smashed_m, key_m):
            def step(carry, i):
                w, k = carry
                k, sk = jax.random.split(k)
                idx = jax.random.randint(sk, (self.bs,), 0, n)
                def loss_fn(w):
                    inv = dnn.inverse_server_forward(w, y1_m[idx], cfg)
                    return mutual.server_loss(inv, smashed_m[idx], tau)
                loss, g = jax.value_and_grad(loss_fn)(w)
                do = (i < e_steps).astype(jnp.float32)
                w = jax.tree.map(lambda p, gg: p - self.lr_s * do * gg, w, g)
                return (w, k), loss
            (w, _), losses = jax.lax.scan(step, (w, key_m),
                                          jnp.arange(self.sp.E_max))
            return w, jnp.mean(losses)

        keys = jax.random.split(key, 2 * M).reshape(2, M, -1)
        targets = jax.vmap(
            lambda y1: dnn.inverse_server_forward(w_s_inv, y1, cfg))(y_onehot)
        w_c_rep = jax.tree.map(lambda p: jnp.broadcast_to(p, (M,) + p.shape),
                               w_c)
        w_c_new, c_loss = jax.vmap(client_local)(w_c_rep, self.x, targets,
                                                 keys[0])
        smashed = jax.vmap(lambda w, x: dnn.client_forward(w, x, cfg))(
            w_c_new, self.x)
        smashed = jax.lax.stop_gradient(smashed)
        w_s_rep = jax.tree.map(lambda p: jnp.broadcast_to(p, (M,) + p.shape),
                               w_s_inv)
        w_s_new, s_loss = jax.vmap(server_local)(w_s_rep, y_onehot, smashed,
                                                 keys[1])
        wsum = jnp.maximum(jnp.sum(a_mask), 1.0)
        agg = lambda stk: jax.tree.map(
            lambda p: jnp.tensordot(a_mask, p, axes=1) / wsum, stk)
        return (agg(w_c_new), agg(w_s_new),
                jnp.sum(c_loss * a_mask) / wsum,
                jnp.sum(s_loss * a_mask) / wsum)

    def run_round(self):
        sp = self.sp
        a = select_trainers(self.E, sp, self.sel_state)
        b, self.E, _ = solve_p2(a, self.E, sp)
        self.sel_state = update_state(self.sel_state, a, b, sp)
        self.key, sub = jax.random.split(self.key)
        self.w_c, self.w_s_inv, closs, sloss = self._jit_round(
            self.w_c, self.w_s_inv, jnp.asarray(a, jnp.float32),
            jnp.asarray(self.E), sub)
        return float(closs), float(sloss)


def _seed_ce_loss(layers, x, y, cfg):
    logits = dnn.mlp_forward(layers, x, cfg.activation)
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


class _SeedFLBase:
    """Transcription of the seed _FLBase round (unmasked static-E scan)."""

    def __init__(self, cfg, sp, client_data, lr, E, batch_size, seed):
        self.cfg, self.sp, self.E, self.bs, self.lr = cfg, sp, E, batch_size, lr
        self.x = jnp.asarray(client_data["x"])
        self.y = jnp.asarray(client_data["y"])
        self.key = jax.random.PRNGKey(seed)
        self.params = dnn.init_mlp(jax.random.PRNGKey(seed + 1),
                                   cfg.layer_dims)
        self._jit_round = jax.jit(self._round_impl)

    def _round_impl(self, params, a_mask, key):
        M, n, _ = self.x.shape
        cfg = self.cfg

        def local(w, x_m, y_m, key_m):
            def step(carry, _):
                w, k = carry
                k, sk = jax.random.split(k)
                idx = jax.random.randint(sk, (self.bs,), 0, n)
                loss, g = jax.value_and_grad(_seed_ce_loss)(w, x_m[idx],
                                                            y_m[idx], cfg)
                w = jax.tree.map(lambda p, gg: p - self.lr * gg, w, g)
                return (w, k), loss
            (w, _), losses = jax.lax.scan(step, (w, key_m),
                                          jnp.arange(self.E))
            return w, jnp.mean(losses)

        rep = jax.tree.map(lambda p: jnp.broadcast_to(p, (M,) + p.shape),
                           params)
        keys = jax.random.split(key, M)
        w_new, losses = jax.vmap(local)(rep, self.x, self.y, keys)
        wsum = jnp.maximum(jnp.sum(a_mask), 1.0)
        agg = jax.tree.map(lambda p: jnp.tensordot(a_mask, p, axes=1) / wsum,
                           w_new)
        return agg, jnp.sum(losses * a_mask) / wsum

    def _train(self, a):
        self.key, sub = jax.random.split(self.key)
        self.params, loss = self._jit_round(self.params,
                                            jnp.asarray(a, jnp.float32), sub)
        return float(loss)


class _SeedFedAvg(_SeedFLBase):
    def __init__(self, cfg, sp, client_data, *, K, E, lr=0.05,
                 batch_size=32, seed=0):
        sp.omega = 1.0
        sp.S_m = np.zeros(sp.M)
        super().__init__(cfg, sp, client_data, lr, E, batch_size, seed)
        self.K = K
        self.rng = np.random.default_rng(seed)

    def run_round(self):
        a = np.zeros(self.sp.M)
        a[self.rng.choice(self.sp.M, self.K, replace=False)] = 1.0
        return self._train(a)


class _SeedSFL(_SeedFedAvg):
    def __init__(self, cfg, sp, client_data, *, K, E, lr=0.05,
                 batch_size=32, seed=0):
        # seed SFL did NOT touch omega/S_m; undo what _SeedFedAvg sets
        omega, s_m = sp.omega, sp.S_m
        super().__init__(cfg, sp, client_data, K=K, E=E, lr=lr,
                         batch_size=batch_size, seed=seed)
        sp.omega, sp.S_m = omega, s_m


class _SeedORANFed(_SeedFLBase):
    def __init__(self, cfg, sp, client_data, *, E, lr=0.05,
                 batch_size=32, seed=0):
        sp.omega = 1.0
        sp.S_m = np.zeros(sp.M)
        sp.Q_C = sp.Q_C + sp.Q_S
        sp.Q_S = np.zeros(sp.M)
        super().__init__(cfg, sp, client_data, lr, E, batch_size, seed)
        self.sel_state = initial_state(sp)

    def run_round(self):
        a = select_trainers(self.E, self.sp, self.sel_state)
        b = solve_bandwidth(a, self.E, self.sp)
        self.sel_state = update_state(self.sel_state, a, b, self.sp)
        return self._train(a)


# ---------------------------------------------------------------------------
# Parity tests
# ---------------------------------------------------------------------------

def _assert_params_close(got, want, atol):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=atol,
                                   rtol=0)


def test_splitme_engine_matches_seed(small_data):
    cd, test = small_data
    ref = _SeedSplitMe(DNN10, SystemParams(M=12, seed=0), cd, seed=0)
    tr = SplitMeTrainer(DNN10, SystemParams(M=12, seed=0), cd, test, seed=0)
    for _ in range(ROUNDS):
        ref_c, ref_s = ref.run_round()
        m = tr.run_round()
        assert abs(m.client_loss - ref_c) < 1e-5, (m.client_loss, ref_c)
        assert abs(m.server_loss - ref_s) < 1e-5, (m.server_loss, ref_s)
        assert m.E == ref.E
    _assert_params_close(tr.w_c, ref.w_c, atol=1e-6)
    _assert_params_close(tr.w_s_inv, ref.w_s_inv, atol=1e-6)


@pytest.mark.parametrize("name", ["fedavg", "sfl", "oranfed"])
def test_baseline_engines_match_seed(small_data, name):
    cd, test = small_data
    ref_cls, cls, kw = {
        "fedavg": (_SeedFedAvg, FedAvgTrainer, {"K": 4, "E": 5}),
        "sfl": (_SeedSFL, SFLTrainer, {"K": 4, "E": 5}),
        "oranfed": (_SeedORANFed, ORANFedTrainer, {"E": 5}),
    }[name]
    ref = ref_cls(DNN10, SystemParams(M=12, seed=0), cd, seed=0, **kw)
    tr = cls(DNN10, SystemParams(M=12, seed=0), cd, test, seed=0, **kw)
    for _ in range(ROUNDS):
        ref_loss = ref.run_round()
        m = tr.run_round()
        assert abs(m.client_loss - ref_loss) < 1e-5, (m.client_loss, ref_loss)
    _assert_params_close(tr.params, ref.params, atol=1e-6)


def test_sharded_round_matches_engine_host_mesh():
    """shard_map engine round == single-device engine round (1-device mesh:
    the aggregation order is identical, so parity is exact)."""
    from sharded_parity_check import run_check   # sibling test-dir module
    run_check(data_shards=1)


def test_sharded_round_matches_engine_multidevice():
    """Same parity on a REAL multi-device CPU mesh (4 forced host devices,
    clients sharded 2-per-device, cross-shard psum reassociation included).
    Runs in a subprocess because device count is fixed at first jax init."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    # the child needs the CPU's forced devices, never the parent's chip
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = f"{root / 'src'}:{env.get('PYTHONPATH', '')}"
    out = subprocess.run(
        [sys.executable, str(root / "tests" / "sharded_parity_check.py"),
         "4"],
        capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, f"stdout:{out.stdout}\nstderr:{out.stderr}"
    assert "PARITY_OK" in out.stdout


def test_shared_system_params_not_mutated(small_data):
    """Regression: the seed trainers overwrote omega/S_m/Q_C/Q_S in place on
    the caller's SystemParams, so sequential framework runs on one instance
    silently corrupted each other."""
    cd, test = small_data
    sp = SystemParams(M=12, seed=0)
    snap = {k: np.array(getattr(sp, k), copy=True)
            for k in ("Q_C", "Q_S", "S_m", "t_round")}
    omega, d_bits = sp.omega, sp.d_model_bits
    trainers = [
        SplitMeTrainer(DNN10, sp, cd, test, seed=0),
        FedAvgTrainer(DNN10, sp, cd, test, K=4, E=3, seed=0),
        ORANFedTrainer(DNN10, sp, cd, test, E=3, seed=0),
        SFLTrainer(DNN10, sp, cd, test, K=4, E=3, seed=0),
    ]
    for tr in trainers:
        tr.run_round()
    assert sp.omega == omega and sp.d_model_bits == d_bits
    for k, v in snap.items():
        np.testing.assert_array_equal(getattr(sp, k), v)
    # each trainer derived its own view
    assert trainers[1].sp.omega == 1.0
    assert trainers[2].sp.Q_S.sum() == 0.0
    assert trainers[0].sp.omega != omega
