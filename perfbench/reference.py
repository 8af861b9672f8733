"""Plain float32 reference of the campaigns the benchmark times.

Written from the paper (arXiv 2508.02534: Alg. 1, problem P2, eq. 5 and
eq. 8-9, Table III) and the semantics the program documents, with no import
from the program.  Given the same fleet, client data and training seeds it
computes what one ``run_campaign`` call returns: the per-round schedule
(selected set A_t, local updates E_t), each round's phase losses, the
final test accuracy, and the final parameters of every seed.

The client model c(.) and FedAvg's whole model are the configuration's
model kind's (``perfbench/models/<kind>.py``, passed in as ``kind``); the
inverse model s^-1(.), the local SGD, the KL, the masked averaging, the
Step-4 ridge inversion and the planner are shared by every kind.

Semantics (shared with the program, by its documented contract):

* initialization: ``PRNGKey(seed + offset)`` (offset 0 for SplitMe, whose
  key splits into the client and inverse-server stacks; 1 for FedAvg); the
  inverse stack He normal weights and zero biases;
* round t of seed s: the round key comes off ``PRNGKey(seed)`` by one split
  per round; client m of phase p takes key ``p * M + m`` of its
  ``2M``-way (``M``-way for FedAvg) split; each local step splits that key
  and draws a batch of ``batch_size`` row indices with replacement;
* SplitMe round (eq. 5): the client phase trains c(.) towards the fixed
  targets s^-1(Y_m) of the round-start inverse model, then the server phase
  trains s^-1(.) towards c(X_m) of that client's updated weights; both
  losses are the temperature-softmax D_KL(x || y), y the target; the
  masked FedAvg average over A_t aggregates both stacks;
* FedAvg round: E local SGD steps of cross-entropy on K random clients;
* a round's phase loss is the mean over A_t of each client's mean loss
  over its E executed steps;
* eval: FedAvg's test accuracy of the aggregated model; SplitMe's Step 4
  (eq. 8-9) ridge-inverts s^-1 layer by layer over every client's data,
  then classifies the test set through c(.) and the recovered s(.).

Every matmul runs in float32 at HIGHEST precision (``run_campaign`` sets
it).  ``compute_dtype`` casts the inputs of the model's matmuls to a
narrower type with float32 accumulation: the control the comparison must
fail.

Memory: a kind may state ``client_block`` and ``sample_block`` (both
optional).  Without them a round trains all M clients at once, masked, and
every client forward takes all its samples at once.  With
``client_block`` a round trains only A_t's clients, that many at a time,
and carries the masked sums over the blocks; with ``sample_block`` every
client forward (the round's smashed data, Step 4, the test set) runs that
many samples at a time.  The reference's memory then grows with the
blocks and not with M: a model too large to copy per client still fits.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# The fleet (Table III) and the host-side schedule (Alg. 1, P2, fixed K)
# ---------------------------------------------------------------------------

B = 1e9            # total uplink bandwidth (bit/s)
P_C = 1.0          # per-unit communication cost
P_TR = 1.0         # per-unit computation cost
B_MIN = 1.0 / 50   # minimum bandwidth share
RHO = 0.8          # Pareto trade-off of eq. 20
ALPHA = 0.7        # Alg. 1 heuristic factor
EPS = 0.1          # target accuracy level of K_eps
E_MAX = 20         # largest admissible number of local updates
GENERIC_S_M = 1e6  # bits of smashed data before the split is known
GENERIC_OMEGA = 0.2
GENERIC_D = 8e6    # bits of the whole model before it is known


def fleet(M: int, seed: int) -> Dict[str, np.ndarray]:
    """Per-RIC compute times per local update and slice deadlines:
    Q_C ~ U(0.34, 0.46) ms, Q_S ~ U(1.2, 1.6) ms, t_round ~ U(50, 100) ms."""
    rng = np.random.default_rng(seed)
    return {"Q_C": rng.uniform(0.34e-3, 0.46e-3, M),
            "Q_S": rng.uniform(1.2e-3, 1.6e-3, M),
            "t_round": rng.uniform(50e-3, 100e-3, M)}


def _uplink(a, b, size):
    """eq. 19 with unit channel gain."""
    t = size / np.maximum(b * B, 1e-12)
    return np.where(a > 0, t, 0.0)


def _bandwidth(a, E, fl, size):
    """Exact min-max bandwidth split of P2 for a fixed E: the shares that
    equalize E*Q_C + T_co over the selected set, found by bisection on the
    common finish time, then the b_min box by clip and renormalise."""
    sel = np.where(a > 0)[0]
    b = np.zeros(len(a))
    if len(sel) == 0:
        return b
    s = size[sel]
    offs = E * fl["Q_C"][sel]

    def excess(tau):
        return float(np.sum(s / (B * np.maximum(tau - offs, 1e-12))) - 1.0)

    lo = float(np.max(offs)) + 1e-9
    hi = lo + float(np.sum(s)) / B + 1.0
    while excess(hi) > 0:
        hi *= 2.0
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        if excess(mid) > 0:
            lo = mid
        else:
            hi = mid
    bs = s / (B * np.maximum(hi - offs, 1e-12))
    for _ in range(len(sel)):
        low = bs < B_MIN
        if not low.any():
            break
        fixed = np.sum(np.where(low, B_MIN, 0.0))
        free = ~low
        if fixed >= 1.0 or not free.any():
            bs = np.full(len(sel), 1.0 / len(sel))
            break
        bs = np.where(low, B_MIN, bs * (1.0 - fixed) / np.sum(bs[free]))
    b[sel] = bs / bs.sum()
    return b


def _objective(a, b, E, fl, size):
    """eq. 22: K_eps(E) * cost, cost of eq. 20 (eq. 16-18)."""
    k_eps = (E + 1) ** 2 / (E ** 2 * EPS ** 2)
    r_co = float(np.sum(a * b) * B * P_C)
    r_cp = float(np.sum(a * E * (fl["Q_C"] + fl["Q_S"])) * P_TR)
    t_co = _uplink(a, b, size)
    t1 = np.max(np.where(a > 0, E * fl["Q_C"] + t_co, -np.inf))
    t2 = np.max(np.where(a > 0, E * fl["Q_S"], -np.inf))
    latency = float(t1 + t2) if a.sum() else 0.0
    return k_eps * (RHO * (r_co / B + r_cp) + (1 - RHO) * latency)


def plan_splitme(M: int, fleet_seed: int, rounds: int, sizes: dict,
                 n_per_client: int, e_initial: int):
    """Alg. 1 selection plus P2's bandwidth and adaptive E (E never
    increases), round by round, for a model of the kind's ``sizes``:
    S_m from the split width, d_model_bits and omega from the client and
    inverse parameter counts.  Returns a (R, M) and E (R,)."""
    fl = fleet(M, fleet_seed)
    # Alg. 1's pessimistic first estimate, from the generic payload
    t0 = float(np.max(M * (GENERIC_S_M + GENERIC_OMEGA * GENERIC_D) / B))
    pc_c, pc_i = sizes["client_params"], sizes["inverse_params"]
    d_bits = 32.0 * (pc_c + pc_i)
    omega = pc_c / (pc_c + pc_i)
    size = (np.full(M, n_per_client * sizes["split_width"] * 32.0)
            + omega * d_bits)
    t_k = t_km1 = t0
    E = e_initial
    a_l, e_l = [], []
    for _ in range(rounds):
        t_est = ALPHA * t_k + (1 - ALPHA) * t_km1
        a = (E * (fl["Q_C"] + fl["Q_S"]) + t_est
             <= fl["t_round"]).astype(np.float64)
        if a.sum() == 0:
            a[np.argmin(E * (fl["Q_C"] + fl["Q_S"]) - fl["t_round"])] = 1.0
        best = None
        for e in range(1, E_MAX + 1):
            b = _bandwidth(a, e, fl, size)
            val = _objective(a, b, e, fl, size)
            if best is None or val < best[2]:
                best = (b, e, val)
        b, e_hat, _ = best
        if e_hat > E:
            e_hat = E
            b = _bandwidth(a, e_hat, fl, size)
        E = e_hat
        realized = float(np.max(_uplink(a, b, size))) if a.sum() else t_k
        t_k, t_km1 = ALPHA * t_k + (1 - ALPHA) * realized, t_k
        a_l.append(a)
        e_l.append(E)
    return np.stack(a_l), np.asarray(e_l, np.int32)


def plan_fixed_k(M: int, rounds: int, K: int, E: int, policy_seed: int):
    """FedAvg: K clients drawn uniformly without replacement each round."""
    rng = np.random.default_rng(policy_seed)
    a = np.zeros((rounds, M))
    for t in range(rounds):
        a[t, rng.choice(M, K, replace=False)] = 1.0
    return a, np.full(rounds, E, np.int32)


# ---------------------------------------------------------------------------
# The dense stack: SplitMe's inverse model s^-1(.), Step 4's recovered
# server s(.), and the layers of the ``mlp`` kind (``models/mlp.py``)
# ---------------------------------------------------------------------------

def init_mlp(key, dims):
    layers = []
    for i, k in enumerate(jax.random.split(key, len(dims) - 1)):
        w = jax.random.normal(k, (dims[i], dims[i + 1]), jnp.float32)
        layers.append({"w": w * jnp.sqrt(2.0 / dims[i]),
                       "b": jnp.zeros((dims[i + 1],), jnp.float32)})
    return layers


def mlp(layers, x, final_relu: bool, dt=None):
    """Dense layers with ReLU between them (and after the last one when
    ``final_relu``).  ``dt`` narrows the matmul inputs, f32 accumulate."""
    for i, p in enumerate(layers):
        if dt is None:
            x = x @ p["w"] + p["b"]
        else:
            x = jnp.dot(x.astype(dt), p["w"].astype(dt),
                        preferred_element_type=jnp.float32) + p["b"]
        if i < len(layers) - 1 or final_relu:
            x = jax.nn.relu(x)
    return x


def kl(x, y, tau):
    """Mean over rows of D_KL(x || y) = sum p_y (log p_y - log p_x), y the
    fixed target, both through a softmax at temperature tau."""
    logp_x = jax.nn.log_softmax(x / tau, -1)
    logp_y = jax.nn.log_softmax(jax.lax.stop_gradient(y) / tau, -1)
    return jnp.mean(jnp.sum(jnp.exp(logp_y) * (logp_y - logp_x), -1))


def cross_entropy(logits, y):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def _local_sgd(w, data, target, key, E, loss_fn, lr, batch):
    """E SGD steps on batches drawn with replacement; returns the weights
    and the mean loss over the E steps."""
    n = data.shape[0]

    def step(i, carry):
        w, k, total = carry
        k, sk = jax.random.split(k)
        idx = jax.random.randint(sk, (batch,), 0, n)
        loss, g = jax.value_and_grad(loss_fn)(w, data[idx], target[idx])
        w = jax.tree.map(lambda p, gp: p - lr * gp, w, g)
        return w, k, total + loss

    w, _, total = jax.lax.fori_loop(0, E, step, (w, key, jnp.float32(0.0)))
    return w, total / E


def _average(stacked, a):
    """Masked FedAvg over the client axis."""
    wsum = jnp.maximum(jnp.sum(a), 1.0)
    return jax.tree.map(lambda p: jnp.tensordot(a, p, axes=1) / wsum,
                        stacked)


def _in_blocks(fn, x, block):
    """``fn`` over the rows of ``x``, ``block`` rows at a time (all at once
    where ``block`` is None): a scan over the whole blocks, then one call
    on the rest."""
    if block is None or block >= x.shape[0]:
        return fn(x)
    n = x.shape[0] // block * block
    out = jax.lax.map(fn, x[:n].reshape((-1, block) + x.shape[1:]))
    out = out.reshape((n,) + out.shape[2:])
    if n < x.shape[0]:
        out = jnp.concatenate([out, fn(x[n:])])
    return out


def _blocked_average(train, params, n_losses, a, sel, n_sel, block):
    """The masked FedAvg of a round that trains only A_t's clients,
    ``block`` at a time: ``train(m)`` gives client m's trained parameters
    and losses.  ``sel`` lists A_t's clients in its first ``n_sel`` slots
    and pads them to whole blocks; blocks past the last client never run,
    and the padding of the last block is selected away, not weighted by 0,
    so nothing of a client that was not trained reaches the sums.  Carries
    sum a_m w_m and sum a_m loss_m and divides once, as ``_average``."""
    ids = sel.reshape(-1, block)

    def body(i, acc):
        m = ids[i]
        live = i * block + jnp.arange(block) < n_sel

        def add(total, out):
            mask = live.reshape((block,) + (1,) * (out.ndim - 1))
            wa = a[m].reshape(mask.shape)
            return total + jnp.sum(jnp.where(mask, wa * out, 0.0), 0)

        return jax.tree.map(add, acc, jax.vmap(train)(m))

    zeros = jax.tree.map(jnp.zeros_like, (params, jnp.zeros(n_losses)))
    sums = jax.lax.fori_loop(0, (n_sel + block - 1) // block, body, zeros)
    wsum = jnp.maximum(jnp.sum(a), 1.0)
    return jax.tree.map(lambda s: s / wsum, sums)


def splitme_round(kind, params, x, y1, a, E, key, hp, dt=None, sel=None,
                  n_sel=None):
    """One SplitMe round of one seed over all M clients (a masks A_t), or,
    given ``sel`` and ``n_sel``, over A_t's alone in the kind's
    ``client_block``: the client model is the kind's, the inverse model a
    dense stack."""
    wc, wi = params
    M = x.shape[0]
    keys = jax.random.split(key, 2 * M).reshape(2, M, -1)
    tau, batch = hp["temperature"], hp["batch_size"]
    sample_block = getattr(kind, "sample_block", None)

    def client_loss(w, xb, tb):
        return kl(kind.client_forward(w, xb, dt), tb, tau)

    def server_loss(w, yb, tb):
        return kl(mlp(w, yb, False, dt), tb, tau)

    def per_client(xm, y1m, kc, ks):
        tgt = mlp(wi, y1m, False, dt)                 # s^-1(Y_m), fixed
        wc_m, lc = _local_sgd(wc, xm, tgt, kc, E, client_loss, hp["lr_c"],
                              batch)
        smashed = jax.lax.stop_gradient(_in_blocks(
            lambda xb: kind.client_forward(wc_m, xb, dt), xm, sample_block))
        wi_m, ls = _local_sgd(wi, y1m, smashed, ks, E, server_loss,
                              hp["lr_s"], batch)
        return wc_m, wi_m, lc, ls

    if sel is not None:
        def train(m):
            wc_m, wi_m, lc, ls = per_client(x[m], y1[m], keys[0][m],
                                            keys[1][m])
            return (wc_m, wi_m), jnp.stack([lc, ls])
        return _blocked_average(train, params, 2, a, sel, n_sel,
                                kind.client_block)
    wc_all, wi_all, lc, ls = jax.vmap(per_client)(x, y1, keys[0], keys[1])
    wsum = jnp.maximum(jnp.sum(a), 1.0)
    losses = jnp.stack([jnp.sum(a * lc), jnp.sum(a * ls)]) / wsum
    return (_average(wc_all, a), _average(wi_all, a)), losses


def fedavg_round(kind, params, x, y, a, E, key, hp, dt=None, sel=None,
                 n_sel=None):
    """One FedAvg round of one seed over all M clients (a masks A_t), or,
    given ``sel`` and ``n_sel``, over A_t's alone in the kind's
    ``client_block``."""
    (w,) = params
    M = x.shape[0]
    keys = jax.random.split(key, M)

    def loss_fn(w, xb, yb):
        return cross_entropy(kind.full_forward(w, xb, dt), yb)

    def local(xm, ym, k):
        return _local_sgd(w, xm, ym, k, E, loss_fn, hp["lr"],
                          hp["batch_size"])

    if sel is not None:
        def train(m):
            w_m, l_m = local(x[m], y[m], keys[m])
            return (w_m,), l_m[None]
        return _blocked_average(train, params, 1, a, sel, n_sel,
                                kind.client_block)
    w_all, l = jax.vmap(local)(x, y, keys)
    return (_average(w_all, a),), jnp.sum(a * l)[None] / jnp.maximum(
        jnp.sum(a), 1.0)


def ridge_invert(wi, smashed, y1, gamma):
    """Step 4 (eq. 8-9): recover s(.) layer by layer from s^-1(.)."""
    acts, h = [], y1
    for i, p in enumerate(wi):
        h = h @ p["w"] + p["b"]
        if i < len(wi) - 1:
            h = jax.nn.relu(h)
        acts.append(h)
    L = len(wi)
    targets = [acts[L - 1 - l] for l in range(1, L)] + [y1]
    server, o = [], smashed
    for l, z in enumerate(targets):
        o_aug = jnp.concatenate([o, jnp.ones((o.shape[0], 1), o.dtype)], -1)
        a0 = o_aug.T @ o_aug
        a1 = o_aug.T @ z
        w_aug = jnp.linalg.solve(a0 + gamma * jnp.eye(a0.shape[0]), a1)
        server.append({"w": w_aug[:-1], "b": w_aug[-1]})
        o = o @ w_aug[:-1] + w_aug[-1]
        if l < len(targets) - 1:
            o = jax.nn.relu(o)
    return server


def splitme_accuracy(kind, params, x, y1, x_test, y_test, gamma, dt=None):
    wc, wi = params
    block = getattr(kind, "sample_block", None)

    def client(xb):
        return kind.client_forward(wc, xb, dt)

    smashed = _in_blocks(client, x.reshape((-1,) + x.shape[2:]), block)
    server = ridge_invert(wi, smashed, y1.reshape(-1, y1.shape[-1]), gamma)
    logits = mlp(server, _in_blocks(client, x_test, block), False)
    return jnp.mean((jnp.argmax(logits, -1) == y_test).astype(jnp.float32))


def fedavg_accuracy(kind, params, x_test, y_test, dt=None):
    logits = _in_blocks(lambda xb: kind.full_forward(params[0], xb, dt),
                        x_test, getattr(kind, "sample_block", None))
    return jnp.mean((jnp.argmax(logits, -1) == y_test).astype(jnp.float32))


# ---------------------------------------------------------------------------
# A whole campaign
# ---------------------------------------------------------------------------

def init_params(kind, framework: str, model: dict, seed: int):
    if framework == "splitme":
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        inverse = tuple(reversed(kind.sizes(model)["server_dims"]))
        return (kind.init_params(framework, model, k1),
                init_mlp(k2, inverse))
    if framework == "fedavg":
        return (kind.init_params(framework, model,
                                 jax.random.PRNGKey(seed + 1)),)
    raise KeyError(f"the reference has no framework {framework!r}")


def _round_seeds(params, a, E, keys, data, kind, framework, hp_items,
                 n_classes, dt, sel=None, n_sel=None):
    """One round of every seed; ``sel``/``n_sel`` as ``splitme_round``."""
    hp = dict(hp_items)
    ks = jax.vmap(jax.random.split)(keys)
    nkeys, subs = ks[:, 0], ks[:, 1]
    if framework == "splitme":
        y1 = jax.nn.one_hot(data["y"], n_classes)
        fn = lambda p, k: splitme_round(kind, p, data["x"], y1, a, E, k, hp,
                                        dt, sel, n_sel)
    else:
        fn = lambda p, k: fedavg_round(kind, p, data["x"], data["y"], a, E,
                                       k, hp, dt, sel, n_sel)
    params, losses = jax.vmap(fn)(params, subs)
    return params, losses, nkeys


_ROUND_STATIC = ("kind", "framework", "hp_items", "n_classes", "dt")
_round = jax.jit(_round_seeds, static_argnames=_ROUND_STATIC)
# the blocked path's round gives its input parameters' buffers to its
# output: a round never holds two copies of a large model
_round_blocked = jax.jit(_round_seeds, static_argnames=_ROUND_STATIC,
                         donate_argnames="params")


def selected(a: np.ndarray, block: int):
    """Each round's selected clients for the blocked round: (R, P) int32
    indices, P the largest |A_t| rounded up to a whole ``block`` (so one
    campaign compiles one round), each row padded with its first client,
    and (R,) the count of each row's real entries."""
    n = (a > 0).sum(axis=1)
    width = max(-(-int(n.max()) // block), 1) * block
    sel = np.zeros((len(a), width), np.int32)
    for t, row in enumerate(a):
        idx = np.flatnonzero(row > 0)
        if len(idx):
            sel[t] = np.concatenate([idx, np.full(width - len(idx), idx[0])])
    return sel, n.astype(np.int32)


@functools.partial(jax.jit, static_argnames=("kind", "framework", "gamma",
                                             "n_classes", "dt"))
def _accuracy(params, data, kind, framework, gamma, n_classes, dt):
    if framework == "splitme":
        y1 = jax.nn.one_hot(data["y"], n_classes)
        fn = lambda p: splitme_accuracy(kind, p, data["x"], y1,
                                        data["x_test"], data["y_test"], gamma,
                                        dt)
    else:
        fn = lambda p: fedavg_accuracy(kind, p, data["x_test"],
                                       data["y_test"], dt)
    return jax.vmap(fn)(params)


def accuracy(kind, config: dict, clients, test, params) -> np.ndarray:
    """Test accuracy per seed of seed-stacked final parameters, by the
    reference's own eval (Step 4 for SplitMe), in float32 at HIGHEST."""
    data = {"x": jnp.asarray(clients["x"]), "y": jnp.asarray(clients["y"]),
            "x_test": jnp.asarray(test[0]), "y_test": jnp.asarray(test[1])}
    params = jax.tree.map(lambda p: jnp.asarray(p, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        return np.asarray(_accuracy(
            params, data, kind, config["framework"], config["eval_gamma"],
            kind.sizes(config["model"])["n_classes"], None))


def plan(kind, config: dict, rounds: int, seeds) -> tuple:
    """The reference schedule of one campaign: a (R, M), E (R,)."""
    fw, hp = config["framework"], config["hyper"]
    M = config["fleet"]["M"]
    if fw == "splitme":
        return plan_splitme(M, config["fleet"]["seed"], rounds,
                            kind.sizes(config["model"]),
                            config["fleet"]["samples_per_client"],
                            hp["e_initial"])
    return plan_fixed_k(M, rounds, hp["K"], hp["E"], int(min(seeds)))


def run_campaign(kind, config: dict, clients, test, *, rounds: int, seeds,
                 compute_dtype=None, train_mask=None) -> dict:
    """The reference of one ``run_campaign`` call.  Returns the schedule,
    the initial and final parameters (stacked over seeds), losses
    (S, R, phases) and the final accuracy per seed (the last row of an
    (R, S) array, as the program reports it; the comparison reads no
    other eval round).
    ``train_mask(a)``, if given, replaces the selected sets the rounds
    train on (the schedule returned stays the planned one): a planted
    fault for the calibration."""
    fw = config["framework"]
    a, E = plan(kind, config, rounds, seeds)
    a_train = a if train_mask is None else train_mask(a)
    hp = {k: v for k, v in config["hyper"].items()
          if k in ("lr", "lr_c", "lr_s", "temperature", "batch_size")}
    data = {"x": jnp.asarray(clients["x"]), "y": jnp.asarray(clients["y"]),
            "x_test": jnp.asarray(test[0]), "y_test": jnp.asarray(test[1])}
    n_classes = kind.sizes(config["model"])["n_classes"]
    block = getattr(kind, "client_block", None)
    if block is None:
        step, blocks = _round, [{}] * rounds
    else:
        sel, n_sel = selected(a_train, block)
        step = _round_blocked
        blocks = [{"sel": jnp.asarray(s), "n_sel": jnp.int32(n)}
                  for s, n in zip(sel, n_sel)]
    with jax.default_matmul_precision("highest"):
        init = jax.tree.map(
            lambda *l: jnp.stack(l),
            *[init_params(kind, fw, config["model"], int(s)) for s in seeds])
        init_host = jax.device_get(init)    # before a round donates it
        params = init
        keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
        losses, acc = [], np.full((rounds, len(seeds)), np.nan)
        for t in range(rounds):
            params, l, keys = step(
                params, jnp.asarray(a_train[t], jnp.float32),
                jnp.int32(E[t]), keys, data, kind, fw,
                tuple(sorted(hp.items())), n_classes, compute_dtype,
                **blocks[t])
            losses.append(l)
        acc[-1] = np.asarray(_accuracy(params, data, kind, fw,
                                       config["eval_gamma"], n_classes,
                                       compute_dtype))
        losses = np.stack([np.asarray(l) for l in losses], axis=1)
    return {"a": a, "E": E, "init": init_host,
            "params": jax.device_get(params), "losses": losses,
            "acc": acc}
