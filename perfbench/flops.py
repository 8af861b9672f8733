"""Operations and bytes that a campaign's schedule needs, from its shapes.

Counts are of the work the schedule requires: the selected clients A_t and
their E_t executed local steps.  Padding of the cohort to a compile bucket,
masked steps past E_t and unselected clients are work the program may do
but the schedule does not need, so a roofline or utilization built on these
counts falls when the program does such work and can never pass 100%.

The client and whole model's operations per sample come from the
configuration's model kind (``perfbench/models/<kind>.py``, passed in as
``kind``); SplitMe's inverse model and the Step-4 Grams are dense stacks
over the kind's ``server_dims``, counted here.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

KL_FLOPS_PER_ELEMENT = 16   # per element of x and y: scale, max, shift,
# exp, sum and log of both softmaxes, then p_y * (log p_y - log p_x), sum


def inverse_dims(kind, model: dict) -> tuple:
    """SplitMe's inverse model s^-1(.): classes back to the cut."""
    return tuple(reversed(kind.sizes(model)["server_dims"]))


def weights(d: Sequence[int]) -> int:
    """Weight count of a dense stack (biases left out)."""
    return sum(d[i] * d[i + 1] for i in range(len(d) - 1))


def forward(d: Sequence[int]) -> int:
    """Multiply-adds of one sample through a dense stack, as operations."""
    return 2 * weights(d)


def backward(d: Sequence[int]) -> int:
    """One sample's backward pass: weight gradients of every layer and
    input gradients of every layer but the first (the data needs none)."""
    return 2 * weights(d) + 2 * (weights(d) - d[0] * d[1])


def train_flops(kind, config: dict, a: np.ndarray, E: np.ndarray,
                n_seeds: int) -> float:
    """Operations of the forward and backward passes of one campaign's
    schedule: a (R, M) selected sets, E (R,) local updates, all seeds."""
    model, hp = config["model"], config["hyper"]
    batch = hp["batch_size"]
    n = config["fleet"]["samples_per_client"]
    sel = np.asarray(a).sum(axis=1)
    steps = float(np.sum(sel * np.asarray(E)))       # sum_t |A_t| E_t
    if config["framework"] == "splitme":
        c_fwd = kind.forward_flops(model, "client")
        i = inverse_dims(kind, model)
        per_step = batch * (c_fwd + kind.backward_flops(model, "client")
                            + forward(i) + backward(i))
        # each round's targets: s^-1(Y_m) before the client phase, c(X_m)
        # of the updated client weights before the server phase
        targets = float(sel.sum()) * n * (forward(i) + c_fwd)
        total = steps * per_step + targets
    else:
        total = steps * batch * (kind.forward_flops(model, "full")
                                 + kind.backward_flops(model, "full"))
    return float(total) * n_seeds


def kl_work(kind, config: dict, a: np.ndarray, E: np.ndarray,
            n_seeds: int):
    """(operations, bytes) of the mutual-KL kernel's forward calls: one per
    phase per selected client per executed step, on (batch, d_split) logits
    read twice (x and the target y) and one (batch, 1) column written."""
    batch = config["hyper"]["batch_size"]
    d = kind.sizes(config["model"])["split_width"]
    item = np.dtype(config["compute_dtype"]).itemsize
    calls = 2.0 * float(np.sum(np.asarray(a).sum(axis=1) * np.asarray(E)))
    calls *= n_seeds
    ops = calls * KL_FLOPS_PER_ELEMENT * 2 * batch * d
    nbytes = calls * (2 * batch * d * item + batch * 4)
    return ops, nbytes


def gram_work(kind, config: dict, n_evals: int):
    """(operations, bytes) of the Step-4 Gram products: per eval and
    server layer l, O_aug^T O_aug and O_aug^T Z over every client's
    samples, O_aug the layer's input with a ones column; float32 inputs."""
    fl = config["fleet"]
    rows = fl["M"] * fl["samples_per_client"]
    s = kind.sizes(config["model"])["server_dims"]
    ops = nbytes = 0.0
    for l in range(len(s) - 1):
        d_in, d_out = s[l] + 1, s[l + 1]
        for d2 in (d_in, d_out):
            ops += 2.0 * rows * d_in * d2
            nbytes += 4.0 * (rows * (d_in + d2) + d_in * d2)
    return ops * n_evals, nbytes * n_evals


def roofline_seconds(ops: float, nbytes: float, peaks: dict):
    """The least time the chip could take, and which bound sets it."""
    t_ops = ops / (peaks["bf16_tflops"] * 1e12)
    t_mem = nbytes / (peaks["hbm_gb_s"] * 1e9)
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
