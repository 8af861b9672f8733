"""The benchmark's own copy of the seeded O-RAN slice-traffic generator.

A copy of ``generate``, ``train_test_split`` and ``partition_non_iid``
from ``repro.data.oran``, kept here so that the benchmark's inputs do not
move when the program changes: the same ``--seed`` gives the same client
and test data on every commit.

Each sample is a 30-feature KPI vector with class-conditional structure
(eMBB, mMTC, URLLC); every near-RT-RIC holds samples of one slice class
only, assigned round-robin (paper §V-A).
"""
from __future__ import annotations

from typing import Dict

import numpy as np

N_FEATURES = 30
N_CLASSES = 3          # 0 = eMBB, 1 = mMTC, 2 = URLLC


def _class_stats(rng: np.random.Generator) -> np.ndarray:
    base = rng.normal(0.0, 1.0, (1, N_FEATURES))
    means = np.repeat(base, N_CLASSES, axis=0)
    means[0, 0:6] += 2.0     # eMBB: throughput / PRB / buffer KPIs
    means[1, 6:12] += 2.0    # mMTC: connection density / small packets
    means[2, 12:18] += 2.0   # URLLC: latency / reliability KPIs
    means[0, 12:15] += 0.8   # cross-talk between classes
    means[2, 0:3] += 0.8
    means[1, 12:15] += 0.6
    return means


def generate(n_per_class: int = 2000, seed: int = 0, noise: float = 2.2,
             label_noise: float = 0.03):
    """Returns (X, y) shuffled, X standardised."""
    rng = np.random.default_rng(seed)
    means = _class_stats(rng)
    xs, ys = [], []
    for c in range(N_CLASSES):
        f = rng.normal(0.0, 1.0, (n_per_class, 1))
        x = means[c] + noise * rng.normal(0.0, 1.0, (n_per_class, N_FEATURES))
        x += 0.5 * f
        lbl = np.full(n_per_class, c)
        flip = rng.random(n_per_class) < label_noise
        lbl = np.where(flip, rng.integers(0, N_CLASSES, n_per_class), lbl)
        xs.append(x)
        ys.append(lbl)
    X = np.concatenate(xs).astype(np.float32)
    y = np.concatenate(ys).astype(np.int32)
    X = (X - X.mean(0)) / (X.std(0) + 1e-6)
    idx = rng.permutation(len(y))
    return X[idx], y[idx]


def partition_non_iid(X: np.ndarray, y: np.ndarray, n_clients: int,
                      samples_per_client: int, seed: int = 0
                      ) -> Dict[str, np.ndarray]:
    """One slice class per client (round-robin): x (M, n, d), y (M, n)."""
    rng = np.random.default_rng(seed)
    by_class = [np.where(y == c)[0] for c in range(N_CLASSES)]
    Xc = np.zeros((n_clients, samples_per_client, X.shape[1]), np.float32)
    yc = np.zeros((n_clients, samples_per_client), np.int32)
    for m in range(n_clients):
        take = rng.choice(by_class[m % N_CLASSES], samples_per_client,
                          replace=True)
        Xc[m], yc[m] = X[take], y[take]
    return {"x": Xc, "y": yc}


def train_test_split(X, y, test_frac: float = 0.2, seed: int = 0):
    rng = np.random.default_rng(seed)
    idx = rng.permutation(len(y))
    n_test = int(len(y) * test_frac)
    te, tr = idx[:n_test], idx[n_test:]
    return (X[tr], y[tr]), (X[te], y[te])


def make(data: dict, n_clients: int, samples_per_client: int, seed: int):
    """Client shards and test set of one run, all from ``seed``."""
    X, y = generate(data["n_per_class"], seed=seed, noise=data["noise"],
                    label_noise=data["label_noise"])
    (Xtr, ytr), test = train_test_split(X, y, data["test_frac"], seed=seed)
    clients = partition_non_iid(Xtr, ytr, n_clients, samples_per_client,
                                seed=seed)
    return clients, test
