"""Step-4 analytic inversion: one-shot quality + cost (paper §III-B / Fig. 2).

Compares the inverted server model's accuracy against the mutual-training
ceiling, and times the distributed least-squares (Gram + solve) — the single
extra communication round SplitMe pays at the end.
"""
from __future__ import annotations

import jax

from benchmarks.common import Row, pallas_interpreter_off_tpu, time_fn
from repro.configs.splitme_dnn import DNN10
from repro.core.cost import SystemParams
from repro.core.splitme import SplitMeTrainer
from repro.data import oran


def run(fast: bool = False):
    X, y = oran.generate(n_per_class=800, seed=0)
    (Xtr, ytr), (Xte, yte) = oran.train_test_split(X, y)
    cd = oran.partition_non_iid(Xtr, ytr, 50, samples_per_client=64, seed=0)
    tr = SplitMeTrainer(DNN10, SystemParams(seed=0), cd, (Xte, yte), seed=0)
    for _ in range(4 if fast else 12):
        tr.run_round()

    us_jnp = time_fn(lambda: jax.tree.map(
        lambda x: x.block_until_ready() if hasattr(x, "block_until_ready")
        else x, tr.finalize(use_kernel=False)), iters=2)
    acc_jnp = tr.evaluate(tr.finalize(use_kernel=False))
    with pallas_interpreter_off_tpu():
        acc_kernel = tr.evaluate(tr.finalize(use_kernel=True))
    rows: list[Row] = [
        ("step4_inversion_jnp", us_jnp, f"acc={acc_jnp:.3f}"),
        ("step4_inversion_pallas", us_jnp, f"acc={acc_kernel:.3f}"),
    ]
    assert abs(acc_jnp - acc_kernel) < 0.02, "kernel path diverges from jnp"
    return rows
