"""A stand-in model kind for the reference's blocked path, and the run that
shows the reference holds a model too large to copy per client.

The kind is the ``mlp`` kind with ``client_block`` 1 and ``sample_block``
32.  ``config(width, depth)`` gives a SplitMe configuration whose client
c(.) is a dense stack 30 -> width x depth -> 2048 and whose server is
2048 -> 64 -> 3: at width 8192 and depth 12 the client holds 755.3 M
parameters (3.02 GB in float32), the size of one period of a hybrid
Mamba2 + attention sequence model.  It is not a configuration of the
benchmark and no cell runs it.

    python3 perfbench/tests/standin.py [--width 8192] [--depth 12] \
        [--fleet 50] [--trained 4] [--rounds 2] [--seed 0]

runs ``reference.run_campaign`` on it for ``--rounds`` rounds of a fleet
of ``--fleet`` RICs, with a ``train_mask`` that trains ``--trained``
clients per round (Table III's deadlines may select none for a model this
size), and prints one JSON line: the parameter count, the seconds, whether
every parameter and loss is finite, and the device's ``peak_bytes_in_use``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import run as harness  # noqa: E402

_mlp = harness.load_kind(harness.ROOT, "mlp")
program_model = _mlp.program_model
make_data = _mlp.make_data
init_params = _mlp.init_params
client_forward = _mlp.client_forward
full_forward = _mlp.full_forward
sizes = _mlp.sizes
forward_flops = _mlp.forward_flops
backward_flops = _mlp.backward_flops

client_block = 1
sample_block = 32


def config(width: int = 8192, depth: int = 12, fleet: int = 50) -> dict:
    """The committed SplitMe configuration with this kind's model and a
    fleet of ``fleet`` RICs."""
    cfg = json.loads((HERE.parent / "configs" / "splitme-dnn10-m50.json")
                     .read_text())
    cfg["model"].update(hidden=[width] * depth + [2048, 64],
                        split_index=depth + 1)
    cfg["fleet"]["M"] = fleet
    return cfg


def trained(k: int, seed: int):
    """A ``train_mask`` that trains ``k`` clients drawn from ``seed`` in
    every round."""
    import numpy as np

    def mask(a):
        rng = np.random.default_rng(seed)
        out = np.zeros_like(a)
        for row in out:
            row[rng.choice(a.shape[1], k, replace=False)] = 1.0
        return out
    return mask


def fits(width=8192, depth=12, fleet=50, k=4, rounds=2, seed=0) -> dict:
    """One reference campaign of one seed on the stand-in."""
    import jax
    import numpy as np
    import reference
    kind = sys.modules[__name__]
    cfg = config(width, depth, fleet)
    clients, test = make_data(cfg["data"], fleet,
                              cfg["fleet"]["samples_per_client"], seed)
    t = time.perf_counter()
    res = reference.run_campaign(kind, cfg, clients, test, rounds=rounds,
                                 seeds=[seed], train_mask=trained(k, seed))
    seconds = time.perf_counter() - t
    stats = jax.devices()[0].memory_stats() or {}
    leaves = jax.tree.leaves(res["params"])
    return {"client_params": sizes(cfg["model"])["client_params"],
            "rounds": rounds, "fleet": fleet, "trained_per_round": k,
            "E": [int(e) for e in res["E"]], "seconds": seconds,
            "finite": bool(all(np.isfinite(l).all() for l in leaves)
                           and np.isfinite(res["losses"]).all()),
            "accuracy": float(res["acc"][-1][0]),
            "device": jax.devices()[0].device_kind,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--width", type=int, default=8192)
    ap.add_argument("--depth", type=int, default=12)
    ap.add_argument("--fleet", type=int, default=50)
    ap.add_argument("--trained", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    print(json.dumps(fits(args.width, args.depth, args.fleet, args.trained,
                          args.rounds, args.seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
