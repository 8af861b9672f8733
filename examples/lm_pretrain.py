"""End-to-end LM pretraining driver on an assigned architecture.

    PYTHONPATH=src python examples/lm_pretrain.py --arch smollm-135m \
        --steps 300 --batch 2 --seq 64            # full ~135M params on CPU
    PYTHONPATH=src python examples/lm_pretrain.py --reduced --steps 20  # smoke

Exercises the same train_step the multi-pod dry-run lowers — data pipeline
(synthetic token stream), optimizer, checkpointing — on the host mesh.
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import io as ckpt
from repro.configs.base import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import build_model
from repro.runtime.steps import default_optimizer, make_train_step


def token_stream(vocab: int, batch: int, seq: int, seed: int = 0):
    """Synthetic Zipf-ish token pipeline (deterministic, sharded-friendly)."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    probs = (1.0 / ranks) / np.sum(1.0 / ranks)
    while True:
        yield rng.choice(vocab, size=(batch, seq), p=probs).astype(np.int32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--reduced", action="store_true",
                    help="2-layer reduced variant (CI smoke)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    else:
        import dataclasses
        cfg = dataclasses.replace(cfg, dtype="float32")
    model = build_model(cfg, remat=False)
    opt = default_optimizer(cfg)
    init_state, train_step = make_train_step(model, optimizer=opt, lr=args.lr)
    params, opt_state, step = init_state(jax.random.PRNGKey(0))
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n / 1e6:.1f}M optimizer={opt}")

    stream = token_stream(cfg.vocab_size, args.batch, args.seq)
    jstep = jax.jit(train_step, donate_argnums=(0, 1))
    t0 = time.time()
    for i in range(args.steps):
        batch = {"tokens": jnp.asarray(next(stream))}
        if cfg.frontend:
            batch["embeds"] = jnp.zeros(
                (args.batch, cfg.frontend_positions, cfg.d_model), cfg.dtype)
        params, opt_state, step, m = jstep(params, opt_state, step, batch)
        if i % max(1, args.steps // 10) == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss={float(m['loss']):.4f} "
                  f"({(time.time() - t0) / (i + 1):.2f}s/step)")
    if args.ckpt:
        ckpt.save(args.ckpt, params, metadata={"arch": cfg.name,
                                               "steps": args.steps})
        print(f"saved checkpoint to {args.ckpt}")


if __name__ == "__main__":
    main()
