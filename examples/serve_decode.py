"""Batched serving example: prefill + decode with the ring-buffer KV cache.

    PYTHONPATH=src python examples/serve_decode.py --arch rwkv6-1.6b \
        --requests 4 --prompt-len 32 --new-tokens 16

Uses the same serve_step the decode_32k / long_500k dry-runs lower; on CPU
the reduced config keeps it interactive.  Demonstrates O(1)-state decode for
SSM archs and sliding-window KV for attention archs (--window).
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import build_model
from repro.runtime.steps import make_serve_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--window", type=int, default=None,
                    help="sliding-window KV size (sub-quadratic decode)")
    ap.add_argument("--full", action="store_true",
                    help="full config instead of the reduced variant")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    model = build_model(cfg, remat=False, decode_window=args.window)
    params = model.init(jax.random.PRNGKey(0))
    serve = jax.jit(make_serve_step(model))

    B = args.requests
    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (B, args.prompt_len), 0, cfg.vocab_size)
    # prefill: replay the prompt through the decode path (cache warm-up)
    cache = model.init_cache(params, B, prefill_len=0)
    t0 = time.time()
    for t in range(args.prompt_len):
        logits, cache = model.decode_step(
            params, prompts[:, t:t + 1], cache,
            position=jnp.asarray(t, jnp.int32))
    print(f"prefill {args.prompt_len} tokens x {B} requests: "
          f"{time.time() - t0:.2f}s")

    tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for i in range(args.new_tokens - 1):
        logits, cache = serve(params, tok, cache)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out.append(tok)
    dt = time.time() - t0
    gen = jnp.concatenate(out, axis=1)
    print(f"decoded {args.new_tokens} tokens x {B} requests in {dt:.2f}s "
          f"({B * args.new_tokens / max(dt, 1e-9):.1f} tok/s)")
    print("sample token ids:", gen[0].tolist())


if __name__ == "__main__":
    main()
