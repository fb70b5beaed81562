"""Batched greedy decoding through the per-layer KV caches.

Serves a small SmolLM-family model: prefills a prompt batch, then decodes
tokens autoregressively with the same cache machinery the decode_32k /
long_500k dry-run shapes exercise (including the sliding-window ring cache).

    PYTHONPATH=src python examples/serve_decode.py [--new-tokens 32]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tfm


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--window", type=int, default=0,
                    help=">0: sliding-window ring cache")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_reduced("smollm_360m")
    if args.window:
        cfg = cfg.with_(sliding_window=args.window)
    params = tfm.init_lm(jax.random.PRNGKey(0), cfg)

    total = args.prompt_len + args.new_tokens
    caches = tfm.init_caches(cfg, args.batch, total)
    step = jax.jit(lambda c, tok: tfm.lm_decode_step(params, c, cfg, tok))

    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab,
                                      size=(args.batch, args.prompt_len)),
                         jnp.int32)
    # prefill by streaming the prompt through the decode path
    tok = prompt[:, 0:1]
    for i in range(args.prompt_len):
        nxt, caches = step(caches, prompt[:, i:i + 1])
    out = [nxt]
    t0 = time.time()
    for _ in range(args.new_tokens - 1):
        nxt, caches = step(caches, out[-1])
        out.append(nxt)
    dt = time.time() - t0
    gen = jnp.concatenate(out, axis=1)
    print(f"cache: {'ring(window=%d)' % args.window if args.window else 'full'}")
    print(f"generated {gen.shape} tokens, "
          f"{args.batch * (args.new_tokens - 1) / dt:.1f} tok/s (CPU)")
    for b in range(min(args.batch, 2)):
        print(f"  seq{b}: {list(np.asarray(gen[b][:12]))} ...")


if __name__ == "__main__":
    main()
