"""GLASU beyond graphs: vertical-split transformer training (~100M params).

The paper's technique as a backbone feature: the hidden dimension is split
into M=4 feature shards; only every 2nd layer aggregates across shards
(lazy aggregation, K=L/2) and each sampled batch is reused for Q=2 stale
local microsteps. Trains a ~100M-param LM on a synthetic bigram stream for a
few hundred steps and prints the loss curve.

    PYTHONPATH=src python examples/transformer_glasu.py [--steps 200]
"""
import argparse
import time

import jax

from repro.configs.base import ArchConfig, GlasuSplit
from repro.core.steps import make_train_step
from repro.data.pipeline import TokenStream
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = ArchConfig(
        name="glasu-tp-20m", kind="dense",
        n_layers=6, d_model=384, n_heads=12, n_kv=4, d_head=32,
        d_ff=1024, vocab=8192, dtype="float32", optimizer="adamw", lr=1e-3,
        remat=False,
        glasu=GlasuSplit(n_clients=4, sync_every=2, local_steps=2),
    )
    print(f"params ~= {cfg.param_count() / 1e6:.0f}M "
          f"(block-diagonal lazy layers shrink this vs dense)")

    init_state, train_step = make_train_step(cfg)
    state = init_state(jax.random.PRNGKey(0))
    step = jax.jit(train_step)
    stream = TokenStream(cfg.vocab, seed=0)

    t0 = time.time()
    for i in range(args.steps):
        tokens, labels = stream.batch(args.batch, args.seq)
        state, metrics = step(state, {"tokens": tokens, "labels": labels})
        if i % 20 == 0 or i == args.steps - 1:
            print(f"step {int(state.step):4d}  loss={float(metrics['loss']):.3f}"
                  f"  ({time.time() - t0:.0f}s)")


if __name__ == "__main__":
    main()
