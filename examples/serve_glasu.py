"""Serve a trained GLASU model: checkpoint -> session -> queries.

    PYTHONPATH=src python examples/serve_glasu.py

Trains a short run to a checkpoint (the quickstart recipe with
checkpointing on), restores PARAMS ONLY into an ``InferenceSession``
(optimizer and error-feedback state are never read), and fires a small
query mix:

  * a **cold** batch — full receptive-field plan, cross-client embedding
    exchange at every aggregation layer, bytes metered per fresh row;
  * the same batch **warm** — every node hits the hot-node aggregate
    cache at the top layer, no exchange, zero wire bytes, bitwise-equal
    logits;
  * the cold mix again on an **int8-compressed** session from the same
    checkpoint — same answers within codec tolerance, ~3x fewer bytes.

The micro-batcher at the end shows concurrent single-node requests
coalescing into one padded dispatch.
"""
import tempfile

import numpy as np

from repro.api import Trainer, get_preset
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import InferenceSession, MicroBatcher, ServeConfig


def main():
    enable_compile_cache()
    ckpt_dir = tempfile.mkdtemp(prefix="glasu-serve-")
    cfg = get_preset("cora-gcnii-glasu").with_(
        rounds=30, eval_every=30, ckpt_dir=ckpt_dir)
    Trainer(cfg).run()

    session = InferenceSession.from_checkpoint(
        ckpt_dir, serve=ServeConfig(max_batch=16))
    rng = np.random.default_rng(0)
    nodes = rng.choice(session.N, size=16, replace=False)

    cold = session.answer(nodes)
    print(f"\ncold : {len(nodes)} nodes in {cold.latency_s * 1e3:.1f} ms, "
          f"{cold.wire_bytes} B on the wire "
          f"(fresh rows per agg layer: {cold.fresh_rows})")

    warm = session.answer(nodes)
    print(f"warm : {warm.latency_s * 1e3:.1f} ms, {warm.wire_bytes} B "
          f"(cache hits {warm.cache_hits}/{len(nodes)}, bitwise equal: "
          f"{np.array_equal(cold.logits, warm.logits)})")

    int8 = InferenceSession.from_checkpoint(
        ckpt_dir, serve=ServeConfig(max_batch=16),
        compression={"method": "int8"})
    comp = int8.answer(nodes)
    agree = float((comp.preds == cold.preds).mean())
    print(f"int8 : {comp.wire_bytes} B "
          f"({cold.wire_bytes / comp.wire_bytes:.1f}x fewer), "
          f"prediction agreement {agree * 100:.0f}%")

    with MicroBatcher(session, deadline_ms=5.0) as mb:
        futs = [mb.submit([int(n)]) for n in nodes[:8]]
        preds = [int(f.result(timeout=30).preds[0]) for f in futs]
    print(f"batch: 8 single-node requests -> {mb.batches} dispatch(es), "
          f"preds {preds}")


if __name__ == "__main__":
    main()
