"""Quickstart: train a GLASU split-GCNII on the Cora proxy in ~1 minute.

    PYTHONPATH=src python examples/quickstart.py

The whole experiment is one preset from the unified API — the 5-line version:

    from repro.api import Trainer, get_preset

    cfg = get_preset("cora-gcnii-glasu").with_(rounds=60, eval_every=20)
    result = Trainer(cfg).run()
    print(result.test_acc, result.comm_bytes)

``get_preset`` names every paper scenario (``<dataset>-<backbone>-<method>``,
45 combinations — see ``repro.api.list_presets()``); ``with_`` overrides any
field with validation; ``Trainer`` derives the model/sampler configs from the
dataset, runs the hook pipeline (periodic exact eval, comm metering, optional
early stop + checkpointing), and returns a ``TrainResult``.

Knobs demonstrated below:
  * ``rounds_per_step=4`` — the device-resident engine advances 4 rounds
    per jitted dispatch (``lax.scan``, donated buffers, prefetched
    sampling); semantics are identical for any value.
  * ``compression={"method": "int8"}`` — the embedding exchange at the
    aggregation boundary ships int8 codes + per-row scales instead of
    float32 (~3.6x fewer bytes/round end to end; also ``"fp8"`` and
    ``"topk_ef"`` with ``k``).
  * ``backend="simulation"`` runs the identical round as explicit
    client/server messages with a byte-audited log;
    ``backend="sharded"`` places each client on its own device.
"""
from repro.api import Trainer, get_preset
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    cfg = get_preset("cora-gcnii-glasu").with_(
        rounds=60, eval_every=20, rounds_per_step=4,
        compression={"method": "int8"})
    res = Trainer(cfg).run()
    print(f"\nGLASU (K={len(cfg.agg_layers)}, Q={cfg.n_local_steps}, "
          f"{cfg.compression.method} exchange) on {cfg.dataset}-proxy:")
    print(f"  test accuracy   : {res.test_acc * 100:.1f}%")
    print(f"  communication   : {res.comm_bytes / 1e6:.1f} MB "
          f"({res.rounds_run} rounds)")
    print(f"  wall time       : {res.wall_seconds:.1f}s")
    print("  history         :",
          [f"r{h['round']}:{h['test_acc']:.2f}" for h in res.history])


if __name__ == "__main__":
    main()
