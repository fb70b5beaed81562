"""Compressed embedding exchange: bytes-per-round vs accuracy, gated.

Runs the cora-profile hot path (L=4 GCNII, hidden 64, M=3, batch 16,
fanout 3, size_cap 512 — the same shape every other training benchmark
uses) once per wire codec:

  none     — dense float32 exchange (baseline)
  int8     — per-row absmax quantization (+ f32 scale per row)
  fp8      — float8_e4m3fn cast
  topk_ef  — top-k magnitude sparsification at k = hidden/8, with decayed
             error feedback (f16 value + i16 index pairs)

and reports per-round communication (the audited byte meter, index-sync
traffic included) plus final training loss / validation accuracy.

Gates (full mode):
  * int8 reduces bytes/round by >= 3x; topk_ef (k = d/8) by >= 6x;
  * final-loss parity: every codec's final loss within ``LOSS_SLACK`` of
    the dense baseline (catches EF divergence — an unstable accumulator
    sends the loss to 10s while accuracy lags behind) and validation
    accuracy within ``ACC_SLACK``;
  * meter integrity on EVERY codec: the sharded backend binds green (its
    trace-recorded collective bytes audit term-by-term against the
    shape-replayed message log at bind — a divergence raises), and one
    simulated round's actual compressed payloads measure exactly the
    analytic bytes the training runs were charged.

``--smoke`` runs tiny shapes for CI signal (meters still audited, no
perf/parity gates). Results append to ``BENCH_comm.json`` so the
bytes-vs-accuracy trajectory accumulates per PR.

Run: ``PYTHONPATH=src python -m benchmarks.comm_compression [--smoke]``
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax

from repro.api import ExperimentConfig, Trainer, make_backend
from repro.comm.compression import make_compressor
from repro.core import glasu
from repro.fed import simulation
from repro.graph.sampler import GlasuSampler
from repro.graph.synth import make_vfl_dataset
from repro.launch.compile_cache import enable_compile_cache

HOT = dict(dataset="cora", n_clients=3, n_layers=4, hidden=64,
           backbone="gcnii", batch_size=16, fanout=3, size_cap=512)
SMOKE = dict(dataset="tiny", n_clients=3, n_layers=4, hidden=16,
             backbone="gcnii", batch_size=8, fanout=3, size_cap=96)

LOSS_SLACK = 0.5      # absolute final-loss slack vs the dense baseline
ACC_SLACK = 0.05      # absolute val-accuracy slack vs the dense baseline


def _codecs(hidden: int):
    return [
        ("none", None),
        ("int8", {"method": "int8"}),
        ("fp8", {"method": "fp8"}),
        (f"topk_ef_k{hidden // 8}",
         {"method": "topk_ef", "k": hidden // 8}),
    ]


def _audit_meters(cfg: ExperimentConfig, data) -> int:
    """Bind the sharded backend (collective-vs-log audit runs there) and
    replay one simulated round; returns the audited bytes/round."""
    mcfg = cfg.glasu_config(data)
    sampler = GlasuSampler(data, cfg.sampler_config(), seed=cfg.seed)
    opt = cfg.make_optimizer()
    sb = make_backend("sharded")
    sb.bind(mcfg, opt, sampler)          # raises if the meters disagree
    mb = make_backend("simulation")
    mb.bind(mcfg, opt, sampler)
    params = glasu.init_params(jax.random.PRNGKey(cfg.seed), mcfg)
    batch = jax.tree.map(jax.numpy.array, sampler.sample_round())
    out = mb.run_round(params, opt.init(params), batch,
                       jax.random.PRNGKey(0))
    up_down = out.message_log.total_bytes("upload") \
        + out.message_log.total_bytes("broadcast")
    assert sum(r.star_bytes() for r in sb.collectives) == up_down, \
        "collective records diverge from the simulated round's payloads"
    assert sb.bytes_per_round == out.comm_bytes, \
        "sharded and simulation byte meters diverge"
    return sb.bytes_per_round


def run(smoke: bool = False, out_path: str = "BENCH_comm.json",
        rounds: int = None):
    shape = SMOKE if smoke else HOT
    rounds = rounds or (8 if smoke else 60)
    base = ExperimentConfig(name="comm-bench", rounds=rounds,
                            eval_every=max(rounds // 3, 1), lr=0.01,
                            **shape)
    data = make_vfl_dataset(base.dataset, n_clients=base.n_clients,
                            seed=base.seed)

    results = {}
    for label, cc in _codecs(base.hidden):
        cfg = base.with_(name=f"comm-{label}", compression=cc)
        audited = _audit_meters(cfg, data)
        t0 = time.perf_counter()
        res = Trainer(cfg, data=data).run()
        per_round = res.comm_bytes // max(res.rounds_run, 1)
        assert per_round == audited, \
            f"{label}: trainer charged {per_round} B/round, audit says " \
            f"{audited}"
        results[label] = {
            "bytes_per_round": per_round,
            "final_loss": float(res.history[-1]["loss"]),
            "val_acc": float(res.val_acc),
            "wall_seconds": time.perf_counter() - t0,
        }

    dense = results["none"]
    for label, r in results.items():
        ratio = dense["bytes_per_round"] / r["bytes_per_round"]
        r["bytes_reduction"] = ratio
        print(f"comm/{label},{r['bytes_per_round']}B/round,"
              f"reduction={ratio:.2f}x loss={r['final_loss']:.4f} "
              f"val={r['val_acc']:.3f}")

    entry = {
        "bench": "comm_compression", "smoke": smoke, "rounds": rounds,
        "shape": shape, "results": results,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    path = Path(out_path)
    history = []
    if path.exists():
        try:
            history = json.loads(path.read_text())
        except (json.JSONDecodeError, ValueError):
            history = []
    if not isinstance(history, list):
        history = [history]
    history.append(entry)
    path.write_text(json.dumps(history, indent=1))
    print(f"comm/bench_json,{path},entries={len(history)}")

    if not smoke:
        topk_label = f"topk_ef_k{base.hidden // 8}"
        assert results["int8"]["bytes_reduction"] >= 3.0, \
            f"int8 must cut bytes/round >= 3x, got " \
            f"{results['int8']['bytes_reduction']:.2f}x"
        assert results[topk_label]["bytes_reduction"] >= 6.0, \
            f"topk_ef at k=d/8 must cut bytes/round >= 6x, got " \
            f"{results[topk_label]['bytes_reduction']:.2f}x"
        for label, r in results.items():
            assert r["final_loss"] <= dense["final_loss"] + LOSS_SLACK, \
                f"{label}: final loss {r['final_loss']:.3f} not within " \
                f"{LOSS_SLACK} of dense {dense['final_loss']:.3f} (EF " \
                f"divergence?)"
            assert r["val_acc"] >= dense["val_acc"] - ACC_SLACK, \
                f"{label}: val acc {r['val_acc']:.3f} more than " \
                f"{ACC_SLACK} below dense {dense['val_acc']:.3f}"
    return entry


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, audits only, no perf gates (CI)")
    ap.add_argument("--out", default="BENCH_comm.json")
    ap.add_argument("--rounds", type=int, default=None)
    args = ap.parse_args()
    enable_compile_cache()
    run(smoke=args.smoke, out_path=args.out, rounds=args.rounds)


if __name__ == "__main__":
    main()
