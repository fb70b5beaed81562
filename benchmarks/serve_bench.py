"""Joint-inference serving: latency/throughput + audited query bytes, gated.

Trains a short cora-profile checkpoint, builds ``repro.serve`` sessions on
it, and measures the three query mixes the serving subsystem is built for:

  cold        — distinct never-seen nodes: full receptive-field plan,
                cross-client exchange at every aggregation layer
  warm-cache  — the same nodes again: every query hits the hot-node
                aggregate cache at the top layer, answers assemble from
                cached (M, h_agg) rows + one classifier matmul, zero
                wire bytes
  compressed  — cold queries with the PR 5 wire codecs (int8, topk_ef)
                on the embedding exchange

Reported: latency p50/p99 and queries/sec per mix, per-query byte bills
per codec, cache statistics.

Gates (full mode):
  * warm-cache throughput >= 2x cold (the point of the cache);
  * per-query bytes audited term-by-term (upload / broadcast /
    index_sync) against an independent ``fed.simulation``
    ``log_query_traffic`` MessageLog replay, for every codec — audited in
    smoke mode too;
  * compressed query bytes match the training-path codec pricing exactly:
    same ``Compressor.wire_bytes`` per fresh row as
    ``GlasuSampler.comm_bytes_per_joint_inference`` charges in training,
    verified against the dense session's identical fresh-row counts.

Results append to ``BENCH_serve.json`` (one trajectory entry per run).

Run: ``PYTHONPATH=src python -m benchmarks.serve_bench [--smoke]``
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time
from pathlib import Path

import numpy as np

from repro.api import ExperimentConfig, Trainer
from repro.comm.compression import make_compressor
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import InferenceSession, ServeConfig

HOT = dict(dataset="cora", n_clients=3, n_layers=4, hidden=64,
           backbone="gcnii", batch_size=16, fanout=3, size_cap=512,
           rounds=30, max_batch=16, n_batches=16)
SMOKE = dict(dataset="tiny", n_clients=3, n_layers=4, hidden=16,
             backbone="gcnii", batch_size=8, fanout=3, size_cap=96,
             rounds=4, max_batch=8, n_batches=6)


def _train_checkpoint(prof: dict, rounds: int, ckpt_dir: str):
    cfg = ExperimentConfig(
        name="serve-bench", dataset=prof["dataset"],
        n_clients=prof["n_clients"], n_layers=prof["n_layers"],
        hidden=prof["hidden"], backbone=prof["backbone"],
        batch_size=prof["batch_size"], fanout=prof["fanout"],
        size_cap=prof["size_cap"], rounds=rounds, lr=0.05,
        optimizer="adam", eval_every=rounds, ckpt_dir=ckpt_dir,
        ckpt_every=0)
    Trainer(cfg).run()
    return cfg


def _query_stream(n_nodes: int, n_batches: int, batch: int, seed: int = 0):
    """Distinct node batches (no repeats across batches) — the cold mix."""
    rng = np.random.default_rng(seed)
    want = n_batches * batch
    ids = rng.permutation(n_nodes)[:want]
    if len(ids) < want:        # tiny graphs: tile, keeping batches distinct
        ids = np.resize(ids, want)
    return [ids[i * batch:(i + 1) * batch].astype(np.int32)
            for i in range(n_batches)]


def _audit_answer(ans, mcfg, comp):
    """Term-by-term: session byte counters vs the MessageLog replay."""
    lg = ans.log
    assert lg is not None, "audit needs record_log=True sessions"
    for kind, got in (("upload", ans.upload_bytes),
                      ("broadcast", ans.broadcast_bytes),
                      ("index_sync", ans.index_bytes)):
        logged = lg.total_bytes(kind)
        assert logged == got, \
            f"{kind}: session charged {got} B, message-log replay says " \
            f"{logged} B"
    # per-layer wire pricing must equal the training-path cost model
    for l, n in ans.fresh_rows.items():
        want_up = mcfg.n_clients * (
            comp.wire_bytes(n, mcfg.hidden) if comp else
            n * mcfg.hidden * 4) if n else 0
        got_up = sum(m.nbytes for m in lg.messages
                     if m.kind == "upload" and m.layer == l)
        assert got_up == want_up, \
            f"layer {l}: upload {got_up} B != codec pricing {want_up} B"


def _timed_mix(session, batches):
    t0 = time.perf_counter()
    answers = [session.answer(b) for b in batches]
    wall = time.perf_counter() - t0
    lat = np.asarray([a.latency_s for a in answers])
    n_q = sum(len(b) for b in batches)
    return answers, {
        "queries": n_q, "qps": n_q / wall, "wall_s": wall,
        "latency_p50_ms": float(np.percentile(lat, 50) * 1e3),
        "latency_p99_ms": float(np.percentile(lat, 99) * 1e3),
    }


def run(smoke: bool = False, out_path: str = "BENCH_serve.json",
        rounds: int = None):
    prof = SMOKE if smoke else HOT
    rounds = rounds if rounds is not None else prof["rounds"]
    serve_cfg = dict(max_batch=prof["max_batch"], record_log=True)

    with tempfile.TemporaryDirectory() as ckpt_dir:
        cfg = _train_checkpoint(prof, rounds, ckpt_dir)
        results = {}

        # -- dense session: cold sweep, then the same nodes warm ---------
        s = InferenceSession.from_checkpoint(
            ckpt_dir, serve=ServeConfig(**serve_cfg))
        batches = _query_stream(s.N, prof["n_batches"], prof["max_batch"])
        s.answer(batches[0])          # trace the bucket + warm jit caches
        s.cache.clear()
        s.metrics = type(s.metrics)()

        cold_ans, cold = _timed_mix(s, batches)
        for a in cold_ans:
            _audit_answer(a, s.mcfg, None)
        assert all(a.cold for a in cold_ans), "cold mix hit the cache?"
        cold["bytes_per_query"] = sum(a.wire_bytes for a in cold_ans) \
            / cold["queries"]

        warm_ans, warm = _timed_mix(s, batches)
        assert not any(a.cold for a in warm_ans), \
            "warm mix missed the cache (capacity too small for the sweep?)"
        assert sum(a.wire_bytes for a in warm_ans) == 0, \
            "warm-cache answers must ship zero bytes"
        warm["bytes_per_query"] = 0.0
        for c, w in zip(cold_ans, warm_ans):
            assert np.array_equal(c.logits, w.logits), \
                "repeat query must be bitwise identical at fixed params"
        results["cold"], results["warm"] = cold, warm
        results["cache"] = {"entries": len(s.cache), "hits": s.cache.hits,
                            "misses": s.cache.misses,
                            "evictions": s.cache.evictions}

        # -- compressed sessions: cold queries, bytes audited ------------
        dense_fresh = [dict(a.fresh_rows) for a in cold_ans]
        codecs = {"int8": {"method": "int8"},
                  f"topk_ef_k{cfg.hidden // 8}": {
                      "method": "topk_ef", "k": max(1, cfg.hidden // 8),
                      "error_feedback": False}}
        for label, comp_cfg in codecs.items():
            sc = InferenceSession.from_checkpoint(
                ckpt_dir, serve=ServeConfig(**serve_cfg),
                compression=comp_cfg)
            comp = make_compressor(sc.mcfg.compression)
            c_ans, c_stats = _timed_mix(sc, batches)
            for a, df in zip(c_ans, dense_fresh):
                _audit_answer(a, sc.mcfg, comp)
                assert dict(a.fresh_rows) == df, \
                    "codec changed the fresh-row plan (it must not: " \
                    "plans depend on cache state, not on the codec)"
            c_bytes = sum(a.wire_bytes for a in c_ans)
            d_bytes = sum(a.wire_bytes for a in cold_ans)
            c_stats["bytes_per_query"] = c_bytes / c_stats["queries"]
            c_stats["bytes_reduction"] = d_bytes / max(c_bytes, 1)
            results[label] = c_stats
            print(f"serve/{label}_bytes_per_query,"
                  f"{c_stats['bytes_per_query']:.0f},"
                  f"reduction={c_stats['bytes_reduction']:.2f}x")

    print(f"serve/cold_qps,{cold['qps']:.1f},"
          f"p50={cold['latency_p50_ms']:.2f}ms "
          f"p99={cold['latency_p99_ms']:.2f}ms")
    print(f"serve/warm_qps,{warm['qps']:.1f},"
          f"p50={warm['latency_p50_ms']:.2f}ms "
          f"p99={warm['latency_p99_ms']:.2f}ms "
          f"speedup={warm['qps'] / cold['qps']:.2f}x")

    entry = {"ts": time.time(), "smoke": smoke, "profile": prof["dataset"],
             "rounds": rounds, "results": results}
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
    print(f"serve/bench_json,{path},entries={len(history)}")

    if not smoke:
        assert warm["qps"] >= 2.0 * cold["qps"], \
            f"warm-cache throughput must be >= 2x cold, got " \
            f"{warm['qps'] / cold['qps']:.2f}x " \
            f"({warm['qps']:.1f} vs {cold['qps']:.1f} q/s)"
    return entry


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, audits only, no perf gates (CI)")
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--rounds", type=int, default=None)
    args = ap.parse_args()
    enable_compile_cache()
    run(smoke=args.smoke, out_path=args.out, rounds=args.rounds)


if __name__ == "__main__":
    main()
