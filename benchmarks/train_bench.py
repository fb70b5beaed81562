"""Training-loop throughput: scanned multi-round engine vs per-round loop.

Measures end-to-end rounds/sec of the vmapped backend at the paper's
hot-path shapes (cora profile: L=4 GCNII, hidden 64, batch 16, fanout 3,
size_cap 512, M=3) for three drivers:

  per_round — the historical Trainer loop: serial host sampling, a
              full-batch ``jnp.array`` copy, one jit dispatch per round;
  scan_K    — the device-resident engine: K pre-sampled rounds stacked and
              advanced by one ``lax.scan`` dispatch with donated
              params/opt_state, sampling prefetched on a worker thread
              (K ∈ {1, 8, 32}).

Gate (full mode): scan_8 must be strictly faster than per_round. Results
are appended to ``BENCH_train.json`` so the wall-clock trajectory
accumulates per PR; ``--smoke`` runs a tiny shape for CI signal (no perf
gate — shared CI boxes are too noisy to gate on) but still exercises every
driver and writes the JSON artifact.

Run: ``PYTHONPATH=src python -m benchmarks.train_bench [--smoke]``
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.api import ExperimentConfig
from repro.api.backends import make_backend
from repro.core import glasu
from repro.graph.prefetch import PrefetchSampler
from repro.graph.sampler import GlasuSampler
from repro.graph.synth import make_vfl_dataset
from repro.launch.compile_cache import enable_compile_cache

HOT = dict(dataset="cora", n_clients=3, n_layers=4, hidden=64,
           backbone="gcnii", batch_size=16, fanout=3, size_cap=512)
SMOKE = dict(dataset="tiny", n_clients=3, n_layers=4, hidden=16,
             backbone="gcnii", batch_size=8, fanout=3, size_cap=96)
# 1M-node power-law profile, streamed feature store (graph/synth.py
# POWERLAW_SPECS): the RSS gate below proves training never materializes X
SCALE = dict(dataset="powerlaw-1m", n_clients=2, n_layers=2, hidden=32,
             backbone="gcn", batch_size=16, fanout=3, size_cap=512,
             table_cap=8)


def _setup(shape):
    cfg = ExperimentConfig(name="train-bench", rounds=0, **shape)
    data = make_vfl_dataset(cfg.dataset, n_clients=cfg.n_clients,
                            seed=cfg.seed)
    mcfg = cfg.glasu_config(data)
    optimizer = cfg.make_optimizer()
    sampler = GlasuSampler(data, cfg.sampler_config(), seed=cfg.seed)
    backend = make_backend("vmapped")
    backend.bind(mcfg, optimizer, sampler)
    params = glasu.init_params(jax.random.PRNGKey(cfg.seed), mcfg)
    opt_state = optimizer.init(params)
    return data, cfg, mcfg, optimizer, sampler, backend, params, opt_state


def _per_round_loop(shape, rounds):
    """The pre-engine Trainer loop, reproduced as the baseline."""
    _, cfg, mcfg, _, sampler, backend, params, opt_state = _setup(shape)
    key = jax.random.PRNGKey(0)
    # warmup: compile the round fn outside the timed region
    batch = jax.tree.map(jnp.array, sampler.sample_round())
    out = backend.run_round(params, opt_state, batch, key)
    jax.block_until_ready(out.losses)
    params, opt_state = out.params, out.opt_state
    t0 = time.perf_counter()
    for t in range(rounds):
        batch = jax.tree.map(jnp.array, sampler.sample_round())
        out = backend.run_round(params, opt_state, batch,
                                jax.random.fold_in(key, t))
        params, opt_state = out.params, out.opt_state
    jax.block_until_ready(out.losses)
    return rounds / (time.perf_counter() - t0)


def _scan_loop(shape, rounds, k):
    """The device-resident engine at rounds_per_step=k."""
    assert rounds % k == 0
    _, cfg, mcfg, _, sampler, backend, params, opt_state = _setup(shape)
    key = jax.random.PRNGKey(0)
    fold_keys = jax.jit(jax.vmap(jax.random.fold_in, in_axes=(None, 0)))
    schedule = [k] * (rounds // k + 1)          # +1 warmup step
    prefetch = PrefetchSampler(sampler, schedule, n_buffers=2)
    try:
        step = prefetch.get()                   # warmup: compile
        keys = fold_keys(key, jnp.arange(k))
        out = backend.run_step(params, opt_state,
                               jax.device_put(step.data), keys)
        jax.block_until_ready(out.losses)
        params, opt_state = out.params, out.opt_state
        prefetch.retire(step, out.losses)
        t0 = time.perf_counter()
        t = k
        for _ in range(rounds // k):
            step = prefetch.get()
            keys = fold_keys(key, jnp.arange(t, t + k))
            out = backend.run_step(params, opt_state,
                                   jax.device_put(step.data), keys)
            params, opt_state = out.params, out.opt_state
            prefetch.retire(step, out.losses)
            t += k
        jax.block_until_ready(out.losses)
        return rounds / (time.perf_counter() - t0)
    finally:
        prefetch.close()


class _RssMonitor:
    """Samples the process RSS on a daemon thread; ``peak`` is the max."""

    def __init__(self, interval_s: float = 0.05):
        import threading
        import psutil
        self._proc = psutil.Process()
        self._interval = interval_s
        self._stop = threading.Event()
        self.peak = self._proc.memory_info().rss
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _loop(self):
        while not self._stop.wait(self._interval):
            self.peak = max(self.peak, self._proc.memory_info().rss)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join()
        self.peak = max(self.peak, self._proc.memory_info().rss)


def _scale_smoke(rounds: int = 2):
    """Streamed-store smoke on the 1M-node power-law profile.

    Gates (always on — memory bounds, not timing, so CI noise is moot):

      * the run COMPLETES: sampler build + ``rounds`` training rounds on a
        2^20-node graph through MemmapFeatureStore row gathers;
      * peak host RSS past dataset build stays BELOW what materializing the
        full per-client padded feature block (M, N, d_pad) would add — the
        invariant that makes the streamed store worth having;
      * steady-state jitted round bodies run under
        ``jax.transfer_guard("disallow")``: the store's host gathers stage
        batches explicitly (``device_put``), never as implicit uploads
        inside the round dispatch.
    """
    import gc

    import numpy as np
    import psutil

    t_build0 = time.perf_counter()
    data, cfg, mcfg, _, sampler, backend, params, opt_state = _setup(SCALE)
    build_s = time.perf_counter() - t_build0
    m, n = data.n_clients, data.n_nodes
    d_pad = max(c.feat_dim for c in data.clients)
    full_feat_bytes = m * n * d_pad * 4
    key = jax.random.PRNGKey(0)
    # warmup OUTSIDE the guard: compilation may stage closure constants
    batch = jax.tree.map(jnp.array, sampler.sample_round())
    out = backend.run_round(params, opt_state, jax.device_put(batch), key)
    jax.block_until_ready(out.losses)
    params, opt_state = out.params, out.opt_state

    # per-round keys staged before the guard: fold_in(key, int) implicitly
    # uploads its scalar, which is exactly what the guard exists to catch
    keys = [jax.random.fold_in(key, t) for t in range(rounds)]
    gc.collect()
    rss0 = psutil.Process().memory_info().rss
    t0 = time.perf_counter()
    with _RssMonitor() as mon:
        with jax.transfer_guard("disallow"):
            for t in range(rounds):
                batch = jax.tree.map(np.array, sampler.sample_round())
                out = backend.run_round(params, opt_state,
                                        jax.device_put(batch), keys[t])
                params, opt_state = out.params, out.opt_state
            jax.block_until_ready(out.losses)
    train_s = time.perf_counter() - t0
    loss = float(jax.device_get(out.losses).mean())
    assert np.isfinite(loss), f"scale smoke diverged: loss={loss}"
    delta = mon.peak - rss0
    print(f"train/scale_1m_build,{build_s:.1f}s,n={n},edges={data.full.n_edges}")
    print(f"train/scale_1m_rounds,{rounds / train_s:.2f}rounds/s,"
          f"loss={loss:.3f}")
    print(f"train/scale_1m_rss_delta,{delta / 1e6:.0f}MB,"
          f"budget_MB={full_feat_bytes / 1e6:.0f}")
    assert delta < full_feat_bytes, (
        f"streamed-store training grew RSS by {delta / 1e6:.0f}MB, at or "
        f"above the {full_feat_bytes / 1e6:.0f}MB a full (M, N, d_pad) "
        f"feature materialization would cost — the store is not streaming")
    return {"n_nodes": n, "n_edges": data.full.n_edges,
            "build_seconds": build_s, "rounds": rounds,
            "rounds_per_sec": rounds / train_s, "loss": loss,
            "rss_delta_bytes": int(delta),
            "full_feat_bytes": int(full_feat_bytes)}


def run(smoke: bool = False, out_path: str = "BENCH_train.json",
        rounds: int = None, reps: int = None):
    shape = SMOKE if smoke else HOT
    ks = (1, 8, 32)
    rounds = rounds or (32 if smoke else 96)
    rounds = ((rounds + 31) // 32) * 32         # round up to an lcm(ks) multiple
    reps = reps or (1 if smoke else 4)
    # Interleaved reps: each rep measures every driver back-to-back, so a
    # load spike hits neighbours, not one driver; best-of-reps is the
    # least-noise estimate per driver (kernel_bench's min-time rationale)
    # and the gate compares scan_8/per_round WITHIN a rep (paired windows).
    samples = {"per_round": []}
    samples.update({f"scan_{k}": [] for k in ks})
    for _ in range(reps):
        samples["per_round"].append(_per_round_loop(shape, rounds))
        for k in ks:
            samples[f"scan_{k}"].append(_scan_loop(shape, rounds, k))
    results = {d: max(v) for d, v in samples.items()}
    paired = max(s / p for s, p in zip(samples["scan_8"],
                                       samples["per_round"]))
    print(f"train/per_round,{results['per_round']:.2f}rounds/s,baseline")
    for k in ks:
        print(f"train/scan_k{k},{results[f'scan_{k}']:.2f}rounds/s,"
              f"speedup_vs_per_round="
              f"{results[f'scan_{k}'] / results['per_round']:.2f}x")
    print(f"train/scan_k8_paired_speedup,{paired:.2f}x,best_paired_rep")

    scale = _scale_smoke()

    entry = {
        "bench": "train", "smoke": smoke, "rounds_timed": rounds,
        "reps": reps, "shape": shape, "rounds_per_sec": results,
        "speedup_scan8_vs_per_round": results["scan_8"] / results["per_round"],
        "paired_speedup_scan8": paired,
        "scale_1m": scale,
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
    print(f"train/bench_json,{path},entries={len(history)}")

    if not smoke:
        assert paired > 1.0, (
            f"scanned engine (K=8) must beat the per-round loop in at least "
            f"one paired measurement window; best paired speedup {paired:.3f}"
            f" (best-of per driver: scan_8 {results['scan_8']:.2f} r/s vs "
            f"per_round {results['per_round']:.2f} r/s)")
    return entry


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, no perf gate (CI)")
    ap.add_argument("--out", default="BENCH_train.json")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--reps", type=int, default=None)
    args = ap.parse_args()
    enable_compile_cache()
    run(smoke=args.smoke, out_path=args.out, rounds=args.rounds,
        reps=args.reps)


if __name__ == "__main__":
    main()
