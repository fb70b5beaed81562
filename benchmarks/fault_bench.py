"""Fault-tolerant federated runtime: wall-clock-to-accuracy, gated.

Runs the cora-profile hot path (L=4 GCNII, hidden 64, M=3, batch 16 — the
shape every other training benchmark uses) through three operating points:

  fault_free  — the legacy engine, no fault model (accuracy anchor; it has
                no virtual clock)
  sync        — synchronous rounds under the skewed-latency profile: no
                deadline, so every round waits for its slowest upload
                (heavy-tailed stragglers set the pace), but nothing is
                ever absent
  deadline    — the fault-tolerant engine on the SAME latency profile plus
                a 20% upload-drop rate and a per-round deadline: late or
                lost uploads fall back to staleness-bounded cached
                embeddings and the round closes on time

All times are the fault schedule's VIRTUAL clock (milliseconds), so the
comparison measures the round protocol, not host jitter.

Gates (full mode):
  * accuracy under faults: the deadline run's final validation accuracy is
    within ``ACC_SLACK`` of the fault-free anchor (GLASU's stale-update
    tolerance, §3.5, doing operational work);
  * wall-clock-to-accuracy: the deadline engine reaches the target
    accuracy (anchor - ACC_SLACK) in strictly less virtual time than the
    synchronous-with-stragglers baseline;
  * meter integrity: simulated fault rounds' delivered-only message logs
    audit term-by-term against the analytic model under dropped uploads
    (index sync + n_present uploads + M broadcasts per aggregation
    layer), the sent-traffic meter prices the attempted uploads, and the
    trainer's accumulated bytes equal the sum of its per-round
    delivered-only prices.

``--smoke`` runs tiny shapes for CI signal (meters still audited, no
perf/parity gates). Results append to ``BENCH_fault.json``.

Run: ``PYTHONPATH=src python -m benchmarks.fault_bench [--smoke]``
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.api import ExperimentConfig, Trainer, make_backend
from repro.core import glasu
from repro.fed.faults import make_schedule
from repro.graph.sampler import GlasuSampler
from repro.graph.synth import make_vfl_dataset
from repro.launch.compile_cache import enable_compile_cache

HOT = dict(dataset="cora", n_clients=3, n_layers=4, hidden=64,
           backbone="gcnii", batch_size=16, fanout=3, size_cap=512)
SMOKE = dict(dataset="tiny", n_clients=3, n_layers=4, hidden=16,
             backbone="gcnii", batch_size=8, fanout=3, size_cap=96)

ACC_SLACK = 0.05      # absolute val-accuracy slack vs the fault-free anchor

# skewed latency: lognormal jitter around 20 ms with a 15% heavy Pareto
# tail — the straggler distribution the deadline protocol exists for
LATENCY = dict(base_latency_ms=20.0, latency_sigma=0.5,
               client_speed_sigma=0.2, straggler_prob=0.15,
               straggler_scale=10.0, straggler_alpha=1.5)
SYNC_FAULTS = dict(seed=7, **LATENCY)                    # no deadline: wait
DEADLINE_FAULTS = dict(seed=7, drop_prob=0.2, deadline_ms=60.0, **LATENCY)


def _audit_fault_meters(cfg: ExperimentConfig, data, rounds: int = 4) -> int:
    """Replay ``rounds`` simulated fault rounds and audit the byte meters
    term-by-term against the analytic model; returns delivered bytes."""
    mcfg = cfg.glasu_config(data)
    sampler = GlasuSampler(data, cfg.sampler_config(), seed=cfg.seed)
    opt = cfg.make_optimizer()
    mb = make_backend("simulation")
    mb.bind(mcfg, opt, sampler)          # run_round re-audits every round
    sched = make_schedule(cfg.faults, mcfg.n_clients)
    params = glasu.init_params(jax.random.PRNGKey(cfg.seed), mcfg)
    opt_state = opt.init(params)
    index_sync = sum(2 * mcfg.n_clients * sampler.layer_sizes[j] * 4
                     for j in range(mcfg.n_layers + 1) if sampler._shared(j))
    per_layer = [sampler.layer_sizes[l + 1] * mcfg.hidden * 4
                 for l in sorted(mcfg.agg_layers)]
    delivered = 0
    for _ in range(rounds):
        plan = sched.next_round()
        batch = jax.tree.map(jnp.asarray, sampler.sample_round())
        out = mb.run_round(params, opt_state, batch, jax.random.PRNGKey(0),
                           faults=plan)
        params, opt_state = out.params, out.opt_state
        log = out.message_log
        n_att = int(plan.attempted.sum())
        want = index_sync + sum(plan.n_present * b + mcfg.n_clients * b
                                for b in per_layer)
        sent = index_sync + sum(n_att * b + mcfg.n_clients * b
                                for b in per_layer)
        assert log.total_bytes() == want, \
            f"delivered meter {log.total_bytes()} != analytic {want}"
        assert log.total_bytes(delivered_only=False) == sent, \
            "sent-traffic meter disagrees with attempted uploads"
        delivered += want
    return delivered


def _time_to_target(history, target: float) -> float:
    """Virtual ms at the first eval entry reaching ``target`` val acc."""
    for h in history:
        if "virtual_ms" in h and h["val_acc"] >= target:
            return h["virtual_ms"]
    return float("inf")


def run(smoke: bool = False, out_path: str = "BENCH_fault.json",
        rounds: int = None):
    shape = SMOKE if smoke else HOT
    rounds = rounds or (8 if smoke else 60)
    base = ExperimentConfig(name="fault-bench", rounds=rounds,
                            eval_every=max(rounds // 6, 1), lr=0.01,
                            **shape)
    data = make_vfl_dataset(base.dataset, n_clients=base.n_clients,
                            seed=base.seed)

    audited = _audit_fault_meters(
        base.with_(name="fault-audit", faults=DEADLINE_FAULTS), data)
    print(f"fault/meter_audit,delivered_bytes={audited},term-by-term OK")

    points = {
        "fault_free": None,
        "sync": SYNC_FAULTS,
        "deadline": DEADLINE_FAULTS,
    }
    results = {}
    for label, faults in points.items():
        cfg = base.with_(name=f"fault-{label}", faults=faults)
        t0 = time.perf_counter()
        res = Trainer(cfg, data=data).run()
        evals = [h for h in res.history if "val_acc" in h]
        results[label] = {
            "val_acc": float(res.val_acc),
            "final_loss": float(res.history[-1]["loss"]),
            "comm_bytes": int(res.comm_bytes),
            "virtual_ms": float(evals[-1].get("virtual_ms", 0.0)),
            "participation": float(evals[-1].get("participation", 1.0)),
            "catch_up_rounds": int(evals[-1].get("catch_up_rounds", 0)),
            "history": [{k: h[k] for k in
                         ("round", "val_acc", "virtual_ms") if k in h}
                        for h in evals],
            "wall_seconds": time.perf_counter() - t0,
        }
        r = results[label]
        print(f"fault/{label},val={r['val_acc']:.3f} "
              f"virtual_ms={r['virtual_ms']:.0f} "
              f"participation={r['participation']:.2f} "
              f"bytes={r['comm_bytes']}")

    anchor = results["fault_free"]["val_acc"]
    target = anchor - ACC_SLACK
    t_sync = _time_to_target(results["sync"]["history"], target)
    t_dead = _time_to_target(results["deadline"]["history"], target)
    results["deadline"]["t_to_target_ms"] = t_dead
    results["sync"]["t_to_target_ms"] = t_sync
    print(f"fault/time_to_target,target={target:.3f} "
          f"sync={t_sync:.0f}ms deadline={t_dead:.0f}ms")

    entry = {
        "bench": "fault_bench", "smoke": smoke, "rounds": rounds,
        "shape": shape, "profiles": {"sync": SYNC_FAULTS,
                                     "deadline": DEADLINE_FAULTS},
        "audited_bytes": audited, "results": results,
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
    print(f"fault/bench_json,{path},entries={len(history)}")

    if not smoke:
        dead = results["deadline"]
        assert dead["val_acc"] >= anchor - ACC_SLACK, \
            f"deadline engine val acc {dead['val_acc']:.3f} more than " \
            f"{ACC_SLACK} below the fault-free anchor {anchor:.3f}"
        assert t_dead < t_sync, \
            f"deadline engine must beat the synchronous-with-stragglers " \
            f"baseline to {target:.3f} val acc: deadline {t_dead:.0f}ms " \
            f"vs sync {t_sync:.0f}ms"
        # dropped uploads were actually priced: fewer delivered bytes
        assert dead["comm_bytes"] < results["sync"]["comm_bytes"], \
            "deadline run must price fewer delivered bytes than sync"
    return entry


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes, audits only, no perf gates (CI)")
    ap.add_argument("--out", default="BENCH_fault.json")
    ap.add_argument("--rounds", type=int, default=None)
    args = ap.parse_args()
    enable_compile_cache()
    run(smoke=args.smoke, out_path=args.out, rounds=args.rounds)


if __name__ == "__main__":
    main()
