"""End-to-end driver (paper's task): full VFL training run comparing GLASU
against the paper's baselines on one dataset, with privacy hooks and
compressed-exchange variants enabled.

Every scenario is one ``ExperimentConfig`` — the method (centralized /
standalone / simulated-centralized / glasu) picks the aggregation schedule,
client count, and eval mode; no hand-assembled config triples. The GLASU
rows run the device-resident engine (``rounds_per_step``) and the
compressed rows show bytes-per-round dropping with accuracy held.

    PYTHONPATH=src python examples/vfl_graph_training.py [--dataset suzhou]
"""
import argparse

from repro.api import ExperimentConfig, Trainer
from repro.launch.compile_cache import enable_compile_cache


def run(label, cfg):
    res = Trainer(cfg).run()
    per_round = res.comm_bytes / max(res.rounds_run, 1)
    print(f"{label:30s} acc={res.test_acc * 100:5.1f}%  "
          f"comm={res.comm_bytes / 1e6:8.1f}MB ({per_round / 1e3:6.1f}kB/rd)"
          f"  t={res.wall_seconds:5.1f}s")
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="suzhou")
    ap.add_argument("--rounds", type=int, default=150)
    ap.add_argument("--backend", default="vmapped",
                    choices=("vmapped", "simulation", "sharded"))
    args = ap.parse_args()
    enable_compile_cache()

    base = ExperimentConfig(
        name=f"{args.dataset}-comparison", dataset=args.dataset,
        n_clients=3, n_layers=4, hidden=64, backbone="gcnii",
        backend=args.backend, rounds=args.rounds, rounds_per_step=5,
        lr=0.01, eval_every=30)

    print(f"== {args.dataset} (3 clients, vertically partitioned, "
          f"{args.backend} backend) ==")
    run("centralized (M=1)", base.with_(method="centralized"))
    run("standalone (no comm)", base.with_(method="standalone"))
    run("simulated-centralized K=4", base.with_(method="simulated-centralized"))
    run("GLASU K=2 Q=1", base)
    run("GLASU K=2 Q=4", base.with_(n_local_steps=4))
    # compressed embedding exchange (wire codecs at the Agg boundary)
    run("GLASU + int8 exchange", base.with_(n_local_steps=4,
                                            compression={"method": "int8"}))
    run("GLASU + topk_ef k=8", base.with_(
        n_local_steps=4, compression={"method": "topk_ef", "k": 8}))
    if args.backend == "vmapped":
        # GLASU + privacy hooks (§3.6; secure-agg masks need the exact
        # dense exchange, so these rows stay uncompressed)
        run("GLASU + secure-agg + DP", base.with_(
            n_local_steps=4, secure_agg=True, dp_sigma=0.05))


if __name__ == "__main__":
    main()
