"""Benchmark orchestrator — one section per paper table/figure + roofline.

CSV convention: name,value,derived

  --quick   small rounds (CI-friendly)
  --full    paper-scale rounds + more datasets/seeds
"""
import argparse
import os
import sys

from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--skip-gnn", action="store_true",
                    help="only kernels + roofline (no GNN training)")
    args = ap.parse_args()
    enable_compile_cache()

    from . import kernel_bench, roofline
    print("# kernels")
    kernel_bench.run()

    print("# roofline (from dry-run artifacts; run launch/dryrun first)")
    if os.path.isdir("results/dryrun"):
        roofline.run(emit_markdown="results/roofline_table.md")
    else:
        print("roofline/SKIPPED,no results/dryrun directory,")

    if args.skip_gnn:
        return

    from . import train_bench
    print("# train loop (scanned engine vs per-round)")
    train_bench.run(smoke=not args.full)

    from . import comm_compression
    print("# comm compression (bytes/round vs accuracy, meters audited)")
    comm_compression.run(smoke=not args.full)

    from . import serve_bench
    print("# serving (cold/warm/compressed query mixes, bytes audited)")
    serve_bench.run(smoke=not args.full)

    from . import (accuracy_parity, backbones, client_scaling, comm_model,
                   lazy_aggregation, stale_updates)
    from .common import BenchSettings

    if args.full:
        s = BenchSettings(rounds=240)
        datasets = ("cora", "citeseer", "suzhou", "venice")
        seeds = (0, 1, 2)
    else:
        s = BenchSettings(rounds=100)
        datasets = ("cora", "suzhou")
        seeds = (0,)

    print("# table2: accuracy parity")
    accuracy_parity.run(datasets=datasets, seeds=seeds, settings=s)
    print("# table3: lazy aggregation")
    lazy_aggregation.run(dataset="cora", seeds=seeds, settings=s)
    print("# table4: stale updates (time/comm to target)")
    stale_updates.run(dataset="cora", target=0.85, seeds=seeds, settings=s)
    print("# fig3: backbones")
    backbones.run(dataset="cora", seeds=seeds, settings=s)
    print("# table5: client scaling")
    client_scaling.run(dataset="citeseer", seeds=seeds, settings=s)
    print("# comm model QL/K")
    comm_model.run(dataset="cora", settings=s)


if __name__ == "__main__":
    main()
