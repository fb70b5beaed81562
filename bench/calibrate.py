"""Readings for the limits of ``correct``: many runs of one cell in one
process (one compile), for the program on many seeds and for the control
and each planted fault on a few.

    python3 bench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 11,12,... [--variants program,control,half_batch,...] \
        [--variant-seeds 91,92,93] [--traffic rate_per_s=800] \
        [--trace 1 --keep-trace DIR] [--out chiprun_out/<file>.jsonl]

Prints one JSON line per run: the variant, the seed and every reading of
the cell's checks. Runs on the chip like ``run.py``; never part of a
benchmark run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="program")
    ap.add_argument("--variant-seeds", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--traffic", action="append", default=[],
                    metavar="KEY=NUMBER",
                    help="override a number of the traffic file (a sweep)")
    ap.add_argument("--keep-trace", default="",
                    help="with --trace 1: copy each run's .xplane.pb here")
    a = ap.parse_args(argv)
    out = open(a.out, "a") if a.out else None
    plan = [("program", int(s)) for s in a.seeds.split(",")]
    for v in a.variants.split(","):
        if v != "program":
            plan += [(v, int(s)) for s in a.variant_seeds.split(",")]
    for variant, seed in plan:
        args = harness.parse(["--workload", a.workload, "--seed", str(seed),
                              "--seconds", str(a.seconds),
                              "--trace", str(a.trace)])
        args.variant = variant
        args.traffic_override = {k: float(v) for k, v in
                                 (kv.split("=", 1) for kv in a.traffic)}
        if a.keep_trace:
            args.keep_trace = f"{a.keep_trace}/{a.workload}-{seed}.xplane.pb"
        t0 = time.perf_counter()
        try:
            line = harness.execute(args, time.perf_counter())
            rec = {"variant": variant, "seed": seed,
                   "readings": {k: c["value"]
                                for k, c in line["checks"].items()},
                   "metrics": {k: m["value"]
                               for k, m in line["metrics"].items()},
                   "device": line["device"],
                   "breakdown": line.get("breakdown")}
        except Exception as e:  # noqa: BLE001 - a crashing fault is a result
            rec = {"variant": variant, "seed": seed, "error": repr(e)}
        rec["wall_s"] = time.perf_counter() - t0
        print(json.dumps(rec), flush=True)
        if out:
            out.write(json.dumps(rec) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
