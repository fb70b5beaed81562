"""Rehearse one cell end to end on the CPU, at a size the CPU can hold.

    python3 bench/rehearse.py --workload <cell> --seed <n> --seconds <s> \
        [--trace 0|1] [--nodes N] [--variant program|control|...]

Runs the cell's driver, metric readers and checks with ``JAX_PLATFORMS=cpu``
(four virtual CPU devices, for the cells that shard over four chips).
Prints the names of the metrics its readers found, never their values: a
CPU run says nothing about the chip. ``--nodes`` shrinks the graph.
"""
import os
import time

T_START = time.perf_counter()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import faults, harness  # noqa: E402


def main(argv):
    extra = {}
    for flag in ("--nodes", "--variant"):
        if flag in argv:
            i = argv.index(flag)
            extra[flag] = argv[i + 1]
            del argv[i:i + 2]
    args = harness.parse(argv)
    args.variant = extra.get("--variant", "program")
    if args.variant not in faults.VARIANTS:
        raise SystemExit(f"--variant must be one of {faults.VARIANTS}")
    override = {"n_nodes": int(extra["--nodes"])} if "--nodes" in extra \
        else None
    return harness.run_cell(args, T_START, rehearsal=True,
                            data_override=override)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
