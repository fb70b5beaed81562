"""``correct`` fails when it should: every cell's run driven end to end on
the CPU (the harness's look for a chip skipped), with the bfloat16 control
in the program's place and with each fault the cell can have planted in
its timed path. The program itself must pass.

Sizes: the cora cells at their full size, ``reddit-gcnii.infer`` on a
3,000-node graph of the same generator, windows of a fraction of a second.
"""
import time

import pytest

from bench import harness

CASES = {
    "cora-gcnii.train": ("unchanged", "half_batch", "no_exchange"),
    "reddit-gcnii.infer": ("no_exchange", "altered_answer"),
}
OVERRIDE = {"reddit-gcnii.infer": {"n_nodes": 3000}}
TRAFFIC = {}


def _run(cell: str, variant: str, seed: int = 2147483653) -> dict:
    args = harness.parse(["--workload", cell, "--seed", str(seed),
                          "--seconds", "0.3"])
    args.variant = variant
    args.traffic_override = TRAFFIC.get(cell, {})
    return harness.execute(args, time.perf_counter(), rehearsal=True,
                           data_override=OVERRIDE.get(cell))


@pytest.mark.parametrize("cell", sorted(CASES))
def test_program_is_correct(cell):
    line = _run(cell, "program")
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0


@pytest.mark.parametrize("cell,variant",
                         [(c, v) for c in sorted(CASES)
                          for v in ("control",) + CASES[c]])
def test_control_and_faults_are_not_correct(cell, variant):
    line = _run(cell, variant)
    assert not line["correct"], line["checks"]
