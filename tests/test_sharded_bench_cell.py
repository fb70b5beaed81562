"""The sharded deployment of the benchmark (``cora-gcnii-m4``: four parties,
one per device, ``backend="sharded"``) against the benchmark's plain
reference, on four virtual CPU devices.

The tier-1 run sees one CPU device, so the run happens in one subprocess
with ``--xla_force_host_platform_device_count=4``. It goes through
``bench/drivers/train.py``, which runs ``Trainer`` on a 300-node graph
from ``bench/data.py``'s generator (Cora's 1,433 feature columns, 7 classes,
GCNII 64 wide), hands it seeded weights from ``bench.reference.init_params``
and compares its first three rounds with ``bench.reference.train_reference``
on the same weights and sampled batches.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent

# On the CPU program and reference both multiply in float32 (the reference
# writes out the bf16 pass on a TPU only), so every gap is rounding from
# summation order: the sharded trunk is compiled per device, the reference
# loops over parties.
TOL = {
    # one forward pass on identical weights and batch: a few float32 ulps
    # (reads 0 to 1.1e-7 over five seeds)
    "first_loss_gap": 1e-6,
    # 12 local-step losses, relative: Adam's m / sqrt(v) turns an ulp of a
    # gradient near zero into a full step, and a late loss near zero turns
    # a small absolute drift into a large relative one (this seed reads
    # 7.3e-5, four others 1.5e-7 to 5.7e-7); the planted faults read 0.4
    # and more at Cora's size
    "loss_gap": 1e-3,
    # Adam's first moment after round 1, worst leaf (reads under 7e-8)
    "grad_gap": 1e-5,
    # the parameters' change over 3 rounds, worst leaf (this seed 6.4e-5,
    # four others under 3.2e-8); the faults read 0.16 and more
    "change_gap": 1e-3,
}

CHILD = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
from bench import harness
from repro import obs
from repro.api import backends

bound = []
bind = backends.ShardedBackend.bind


def spy(self, *a, **kw):
    bind(self, *a, **kw)
    bound.append(self)


backends.ShardedBackend.bind = spy
per_step = []
placed = backends.ShardedBackend._placed


def placed_spy(self, *a, **kw):
    before = obs.counters()
    out = placed(self, *a, **kw)
    after = obs.counters()
    per_step.append([after[n] - before.get(n, 0)
                     for n in ("place_moved", "place_kept")])
    return out


backends.ShardedBackend._placed = placed_spy
obs.reset()
args = harness.parse(["--workload", "cora-gcnii-m4.train-sharded",
                      "--seed", "2147483659", "--seconds", "0.2"])
line = harness.execute(args, time.perf_counter(), rehearsal=True,
                       data_override={"n_nodes": 300})
be, = bound
spans = [s for s in obs.records() if s.name == "place"]
print(json.dumps({
    "checks": {k: c["value"] for k, c in line["checks"].items()},
    "correct": line["correct"], "failed": line["failed"],
    "mesh": dict(be.mesh.shape),
    "mesh_devices": len({d.id for d in be.mesh.devices.flat}),
    "bytes_per_round": be.bytes_per_round,
    "counters": obs.counters(),
    "place_spans": len(spans),
    "place_parents": sorted({s.parent for s in spans}),
    "place_per_step": per_step,
    "batch_shardings": len(be._batch_shs),
}))
"""


@pytest.fixture(scope="module")
def sharded_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", CHILD, str(ROOT)], env=env,
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_one_party_per_device(sharded_run):
    assert sharded_run["mesh"] == {"clients": 4}
    assert sharded_run["mesh_devices"] == 4


@pytest.mark.parametrize("reading", sorted(TOL))
def test_matches_plain_reference(sharded_run, reading):
    assert sharded_run["checks"][reading] <= TOL[reading]


def test_cell_reads_correct(sharded_run):
    assert sharded_run["correct"] and sharded_run["failed"] == 0


def test_place_spans_and_exchange_counter(sharded_run):
    counters = sharded_run["counters"]
    rounds = counters["rounds"]
    assert rounds > 8          # the warm-up rounds and the window's
    # one rounds_per_step=1 step a round, each placed once inside dispatch
    assert sharded_run["place_spans"] == rounds
    assert sharded_run["place_parents"] == ["dispatch"]
    assert sharded_run["bytes_per_round"] > 0
    assert counters["exchange_bytes"] == \
        sharded_run["bytes_per_round"] * rounds


def test_only_the_first_step_moves_state(sharded_run):
    """The benchmark's weights arrive on one chip and the Adam state off
    the mesh, so the first step moves them; the batch is staged onto the
    mesh already, and every later step passes all of its leaves through."""
    (moved, kept), *rest = sharded_run["place_per_step"]
    assert len(rest) + 1 == sharded_run["counters"]["rounds"]
    assert moved > 0 and kept > 0
    assert all(m == 0 and k == moved + kept for m, k in rest)
    assert sharded_run["counters"]["place_moved"] == moved
    # staging and every step share one build of the batch shardings
    assert sharded_run["batch_shardings"] == 1
