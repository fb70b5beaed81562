"""Mesh placement in ``ShardedBackend`` on four virtual CPU devices.

A step puts a leaf on the ``('clients',)`` mesh only when its sharding is
not already equivalent to the target (``_placed``); the ``Trainer`` stages
each step's host batch straight onto the client shardings
(``ShardedBackend.stage``), whose shardings are built once per shape.

The tier-1 run sees one CPU device, so everything runs in one subprocess
with ``--xla_force_host_platform_device_count=4`` and prints its findings
as one JSON line for the tests below.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent

CHILD = r"""
import json, sys, tempfile
from pathlib import Path
root = sys.argv[1]
sys.path[:0] = [root + "/src", root + "/tests"]
import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.api import ExperimentConfig, Trainer
from repro.api import backends
from repro.core import glasu
from repro.graph.prefetch import PrefetchSampler, stack_rounds
from repro.launch import sharding as shd

out = {}


def moved_kept():
    c = obs.counters()
    return [c.get("place_moved", 0), c.get("place_kept", 0)]


cfg = ExperimentConfig(name="placement", dataset="tiny", n_clients=4,
                       backend="sharded", hidden=16, batch_size=8,
                       size_cap=96, rounds=4, eval_every=0, lr=0.05,
                       optimizer="adam")
tr = Trainer(cfg)
be = tr.backend
out["mesh"] = dict(be.mesh.shape)
keys = jax.random.split(jax.random.PRNGKey(1), 1)

# one step from off the mesh: uncommitted params and Adam state, a host batch
params = glasu.init_params(jax.random.PRNGKey(0), tr.model_cfg)
opt_state = tr.optimizer.init(params)
host = stack_rounds([tr.sampler.sample_round()])
n_state = len(jax.tree.leaves((params, opt_state)))
n_batch = len(jax.tree.leaves(host))
out["n_state"], out["n_batch"] = n_state, n_batch
obs.reset()
step = be.run_step(params, opt_state, host, keys)
out["first_step"] = moved_kept()

# the step's own outputs and a staged batch: every leaf passes through
staged = be.stage(host)
obs.reset()
ins = (step.params, step.opt_state, staged)
got = be._placed(step.params, step.opt_state, staged, round_stacked=True)
flat_in = jax.tree.leaves(ins)
flat_out = jax.tree.leaves(got[:3])
out["kept_is_in"] = all(a is b for a, b in zip(flat_in, flat_out))
out["kept_counts"] = moved_kept()
targets = jax.tree.leaves(be.param_sh)
outs = jax.tree.leaves(step.params)
out["spelled_apart"] = sum(x.sharding != t for x, t in zip(outs, targets))
out["equivalent"] = all(x.sharding.is_equivalent_to(t, x.ndim)
                        for x, t in zip(outs, targets))

# a P('clients',) leaf against a P('clients', None) target
x = jax.device_put(np.arange(32.0, dtype=np.float32).reshape(4, 8),
                   NamedSharding(be.mesh, P("clients")))
target = NamedSharding(be.mesh, P("clients", None))
(y,), moved, kept = backends._put((x,), (target,))
out["spec_pass"] = [y is x, moved, kept, x.sharding != target]

# host numpy leaves and chip-0 leaves move
chip0 = jax.devices()[0]
host_params = jax.tree.map(np.asarray, step.params)
chip0_opt = jax.device_put(jax.tree.map(np.asarray, step.opt_state), chip0)
obs.reset()
got = be._placed(host_params, chip0_opt, host, round_stacked=True)
out["moved_counts"] = moved_kept()
out["moved_onto_mesh"] = all(
    a is not b and b.sharding.is_equivalent_to(t, b.ndim)
    for a, b, t in zip(jax.tree.leaves((host_params, chip0_opt)),
                       jax.tree.leaves(got[:2]),
                       jax.tree.leaves((be.param_sh, be.opt_sh))))
out["chip0_committed"] = all(x.committed and x.devices() == {chip0}
                             for x in jax.tree.leaves(chip0_opt))

# the batch shardings: built once per shape, shared by stage and _placed
calls = []
specs = shd.client_batch_specs


def counted(*a, **kw):
    calls.append(kw.get("round_stacked"))
    return specs(*a, **kw)


shd.client_batch_specs = counted
be2 = Trainer(cfg).backend
b1 = stack_rounds([tr.sampler.sample_round()])
b2 = stack_rounds([tr.sampler.sample_round()])
b3 = stack_rounds([tr.sampler.sample_round() for _ in range(2)])
out["cache"] = [be2._batch_sh(b1, True) is be2._batch_sh(b2, True),
                be2._batch_sh(b3, True) is be2._batch_sh(b1, True),
                be2.stage(b1)[0].sharding is be2._batch_sh(b1, True)[0]]
out["cache_builds"] = calls
shd.client_batch_specs = specs

# a Trainer run: each step's counters, and one build of the shardings
per_step = []
placed = backends.ShardedBackend._placed


def spy(self, *a, **kw):
    before = moved_kept()
    r = placed(self, *a, **kw)
    per_step.append([n - b for n, b in zip(moved_kept(), before)])
    return r


backends.ShardedBackend._placed = spy
obs.reset()
trainer = Trainer(cfg.with_(rounds=5))
trainer.run()
out["trainer_steps"] = per_step[:]
out["trainer_shardings"] = len(trainer.backend._batch_shs)

# a checkpoint restore moves again on its first step, then replays exactly
import test_backend_conformance as conformance
per_step.clear()
with tempfile.TemporaryDirectory() as d:
    conformance.test_sharded_checkpoint_save_resume_bit_exact(Path(d))
out["resume_replayed"] = True
out["resume_steps"] = per_step[:]
backends.ShardedBackend._placed = placed

# staging does not alias the prefetch generation it came from; one round
# a step, as the 4-chip cell runs, leaves each client's slice contiguous,
# which a CPU client would alias if handed it
pf = PrefetchSampler(tr.sampler, [1], n_buffers=1)
gen = pf.get()
staged = be.stage(gen.data)
jax.block_until_ready(staged)
before = jax.tree.map(np.array, gen.data)
for leaf in jax.tree.leaves(gen.data):
    leaf[...] = ~leaf if leaf.dtype == bool else leaf + 1
out["host_overwritten"] = all(
    not np.array_equal(a, b)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(gen.data)))
out["staged_unchanged"] = all(
    np.array_equal(np.asarray(s), b)
    for s, b in zip(jax.tree.leaves(staged), jax.tree.leaves(before)))
out["staged_on_mesh"] = all(
    len(s.sharding.device_set) == 4 for s in jax.tree.leaves(staged))
pf.close()
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def placement():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", CHILD, str(ROOT)], env=env,
                         cwd=ROOT, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_one_client_per_device(placement):
    assert placement["mesh"] == {"clients": 4}


def test_first_step_moves_everything_from_off_the_mesh(placement):
    # uncommitted params and Adam state, and a host batch
    n = placement["n_state"] + placement["n_batch"]
    assert placement["first_step"] == [n, 0]


def test_equivalent_leaves_pass_through_as_themselves(placement):
    n = placement["n_state"] + placement["n_batch"]
    assert placement["kept_is_in"]
    assert placement["kept_counts"] == [0, n]
    # the step's outputs spell their spec apart from the targets, yet sit
    # where the targets say
    assert placement["spelled_apart"] > 0
    assert placement["equivalent"]


def test_clients_spec_passes_a_clients_none_target(placement):
    assert placement["spec_pass"] == [True, 0, 1, True]


def test_host_and_chip0_leaves_move(placement):
    assert placement["chip0_committed"]
    assert placement["moved_onto_mesh"]
    n = placement["n_state"] + placement["n_batch"]
    assert placement["moved_counts"] == [n, 0]


def test_batch_shardings_cached_per_shape(placement):
    same_shape, other_k, staged_uses_cache = placement["cache"]
    assert same_shape and not other_k and staged_uses_cache
    # one build for K = 1 and one for K = 2
    assert placement["cache_builds"] == [True, True]
    # a whole Trainer run: staging and every step share one entry
    assert placement["trainer_shardings"] == 1


def test_trainer_moves_only_on_its_first_step(placement):
    steps = placement["trainer_steps"]
    assert len(steps) == 5
    first, *rest = steps
    assert first[0] == placement["n_state"] and first[1] == \
        placement["n_batch"]
    assert all(moved == 0 for moved, _ in rest)
    n = placement["n_state"] + placement["n_batch"]
    assert all(moved + kept == n for moved, kept in steps)


def test_restored_checkpoint_moves_once_and_replays_bit_exactly(placement):
    assert placement["resume_replayed"]
    # three runs (save to 2, resume 2 -> 4, straight 0 -> 4): each first
    # step moves its state from off the mesh, no later step moves
    steps = placement["resume_steps"]
    assert sum(1 for moved, _ in steps if moved) == 3
    assert steps[0][0] > 0
    assert len({moved + kept for moved, kept in steps}) == 1


def test_staging_does_not_alias_the_generation(placement):
    assert placement["host_overwritten"]
    assert placement["staged_unchanged"]
    assert placement["staged_on_mesh"]
