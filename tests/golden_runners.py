"""Golden-parity harness for the unified round engine.

Each combo below drives ONE engine/policy pair (vmapped | sharded |
simulation  x  plain | compressed | fault-tolerant) on a tiny fixed
problem and returns a flat dict of numpy arrays (final params leaves,
per-round losses, and any carried sidecar state). The fixtures under
``tests/golden/`` were generated from the three legacy hand-synced
engines immediately BEFORE they were unified into the single
policy-parameterized round body; ``tests/test_golden_parity.py`` replays
every combo against the stored arrays so the unified body provably
reproduces each legacy engine (bitwise for the vmapped and simulation
paths, float32-ULP for the sharded lowering).

Regenerate (only when the numerical contract is INTENTIONALLY changed)::

    PYTHONPATH=src python tests/golden_runners.py --write
"""
import os

import numpy as np

GOLDEN_DIR = os.path.join(os.path.dirname(__file__), "golden")

SEED = 0
ROUNDS = 3


def _base(**kw):
    from repro.core.glasu import GlasuConfig
    from repro.graph.sampler import GlasuSampler, SamplerConfig
    from repro.graph.synth import make_vfl_dataset

    data = make_vfl_dataset("tiny", n_clients=3, seed=SEED)
    d_in = max(c.feat_dim for c in data.clients)
    mcfg = GlasuConfig(n_clients=3, n_layers=4, hidden=16, backbone="gcn",
                       n_classes=data.n_classes, d_in=d_in,
                       agg_layers=(1, 3), n_local_steps=2, **kw)
    scfg = SamplerConfig(n_layers=4, agg_layers=(1, 3), batch_size=8,
                         fanout=3, size_cap=96)
    sampler = GlasuSampler(data, scfg, seed=SEED)
    return mcfg, sampler


def _rounds_and_keys(sampler, n=ROUNDS, as_numpy=True):
    import jax
    import jax.numpy as jnp

    # snapshot with np.array FIRST: the sampler reuses its internal numpy
    # buffers across draws and jnp.asarray is zero-copy on CPU, so a
    # device view of the live buffers would alias the NEXT round's draw
    rounds = [jax.tree.map(np.array, sampler.sample_round())
              for _ in range(n)]
    if not as_numpy:
        rounds = [jax.tree.map(jnp.asarray, r) for r in rounds]
    keys = [jax.random.fold_in(jax.random.PRNGKey(7), t) for t in range(n)]
    return rounds, keys


def _init(mcfg, lr=0.05, opt="sgd"):
    import jax

    from repro.core import glasu
    from repro.optim import optimizers as opt_lib

    optimizer = opt_lib.make_optimizer(opt, lr)
    params = glasu.init_params(jax.random.PRNGKey(SEED), mcfg)
    return optimizer, params, optimizer.init(params)


def _flat(prefix, tree):
    import jax

    leaves = jax.tree.leaves(tree)
    return {f"{prefix}_{i:03d}": np.asarray(x)
            for i, x in enumerate(leaves)}


def _plans(mcfg, n=ROUNDS):
    """A fixed, reproducible fault draw with drops, deadline kills and
    catch-up pressure (every plan shape the engine branches on)."""
    from repro.fed import faults as faults_lib

    fcfg = faults_lib.FaultConfig(seed=11, participation=0.8, drop_prob=0.25,
                                  deadline_ms=40.0, base_latency_ms=10.0,
                                  straggler_prob=0.3, straggler_scale=6.0,
                                  max_staleness=2)
    sched = faults_lib.make_schedule(fcfg, mcfg.n_clients)
    return [sched.next_round() for _ in range(n)]


def _masks(plans):
    import jax.numpy as jnp

    from repro.core import glasu
    from repro.fed import faults as faults_lib

    present, weight = faults_lib.stack_plans(plans)
    return glasu.RoundFaults(jnp.asarray(present), jnp.asarray(weight))


def _one_round(step, params, opt_state, batch, key, carry=None,
               plan=None):
    """One round through a builder's step at K = 1: the batch, the key and
    the plan get a leading round axis of 1. Returns ``(params, opt_state,
    losses (Q,), carry)``."""
    from repro.graph.prefetch import stack_rounds

    params, opt_state, losses, carry = step(
        params, opt_state, stack_rounds([batch]), key[None], carry,
        None if plan is None else _masks([plan]))
    return params, opt_state, np.asarray(losses[0]), carry


# --------------------------------------------------------------- combos

def vmapped_plain_multi():
    from repro.core import glasu
    from repro.graph.prefetch import stack_rounds

    mcfg, sampler = _base()
    optimizer, params, opt_state = _init(mcfg)
    rounds, keys = _rounds_and_keys(sampler)
    step = glasu.make_multi_round_fn(mcfg, optimizer, rounds_per_step=ROUNDS)
    import jax.numpy as jnp
    params, opt_state, losses, _ = step(params, opt_state,
                                        stack_rounds(rounds), jnp.stack(keys))
    return {**_flat("params", params), "losses": np.asarray(losses)}


def vmapped_privacy_round():
    from repro.core import glasu

    mcfg, sampler = _base(secure_agg=True, dp_sigma=0.01)
    optimizer, params, opt_state = _init(mcfg)
    rounds, keys = _rounds_and_keys(sampler)
    step = glasu.make_multi_round_fn(mcfg, optimizer, rounds_per_step=1)
    losses = []
    for t in range(ROUNDS):
        params, opt_state, l, _ = _one_round(step, params, opt_state,
                                             rounds[t], keys[t])
        losses.append(l)
    return {**_flat("params", params), "losses": np.stack(losses)}


def vmapped_concat_labels_round():
    from repro.core import glasu

    mcfg, sampler = _base(agg="concat", labels_at_client=1)
    optimizer, params, opt_state = _init(mcfg)
    rounds, keys = _rounds_and_keys(sampler)
    step = glasu.make_multi_round_fn(mcfg, optimizer, rounds_per_step=1)
    losses = []
    for t in range(ROUNDS):
        params, opt_state, l, _ = _one_round(step, params, opt_state,
                                             rounds[t], keys[t])
        losses.append(l)
    return {**_flat("params", params), "losses": np.stack(losses)}


def vmapped_int8_ef_round():
    from repro.comm import compression
    from repro.core import glasu

    mcfg, sampler = _base(compression=compression.CompressionConfig(
        method="int8", error_feedback=True))
    optimizer, params, opt_state = _init(mcfg)
    comp = glasu.init_comp_state(mcfg, sampler.layer_sizes,
                                 compression.make_compressor(mcfg.compression))
    rounds, keys = _rounds_and_keys(sampler)
    step = glasu.make_multi_round_fn(mcfg, optimizer, rounds_per_step=1)
    carry = glasu.RoundCarry(comp_state=comp)
    losses = []
    for t in range(ROUNDS):
        params, opt_state, l, carry = _one_round(step, params, opt_state,
                                                 rounds[t], keys[t], carry)
        losses.append(l)
    comp = carry.comp_state
    return {**_flat("params", params), **_flat("comp", comp),
            "losses": np.stack(losses)}


def vmapped_topk_multi():
    import jax.numpy as jnp

    from repro.comm import compression
    from repro.core import glasu
    from repro.graph.prefetch import stack_rounds

    mcfg, sampler = _base(compression=compression.CompressionConfig(
        method="topk_ef", k=4))
    optimizer, params, opt_state = _init(mcfg)
    comp = glasu.init_comp_state(mcfg, sampler.layer_sizes,
                                 compression.make_compressor(mcfg.compression))
    rounds, keys = _rounds_and_keys(sampler)
    step = glasu.make_multi_round_fn(mcfg, optimizer, rounds_per_step=ROUNDS)
    params, opt_state, losses, carry = step(
        params, opt_state, stack_rounds(rounds), jnp.stack(keys),
        glasu.RoundCarry(comp_state=comp))
    comp = carry.comp_state
    return {**_flat("params", params), **_flat("comp", comp),
            "losses": np.asarray(losses)}


def vmapped_fault_multi():
    import jax.numpy as jnp

    from repro.core import glasu
    from repro.graph.prefetch import stack_rounds

    mcfg, sampler = _base(fault_tolerant=True)
    optimizer, params, opt_state = _init(mcfg)
    cache = glasu.init_fault_state(mcfg, sampler.layer_sizes)
    rounds, keys = _rounds_and_keys(sampler)
    step = glasu.make_multi_round_fn(mcfg, optimizer, rounds_per_step=ROUNDS)
    params, opt_state, losses, carry = step(
        params, opt_state, stack_rounds(rounds), jnp.stack(keys),
        glasu.RoundCarry(fault_state=cache), _masks(_plans(mcfg)))
    cache = carry.fault_state
    return {**_flat("params", params), **_flat("cache", cache),
            "losses": np.asarray(losses)}


def sim_plain():
    from repro.fed import simulation

    mcfg, sampler = _base()
    optimizer, params, opt_state = _init(mcfg)
    rounds, _ = _rounds_and_keys(sampler, n=2, as_numpy=False)
    losses = []
    for t in range(2):
        params, opt_state, l, _, _ = simulation.simulate_round(
            params, opt_state, rounds[t], mcfg, optimizer, None, None)
        losses.append(np.asarray(l))
    return {**_flat("params", params), "losses": np.stack(losses)}


def sim_fault():
    from repro.core import glasu
    from repro.fed import simulation

    mcfg, sampler = _base(fault_tolerant=True)
    optimizer, params, opt_state = _init(mcfg)
    cache = glasu.init_fault_state(mcfg, sampler.layer_sizes)
    plans = _plans(mcfg, n=2)
    rounds, _ = _rounds_and_keys(sampler, n=2, as_numpy=False)
    losses = []
    for t in range(2):
        params, opt_state, l, _, cache = simulation.simulate_fault_round(
            params, opt_state, rounds[t], mcfg, optimizer, cache, plans[t])
        losses.append(np.asarray(l))
    return {**_flat("params", params), **_flat("cache", cache),
            "losses": np.stack(losses)}


def _sharded(mcfg):
    from repro.launch.mesh import make_client_mesh

    return make_client_mesh(mcfg.n_clients)


def sharded_plain_multi():
    import jax.numpy as jnp

    from repro.core import glasu
    from repro.graph.prefetch import stack_rounds

    mcfg, sampler = _base()
    optimizer, params, opt_state = _init(mcfg)
    rounds, keys = _rounds_and_keys(sampler)
    step = glasu.make_sharded_multi_round_fn(mcfg, optimizer, _sharded(mcfg),
                                             rounds_per_step=ROUNDS)
    params, opt_state, losses, _ = step(params, opt_state,
                                        stack_rounds(rounds), jnp.stack(keys))
    return {**_flat("params", params), "losses": np.asarray(losses)}


def sharded_fault_round():
    from repro.core import glasu

    mcfg, sampler = _base(fault_tolerant=True)
    optimizer, params, opt_state = _init(mcfg)
    cache = glasu.init_fault_state(mcfg, sampler.layer_sizes)
    plans = _plans(mcfg)
    rounds, keys = _rounds_and_keys(sampler)
    step = glasu.make_sharded_multi_round_fn(mcfg, optimizer, _sharded(mcfg),
                                             rounds_per_step=1)
    carry = glasu.RoundCarry(fault_state=cache)
    losses = []
    for t in range(ROUNDS):
        params, opt_state, l, carry = _one_round(
            step, params, opt_state, rounds[t], keys[t], carry, plans[t])
        losses.append(l)
    cache = carry.fault_state
    return {**_flat("params", params), **_flat("cache", cache),
            "losses": np.stack(losses)}


def sharded_int8_ef_round():
    from repro.comm import compression
    from repro.core import glasu

    mcfg, sampler = _base(compression=compression.CompressionConfig(
        method="int8", error_feedback=True))
    optimizer, params, opt_state = _init(mcfg)
    comp = glasu.init_comp_state(mcfg, sampler.layer_sizes,
                                 compression.make_compressor(mcfg.compression))
    rounds, keys = _rounds_and_keys(sampler)
    step = glasu.make_sharded_multi_round_fn(mcfg, optimizer, _sharded(mcfg),
                                             rounds_per_step=1)
    carry = glasu.RoundCarry(comp_state=comp)
    losses = []
    for t in range(ROUNDS):
        params, opt_state, l, carry = _one_round(step, params, opt_state,
                                                 rounds[t], keys[t], carry)
        losses.append(l)
    comp = carry.comp_state
    return {**_flat("params", params), **_flat("comp", comp),
            "losses": np.stack(losses)}


# bitwise: same engine lowering replayed on the same host
EXACT = ("vmapped_plain_multi", "vmapped_privacy_round",
         "vmapped_concat_labels_round", "vmapped_int8_ef_round",
         "vmapped_topk_multi", "vmapped_fault_multi", "sim_plain",
         "sim_fault")
# float32-ULP: the sharded shard_map lowering fuses differently per build
CLOSE = ("sharded_plain_multi", "sharded_fault_round",
         "sharded_int8_ef_round")

COMBOS = {name: globals()[name] for name in EXACT + CLOSE}


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--write", action="store_true")
    args = ap.parse_args()
    os.makedirs(GOLDEN_DIR, exist_ok=True)
    for name, fn in COMBOS.items():
        out = fn()
        path = os.path.join(GOLDEN_DIR, f"{name}.npz")
        if args.write:
            np.savez_compressed(path, **out)
            print(f"wrote {path} ({len(out)} arrays)")
        else:
            print(f"{name}: {len(out)} arrays (dry run)")


if __name__ == "__main__":
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    main()
