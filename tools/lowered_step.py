"""Print a fingerprint of each benchmark training config's lowered step.

Builds the ``Trainer`` of every configuration that a training cell of
``BENCHMARK.json`` runs (with the benchmark's own experiment config, at
the configuration's full size), lowers its backend's ``step_fn(params,
opt_state, batches, keys)`` for a K = 2 batch stack on the CPU and prints
one line per configuration: the backend, the length and a sha256 of the
lowered StableHLO text with the module name (the jitted function's name)
stripped; ``as_text()`` carries no location metadata. Two trees whose
lines match dispatch the same program in the benchmark's training cells;
``--out DIR`` also writes each stripped text, for a ``diff`` where they
do not.

Run from a tree's root (the sharded config needs four host devices, which
the script asks for itself)::

    PYTHONPATH=src:. python tools/lowered_step.py [--out DIR]
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def strip(text: str) -> str:
    """The lowered text without the module name."""
    return re.sub(r"module @\S+", "module @M", text, count=1)


def lowered_step(cfg: dict) -> tuple[str, str]:
    """(backend name, stripped lowered text) of ``cfg``'s training step."""
    import jax
    import jax.numpy as jnp

    from bench import data
    from bench.drivers.train import experiment_config
    from repro.api import Trainer
    from repro.core import glasu
    from repro.graph.prefetch import stack_rounds

    trainer = Trainer(experiment_config(cfg, 0, 0),
                      data=data.vfl_dataset(cfg, data.load(cfg)))
    backend = trainer.backend
    params = glasu.init_params(jax.random.PRNGKey(0), trainer.model_cfg)
    opt_state = trainer.optimizer.init(params)
    batches = stack_rounds([trainer.sampler.sample_round() for _ in range(2)])
    keys = jnp.stack([jax.random.PRNGKey(t) for t in range(2)])
    if backend.name == "sharded":       # placed as the step receives them
        params = jax.device_put(params, backend.param_sh)
        opt_state = jax.device_put(opt_state, backend.opt_sh)
        batches = backend.stage(batches)
    return backend.name, strip(
        backend.step_fn.lower(params, opt_state, batches, keys).as_text())


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="python tools/lowered_step.py")
    ap.add_argument("--out", default=None,
                    help="directory to write each stripped text to")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    files = {c["name"]: c["file"] for c in spec["configs"]}
    training = sorted({w["config"] for w in spec["workloads"]
                       if w["traffic"].startswith("train")})
    for config in training:
        cfg = json.loads((ROOT / files[config]).read_text())
        name, text = lowered_step(cfg)
        digest = hashlib.sha256(text.encode()).hexdigest()[:16]
        print(f"{cfg['name']} {name} {len(text)} {digest}")
        if args.out:
            Path(args.out).mkdir(parents=True, exist_ok=True)
            (Path(args.out) / f"{cfg['name']}.txt").write_text(text)


if __name__ == "__main__":
    main()
