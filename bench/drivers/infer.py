"""Inference traffic: exact full-graph passes over every node.

The entry is ``repro.core.glasu.full_forward`` jitted as the trainer's
exact eval jits it (``chunk=min(4096, N)``), with the party feature blocks
and the neighbour tables of the configuration (built by the benchmark,
``data.neighbor_table``) staged on the device once. Each pass ends in
``block_until_ready``. The weights are the benchmark's, from ``--seed``.

The traffic file sets ``warmup_passes``.
"""
from __future__ import annotations

import time

import numpy as np

from bench import checks, data, faults, flops, reference


def run(run):
    import jax
    from repro.core import glasu

    cfg, tr = run.config, run.traffic
    with run.spans("data"):
        arrays = data.load(cfg)
        feats = data.party_features(cfg, arrays)
        idx, mask = arrays["table_idx"], arrays["table_mask"]
        dev = jax.device_put((feats, idx, mask))
    n, d_in = feats.shape[1], feats.shape[2]
    n_classes = int(arrays["labels"].max()) + 1
    mcfg = glasu.GlasuConfig(
        n_clients=cfg["model"]["n_clients"], n_layers=cfg["model"]["n_layers"],
        hidden=cfg["model"]["hidden"], n_classes=n_classes, d_in=d_in,
        backbone=cfg["model"]["backbone"], agg=cfg["model"]["agg"],
        agg_layers=tuple(reference.agg_layers(cfg["model"])),
        gcnii_alpha=cfg["model"]["gcnii_alpha"],
        gcnii_beta=cfg["model"]["gcnii_beta"])
    params = reference.init_params(run.seed, cfg["model"], d_in, n_classes)
    chunk = min(4096, n)
    with faults.patched(run.args.variant):
        fn = jax.jit(lambda p, f, i, m: glasu.full_forward(
            p, mcfg, f, i, m, chunk=chunk))
        for _ in range(tr["warmup_passes"]):
            out = fn(params, *dev).block_until_ready()
    run.end_setup()
    run.begin_window()
    passes = 0
    while time.perf_counter() - run.window[0] < run.args.seconds:
        with run.spans("pass"):
            out = fn(params, *dev).block_until_ready()
        passes += 1
    run.end_window()

    logits = np.asarray(out)
    failed = int((~np.isfinite(logits).all(axis=(0, 2))).sum())
    pass_flops, pass_bytes = flops.infer_pass(
        cfg, n, d_in, n_classes,
        mask.reshape(mask.shape[0], -1).sum(axis=1, dtype=np.float64),
        idx.shape[-1])
    run.facts.update(passes=passes, pass_flops=pass_flops,
                     pass_bytes=pass_bytes)
    params_host = jax.tree.map(np.asarray, params)
    del out, dev, params
    return {"e2e": {"infer_nodes_per_s": passes * n / run.window_s},
            "attempted": passes * n, "failed": failed,
            "rec": {"logits": logits, "params": params_host, "feats": feats,
                    "idx": idx, "mask": mask}}


def check(run, result):
    """The window's last pass, every node, against the reference's exact
    full-graph forward from the same weights, features and tables."""
    import jax.numpy as jnp

    rec, model = result["rec"], run.config["model"]
    ref = reference.full_forward(model, rec["params"], rec["feats"],
                                 rec["idx"], rec["mask"])
    got = rec["logits"]
    if run.args.variant == "control":
        got = reference.full_forward(model, rec["params"], rec["feats"],
                                     rec["idx"], rec["mask"], jnp.bfloat16)
    return checks.with_limits(checks.logit_readings(got, ref),
                              run.cell["name"])
