"""Training traffic: GLASU rounds through ``Trainer.run()``.

Set-up builds ONE ``Trainer`` from the configuration and hands it the
benchmark's weights (made from ``--seed``) as its initial state, the way a
checkpoint restore does. Its first ``warmup_rounds`` rounds go through the
window's own call and feed (the trainer's sampler and prefetch thread, its
``jax.device_put`` staging and its compiled multi-round step); the first
``check_rounds`` of them are recorded for the reference. Then the same run
continues into the measured window, which a hook closes after ``--seconds``;
the window ends when the device has finished the last step.

The traffic file sets: ``warmup_rounds``, ``check_rounds`` and the
``eval_every`` of the run (0: no exact eval inside the window).
"""
from __future__ import annotations

import time

import numpy as np

from bench import checks, data, faults, flops, reference

# a bound on the rounds the trainer schedules; the hook stops it far sooner
MAX_ROUNDS = 1_000_000


def experiment_config(cfg: dict, seed: int, eval_every: int):
    from repro.api import ExperimentConfig

    m, s = cfg["model"], cfg["sampler"]
    return ExperimentConfig(
        name=cfg["name"], dataset=cfg["name"], method="glasu",
        backend=cfg["backend"], n_clients=m["n_clients"],
        n_layers=m["n_layers"], hidden=m["hidden"], backbone=m["backbone"],
        agg=m["agg"], k=m["k"], n_local_steps=m["n_local_steps"],
        gcnii_alpha=m["gcnii_alpha"], gcnii_beta=m["gcnii_beta"],
        lr=m["lr"], optimizer=m["optimizer"], batch_size=s["batch_size"],
        fanout=s["fanout"], size_cap=s["size_cap"],
        table_cap=s["table_cap"], eval_table_cap=cfg["eval_table_cap"],
        rounds=MAX_ROUNDS, eval_every=eval_every, seed=seed)


def _host(tree):
    import jax
    return jax.tree.map(lambda x: np.array(x, copy=True), tree)


def _batch_dict(batch):
    return {"feats": batch.feats, "gather_idx": batch.gather_idx,
            "gather_mask": batch.gather_mask, "self_pos": batch.self_pos,
            "labels": batch.labels}


def run(run):
    import jax
    from repro.api import Trainer
    from repro.api.trainer import Hook

    cfg, tr = run.config, run.traffic
    warm, n_check = tr["warmup_rounds"], tr["check_rounds"]
    with run.spans("data"):
        dataset = data.vfl_dataset(cfg, data.load(cfg))
    exp = experiment_config(cfg, run.seed, tr["eval_every"])
    rec = {"losses": [], "batches": [], "window_losses": []}

    class Window(Hook):
        """Hands the trainer the benchmark's weights, then opens and closes
        the measured window on round boundaries."""

        def on_train_start(self, trainer):
            trainer.state.params = jax.device_put(params0)

        def on_round_end(self, trainer, metrics):
            r = metrics["round"]
            if len(rec["losses"]) < len(rec["batches"]):
                rec["losses"].append(metrics["losses"])
            if r == warm:
                jax.block_until_ready(trainer.state.params)
                run.end_setup()
                rec["r0"] = r
                run.begin_window()
            elif r > warm:
                rec["window_losses"].append(metrics["losses"])
                if time.perf_counter() - run.window[0] >= run.args.seconds:
                    trainer.state.should_stop = True

    trainer = Trainer(exp, data=dataset, hooks=[Window()])
    mcfg = trainer.model_cfg
    params0 = reference.init_params(run.seed, cfg["model"], mcfg.d_in,
                                    mcfg.n_classes)
    rec["params0"] = _host(params0)

    sample = trainer.sampler.sample_round

    def traced_sample():
        with run.spans("sample"):
            return sample()

    trainer.sampler.sample_round = traced_sample
    step = faults.wrap_step(trainer.backend.run_step, run.args.variant)
    done = {"rounds": 0, "calls": 0}

    def traced_step(params, opt_state, batches, keys, *a, **kw):
        k = batches.labels.shape[0]
        if done["calls"] == 0:
            run.facts["stage_bytes_per_round"] = sum(
                x.nbytes for x in jax.tree.leaves(batches)) / k
        if done["rounds"] < n_check:
            host = _batch_dict(_host(batches))
            rec["batches"] += [jax.tree.map(lambda x: x[i], host)
                               for i in range(k)]
        elif "params_after" not in rec:
            rec["params_after"] = _host(params)
            rec["n_ref"] = done["rounds"]
        with run.spans("dispatch"):
            out = step(params, opt_state, batches, keys, *a, **kw)
        if done["calls"] == 0:
            rec["mu1"] = _host(out.opt_state.mu)
            rec["mu1_rounds"] = k
        done["calls"] += 1
        done["rounds"] += k
        return out

    trainer.backend.run_step = traced_step
    with faults.patched(run.args.variant):
        trainer.run()
        jax.block_until_ready(trainer.state.params)
    run.end_window()

    rounds = trainer.state.round - rec["r0"]
    window_losses = np.asarray(jax.device_get(rec["window_losses"]))
    failed = int((~np.isfinite(window_losses).reshape(rounds, -1)
                  .all(axis=1)).sum())
    run.facts.update(
        rounds=rounds,
        flops_per_round=flops.train_round(cfg, trainer.sampler.layer_sizes,
                                          mcfg.d_in, mcfg.n_classes))
    rec["losses"] = np.asarray(jax.device_get(rec["losses"]))
    if "params_after" not in rec or warm < rec["n_ref"]:
        raise RuntimeError(f"warmup_rounds={warm} ends before the "
                           "checked rounds do")
    rec["batches"] = rec["batches"][:rec["n_ref"]]
    rec["model"] = cfg["model"]
    del trainer
    return {"e2e": {"train_rounds_per_s": rounds / run.window_s},
            "attempted": rounds, "failed": failed, "rec": rec}


def check(run, result):
    """The first ``check_rounds`` rounds of the timed path against the
    plain reference from the same weights and batches."""
    import jax.numpy as jnp

    rec = result["rec"]
    ref = reference.train_reference(rec["model"], rec["params0"],
                                    rec["batches"], jnp.float32,
                                    rec["mu1_rounds"])
    if run.args.variant == "control":
        got = reference.train_reference(rec["model"], rec["params0"],
                                        rec["batches"], jnp.bfloat16,
                                        rec["mu1_rounds"])
    else:
        got = (rec["losses"], rec["mu1"], rec["params_after"])
    readings = checks.train_readings(got, ref, rec["params0"])
    return checks.with_limits(readings, run.cell["name"])
