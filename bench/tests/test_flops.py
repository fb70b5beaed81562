"""The FLOP and byte models against XLA's own cost analysis of the same
compiled step, at tiny sizes on the CPU.

XLA counts every elementwise operation (relu, the residual mixes, the
masked mean's multiply and divide, Adam) and every byte its unfused
operands touch; the models count the matmuls and the aggregation's
multiply-adds, and the least bytes a pass must move. So each model must
stay at or below XLA's count and close to it, here within 40%.
"""
import json

import pytest

ROOT = __import__("pathlib").Path(__file__).resolve().parents[2]


@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp
    from bench import data, reference
    from bench.drivers.train import experiment_config
    from repro.api import Trainer
    from repro.graph.prefetch import stack_rounds

    cfg = json.loads((ROOT / "bench/configs/cora-gcnii.json").read_text())
    # dense enough that every neighbour table is full, as on reddit-gcnii
    cfg["data"].update(n_nodes=300, feat_dim=60, n_classes=4, avg_deg=80)
    cfg["model"].update(hidden=16, n_local_steps=1)
    cfg["sampler"].update(batch_size=4, fanout=2, size_cap=32)
    arrays = data._build(cfg)
    trainer = Trainer(experiment_config(cfg, 0, 0),
                      data=data.vfl_dataset(cfg, arrays))
    params = reference.init_params(0, cfg["model"], trainer.model_cfg.d_in,
                                   trainer.model_cfg.n_classes)
    return cfg, arrays, trainer, params, jax, jnp, stack_rounds


def _cost(compiled):
    c = compiled.cost_analysis()
    return c[0] if isinstance(c, list) else c


def test_train_round_flops_below_and_near_xla(tiny):
    from bench import flops
    cfg, _, trainer, params, jax, jnp, stack_rounds = tiny
    batch = stack_rounds([trainer.sampler.sample_round()])
    keys = jnp.stack([jax.random.PRNGKey(0)])
    cost = _cost(trainer.backend.step_fn.lower(
        params, trainer.optimizer.init(params), batch, keys).compile())
    mcfg = trainer.model_cfg
    model = flops.train_round(cfg, trainer.sampler.layer_sizes, mcfg.d_in,
                              mcfg.n_classes)
    assert 0.6 * cost["flops"] <= model <= cost["flops"]


def test_infer_pass_flops_and_bytes_below_and_near_xla(tiny):
    from bench import data, flops
    from repro.core import glasu
    cfg, arrays, trainer, params, jax, _, _ = tiny
    feats = data.party_features(cfg, arrays)
    idx, mask = arrays["table_idx"], arrays["table_mask"]
    n = feats.shape[1]
    assert mask.mean() > 0.95                        # nearly full tables
    fn = jax.jit(lambda p, f, i, m: glasu.full_forward(
        p, trainer.model_cfg, f, i, m, chunk=n))
    cost = _cost(fn.lower(params, feats, idx, mask).compile())
    f, b = flops.infer_pass(cfg, n, feats.shape[2],
                            trainer.model_cfg.n_classes,
                            mask.reshape(mask.shape[0], -1).sum(axis=1),
                            idx.shape[-1])
    assert 0.6 * cost["flops"] <= f <= cost["flops"]
    assert b <= cost["bytes accessed"]
