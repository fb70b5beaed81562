"""Cross-backend parity: the explicit message-passing backend must agree
with the vmapped fast path — logits, trained parameters, AND bytes (the
message log audits the sampler's analytic cost model every round)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (ExperimentConfig, SimulationBackend, Trainer,
                       VmappedBackend)
from repro.core import glasu
from repro.graph.sampler import GlasuSampler
from repro.graph.synth import make_vfl_dataset

CFG = ExperimentConfig(name="parity", dataset="tiny", hidden=16, batch_size=8,
                       size_cap=96, rounds=2, eval_every=2, optimizer="sgd",
                       lr=0.05)


def _bind_both(cfg):
    data = make_vfl_dataset(cfg.dataset, n_clients=cfg.n_clients,
                            seed=cfg.seed)
    mcfg = cfg.glasu_config(data)
    sampler = GlasuSampler(data, cfg.sampler_config(), seed=cfg.seed)
    vb, sb = VmappedBackend(), SimulationBackend()
    vb.bind(mcfg, cfg.make_optimizer(), sampler)
    sb.bind(mcfg, cfg.make_optimizer(), sampler)
    params = glasu.init_params(jax.random.PRNGKey(cfg.seed), mcfg)
    batch = jax.tree.map(jnp.asarray, sampler.sample_round())
    return mcfg, sampler, vb, sb, params, batch


@pytest.mark.slow
def test_joint_logits_parity():
    _, _, vb, sb, params, batch = _bind_both(CFG)
    np.testing.assert_allclose(np.asarray(sb.joint_logits(params, batch)),
                               np.asarray(vb.joint_logits(params, batch)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.slow
def test_round_parity_params_and_bytes():
    cfg = CFG
    mcfg, sampler, vb, sb, params, batch = _bind_both(cfg)
    opt = cfg.make_optimizer()
    state_v = opt.init(params)
    state_s = opt.init(params)
    # vmapped run_round consumes (donates) its params, as run_step does
    pv, ps = jax.tree.map(jnp.array, params), params
    analytic = sampler.comm_bytes_per_joint_inference(mcfg.hidden, mcfg.agg)
    key = jax.random.PRNGKey(0)
    for t in range(2):
        out_v = vb.run_round(pv, state_v, batch, jax.random.fold_in(key, t))
        out_s = sb.run_round(ps, state_s, batch, jax.random.fold_in(key, t))
        pv, state_v = out_v.params, out_v.opt_state
        ps, state_s = out_s.params, out_s.opt_state
        # bytes: measured message log == analytic meter == vmapped estimate
        assert out_s.message_log is not None
        assert out_s.message_log.total_bytes() == analytic
        assert out_s.comm_bytes == out_v.comm_bytes == analytic
        for a, b in zip(jax.tree.leaves(ps), jax.tree.leaves(pv)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)


def test_message_log_breakdown_matches_cost_model_terms():
    """Per-kind audit: uploads+broadcasts = activation term, index_sync =
    index-union term of §3.2's cost model."""
    mcfg, sampler, _, sb, params, batch = _bind_both(CFG)
    out = sb.run_round(params, sb.optimizer.init(params), batch,
                       jax.random.PRNGKey(0))
    log = out.message_log
    act = sum(2 * mcfg.n_clients * sampler.layer_sizes[l + 1] * mcfg.hidden * 4
              for l in mcfg.agg_layers)
    idx = sum(2 * mcfg.n_clients * sampler.layer_sizes[j] * 4
              for j in range(mcfg.n_layers + 1) if sampler._shared(j))
    assert log.total_bytes("upload") + log.total_bytes("broadcast") == act
    assert log.total_bytes("index_sync") == idx


@pytest.mark.slow
def test_trainer_runs_on_simulation_backend():
    res = Trainer(CFG.with_(backend="simulation")).run()
    assert res.rounds_run == 2
    assert res.comm_bytes > 0
    assert np.isfinite(res.history[-1]["loss"])


@pytest.mark.slow
def test_standalone_simulation_has_no_traffic():
    cfg = CFG.with_(method="standalone", agg_layers=None, backend="simulation")
    res = Trainer(cfg).run()
    assert res.comm_bytes == 0


def test_simulation_backend_rejects_privacy_hooks():
    cfg = CFG.with_(secure_agg=True)
    data = make_vfl_dataset("tiny", n_clients=3, seed=0)
    sb = SimulationBackend()
    with pytest.raises(ValueError, match="privacy"):
        sb.bind(cfg.glasu_config(data), cfg.make_optimizer(),
                GlasuSampler(data, cfg.sampler_config(), seed=0))
