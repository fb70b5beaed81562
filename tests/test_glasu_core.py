"""GLASU algorithm invariants (unit + integration + hypothesis property)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import glasu
from repro.core.glasu import GlasuConfig
from repro.core.train import TrainConfig, make_centralized_dataset, train_glasu
from repro.graph.sampler import GlasuSampler, SamplerConfig
from repro.graph.synth import make_vfl_dataset
from repro.optim import optimizers as opt_lib


def _setup(backbone="gcnii", agg="mean", agg_layers=(1, 3), m=3, q=1, seed=0):
    data = make_vfl_dataset("tiny", n_clients=m, seed=seed)
    d_in = max(c.feat_dim for c in data.clients)
    mcfg = GlasuConfig(n_clients=m, n_layers=4, hidden=16,
                       n_classes=data.n_classes, d_in=d_in, backbone=backbone,
                       agg=agg, agg_layers=agg_layers, n_local_steps=q)
    scfg = SamplerConfig(n_layers=4, agg_layers=agg_layers, batch_size=8,
                         fanout=3, size_cap=96)
    sampler = GlasuSampler(data, scfg, seed=seed)
    params = glasu.init_params(jax.random.PRNGKey(seed), mcfg)
    batch = jax.tree.map(jnp.asarray, sampler.sample_round())
    return data, mcfg, sampler, params, batch


def _one_round(batch):
    """``batch`` with a leading round axis of 1: the K = 1 step's input."""
    return jax.tree.map(lambda x: x[None], batch)


def _copy(tree):
    return jax.tree.map(jnp.array, tree)


def test_extract_consistency_mean():
    """Alg 3/4 core algebra: Agg(Extract(H, H_m+), H_m+) == H for every m.

    The local forward at q=0 (fresh own representation + stale others) must
    exactly reconstruct the joint-inference activations and logits.
    """
    _, cfg, _, params, batch = _setup()
    joint_logits, stale = glasu.joint_inference(params, batch, cfg)
    for m in range(cfg.n_clients):
        pm = jax.tree.map(lambda v: v[m], params)
        sm = {l: v[m] for l, v in stale.items()}
        local_logits = glasu._client_trunk(cfg, pm, batch.feats[m], batch, m, sm)
        np.testing.assert_allclose(np.asarray(local_logits),
                                   np.asarray(joint_logits[m]),
                                   rtol=1e-5, atol=1e-5)


def test_extract_consistency_concat():
    _, cfg, _, params, batch = _setup(backbone="gcn", agg="concat")
    joint_logits, stale = glasu.joint_inference(params, batch, cfg)
    for m in range(cfg.n_clients):
        pm = jax.tree.map(lambda v: v[m], params)
        sm = {l: v[m] for l, v in stale.items()}
        local_logits = glasu._client_trunk(cfg, pm, batch.feats[m], batch, m, sm)
        np.testing.assert_allclose(np.asarray(local_logits),
                                   np.asarray(joint_logits[m]),
                                   rtol=1e-5, atol=1e-5)


def test_secure_agg_masks_cancel():
    """§3.6: pairwise-cancelling masks leave the mean aggregate unchanged."""
    _, cfg, _, params, batch = _setup()
    cfg_sa = GlasuConfig(**{**cfg.__dict__, "secure_agg": True})
    logits, _ = glasu.joint_inference(params, batch, cfg)
    logits_sa, _ = glasu.joint_inference(params, batch, cfg_sa,
                                         key=jax.random.PRNGKey(7))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits_sa),
                               rtol=2e-4, atol=2e-4)


def test_dp_noise_changes_aggregate():
    _, cfg, _, params, batch = _setup()
    cfg_dp = GlasuConfig(**{**cfg.__dict__, "dp_sigma": 0.5})
    logits, _ = glasu.joint_inference(params, batch, cfg)
    logits_dp, _ = glasu.joint_inference(params, batch, cfg_dp,
                                         key=jax.random.PRNGKey(7))
    assert float(jnp.max(jnp.abs(logits - logits_dp))) > 1e-3


@pytest.mark.slow
def test_fedbcd_special_case_no_graph():
    """§3.5: with A(E_m) = I (no edges) GLASU reduces to FedBCD — the layer
    aggregation sees only the self loop."""
    data = make_vfl_dataset("tiny", n_clients=2, seed=3)
    # erase edges: keep only self-loops via empty neighbor tables
    for c in data.clients:
        c.indptr = np.zeros(c.n_nodes + 1, np.int32)
        c.indices = np.zeros(0, np.int32)
    d_in = max(c.feat_dim for c in data.clients)
    mcfg = GlasuConfig(n_clients=2, n_layers=2, hidden=16,
                       n_classes=data.n_classes, d_in=d_in, backbone="gcn",
                       agg_layers=(1,), n_local_steps=2)
    scfg = SamplerConfig(n_layers=2, agg_layers=(1,), batch_size=8, fanout=2,
                         size_cap=64)
    res = train_glasu(data, mcfg, scfg,
                      TrainConfig(rounds=10, eval_every=5, lr=0.02))
    assert res.history[-1]["loss"] < 2.0   # trains without graph structure


def test_q_steps_update_params_q_times():
    _, cfg, sampler, params, batch = _setup(q=3)
    opt = opt_lib.sgd(0.1)
    state = opt.init(params)
    step = glasu.make_multi_round_fn(cfg, opt)
    p2, state, losses, _ = step(params, state, _one_round(batch),
                                jax.random.PRNGKey(0)[None])
    assert losses.shape == (1, 3)
    assert int(state.step) == 3


def test_stale_updates_match_paper_semantics():
    """During q>0 the OTHER clients' contribution stays frozen: client m's
    local update changes only its own slice of the next joint aggregate."""
    _, cfg, _, params, batch = _setup()
    _, stale = glasu.joint_inference(params, batch, cfg)
    # perturb client 0's params; stale buffers for client 1 must be unchanged
    params2 = jax.tree.map(lambda v: v, params)
    params2["inp"]["W"] = params2["inp"]["W"].at[0].add(1.0)
    _, stale2 = glasu.joint_inference(params2, batch, cfg)
    # At the FIRST aggregation layer: stale_0 = mean_m(h_m) - h_0/M contains
    # no h_0 term, so perturbing client 0 leaves stale_0 unchanged while
    # stale_1 (which includes h_0/M) must change. At later aggregation layers
    # client 0 leaks into everyone through the earlier shared aggregate.
    l = min(stale.keys())
    d0 = float(jnp.max(jnp.abs(stale[l][0] - stale2[l][0])))
    d1 = float(jnp.max(jnp.abs(stale[l][1] - stale2[l][1])))
    assert d0 < 1e-5 and d1 > 1e-4


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000), m=st.integers(2, 4),
       k=st.sampled_from([1, 2, 4]))
def test_sampler_invariants(seed, m, k):
    data = make_vfl_dataset("tiny", n_clients=m, seed=seed % 5)
    agg = {1: (3,), 2: (1, 3), 4: (0, 1, 2, 3)}[k]
    scfg = SamplerConfig(n_layers=4, agg_layers=agg, batch_size=8, fanout=2,
                         size_cap=96)
    sampler = GlasuSampler(data, scfg, seed=seed)
    b = sampler.sample_round()
    for l in range(4):
        n_next = sampler.layer_sizes[l + 1]
        assert b.gather_idx[l].shape == (m, n_next, 3)
        # indices always in range of layer-l set
        assert int(b.gather_idx[l].max()) < sampler.layer_sizes[l]
        assert int(b.gather_idx[l].min()) >= 0
        # masked entries -> zero weight; valid rows have a valid self column
        valid = b.row_valid[l] > 0
        assert np.all(b.gather_mask[l][valid][:, 0] == 1.0)
    # shared node sets at aggregation boundaries: gather targets of layer l+1
    # use identical position spaces across clients — verified structurally by
    # equality of layer sizes (padding identical) and identical batch labels
    assert b.labels.shape == (8,)


def test_comm_meter_matches_qlk_formula():
    data = make_vfl_dataset("tiny", n_clients=3, seed=0)
    hidden = 16
    byts = {}
    for k, agg in [(4, (0, 1, 2, 3)), (2, (1, 3)), (1, (3,))]:
        scfg = SamplerConfig(n_layers=4, agg_layers=agg, batch_size=8,
                             fanout=2, size_cap=96)
        byts[k] = GlasuSampler(data, scfg, seed=0) \
            .comm_bytes_per_joint_inference(hidden)
    # more aggregation layers => strictly more bytes, roughly linear in K
    assert byts[4] > byts[2] > byts[1]
    ratio = byts[4] / byts[2]
    assert 1.3 < ratio < 3.5


def test_centralized_equals_m1():
    data = make_vfl_dataset("tiny", n_clients=3, seed=0)
    cdata = make_centralized_dataset(data)
    assert cdata.n_clients == 1
    assert cdata.clients[0].feat_dim == data.full.feat_dim
    assert cdata.clients[0].n_edges == data.full.n_edges


@pytest.mark.slow
def test_label_at_one_client_gradient_equivalence():
    """Appendix B.2 eq.(3): the broadcast-gradient surrogate gives every
    non-owner client EXACTLY the gradient of the owner's end-to-end loss."""
    _, cfg, _, params, batch = _setup()
    cfg1 = GlasuConfig(**{**cfg.__dict__, "labels_at_client": 0})
    _, stale = glasu.joint_inference(params, batch, cfg)
    g_hl = glasu.label_owner_grad(params, batch, stale, cfg1)

    # surrogate gradient for client 1
    def surrogate(params_m):
        h = glasu._client_trunk(cfg1, params_m, batch.feats[1], batch, 1,
                                {l: v[1] for l, v in stale.items()},
                                return_hidden=True)
        return jnp.sum(jax.lax.stop_gradient(g_hl) * h)

    p1 = jax.tree.map(lambda v: v[1], params)
    g_sur = jax.grad(surrogate)(p1)

    # reference: end-to-end grad of client-0's loss wrt client-1's weights,
    # holding the stale buffers fixed (the local-update computational graph)
    def owner_loss_via_client1(p1_vars):
        h1 = glasu._client_trunk(cfg1, p1_vars, batch.feats[1], batch, 1,
                                 {l: v[1] for l, v in stale.items()},
                                 return_hidden=True)
        # client 1's fresh H[L]; owner's classifier applied to it (shared
        # final representation per Appendix B.2 requirement K includes L-1)
        p0 = jax.tree.map(lambda v: v[0], params)
        logits = h1 @ p0["cls"]["W"] + p0["cls"]["b"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return jnp.mean(-jnp.take_along_axis(logp, batch.labels[:, None],
                                             axis=1)[:, 0])

    g_ref = jax.grad(owner_loss_via_client1)(p1)
    for a, b in zip(jax.tree.leaves(g_sur), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_label_at_one_client_trains():
    data = make_vfl_dataset("tiny", n_clients=3, seed=0)
    d_in = max(c.feat_dim for c in data.clients)
    mcfg = GlasuConfig(n_clients=3, n_layers=4, hidden=16,
                       n_classes=data.n_classes, d_in=d_in, backbone="gcnii",
                       agg_layers=(1, 3), n_local_steps=2, labels_at_client=0)
    scfg = SamplerConfig(n_layers=4, agg_layers=(1, 3), batch_size=8,
                         fanout=3, size_cap=96)
    res = train_glasu(data, mcfg, scfg,
                      TrainConfig(rounds=25, eval_every=25, lr=0.02))
    assert res.test_acc > 0.5


@pytest.mark.parametrize("backbone", ["gcn", "gcnii", "gat"])
def test_pallas_backed_backbone_matches_jnp(backbone):
    """use_pallas=True swaps the client sub-layer onto the fused Pallas
    kernels for ALL three paper backbones; joint inference must match the
    pure-jnp path."""
    _, cfg, _, params, batch = _setup(backbone=backbone)
    cfg_k = GlasuConfig(**{**cfg.__dict__, "use_pallas": True})
    logits, _ = glasu.joint_inference(params, batch, cfg)
    logits_k, _ = glasu.joint_inference(params, batch, cfg_k)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits_k),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
@pytest.mark.parametrize("backbone", ["gcn", "gcnii", "gat"])
def test_pallas_backed_training_round_matches_jnp(backbone):
    """A full training round (JointInference + LocalUpdate gradients) through
    the fused kernels stays on the jnp trajectory — the custom_vjp backward
    is exact up to float32 reassociation."""
    _, cfg, _, params, batch = _setup(backbone=backbone)
    cfg_k = GlasuConfig(**{**cfg.__dict__, "use_pallas": True})
    opt = opt_lib.sgd(0.05)              # sgd: no adaptive noise amplification
    batches, keys = _one_round(batch), jax.random.PRNGKey(0)[None]
    # each step consumes (donates) its params and optimizer state
    p_j, _, loss_j, _ = glasu.make_multi_round_fn(cfg, opt)(
        _copy(params), opt.init(params), batches, keys)
    p_k, _, loss_k, _ = glasu.make_multi_round_fn(cfg_k, opt)(
        _copy(params), opt.init(params), batches, keys)
    np.testing.assert_allclose(np.asarray(loss_j), np.asarray(loss_k),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(p_j), jax.tree.leaves(p_k)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
