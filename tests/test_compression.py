"""Compressed embedding exchange: codecs, engines, meters, checkpoints.

Codec units pin the wire formats (including the edge cases: all-zero rows
under the int8 absmax guard, fp8 overflow clipping, top-k with k >= d
degenerating to exact identity) and that ``wire_bytes`` prices the actual
encoded payload exactly. Engine tests pin the compressed vmapped scan
against sequential rounds and against the independent message-passing
simulation, the byte meters against each other, and the error-feedback
accumulators through a bitwise checkpoint round-trip and a codec change
across a resume.

Numerical contract: quantization AMPLIFIES compilation-level ULP noise —
a last-ULP difference in an upload can flip a round-to-nearest bucket and
move the decoded value by a whole quantization step — so compressed
cross-program comparisons (scan vs sequential, sharded vs vmapped) are
pinned at ``COMP_TOL`` rather than the bitwise/ULP contracts of the
uncompressed engines. Within one program the math is deterministic:
checkpoint resume is still bitwise.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (CompressionConfig, ExperimentConfig,
                       SimulationBackend, Trainer, VmappedBackend)
from repro.comm import compression as comp_lib
from repro.core import glasu
from repro.fed import simulation
from repro.graph.prefetch import stack_rounds
from repro.graph.sampler import GlasuSampler
from repro.graph.synth import make_vfl_dataset

COMP_TOL = dict(rtol=2e-4, atol=2e-4)

METHODS = [("int8", {}), ("fp8", {}), ("topk_ef", {"k": 2}),
           ("int8", {"error_feedback": True})]


def _payload_nbytes(payload):
    return sum(np.asarray(l).size * np.asarray(l).dtype.itemsize
               for l in jax.tree.leaves(payload))


# ------------------------------------------------------------------- codecs
def test_int8_roundtrip_bounded_and_zero_row_guard():
    comp = comp_lib.make_compressor(CompressionConfig("int8"))
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(6, 32)).astype(np.float32))
    x = x.at[2].set(0.0)                    # absmax == 0 row
    x_hat = comp.roundtrip(x)
    assert np.all(np.isfinite(np.asarray(x_hat)))
    # per-row error bounded by half a quantization step
    step = np.max(np.abs(np.asarray(x)), axis=1, keepdims=True) / 127.0
    assert np.all(np.abs(np.asarray(x_hat - x)) <= step / 2 + 1e-7)
    np.testing.assert_array_equal(np.asarray(x_hat[2]), np.zeros(32))


def test_fp8_overflow_clips_instead_of_nan():
    comp = comp_lib.make_compressor(CompressionConfig("fp8"))
    x = jnp.asarray([[1e6, -1e6, 0.5, 0.0]], jnp.float32)
    x_hat = np.asarray(comp.roundtrip(x))
    assert np.all(np.isfinite(x_hat))
    fmax = float(jnp.finfo(jnp.float8_e4m3fn).max)
    np.testing.assert_allclose(x_hat[0, :2], [fmax, -fmax])


def test_topk_keeps_largest_magnitudes():
    comp = comp_lib.make_compressor(CompressionConfig("topk_ef", k=3))
    x = jnp.asarray([[0.1, -5.0, 0.2, 4.0, -0.3, 3.0, 0.0, 0.05]],
                    jnp.float32)
    x_hat = np.asarray(comp.roundtrip(x))
    kept = np.flatnonzero(x_hat[0])
    np.testing.assert_array_equal(sorted(kept), [1, 3, 5])
    np.testing.assert_allclose(x_hat[0, kept], [-5.0, 4.0, 3.0], rtol=1e-3)


def test_topk_values_clip_to_f16_finite_range():
    """|value| > 65504 must ship as the f16 max, not overflow to inf
    (which would poison the server mean); the clipped-off magnitude lands
    in the EF residual instead."""
    comp = comp_lib.make_compressor(CompressionConfig("topk_ef", k=2))
    x = jnp.asarray([[1e6, -1e6, 0.5, 0.1, 0.0, 0.0, 0.0, 0.0]],
                    jnp.float32)
    x_hat = np.asarray(comp.roundtrip(x))
    assert np.all(np.isfinite(x_hat))
    np.testing.assert_allclose(x_hat[0, :2], [65504.0, -65504.0])
    _, xh, ef = comp_lib.roundtrip_with_ef(comp, x, jnp.zeros_like(x))
    assert np.all(np.isfinite(np.asarray(ef)))


def test_topk_wide_rows_use_i32_indices():
    """Rows wider than the int16 range (huge concat broadcasts) must ship
    i32 columns — a wrapped i16 index would scatter out of bounds and be
    silently dropped under jit."""
    comp = comp_lib.make_compressor(CompressionConfig("topk_ef", k=2))
    d = 2 ** 15 + 8
    x = np.zeros((1, d), np.float32)
    x[0, d - 1] = 3.0            # index beyond int16 range
    x[0, d - 2] = -2.0
    payload = comp.encode(jnp.asarray(x))
    assert payload["i"].dtype == jnp.int32
    x_hat = np.asarray(comp.decode(payload, d))
    np.testing.assert_allclose(x_hat[0, d - 1], 3.0, rtol=1e-3)
    np.testing.assert_allclose(x_hat[0, d - 2], -2.0, rtol=1e-3)
    assert comp.wire_bytes(1, d) == 2 * (2 + 4)
    assert _payload_nbytes(payload) == comp.wire_bytes(1, d)
    # narrow rows keep the 2-byte index format
    narrow = comp.encode(jnp.asarray(np.zeros((1, 16), np.float32)))
    assert narrow["i"].dtype == jnp.int16


def test_topk_k_geq_d_degenerates_to_identity():
    """k >= d keeps every entry: the codec ships the dense float32 row
    (cheaper than value+index pairs), the round-trip is EXACT, and the
    error-feedback residual is identically zero."""
    d = 16
    comp = comp_lib.make_compressor(CompressionConfig("topk_ef", k=d))
    x = jnp.asarray(np.random.default_rng(1).normal(
        size=(4, d)).astype(np.float32))
    payload = comp.encode(x)
    assert set(payload) == {"dense"}
    np.testing.assert_array_equal(np.asarray(comp.decode(payload, d)),
                                  np.asarray(x))
    assert comp.wire_bytes(4, d) == 4 * d * 4
    _, x_hat, ef = comp_lib.roundtrip_with_ef(comp, x, jnp.zeros_like(x))
    np.testing.assert_array_equal(np.asarray(ef), np.zeros_like(ef))


@pytest.mark.parametrize("method,kw", METHODS)
def test_wire_bytes_prices_actual_payload(method, kw):
    cc = CompressionConfig(method, **{k: v for k, v in kw.items()})
    comp = comp_lib.make_compressor(cc)
    for n, d in [(7, 16), (96, 64), (1, 8)]:
        x = jnp.asarray(np.random.default_rng(n).normal(
            size=(n, d)).astype(np.float32))
        assert _payload_nbytes(comp.encode(x)) == comp.wire_bytes(n, d)


def test_wire_ratios_meet_the_paper_targets():
    """The pure-embedding wire ratios that back the benchmark gate:
    int8 > 3x, topk_ef at k = d/8 >= 6x (at the cora-profile width)."""
    d = 64
    dense = 512 * d * 4
    int8 = comp_lib.make_compressor(CompressionConfig("int8"))
    topk = comp_lib.make_compressor(CompressionConfig("topk_ef", k=d // 8))
    assert dense / int8.wire_bytes(512, d) > 3.0
    assert dense / topk.wire_bytes(512, d) >= 6.0


def test_roundtrip_with_ef_conserves_signal():
    # classic EF (ef_decay=1): wire value plus kept residual IS the input
    comp = comp_lib.make_compressor(
        CompressionConfig("int8", error_feedback=True, ef_decay=1.0))
    x = jnp.asarray(np.random.default_rng(2).normal(
        size=(3, 5, 16)).astype(np.float32))
    ef = jnp.asarray(np.random.default_rng(3).normal(
        size=(3, 5, 16)).astype(np.float32)) * 0.01
    _, x_hat, new_ef = comp_lib.roundtrip_with_ef(comp, x, ef)
    np.testing.assert_allclose(np.asarray(x_hat + new_ef),
                               np.asarray(x + ef), rtol=1e-6, atol=1e-6)
    # decayed EF carries exactly ef_decay of that residual
    comp2 = comp_lib.make_compressor(
        CompressionConfig("int8", error_feedback=True, ef_decay=0.5))
    _, x_hat2, new_ef2 = comp_lib.roundtrip_with_ef(comp2, x, ef)
    np.testing.assert_allclose(np.asarray(new_ef2),
                               0.5 * np.asarray(new_ef), rtol=1e-6,
                               atol=1e-7)
    with pytest.raises(ValueError, match="ef_decay"):
        CompressionConfig("int8", ef_decay=1.5)


# ------------------------------------------------------------ config surface
def test_compression_config_validation():
    with pytest.raises(ValueError, match="unknown compression method"):
        CompressionConfig("int4")
    with pytest.raises(ValueError, match="requires k"):
        CompressionConfig("topk_ef")
    with pytest.raises(ValueError, match="only meaningful"):
        CompressionConfig("int8", k=4)
    assert CompressionConfig("topk_ef", k=4).resolved_error_feedback
    assert not CompressionConfig("int8").resolved_error_feedback
    assert CompressionConfig("int8", error_feedback=True) \
        .resolved_error_feedback
    assert not CompressionConfig("none").active
    assert comp_lib.make_compressor(CompressionConfig("identity")) is None


def test_experiment_config_compression_block():
    cfg = ExperimentConfig(name="c", dataset="tiny", hidden=16,
                           compression={"method": "topk_ef", "k": 2})
    assert isinstance(cfg.compression, CompressionConfig)
    assert cfg.compression.k == 2
    rt = ExperimentConfig.from_dict(cfg.to_dict())
    assert rt == cfg and isinstance(rt.compression, CompressionConfig)
    with pytest.raises(ValueError, match="invalid compression block"):
        ExperimentConfig(name="c", compression={"method": "nope"})
    with pytest.raises(ValueError, match="secure_agg"):
        ExperimentConfig(name="c", compression={"method": "int8"},
                         secure_agg=True)
    # a GlasuConfig built directly enforces the same incompatibility
    with pytest.raises(AssertionError, match="secure_agg"):
        glasu.GlasuConfig(secure_agg=True,
                          compression=CompressionConfig("int8"))


# ----------------------------------------------------------- engine parity
def _setup(method, kw, **cfg_kw):
    cfg = ExperimentConfig(
        name=f"comp-{method}", dataset="tiny", hidden=16, batch_size=8,
        size_cap=96, rounds=4, eval_every=4, lr=0.05, optimizer="adam",
        compression=dict(method=method, **kw), **cfg_kw)
    data = make_vfl_dataset("tiny", n_clients=cfg.n_clients, seed=cfg.seed)
    mcfg = cfg.glasu_config(data)
    sampler = GlasuSampler(data, cfg.sampler_config(), seed=cfg.seed)
    return cfg, data, mcfg, sampler


@pytest.mark.parametrize("method,kw", METHODS)
def test_scan_matches_sequential_rounds(method, kw):
    cfg, data, mcfg, sampler = _setup(method, kw)
    opt = cfg.make_optimizer()
    params = glasu.init_params(jax.random.PRNGKey(0), mcfg)
    cs0 = glasu.init_comp_state(mcfg, sampler.layer_sizes)
    rounds = [jax.tree.map(np.array, sampler.sample_round())
              for _ in range(4)]
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        jax.random.PRNGKey(1), jnp.arange(4))

    mf = glasu.make_multi_round_fn(mcfg, opt)
    p1, s1, c1 = jax.tree.map(jnp.array, (params, opt.init(params),
                                          glasu.RoundCarry(cs0)))
    seq = []
    for t in range(4):
        p1, s1, l, c1 = mf(p1, s1, stack_rounds([rounds[t]]),
                           keys[t:t + 1], c1)
        seq.append(np.asarray(l[0]))

    p2, s2, c2 = jax.tree.map(jnp.array, (params, opt.init(params),
                                          glasu.RoundCarry(cs0)))
    p2, s2, losses, c2 = mf(p2, s2,
                            jax.tree.map(jnp.asarray, stack_rounds(rounds)),
                            keys, c2)
    np.testing.assert_allclose(np.asarray(losses), np.stack(seq), **COMP_TOL)
    for (pa, la), (_, lb) in zip(jax.tree_util.tree_leaves_with_path(p1),
                                 jax.tree_util.tree_leaves_with_path(p2)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   err_msg=jax.tree_util.keystr(pa),
                                   **COMP_TOL)
    for (pa, la), (_, lb) in zip(jax.tree_util.tree_leaves_with_path(c1),
                                 jax.tree_util.tree_leaves_with_path(c2)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   err_msg=jax.tree_util.keystr(pa),
                                   **COMP_TOL)


@pytest.mark.parametrize("method,kw", [("int8", {}),
                                       ("topk_ef", {"k": 2})])
def test_vmapped_matches_simulation_compressed(method, kw):
    """The message-passing simulation is an independent implementation of
    the compressed protocol; two rounds must agree (and so must the EF
    accumulators and every byte meter)."""
    cfg, data, mcfg, sampler = _setup(method, kw)
    opt = cfg.make_optimizer()
    params = glasu.init_params(jax.random.PRNGKey(0), mcfg)
    rounds = [jax.tree.map(np.array, sampler.sample_round())
              for _ in range(2)]
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        jax.random.PRNGKey(1), jnp.arange(2))

    def run(backend):
        backend.bind(mcfg, opt, sampler)
        p = jax.tree.map(jnp.array, params)
        s = opt.init(p)
        losses, comm = [], None
        for t in range(2):
            out = backend.run_round(p, s, jax.tree.map(jnp.asarray,
                                                       rounds[t]), keys[t])
            p, s = out.params, out.opt_state
            losses.append(np.asarray(out.losses))
            comm = out.comm_bytes
        return p, np.stack(losses), comm, backend.comp_state

    p_v, l_v, comm_v, cs_v = run(VmappedBackend())
    p_s, l_s, comm_s, cs_s = run(SimulationBackend())
    assert comm_v == comm_s > 0
    np.testing.assert_allclose(l_s, l_v, **COMP_TOL)
    for (pa, la), (_, lb) in zip(jax.tree_util.tree_leaves_with_path(p_s),
                                 jax.tree_util.tree_leaves_with_path(p_v)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   err_msg=jax.tree_util.keystr(pa),
                                   **COMP_TOL)
    if cs_v:
        # EF accumulators are NOT compared element-wise across the two
        # implementations: a ULP-level tie between two near-equal
        # magnitudes makes top_k keep different entries, so the residuals
        # legitimately differ by a full entry value at those slots. The
        # behavioral contract is that losses/params agree (above) and
        # that each implementation conserves signal: x_hat + ef == input.
        assert jax.tree.structure(cs_s) == jax.tree.structure(cs_v)


@pytest.mark.parametrize("method,kw", METHODS)
def test_byte_meters_agree_and_shrink(method, kw):
    """analytic (sampler cost model) == measured (simulation message log)
    == shape-only replay, and all are smaller than the dense meter."""
    cfg, data, mcfg, sampler = _setup(method, kw)
    comp = comp_lib.make_compressor(mcfg.compression)
    analytic = sampler.comm_bytes_per_joint_inference(
        mcfg.hidden, mcfg.agg, compressor=comp)
    dense = sampler.comm_bytes_per_joint_inference(mcfg.hidden, mcfg.agg)
    assert analytic < dense

    sb = SimulationBackend()
    sb.bind(mcfg, cfg.make_optimizer(), sampler)
    params = glasu.init_params(jax.random.PRNGKey(0), mcfg)
    batch = jax.tree.map(jnp.array, sampler.sample_round())
    out = sb.run_round(params, sb.optimizer.init(params), batch,
                       jax.random.PRNGKey(0))
    assert out.comm_bytes == analytic      # audit already enforced at raise

    shell = sampler.shape_shell_batch()
    log = simulation.MessageLog()
    simulation.log_index_sync(log, shell, mcfg)
    simulation.log_agg_traffic(log, shell, mcfg, compressor=comp)
    assert log.total_bytes() == analytic
    for kind in ("upload", "broadcast", "index_sync"):
        assert log.total_bytes(kind) == out.message_log.total_bytes(kind)


# --------------------------------------------------- checkpointing of EF
def test_ef_accumulator_checkpoint_bitwise_roundtrip(tmp_path):
    """Interrupt/resume with error feedback: the comp_<step>.npz sidecar
    restores the accumulators bitwise, so a resumed run reproduces the
    uninterrupted one exactly (same program, same state)."""
    data = make_vfl_dataset("tiny", n_clients=3, seed=0)
    base = ExperimentConfig(
        name="ef-ckpt", dataset="tiny", hidden=16, batch_size=8,
        size_cap=96, rounds=4, eval_every=2, lr=0.05, optimizer="adam",
        compression={"method": "topk_ef", "k": 2})
    cfg = base.with_(ckpt_dir=str(tmp_path), ckpt_every=2, rounds=2)
    Trainer(cfg, data=data).run()
    assert (tmp_path / "comp_00000002.npz").exists()

    res = Trainer(cfg.with_(rounds=4), data=data).run()   # resume 2 -> 4
    straight = Trainer(base, data=data).run()
    for (pa, la), (_, lb) in zip(
            jax.tree_util.tree_leaves_with_path(res.params),
            jax.tree_util.tree_leaves_with_path(straight.params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=jax.tree_util.keystr(pa))
    assert res.comm_bytes == straight.comm_bytes


def test_ef_restored_bitwise_at_resume(tmp_path):
    data = make_vfl_dataset("tiny", n_clients=3, seed=0)
    cfg = ExperimentConfig(
        name="ef-bits", dataset="tiny", hidden=16, batch_size=8,
        size_cap=96, rounds=2, eval_every=2, lr=0.05,
        compression={"method": "int8", "error_feedback": True},
        ckpt_dir=str(tmp_path), ckpt_every=2)
    t1 = Trainer(cfg, data=data)
    t1.run()
    saved_cs = jax.tree.map(np.array, t1.backend.comp_state)
    t2 = Trainer(cfg, data=data)            # resume landing on rounds == 2
    t2.run()
    for (pa, la), (_, lb) in zip(
            jax.tree_util.tree_leaves_with_path(saved_cs),
            jax.tree_util.tree_leaves_with_path(t2.backend.comp_state)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=jax.tree_util.keystr(pa))


def test_compression_is_resume_mutable(tmp_path):
    """The compression block may change across a resume (it is a wire
    strategy, not model state): codec changes reset the EF accumulators,
    enabling/disabling compression round-trips cleanly."""
    data = make_vfl_dataset("tiny", n_clients=3, seed=0)
    base = ExperimentConfig(
        name="comp-resume", dataset="tiny", hidden=16, batch_size=8,
        size_cap=96, rounds=2, eval_every=2, lr=0.05,
        ckpt_dir=str(tmp_path), ckpt_every=2,
        compression={"method": "topk_ef", "k": 2})
    Trainer(base, data=data).run()
    # switch codec: topk_ef -> int8+EF; the stale accumulators must NOT be
    # restored (same tree shapes, different meaning)
    t2 = Trainer(base.with_(rounds=4,
                            compression={"method": "int8",
                                         "error_feedback": True}),
                 data=data)
    t2.run()
    # then drop compression entirely and resume again
    res = Trainer(base.with_(rounds=6, compression=None), data=data).run()
    assert res.rounds_run == 6
    # and re-enable from a dense checkpoint
    res = Trainer(base.with_(rounds=8), data=data).run()
    assert res.rounds_run == 8


def test_codec_change_resets_accumulators(tmp_path):
    data = make_vfl_dataset("tiny", n_clients=3, seed=0)
    base = ExperimentConfig(
        name="comp-reset", dataset="tiny", hidden=16, batch_size=8,
        size_cap=96, rounds=2, eval_every=2, lr=0.05,
        ckpt_dir=str(tmp_path), ckpt_every=2,
        compression={"method": "topk_ef", "k": 2})
    t1 = Trainer(base, data=data)
    t1.run()
    assert any(float(jnp.sum(jnp.abs(v))) > 0
               for v in jax.tree.leaves(t1.backend.comp_state))
    t2 = Trainer(base.with_(rounds=2,
                            compression={"method": "int8",
                                         "error_feedback": True}),
                 data=data)
    t2.state.params = glasu.init_params(jax.random.PRNGKey(t2.cfg.seed),
                                        t2.model_cfg)
    t2.state.opt_state = t2.optimizer.init(t2.state.params)
    for h in t2.hooks:
        h.on_train_start(t2)                # resume to round 2, no new rounds
    for v in jax.tree.leaves(t2.backend.comp_state):
        np.testing.assert_array_equal(np.asarray(v), np.zeros_like(v))


# ----------------------------------------------------------- trainer E2E
def test_trainer_comm_bytes_shrink_and_loss_trains():
    data = make_vfl_dataset("tiny", n_clients=3, seed=0)
    base = ExperimentConfig(name="comp-e2e", dataset="tiny", hidden=16,
                            batch_size=8, size_cap=96, rounds=4,
                            eval_every=4, lr=0.05, optimizer="adam")
    dense = Trainer(base, data=data).run()
    comp = Trainer(base.with_(compression={"method": "int8"}),
                   data=data).run()
    assert 0 < comp.comm_bytes < dense.comm_bytes
    assert np.isfinite(comp.history[-1]["loss"])


def test_uncompressed_backend_state_is_none():
    data = make_vfl_dataset("tiny", n_clients=3, seed=0)
    cfg = ExperimentConfig(name="dense", dataset="tiny", hidden=16,
                           batch_size=8, size_cap=96, rounds=0)
    t = Trainer(cfg, data=data)
    assert t.backend.compressor is None and t.backend.comp_state is None
