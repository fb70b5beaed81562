"""Device-resident round engine: scan parity, compile stability, prefetch.

The engine's contract is that ``rounds_per_step=K`` is *observationally
identical* to K sequential rounds — same final params (bit-exact), same
loss history, same comm-byte accounting — while compiling one round body
and dispatching once per K rounds.
"""
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.api import ExperimentConfig, Trainer
from repro.api.trainer import step_schedule
from repro.comm.compression import CompressionConfig
from repro.core import glasu
from repro.core.glasu import GlasuConfig
from repro.fed.faults import FaultConfig, FaultSchedule, stack_plans
from repro.graph.prefetch import PrefetchSampler, stack_rounds, unstack_round
from repro.graph.sampler import GlasuSampler, SamplerConfig
from repro.graph.synth import make_vfl_dataset
from repro.kernels import ops as kops
from repro.launch.mesh import make_client_mesh
from repro.optim import optimizers as opt_lib
from tools.glint.contracts import POLICY_COMBOS

TINY = dict(name="engine", dataset="tiny", hidden=16, batch_size=8,
            size_cap=96, lr=0.02)


def _setup(seed=0):
    data = make_vfl_dataset("tiny", n_clients=3, seed=seed)
    d_in = max(c.feat_dim for c in data.clients)
    mcfg = GlasuConfig(n_clients=3, n_layers=4, hidden=16,
                       n_classes=data.n_classes, d_in=d_in,
                       agg_layers=(1, 3))
    scfg = SamplerConfig(n_layers=4, agg_layers=(1, 3), batch_size=8,
                         fanout=3, size_cap=96)
    sampler = GlasuSampler(data, scfg, seed=seed)
    params = glasu.init_params(jax.random.PRNGKey(seed), mcfg)
    return data, mcfg, sampler, params


def _copy(tree):
    return jax.tree.map(jnp.array, tree)


def _assert_trees_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _assert_trees_close(a, b, rtol=1e-5, atol=1e-6):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=rtol, atol=atol)


# ----------------------------------------------------------- core scan fn
def _with_policy(mcfg, policy: str):
    """``mcfg`` under one of glint's execution-policy combinations."""
    kw = {}
    if "int8" in policy:
        kw["compression"] = CompressionConfig(method="int8")
    if "faults" in policy:
        kw["fault_tolerant"] = True
    return dataclasses.replace(mcfg, **kw)


def _build(transport: str, mcfg, opt):
    """The transport's round builder; the sharded one on the client mesh
    of the available devices (one on the tier-1 CPU)."""
    if transport == "vmapped":
        return glasu.make_multi_round_fn(mcfg, opt)
    return glasu.make_sharded_multi_round_fn(
        mcfg, opt, make_client_mesh(mcfg.n_clients))


@pytest.mark.parametrize("transport", ["vmapped", "sharded"])
@pytest.mark.parametrize("policy", POLICY_COMBOS)
def test_multi_round_fn_matches_sequential_rounds(policy, transport):
    """One scanned K = 4 dispatch == four K = 1 steps of the same builder,
    the carries (error-feedback accumulators, fault cache) included, under
    every execution policy and both transports.

    XLA may fuse the K = 1 and K = 4 scans differently, so this is
    ULP-close rather than bit-equal; the engine's bit-exact contract
    (rounds_per_step=K vs K steps of the engine at K=1) is covered by the
    Trainer parity tests below."""
    _, mcfg, sampler, params = _setup()
    mcfg = _with_policy(mcfg, policy)
    opt = opt_lib.make_optimizer("adam", 0.02)
    step_fn = _build(transport, mcfg, opt)
    rounds = [jax.tree.map(np.array, sampler.sample_round())
              for _ in range(4)]
    key = jax.random.PRNGKey(7)
    keys = jnp.stack([jax.random.fold_in(key, t) for t in range(4)])
    carry0 = glasu.RoundCarry(
        glasu.init_comp_state(mcfg, sampler.layer_sizes),
        glasu.init_fault_state(mcfg, sampler.layer_sizes))
    faults = None
    if mcfg.fault_tolerant:
        sched = FaultSchedule(FaultConfig(
            seed=5, drop_prob=0.3, deadline_ms=40.0, base_latency_ms=10.0,
            straggler_prob=0.3, straggler_scale=6.0, max_staleness=2),
            mcfg.n_clients)
        present, weight = stack_plans([sched.next_round()
                                       for _ in range(4)])
        faults = glasu.RoundFaults(jnp.asarray(present), jnp.asarray(weight))

    p_seq, s_seq, c_seq = _copy((params, opt.init(params), carry0))
    seq_losses = []
    for t in range(4):
        p_seq, s_seq, losses, c_seq = step_fn(
            p_seq, s_seq, stack_rounds([rounds[t]]), keys[t:t + 1], c_seq,
            None if faults is None else jax.tree.map(lambda x: x[t:t + 1],
                                                     faults))
        seq_losses.append(losses[0])

    p_k, s_k, losses_k, c_k = step_fn(
        *_copy((params, opt.init(params), stack_rounds(rounds))), keys,
        _copy(carry0), faults)
    assert losses_k.shape == (4, mcfg.n_local_steps)
    assert jax.tree.structure(c_k) == jax.tree.structure(carry0)
    _assert_trees_close(p_k, p_seq)
    _assert_trees_close(s_k, s_seq)
    _assert_trees_close(c_k, c_seq)
    _assert_trees_close(losses_k, jnp.stack(seq_losses))


@pytest.mark.parametrize("transport", ["vmapped", "sharded"])
def test_plain_step_lowers_without_carry_arguments(transport):
    """Off the codec and fault axes the carry is ``RoundCarry(None, None)``,
    an empty pytree: the step lowers to the same program whether the call
    passes it or leaves it at its default, with one argument per leaf of
    params, opt state, batches and keys."""
    _, mcfg, sampler, params = _setup()
    opt = opt_lib.make_optimizer("adam", 0.02)
    step_fn = _build(transport, mcfg, opt)
    args = (params, opt.init(params),
            stack_rounds([sampler.sample_round() for _ in range(2)]),
            jnp.stack([jax.random.PRNGKey(t) for t in range(2)]))
    lowered = step_fn.lower(*args)
    assert lowered.as_text() == step_fn.lower(
        *args, glasu.RoundCarry(), None).as_text()
    assert len(jax.tree.leaves(lowered.in_avals)) == \
        len(jax.tree.leaves(args))


def test_multi_round_fn_rejects_mismatched_k():
    _, mcfg, sampler, params = _setup()
    opt = opt_lib.make_optimizer("adam", 0.02)
    step_fn = glasu.make_multi_round_fn(mcfg, opt, rounds_per_step=4)
    rounds = [jax.tree.map(np.array, sampler.sample_round())
              for _ in range(2)]
    keys = jnp.stack([jax.random.PRNGKey(0)] * 2)
    with pytest.raises(ValueError, match="rounds_per_step"):
        step_fn(_copy(params), opt.init(params), stack_rounds(rounds), keys)


# ------------------------------------------------------------- scheduling
def test_step_schedule_cuts_at_cadence_boundaries():
    # uniform when everything divides
    assert step_schedule(0, 16, 4, (8, 0)) == [4, 4, 4, 4]
    # eval cadence 5 cuts each K=4 run short at multiples of 5
    assert step_schedule(0, 12, 4, (5,)) == [4, 1, 4, 1, 2]
    # resume from a mid-grid round realigns at the next boundary
    assert step_schedule(3, 10, 4, (4,)) == [1, 4, 2]
    # K=1 degenerates to the per-round loop
    assert step_schedule(0, 3, 1, (2,)) == [1, 1, 1]
    assert step_schedule(5, 5, 4, (2,)) == []
    # every boundary of every cadence ends a step
    for steps, cads in [((0, 40, 8), (6, 10)), ((7, 31, 16), (5,))]:
        sched = step_schedule(*steps, cads)
        t, ends = steps[0], []
        for k in sched:
            t += k
            ends.append(t)
        assert t == steps[1]
        for c in cads:
            for b in range(steps[0] + 1, steps[1] + 1):
                if c and b % c == 0:
                    assert b in ends


# ------------------------------------------------------- trainer parity
@pytest.mark.parametrize("k", [2, 4])
def test_trainer_rounds_per_step_bit_exact(k):
    """K-round steps vs per-round loop: params, losses, history, bytes."""
    data = make_vfl_dataset("tiny", n_clients=3, seed=0)
    cfg = ExperimentConfig(**TINY, rounds=8, eval_every=4)
    r1 = Trainer(cfg, data=data).run()
    rk = Trainer(cfg.with_(rounds_per_step=k), data=data).run()
    _assert_trees_equal(rk.params, r1.params)
    assert rk.comm_bytes == r1.comm_bytes
    assert [h["round"] for h in rk.history] == [h["round"] for h in r1.history]
    assert [h["loss"] for h in rk.history] == [h["loss"] for h in r1.history]
    assert [h["comm_bytes"] for h in rk.history] == \
        [h["comm_bytes"] for h in r1.history]


@pytest.mark.slow
def test_trainer_parity_with_misaligned_cadence():
    """eval_every that does not divide rounds_per_step still evaluates the
    exact same rounds with the exact same state (remainder steps)."""
    data = make_vfl_dataset("tiny", n_clients=3, seed=0)
    cfg = ExperimentConfig(**TINY, rounds=7, eval_every=3)
    r1 = Trainer(cfg, data=data).run()
    rk = Trainer(cfg.with_(rounds_per_step=4), data=data).run()
    assert [h["round"] for h in rk.history] == [3, 6, 7]
    _assert_trees_equal(rk.params, r1.params)
    assert [h["loss"] for h in rk.history] == [h["loss"] for h in r1.history]


@pytest.mark.slow
def test_resume_mid_step_bit_exact(tmp_path):
    """A checkpoint landing mid-K-grid (ckpt_every cuts the step) resumes
    into the scanned engine bit-exact with an uninterrupted sequential run."""
    data = make_vfl_dataset("tiny", n_clients=3, seed=0)
    cfg = ExperimentConfig(**TINY, rounds=3, rounds_per_step=2, eval_every=2,
                           ckpt_dir=str(tmp_path), ckpt_every=3)
    Trainer(cfg, data=data).run()        # steps [2, 1] -> ckpt at round 3
    assert (tmp_path / "LATEST").read_text().strip() == "3"
    res = Trainer(cfg.with_(rounds=7), data=data).run()   # resumes mid-grid
    seq = Trainer(ExperimentConfig(**TINY, rounds=7, eval_every=2),
                  data=data).run()
    _assert_trees_equal(res.params, seq.params)
    assert res.comm_bytes == seq.comm_bytes
    # the first run's end-of-run eval at round 3 rides along in the restored
    # history; every cadence entry matches the uninterrupted run exactly
    assert [h["round"] for h in res.history] == [2, 3, 4, 6, 7]
    seq_by_round = {h["round"]: h["loss"] for h in seq.history}
    for h in res.history:
        if h["round"] in seq_by_round:
            assert h["loss"] == seq_by_round[h["round"]]


def test_rng_sidecar_skips_replay_on_resume(tmp_path):
    """New sidecars restore the sampler bit state directly: the resumed run
    draws only the remaining rounds instead of replaying the whole stream."""
    data = make_vfl_dataset("tiny", n_clients=3, seed=0)
    cfg = ExperimentConfig(**TINY, rounds=4, eval_every=2,
                           ckpt_dir=str(tmp_path))
    Trainer(cfg, data=data).run()
    sidecar = json.loads((tmp_path / "state_00000004.json").read_text())
    assert sidecar["sampler_rng"] is not None

    tr = Trainer(cfg.with_(rounds=6), data=data)
    calls = []
    orig = tr.sampler.sample_round
    tr.sampler.sample_round = lambda: calls.append(1) or orig()
    res = tr.run()
    assert tr.sampler_restored
    assert len(calls) == 2               # rounds 5..6 only, no 1..4 replay
    assert res.rounds_run == 6


def test_rng_sidecar_fallback_to_replay(tmp_path):
    """Old sidecars (no sampler_rng field) keep the replay fallback and
    still produce the uninterrupted stream."""
    data = make_vfl_dataset("tiny", n_clients=3, seed=0)
    cfg = ExperimentConfig(**TINY, rounds=3, eval_every=3,
                           ckpt_dir=str(tmp_path))
    Trainer(cfg, data=data).run()
    sc = tmp_path / "state_00000003.json"
    legacy = json.loads(sc.read_text())
    legacy.pop("sampler_rng")
    sc.write_text(json.dumps(legacy))

    tr = Trainer(cfg.with_(rounds=5), data=data)
    calls = []
    orig = tr.sampler.sample_round
    tr.sampler.sample_round = lambda: calls.append(1) or orig()
    res = tr.run()
    assert not tr.sampler_restored
    assert len(calls) == 5               # 3 replayed + 2 new
    seq = Trainer(ExperimentConfig(**TINY, rounds=5, eval_every=3),
                  data=data).run()
    _assert_trees_equal(res.params, seq.params)


def test_run_round_only_backend_falls_back_to_sequential_step():
    """A backend implementing only the pre-engine protocol (bind/run_round/
    joint_logits) still trains: the Trainer falls back to K sequential
    audited rounds per step."""
    from repro.api.backends import VmappedBackend

    class LegacyBackend:
        name = "legacy"

        def bind(self, mcfg, opt, sampler):
            self._v = VmappedBackend()
            self._v.bind(mcfg, opt, sampler)

        def run_round(self, p, s, b, key):
            return self._v.run_round(p, s, b, key)

        def joint_logits(self, p, b, key=None):
            return self._v.joint_logits(p, b, key)

    data = make_vfl_dataset("tiny", n_clients=3, seed=0)
    cfg = ExperimentConfig(**TINY, rounds=4, eval_every=2, rounds_per_step=2)
    res = Trainer(cfg, data=data, backend=LegacyBackend()).run()
    ref = Trainer(cfg, data=data).run()
    assert res.rounds_run == 4
    assert res.comm_bytes == ref.comm_bytes
    assert [h["round"] for h in res.history] == \
        [h["round"] for h in ref.history]


def test_extra_checkpoint_hook_cadence_cuts_steps(tmp_path):
    """Every CheckpointHook's cadence ends a step — not just the config-owned
    one — so a user hook's sidecar rng state matches st.round exactly."""
    from repro.api import CheckpointHook

    data = make_vfl_dataset("tiny", n_clients=3, seed=0)
    cfg = ExperimentConfig(**TINY, rounds=5, rounds_per_step=4, eval_every=5)
    tr = Trainer(cfg, data=data,
                 hooks=[CheckpointHook(str(tmp_path), every=3)])
    tr.run()
    sidecar = json.loads((tmp_path / "state_00000003.json").read_text())
    ref = GlasuSampler(data, cfg.sampler_config(), seed=cfg.seed)
    for _ in range(3):
        ref.sample_round()
    assert sidecar["sampler_rng"] == ref.rng.bit_generator.state


# -------------------------------------------------------- compile stability
def test_multi_round_fn_traces_once_across_run():
    """Aligned cadences -> a uniform step schedule -> exactly one trace of
    the scanned step function for the whole training run."""
    data = make_vfl_dataset("tiny", n_clients=3, seed=0)
    cfg = ExperimentConfig(**TINY, rounds=12, rounds_per_step=4, eval_every=4)
    tr = Trainer(cfg, data=data)
    tr.run()
    assert tr.backend.step_fn._cache_size() == 1


def test_remainder_steps_add_at_most_one_retrace():
    data = make_vfl_dataset("tiny", n_clients=3, seed=0)
    cfg = ExperimentConfig(**TINY, rounds=10, rounds_per_step=4, eval_every=5)
    tr = Trainer(cfg, data=data)
    tr.run()                              # schedule [4, 1, 4, 1] -> K in {4, 1}
    assert tr.backend.step_fn._cache_size() == 2


# ---------------------------------------------------------------- prefetch
def test_prefetch_reproduces_sequential_stream():
    _, _, ref_sampler, _ = _setup(seed=3)
    want = [jax.tree.map(np.array, ref_sampler.sample_round())
            for _ in range(5)]
    _, _, sampler, _ = _setup(seed=3)
    schedule = [2, 2, 1]
    pf = PrefetchSampler(sampler, schedule, n_buffers=2)
    try:
        got, states = [], []
        for _ in schedule:
            step = pf.get()
            for i in range(step.rounds):
                got.append(jax.tree.map(np.array,
                                        unstack_round(step.data, i)))
            states.append(step.rng_state_after)
            pf.retire(step, None)
    finally:
        pf.close()
    assert len(got) == 5
    for a, b in zip(got, want):
        _assert_trees_equal(a, b)
    # the final carried state is exactly the sequential sampler's state
    assert states[-1] == ref_sampler.rng.bit_generator.state


def test_prefetch_generation_not_reused_before_release():
    """The worker must not refill a generation until retire() released it:
    batches from consecutive steps live in distinct buffers."""
    _, _, sampler, _ = _setup()
    pf = PrefetchSampler(sampler, [1, 1, 1], n_buffers=2)
    try:
        s0 = pf.get()
        s1 = pf.get()                    # both generations filled
        assert s0.gen != s1.gen
        assert s0.data.labels.base is not s1.data.labels.base
        first = np.array(s0.data.labels)
        pf.retire(s0, None)
        pf.retire(s1, None)              # releases gen of s0 -> worker refills
        s2 = pf.get()
        assert s2.gen == s0.gen          # buffer recycled ...
        np.testing.assert_array_equal(first, np.asarray(first))
        pf.retire(s2, None)
    finally:
        pf.close()


def test_prefetch_worker_error_propagates():
    _, _, sampler, _ = _setup()
    sampler.sample_round = lambda: (_ for _ in ()).throw(
        RuntimeError("boom"))
    pf = PrefetchSampler(sampler, [1, 1], n_buffers=2)
    try:
        with pytest.raises(RuntimeError, match="prefetch worker failed"):
            pf.get()
    finally:
        pf.close()


def test_prefetch_close_mid_run_joins_worker():
    _, _, sampler, _ = _setup()
    pf = PrefetchSampler(sampler, [1] * 50, n_buffers=2)
    pf.get()                              # consume one, leave the rest
    pf.close()
    assert not pf._thread.is_alive()


def test_prefetch_worker_error_while_consumer_blocked():
    """A worker that dies MID-STREAM (after a successful step) must wake the
    consumer blocked in get() with the error — not leave it deadlocked."""
    _, _, sampler, _ = _setup()
    real = sampler.sample_round
    calls = []

    def failing_round():
        calls.append(1)
        if len(calls) > 1:
            time.sleep(0.2)         # let the consumer block in get() first
            raise RuntimeError("boom mid-stream")
        return real()

    sampler.sample_round = failing_round
    pf = PrefetchSampler(sampler, [1, 1], n_buffers=1)
    try:
        first = pf.get()
        pf.retire(first, None)      # frees the generation -> worker refills
        with pytest.raises(RuntimeError, match="prefetch worker failed"):
            pf.get()
    finally:
        pf.close()
    assert not pf._thread.is_alive()


def test_prefetch_close_mid_fill_joins_promptly():
    """close() while the worker is inside a long multi-round fill must cut
    the fill at the next round boundary, not sample the whole step out."""
    _, _, sampler, _ = _setup()
    real = sampler.sample_round

    def slow_round():
        time.sleep(0.15)
        return real()

    sampler.sample_round = slow_round
    pf = PrefetchSampler(sampler, [40], n_buffers=1)
    time.sleep(0.4)                       # worker is a few rounds into the fill
    t0 = time.monotonic()
    pf.close()
    assert not pf._thread.is_alive()
    # a full fill is 40 * 0.15s = 6s; the stop-check exits within one round
    assert time.monotonic() - t0 < 2.0


# ------------------------------------------------------------- validation
@pytest.mark.parametrize("kw", [dict(rounds_per_step=0),
                                dict(prefetch_buffers=0)])
def test_engine_config_validation(kw):
    with pytest.raises(ValueError):
        ExperimentConfig(**TINY, **kw)


# ------------------------------------------------------------ full_forward
def test_full_forward_chunked_matches_unchunked():
    """lax.map chunking is exact, including chunk sizes that do not divide
    the node count (the old clamped-slice concatenation misaligned rows
    there)."""
    rng = np.random.default_rng(0)
    m, n, d_in, cap = 2, 75, 12, 5
    cfg = GlasuConfig(n_clients=m, n_layers=4, hidden=16, n_classes=4,
                      d_in=d_in, agg_layers=(1, 3))
    params = glasu.init_params(jax.random.PRNGKey(0), cfg)
    feats = jnp.asarray(rng.normal(size=(m, n, d_in)), jnp.float32)
    idx = rng.integers(0, n, size=(m, n, cap + 1)).astype(np.int32)
    idx[..., 0] = np.arange(n)[None]
    mask = (rng.random((m, n, cap + 1)) < 0.8).astype(np.float32)
    mask[..., 0] = 1.0
    idx, mask = jnp.asarray(idx), jnp.asarray(mask)

    full = glasu.full_forward(params, cfg, feats, idx, mask, chunk=n)
    for chunk in (32, 25, 75):            # 32 does not divide 75
        out = glasu.full_forward(params, cfg, feats, idx, mask, chunk=chunk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                                   rtol=1e-5, atol=1e-5)


def _full_graph(m=3, n=301, d_in=12, cap=6, seed=0):
    """Party features and neighbour tables as the full-graph pass takes
    them: column 0 is the node itself, pad entries are node 0 under mask
    0, and some rows have no neighbour at all."""
    rng = np.random.default_rng(seed)
    feats = jnp.asarray(rng.normal(size=(m, n, d_in)), jnp.float32)
    idx = rng.integers(0, n, size=(m, n, cap + 1)).astype(np.int32)
    idx[..., 0] = np.arange(n)[None]
    mask = (rng.random((m, n, cap + 1)) < 0.8).astype(np.float32)
    mask[..., 0] = 1.0
    mask[:, ::7] = 0.0
    idx = np.where(mask > 0, idx, 0).astype(np.int32)
    return feats, jnp.asarray(idx), jnp.asarray(mask)


def _full_agg_counts(fn):
    """Run ``fn`` and return the ``full_agg:*`` counts it added."""
    names = ("full_agg:resident", "full_agg:xla")
    before = obs.counters()
    out = fn()
    after = obs.counters()
    return out, {k: after.get(k, 0) - before.get(k, 0) for k in names}


# the v5e core's VMEM and SMEM (pltpu.get_tpu_info)
V5E_MEMORY = (128 * 1024 * 1024, 1024 * 1024)


@pytest.mark.parametrize("backbone,hidden,agg", [
    ("gcn", 64, "mean"), ("gcnii", 64, "mean"),
    ("gcn", 32, "concat"),     # concat widens layer 2 to 96: not packable
])
def test_full_forward_resident_matches_xla(monkeypatch, backbone, hidden,
                                           agg):
    """The resident-kernel pass (in interpret mode, on a described v5e's
    memory) gives the XLA pass's logits and collected stacks, layer by
    layer wherever the source width packs into 128 lanes."""
    m, n_layers = 3, 4
    cfg = GlasuConfig(n_clients=m, n_layers=n_layers, hidden=hidden,
                      n_classes=5, d_in=12, backbone=backbone, agg=agg,
                      agg_layers=(1, 3))
    params = glasu.init_params(jax.random.PRNGKey(1), cfg)
    feats, idx, mask = _full_graph(m)

    def run():      # a fresh jit each time: the path is chosen as it traces
        return jax.jit(lambda p: glasu.full_forward(
            p, cfg, feats, idx, mask, chunk=128, collect_agg=True))(params)

    (want, want_aggs), xla = _full_agg_counts(run)
    assert xla == {"full_agg:resident": 0, "full_agg:xla": n_layers}
    monkeypatch.setattr(kops, "_tpu_memory", lambda: V5E_MEMORY)
    (got, got_aggs), res = _full_agg_counts(run)
    n_wide = 1 if agg == "concat" else 0
    assert res == {"full_agg:resident": n_layers - n_wide,
                   "full_agg:xla": n_wide}
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert sorted(got_aggs) == sorted(want_aggs) == [1, 3]
    for l in want_aggs:
        np.testing.assert_allclose(np.asarray(got_aggs[l]),
                                   np.asarray(want_aggs[l]),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", ["gat", "over_budget", "no_tpu",
                                  "use_pallas"])
def test_full_forward_dispatch_takes_xla(monkeypatch, case):
    """GAT, a source larger than VMEM, a CPU backend and ``use_pallas``
    all keep the XLA chunk loop, whose output is unchanged."""
    m, n_layers = 3, 4
    cfg = GlasuConfig(n_clients=m, n_layers=n_layers, hidden=64, n_classes=5,
                      d_in=12, backbone="gat" if case == "gat" else "gcnii",
                      agg_layers=(1, 3), use_pallas=case == "use_pallas")
    params = glasu.init_params(jax.random.PRNGKey(2), cfg)
    feats, idx, mask = _full_graph(m, n=150)
    if case == "over_budget":   # VMEM one reserve short of this source
        monkeypatch.setattr(kops, "_tpu_memory", lambda: (
            kops.resident_vmem_bytes(150, 64)
            + kops.RESIDENT_VMEM_RESERVE - 1, V5E_MEMORY[1]))
    elif case != "no_tpu":
        monkeypatch.setattr(kops, "_tpu_memory", lambda: V5E_MEMORY)
    else:
        assert kops._tpu_memory() == (0, 0)
    out, counts = _full_agg_counts(lambda: jax.jit(lambda p: glasu.full_forward(
        p, cfg, feats, idx, mask, chunk=64))(params))
    assert counts == {"full_agg:resident": 0, "full_agg:xla": n_layers}
    assert np.isfinite(np.asarray(out)).all()


# -------------------------------------------- glint layer-3 runtime guards
def test_round_fn_hot_path_is_transfer_free_and_trace_stable(
        retrace_guard, transfer_guard):
    """After the warmup compile, same-signature round dispatches must
    neither recompile (retrace_guard) nor move data implicitly between host
    and device (transfer_guard) — batches and keys are staged explicitly,
    everything else lives on device for the whole run."""
    _, mcfg, sampler, params = _setup()
    opt = opt_lib.make_optimizer("adam", 0.02)
    step_fn = glasu.make_multi_round_fn(mcfg, opt)
    rounds = [jax.device_put(stack_rounds([sampler.sample_round()]))
              for _ in range(4)]
    # pre-staged one-round key stacks: eager `keys[t]` indexing inside the
    # guard would upload its index scalar and (correctly) trip it
    keys = [jax.random.fold_in(jax.random.PRNGKey(0), t)[None]
            for t in range(4)]
    p, s = _copy(params), opt.init(_copy(params))
    p, s, _, _ = step_fn(p, s, rounds[0], keys[0])    # the one compile
    retrace_guard.watch(step_fn, "make_multi_round_fn")
    with transfer_guard():
        for t in range(1, 4):
            p, s, _, _ = step_fn(p, s, rounds[t], keys[t])
    assert all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(p))


def test_multi_round_fn_k_change_is_the_only_retrace(retrace_guard):
    """The scanned step fn compiles once per K; driving a second batch at
    the same K must hit the cache (max_new=0 after the K=2 warmup)."""
    _, mcfg, sampler, params = _setup()
    opt = opt_lib.make_optimizer("adam", 0.02)
    step_fn = glasu.make_multi_round_fn(mcfg, opt)
    rounds = [jax.tree.map(np.array, sampler.sample_round())
              for _ in range(4)]
    keys = jnp.stack([jax.random.PRNGKey(t) for t in range(2)])
    p, s = _copy(params), opt.init(_copy(params))
    p, s, _, _ = step_fn(p, s, stack_rounds(rounds[:2]), keys)
    retrace_guard.watch(step_fn, "make_multi_round_fn")
    p, s, _, _ = step_fn(p, s, stack_rounds(rounds[2:]), keys)
