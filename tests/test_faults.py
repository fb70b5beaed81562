"""Fault-model tests: config validation, schedule determinism/replay,
catch-up semantics, the timestamped message replay with its term-by-term
byte audit under dropped uploads, trainer participation telemetry, and the
fixed-seed chaos matrix (fault profiles x backends must produce the same
trace, losses, and delivered-byte meters).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExperimentConfig, FaultConfig, Trainer
from repro.api.backends import VmappedBackend
from repro.core import glasu
from repro.fed import simulation
from repro.fed.faults import FaultSchedule, make_schedule, stack_plans
from repro.graph.sampler import GlasuSampler
from repro.graph.synth import make_vfl_dataset

# independent-implementation tolerance class (test_backend_conformance)
SIM_TOL = dict(rtol=2e-4, atol=2e-5)


def _cfg(**kw):
    kw.setdefault("optimizer", "sgd")
    kw.setdefault("eval_every", 4)
    kw.setdefault("rounds", 8)
    return ExperimentConfig(name="faults-t", dataset="tiny", backbone="gcn",
                            agg="mean", hidden=16, batch_size=8, size_cap=96,
                            lr=0.05, **kw)


# -------------------------------------------------------- config validation
@pytest.mark.parametrize("kw", [
    dict(participation=0.0), dict(participation=1.5),
    dict(drop_prob=1.0), dict(drop_prob=-0.1),
    dict(deadline_ms=-1.0), dict(deadline_ms=float("inf")),
    dict(base_latency_ms=-1.0), dict(latency_sigma=-0.5),
    dict(straggler_prob=1.5), dict(straggler_scale=0.0),
    dict(crash_prob=1.0), dict(rejoin_after=0), dict(max_staleness=0),
    # a drop can only resolve against a deadline
    dict(drop_prob=0.2),
])
def test_fault_config_rejects_bad_values(kw):
    with pytest.raises(ValueError, match="FaultConfig"):
        FaultConfig(**kw)


def test_fault_config_active_property():
    assert not FaultConfig().active                    # degraded block
    assert not FaultConfig(base_latency_ms=5.0).active  # latency, no deadline
    assert FaultConfig(participation=0.5).active
    assert FaultConfig(drop_prob=0.1, deadline_ms=10.0).active
    assert FaultConfig(crash_prob=0.1).active
    assert FaultConfig(deadline_ms=10.0, base_latency_ms=5.0).active


@pytest.mark.parametrize("kw,msg", [
    (dict(secure_agg=True), "privacy hooks"),
    (dict(dp_sigma=0.1), "privacy hooks"),
    (dict(labels_at_client=0), "labels_at_client"),
    (dict(method="standalone"), "standalone"),
])
def test_experiment_config_fault_exclusions(kw, msg):
    with pytest.raises(ValueError, match=msg):
        _cfg(faults={"seed": 1}, **kw)


def test_experiment_config_coerces_and_roundtrips_faults():
    cfg = _cfg(faults={"seed": 3, "drop_prob": 0.2, "deadline_ms": 50.0})
    assert isinstance(cfg.faults, FaultConfig)
    assert cfg.glasu_config(make_vfl_dataset(
        "tiny", n_clients=cfg.n_clients, seed=0)).fault_tolerant
    assert ExperimentConfig.from_dict(cfg.to_dict()) == cfg


def test_experiment_config_accepts_faults_with_compression():
    """faults x compression compose since the round-engine unification:
    the codec runs on the fault exchange (the server caches each client's
    last DELIVERED decoded block; EF freezes for absent clients)."""
    cfg = _cfg(faults={"seed": 1, "participation": 0.67},
               compression={"method": "int8", "error_feedback": True})
    mcfg = cfg.glasu_config(make_vfl_dataset(
        "tiny", n_clients=cfg.n_clients, seed=0))
    assert mcfg.fault_tolerant and mcfg.compression.active
    assert ExperimentConfig.from_dict(cfg.to_dict()) == cfg


# ------------------------------------------------------------- the schedule
CHAOTIC = FaultConfig(seed=5, participation=0.67, drop_prob=0.2,
                      deadline_ms=40.0, base_latency_ms=10.0,
                      straggler_prob=0.2, straggler_scale=8.0,
                      crash_prob=0.1, rejoin_after=2, max_staleness=3)


def _trace(sched, n):
    return [sched.next_round() for _ in range(n)]


def test_schedule_fixed_seed_replays_identically():
    a = _trace(FaultSchedule(CHAOTIC, 3), 20)
    b = _trace(FaultSchedule(CHAOTIC, 3), 20)
    for pa, pb in zip(a, b):
        np.testing.assert_array_equal(pa.present, pb.present)
        np.testing.assert_array_equal(pa.weight, pb.weight)
        np.testing.assert_array_equal(pa.latency_ms, pb.latency_ms)
        assert pa.t_end == pb.t_end and pa.catch_up == pb.catch_up


def test_schedule_state_json_roundtrip_resumes_exactly():
    ref = FaultSchedule(CHAOTIC, 3)
    _trace(ref, 5)
    snap = json.loads(json.dumps(ref.state()))   # through the sidecar format
    want = _trace(ref, 5)

    resumed = FaultSchedule(CHAOTIC, 3)
    resumed.load_state(snap)
    assert resumed.round == 5
    got = _trace(resumed, 5)
    for pa, pb in zip(got, want):
        np.testing.assert_array_equal(pa.present, pb.present)
        np.testing.assert_array_equal(pa.weight, pb.weight)
        assert pa.t_start == pb.t_start and pa.t_end == pb.t_end


def test_schedule_catch_up_bounds_staleness():
    """Partial participation ages the unselected clients; when any live
    client's cache reaches max_staleness the next round is a synchronous
    catch-up: every live client is waited for, and ages reset."""
    cfg = FaultConfig(seed=0, participation=0.34, max_staleness=2)
    sched = FaultSchedule(cfg, 3)
    plans = _trace(sched, 12)
    assert any(p.catch_up for p in plans)
    for p in plans:
        if p.catch_up:
            # the server waits for every live client (no deadline, no drops)
            np.testing.assert_array_equal(p.present,
                                          p.active.astype(np.float32))
    # the bound holds throughout: no live client's cache ever exceeds it
    chk = FaultSchedule(cfg, 3)
    for _ in range(12):
        p = chk.next_round()
        assert int(chk.age[p.active].max(initial=0)) <= cfg.max_staleness


def test_schedule_weight_excludes_aged_out_and_never_delivered():
    """weight[m] = fresh or valid cache; a client that has never delivered
    (or whose cache aged out) is excluded from the aggregate entirely."""
    cfg = FaultConfig(seed=2, participation=0.34, max_staleness=5)
    sched = FaultSchedule(cfg, 3)
    p0 = sched.next_round()
    # round 0: no caches exist yet, so weight == present exactly
    np.testing.assert_array_equal(p0.weight, p0.present)
    for p in _trace(sched, 10):
        assert ((p.weight == 0) | (p.weight == 1)).all()
        # fresh blocks always carry weight
        assert (p.weight >= p.present).all()


def test_schedule_virtual_clock_and_deadline_duration():
    cfg = FaultConfig(seed=4, drop_prob=0.4, deadline_ms=25.0,
                      base_latency_ms=5.0)
    sched = FaultSchedule(cfg, 3)
    t = 0.0
    saw_wait = False
    for p in _trace(sched, 15):
        assert p.t_start == t and p.t_end >= p.t_start
        t = p.t_end
        if not p.catch_up and p.n_present < int(p.attempted.sum()):
            # a drop/straggler forces the server to wait out the deadline
            assert p.duration_ms == cfg.deadline_ms
            saw_wait = True
        elif not p.catch_up:
            assert p.duration_ms <= cfg.deadline_ms
    assert saw_wait


def test_stack_plans_shapes_and_make_schedule():
    plans = _trace(FaultSchedule(CHAOTIC, 3), 4)
    present, weight = stack_plans(plans)
    assert present.shape == weight.shape == (4, 3)
    assert present.dtype == weight.dtype == np.float32
    assert make_schedule(None, 3) is None
    assert make_schedule(CHAOTIC, 3).m == 3


# ------------------------------------------ timestamped replay + byte audit
def _fault_setup(fcfg_kw):
    cfg = _cfg(faults=fcfg_kw)
    data = make_vfl_dataset(cfg.dataset, n_clients=cfg.n_clients, seed=0)
    mcfg = cfg.glasu_config(data)
    sampler = GlasuSampler(data, cfg.sampler_config(), seed=0)
    params = glasu.init_params(jax.random.PRNGKey(0), mcfg)
    return cfg, mcfg, sampler, params


def test_fault_round_byte_audit_term_by_term():
    """Under dropped uploads the delivered-only meter must equal the
    analytic model term by term: index sync (everyone coordinates) +
    n_present uploads + M broadcasts per aggregation layer — and the
    sent-traffic meter prices the attempted uploads instead."""
    cfg, mcfg, sampler, params = _fault_setup(
        {"seed": 9, "drop_prob": 0.5, "deadline_ms": 30.0,
         "base_latency_ms": 5.0})
    sched = make_schedule(cfg.faults, mcfg.n_clients)
    opt = cfg.make_optimizer()
    opt_state = opt.init(params)
    fstate = glasu.init_fault_state(mcfg, sampler.layer_sizes)
    audited_partial = False
    for _ in range(6):
        plan = sched.next_round()
        batch = jax.tree.map(jnp.asarray, sampler.sample_round())
        params, opt_state, _, log, fstate = simulation.simulate_fault_round(
            params, opt_state, batch, mcfg, opt, fstate, plan)
        m, h = mcfg.n_clients, mcfg.hidden
        index_sync = sum(2 * m * sampler.layer_sizes[j] * 4
                         for j in range(mcfg.n_layers + 1)
                         if sampler._shared(j))
        per_layer = {l: sampler.layer_sizes[l + 1] * h * 4
                     for l in mcfg.agg_layers}
        n_att = int(plan.attempted.sum())
        want_delivered = index_sync + sum(
            plan.n_present * b + m * b for b in per_layer.values())
        want_sent = index_sync + sum(
            n_att * b + m * b for b in per_layer.values())
        assert log.total_bytes() == want_delivered
        assert log.total_bytes(delivered_only=False) == want_sent
        assert len(log.dropped_messages()) == \
            (n_att - plan.n_present) * len(mcfg.agg_layers)
        assert all(msg.kind == "upload" for msg in log.dropped_messages())
        audited_partial |= plan.n_present < n_att
    assert audited_partial      # the profile actually dropped something


def test_fault_round_message_timestamps():
    cfg, mcfg, sampler, params = _fault_setup(
        {"seed": 1, "drop_prob": 0.3, "deadline_ms": 25.0,
         "base_latency_ms": 8.0, "latency_sigma": 0.8})
    sched = make_schedule(cfg.faults, mcfg.n_clients)
    plan = sched.next_round()
    batch = jax.tree.map(jnp.asarray, sampler.sample_round())
    fstate = glasu.init_fault_state(mcfg, sampler.layer_sizes)
    *_, log, _ = simulation.simulate_fault_round(
        params, opt_state=cfg.make_optimizer().init(params), batch=batch,
        cfg=mcfg, optimizer=cfg.make_optimizer(), fault_state=fstate,
        plan=plan)
    for msg in log.messages:
        if msg.kind == "index_sync":
            assert msg.t == plan.t_start       # round opens with coordination
        elif msg.kind == "broadcast":
            assert msg.t == plan.t_end         # server closes the round
        elif not msg.dropped:
            assert plan.t_start <= msg.t <= plan.t_end  # arrived in time


# ------------------------------------------------------- trainer telemetry
def test_trainer_participation_telemetry_and_virtual_clock():
    cfg = _cfg(faults={"seed": 3, "participation": 0.67, "drop_prob": 0.2,
                       "deadline_ms": 50.0, "base_latency_ms": 10.0})
    res = Trainer(cfg).run()
    entries = [h for h in res.history if "participation" in h]
    assert entries, "eval entries must carry participation telemetry"
    for e in entries:
        assert 0.0 <= e["participation"] <= 1.0
        assert e["catch_up_rounds"] >= 0
    clocks = [e["virtual_ms"] for e in entries]
    assert clocks == sorted(clocks) and clocks[-1] > 0.0
    # partial participation must actually have priced fewer delivered bytes
    dense = Trainer(_cfg()).run()
    assert 0 < res.comm_bytes < dense.comm_bytes


# -------------------------------------- degraded mode / mixed precision
@pytest.mark.parametrize("agg", ["mean", "concat"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fault_agg_math_preserves_upload_dtype(agg, dtype):
    """Degraded-mode conformance row (mixed-precision uploads under
    partial participation): the weighted-mean denominator is cast to the
    uploads dtype exactly once, inside ``_fault_agg_math`` — the sharded
    engine previously divided by an f32 weak type, silently upcasting
    bf16 exchanges (the dtype drift the unified round body retired)."""
    cfg = _cfg(faults={"seed": 0, "participation": 0.67}).with_(agg=agg)
    mcfg = cfg.glasu_config(make_vfl_dataset(
        "tiny", n_clients=cfg.n_clients, seed=0))
    rng = np.random.default_rng(0)
    m, n, h = mcfg.n_clients, 8, mcfg.hidden
    uploads = jnp.asarray(rng.normal(size=(m, n, h)), dtype)
    weight = jnp.asarray([1.0, 0.0, 1.0][:m])     # partial participation
    agg_out, stale, denom = glasu._fault_agg_math(mcfg, uploads, weight)
    assert agg_out.dtype == uploads.dtype
    assert stale.dtype == uploads.dtype
    assert denom.dtype == uploads.dtype
    if agg == "mean":
        # value check against a host-side f64 reference of the weighted
        # mean (NumPy only — never crosses into a trace)
        w = np.asarray(weight, np.float64)[:, None, None]  # glint: disable=GL003 host-side reference math
        u = np.asarray(uploads.astype(jnp.float32), np.float64)  # glint: disable=GL003 host-side reference math
        ref = (w * u).sum(axis=0) / max(float(w.sum()), 1.0)
        tol = 1e-6 if dtype == jnp.float32 else 0.05
        np.testing.assert_allclose(
            np.asarray(agg_out[0].astype(jnp.float32), np.float64), ref,  # glint: disable=GL003 host-side reference math
            rtol=tol, atol=tol)


# ----------------------- composed faults x compression (unified engine)
def test_composed_round_vmapped_matches_simulation_and_audits_bytes():
    """One faults+int8 exchange through the unified round body (vmapped)
    and the independent NumPy replay must agree within SIM_TOL — and the
    delivered-only meter must equal the analytic cost model TERM BY TERM
    against the simulation message log: compressed wire size for present
    clients' uploads only, all-M compressed broadcasts, and the
    codec-independent int32 index sync."""
    from repro.comm.compression import make_compressor

    cfg, mcfg, sampler, params = _fault_setup(
        {"seed": 7, "drop_prob": 0.4, "deadline_ms": 40.0,
         "base_latency_ms": 5.0})
    cfg = cfg.with_(compression={"method": "int8", "error_feedback": True})
    data = make_vfl_dataset(cfg.dataset, n_clients=cfg.n_clients, seed=0)
    mcfg = cfg.glasu_config(data)
    comp = make_compressor(mcfg.compression)
    opt = cfg.make_optimizer()
    sched = make_schedule(cfg.faults, mcfg.n_clients)
    step_fn = glasu.make_multi_round_fn(mcfg, opt)

    # the step consumes (donates) its params, optimizer state and carry
    pv, ov = jax.tree.map(jnp.array, params), opt.init(params)
    carry_v = glasu.RoundCarry(
        glasu.init_comp_state(mcfg, sampler.layer_sizes, comp),
        glasu.init_fault_state(mcfg, sampler.layer_sizes))
    ps, os_ = params, opt.init(params)
    cs_s, fs_s = jax.tree.map(jnp.array, tuple(carry_v))
    saw_partial = False
    for r in range(5):
        plan = sched.next_round()
        batch = jax.tree.map(jnp.asarray, sampler.sample_round())
        masks = glasu.RoundFaults(*map(jnp.asarray, stack_plans([plan])))
        pv, ov, losses_v, carry_v = step_fn(
            pv, ov, jax.tree.map(lambda x: x[None], batch),
            jax.random.PRNGKey(r)[None], carry_v, masks)
        losses_v = losses_v[0]
        (ps, os_, losses_s, log, fs_s,
         cs_s) = simulation.simulate_fault_round(
            ps, os_, batch, mcfg, opt, fs_s, plan,
            compressor=comp, comp_state=cs_s)
        np.testing.assert_allclose(np.asarray(losses_v),
                                   np.asarray(losses_s), **SIM_TOL)
        # term-by-term audit: analytic model == message log
        m, h = mcfg.n_clients, mcfg.hidden
        index_sync = sum(2 * m * sampler.layer_sizes[j] * 4
                         for j in range(mcfg.n_layers + 1)
                         if sampler._shared(j))
        up = {l: comp.wire_bytes(sampler.layer_sizes[l + 1], h)
              for l in mcfg.agg_layers}
        down = up                     # mean agg: downlink width == hidden
        n_att = int(plan.attempted.sum())
        assert log.total_bytes("index_sync") == index_sync
        assert log.total_bytes("upload") == \
            plan.n_present * sum(up.values())
        assert log.total_bytes("upload", delivered_only=False) == \
            n_att * sum(up.values())
        assert log.total_bytes("broadcast") == m * sum(down.values())
        want = index_sync + sum(plan.n_present * up[l] + m * down[l]
                                for l in mcfg.agg_layers)
        assert log.total_bytes() == want
        assert want == sampler.comm_bytes_per_joint_inference(
            h, agg=mcfg.agg, compressor=comp, n_uploads=plan.n_present)
        assert len(log.dropped_messages()) == \
            (n_att - plan.n_present) * len(mcfg.agg_layers)
        saw_partial |= plan.n_present < n_att
    assert saw_partial          # the profile actually dropped something
    for (pa, la), (_, lb) in zip(
            jax.tree_util.tree_leaves_with_path(pv),
            jax.tree_util.tree_leaves_with_path(ps)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   **SIM_TOL,
                                   err_msg=jax.tree_util.keystr(pa))


def test_composed_e2e_trainer_resume_bitwise(tmp_path):
    """Faults + int8 EF compose end-to-end: an interrupted run restores
    BOTH sidecars (comp_<step>.npz EF accumulators, fault_<step>.npz
    stale caches + schedule state) bitwise, so the resumed run reproduces
    the uninterrupted one exactly."""
    data = make_vfl_dataset("tiny", n_clients=3, seed=0)
    base = _cfg(faults={"seed": 5, "drop_prob": 0.3, "deadline_ms": 40.0,
                        "base_latency_ms": 5.0},
                compression={"method": "int8", "error_feedback": True},
                rounds=4, eval_every=2)
    cfg = base.with_(ckpt_dir=str(tmp_path), ckpt_every=2, rounds=2)
    Trainer(cfg, data=data).run()
    assert (tmp_path / "comp_00000002.npz").exists()
    assert (tmp_path / "fault_00000002.npz").exists()

    res = Trainer(cfg.with_(rounds=4), data=data).run()   # resume 2 -> 4
    straight = Trainer(base, data=data).run()
    for (pa, la), (_, lb) in zip(
            jax.tree_util.tree_leaves_with_path(res.params),
            jax.tree_util.tree_leaves_with_path(straight.params)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb),
                                      err_msg=jax.tree_util.keystr(pa))
    assert res.comm_bytes == straight.comm_bytes


@pytest.mark.slow
def test_cora_profile_composed_faults_compression_trains_and_audits():
    """Acceptance row: the cora preset with faults + int8 compression
    TRAINS under the simulation backend, whose per-round delivered-only
    byte audit (analytic model vs message log) runs on every round."""
    from repro.api import get_preset

    cfg = get_preset("cora-gcn-glasu").with_(
        rounds=4, eval_every=2, backend="simulation",
        batch_size=16, size_cap=256,
        faults={"seed": 3, "drop_prob": 0.3, "deadline_ms": 40.0,
                "base_latency_ms": 5.0},
        compression={"method": "int8", "error_feedback": True})
    res = Trainer(cfg).run()
    assert res.rounds_run == 4
    losses = [h["loss"] for h in res.history]
    assert losses and np.isfinite(losses).all()
    assert res.comm_bytes > 0


# ------------------------------------------------ fault-support contract
def test_run_step_sequential_rejects_backend_without_fault_support():
    """A backend that never declared the fault contract must fail loudly
    when handed plans instead of silently training fault-free."""
    from repro.api.backends import run_step_sequential

    class LegacyBackend:
        name = "legacy"

        def run_round(self, params, opt_state, batch, key, **kw):
            raise AssertionError("must not be reached")

    plans = _trace(FaultSchedule(CHAOTIC, 3), 2)
    with pytest.raises(ValueError, match="supports_faults"):
        run_step_sequential(LegacyBackend(), None, None, None,
                            keys=[None, None], faults=plans)


def test_trainer_rejects_fault_schedule_on_unsupporting_backend(monkeypatch):
    """Satellite of the same contract: the Trainer refuses the pairing at
    config time, before any round runs."""
    from repro.api import backends as backends_mod

    monkeypatch.setattr(backends_mod.VmappedBackend, "supports_faults",
                        False)
    with pytest.raises(ValueError, match="supports_faults"):
        Trainer(_cfg(faults={"seed": 1, "participation": 0.67}))


def test_backend_rejects_faults_on_fault_free_bind():
    cfg = _cfg()
    data = make_vfl_dataset(cfg.dataset, n_clients=cfg.n_clients, seed=0)
    mcfg = cfg.glasu_config(data)
    sampler = GlasuSampler(data, cfg.sampler_config(), seed=0)
    vb = VmappedBackend()
    vb.bind(mcfg, cfg.make_optimizer(), sampler)
    params = glasu.init_params(jax.random.PRNGKey(0), mcfg)
    plan = FaultSchedule(FaultConfig(), mcfg.n_clients).next_round()
    batch = jax.tree.map(jnp.asarray, sampler.sample_round())
    with pytest.raises(ValueError, match="fault_tolerant"):
        vb.run_round(params, cfg.make_optimizer().init(params), batch,
                     jax.random.PRNGKey(0), faults=plan)


# ---------------------------------------------------------- chaos matrix
# Three fixed-seed fault profiles; every backend must replay the identical
# host-side trace, agree on losses within the independent-implementation
# tolerance, and price the identical delivered-only bytes.
CHAOS_PROFILES = {
    "drops": {"seed": 11, "drop_prob": 0.3, "deadline_ms": 50.0,
              "base_latency_ms": 5.0},
    "stragglers": {"seed": 12, "deadline_ms": 30.0, "base_latency_ms": 10.0,
                   "straggler_prob": 0.3, "straggler_scale": 20.0,
                   "client_speed_sigma": 0.3},
    "crashes": {"seed": 13, "participation": 0.67, "crash_prob": 0.2,
                "rejoin_after": 2, "max_staleness": 3},
}


@pytest.mark.slow
@pytest.mark.parametrize("profile", sorted(CHAOS_PROFILES))
def test_chaos_matrix_backends_agree(profile):
    cfg = _cfg(faults=CHAOS_PROFILES[profile])
    res_v = Trainer(cfg).run()
    res_s = Trainer(cfg.with_(backend="simulation")).run()
    assert res_s.comm_bytes == res_v.comm_bytes > 0
    assert [h["round"] for h in res_s.history] == \
        [h["round"] for h in res_v.history]
    np.testing.assert_allclose([h["loss"] for h in res_s.history],
                               [h["loss"] for h in res_v.history], **SIM_TOL)
    tv = [h["virtual_ms"] for h in res_v.history if "virtual_ms" in h]
    ts = [h["virtual_ms"] for h in res_s.history if "virtual_ms" in h]
    assert tv == ts                     # identical replayed fault trace


@pytest.mark.slow
@pytest.mark.parametrize("profile", sorted(CHAOS_PROFILES))
def test_chaos_matrix_sharded_agrees_with_vmapped(profile):
    cfg = _cfg(faults=CHAOS_PROFILES[profile])
    res_v = Trainer(cfg).run()
    res_h = Trainer(cfg.with_(backend="sharded")).run()
    assert res_h.comm_bytes == res_v.comm_bytes
    np.testing.assert_allclose([h["loss"] for h in res_h.history],
                               [h["loss"] for h in res_v.history],
                               rtol=5e-5, atol=5e-5)
