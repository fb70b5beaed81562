"""Hook-driven training loop replacing the monolithic ``train_glasu``.

The ``Trainer`` owns the dataset binding, the host-side sampler, and the
round loop; everything episodic — periodic exact evaluation, early stopping
at a target accuracy (paper Table 4), communication metering, checkpoint
save/restore — is a ``Hook``. Default hooks reproduce the seed driver's
behavior exactly; callers append their own for logging, sweeps, etc.

    cfg = get_preset("cora-gcnii-glasu")
    result = Trainer(cfg).run()

The loop itself is a device-resident round engine: ``cfg.rounds_per_step``
rounds advance per jitted dispatch (``lax.scan`` over round-stacked
batches, donated parameter/optimizer buffers) and host-side sampling runs
in a background prefetch thread overlapped with device compute. For the
built-in hooks — and any hook that acts on eval/checkpoint cadence
boundaries — the engine is bit-identical to the historical per-round loop
at every ``rounds_per_step`` (see ``step_schedule``). A custom hook that
inspects ``state.params`` or requests a stop on a round OFF those
cadences sees end-of-step state: the K rounds of a step are one device
dispatch, so mid-step stops take effect once the already-computed step
finishes (up to K-1 rounds later than the per-round loop).
"""
from __future__ import annotations

import copy
import json
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import obs
from ..core import checkpoint, glasu
from ..core.train import TrainResult, _eval_tables, make_centralized_dataset
from ..fed.faults import make_schedule
from ..graph.prefetch import PrefetchSampler
from ..graph.sampler import GlasuSampler
from ..graph.synth import make_vfl_dataset
from .backends import Backend, make_backend
from .config import ExperimentConfig


# (K,) per-round keys in ONE dispatch — K sequential fold_in calls would
# hand back a chunk of the per-round host overhead the scan removes
_fold_keys = jax.jit(jax.vmap(jax.random.fold_in, in_axes=(None, 0)))


def step_schedule(start: int, rounds: int, rounds_per_step: int,
                  cadences: Tuple[int, ...] = ()) -> List[int]:
    """Step sizes covering rounds (start, rounds], cut at cadence boundaries.

    Every multiple of every (non-zero) cadence — eval_every, ckpt_every —
    ends a step, so hooks that act on those rounds always see end-of-step
    parameters and the multi-round engine is observationally identical to
    the per-round loop for ANY cadence. Aligned cadences (multiples of
    ``rounds_per_step``) keep the schedule uniform, which keeps the scanned
    step function at a single trace; misaligned ones just add remainder
    steps (extra traces, same results).
    """
    steps: List[int] = []
    t = start
    while t < rounds:
        k = min(rounds_per_step, rounds - t)
        for c in cadences:
            if c:
                k = min(k, (t // c + 1) * c - t)
        steps.append(k)
        t += k
    return steps


@dataclass
class TrainerState:
    """Mutable run state shared with hooks."""
    params: Any = None
    opt_state: Any = None
    round: int = 0
    comm_bytes: int = 0
    history: List[Dict] = field(default_factory=list)
    val_acc: float = 0.0
    test_acc: float = 0.0
    should_stop: bool = False
    t0: float = 0.0
    wall_seconds: float = 0.0
    last_losses: Any = None
    sampler_rng_state: Optional[dict] = None   # after st.round rounds drawn
    virtual_ms: float = 0.0                    # fault runs: simulated clock


class Hook:
    """Override any subset; hooks run in registration order."""

    def on_train_start(self, trainer: "Trainer"):
        pass

    def on_round_end(self, trainer: "Trainer", metrics: Dict):
        pass

    def on_eval(self, trainer: "Trainer", entry: Dict):
        pass

    def on_train_end(self, trainer: "Trainer"):
        pass


class CommMeterHook(Hook):
    """Accumulates the backend's per-round byte count into the run state.

    Fault-tolerant steps report per-round DELIVERED bytes (the Trainer
    threads ``StepResult.comm_bytes_rounds`` into each round's metrics),
    so dropped uploads never accumulate here.
    """

    def on_round_end(self, trainer, metrics):
        trainer.state.comm_bytes += metrics["comm_bytes_round"]


class ParticipationHook(Hook):
    """Fault-run telemetry: participation rate, catch-ups, virtual clock.

    Registered automatically when ``cfg.faults`` is set (before
    ``EvalHook``, so eval entries see the stats through the eval round).
    Each eval entry gains the running mean participation fraction, the
    count of forced catch-up rounds, and the virtual wall-clock.
    """

    def on_train_start(self, trainer):
        self.rounds = 0
        self.presence = 0.0
        self.catch_ups = 0

    def on_round_end(self, trainer, metrics):
        plan = metrics.get("fault_plan")
        if plan is None:
            return
        self.rounds += 1
        self.presence += plan.n_present / len(plan.present)
        self.catch_ups += bool(plan.catch_up)
        trainer.state.virtual_ms = plan.t_end

    def on_eval(self, trainer, entry):
        if self.rounds:
            entry["participation"] = self.presence / self.rounds
            entry["catch_up_rounds"] = self.catch_ups
            entry["virtual_ms"] = trainer.state.virtual_ms


class EvalHook(Hook):
    """Periodic exact full-graph evaluation + best-checkpoint bookkeeping.

    Appends a history entry every ``eval_every`` rounds (and at the final
    round) and dispatches ``on_eval`` to every hook — early stopping and
    user hooks key off those entries.
    """

    def on_train_start(self, trainer):
        cfg, data = trainer.cfg, trainer.data
        feats, nbr_idx, nbr_mask = _eval_tables(
            data, cfg.eval_table_cap, cfg.seed)
        mcfg = trainer.model_cfg
        self.eval_fn = jax.jit(lambda p: glasu.full_forward(
            p, mcfg, feats, nbr_idx, nbr_mask,
            chunk=min(4096, data.n_nodes)))

    def _append_entry(self, trainer):
        cfg, st, data = trainer.cfg, trainer.state, trainer.data
        logits = self.eval_fn(st.params)
        mode = cfg.resolved_eval_mode
        val = float(glasu.accuracy_from_logits(
            logits, data.full.labels, data.full.val_idx, mode))
        test = float(glasu.accuracy_from_logits(
            logits, data.full.labels, data.full.test_idx, mode))
        # no round has run yet (rounds == 0, or a resume landing exactly on
        # cfg.rounds): there is no loss to report, not a crash. One
        # device_get here — at eval cadence — is the only host sync the
        # loss reporting pays; non-eval rounds never block on device.
        loss = (float(jax.device_get(st.last_losses)[-1])
                if st.last_losses is not None else float("nan"))
        entry = {"round": st.round, "loss": loss,
                 "val_acc": val, "test_acc": test,
                 "comm_bytes": st.comm_bytes,
                 "seconds": time.perf_counter() - st.t0}
        st.history.append(entry)
        if val >= st.val_acc:
            st.val_acc, st.test_acc = val, test
        for h in trainer.hooks:
            h.on_eval(trainer, entry)

    def on_round_end(self, trainer, metrics):
        cfg, st = trainer.cfg, trainer.state
        if st.round % cfg.eval_every != 0 and st.round != cfg.rounds:
            return
        self._append_entry(trainer)

    def on_train_end(self, trainer):
        """Guarantee a final history entry: covers rounds == 0, a resume
        landing exactly on cfg.rounds, and a hook stopping the run between
        eval cadences (e.g. early stop triggered off round metrics)."""
        st = trainer.state
        if st.history and st.history[-1]["round"] == st.round:
            return
        self._append_entry(trainer)


class EarlyStopHook(Hook):
    """Stop once validation accuracy reaches ``target_acc`` (paper Table 4)."""

    def __init__(self, target_acc: float):
        self.target_acc = target_acc

    def on_eval(self, trainer, entry):
        if entry["val_acc"] >= self.target_acc:
            trainer.state.should_stop = True


class CheckpointHook(Hook):
    """Save/restore (params, opt_state, round, comm_bytes) via core.checkpoint.

    The experiment config is written alongside as ``experiment.json``; on
    resume everything that shapes the state must round-trip equal —
    restoring under a different model/optimizer config is an error, not a
    silent shape mismatch. Loop-schedule fields (rounds, eval cadence,
    early-stop target, ...) may change between resumes.
    """

    RESUME_MUTABLE = ("name", "rounds", "eval_every", "eval_table_cap",
                      "target_acc", "ckpt_every", "ckpt_dir",
                      "rounds_per_step", "prefetch_buffers", "mesh_devices",
                      "compression", "serve", "faults")

    def __init__(self, ckpt_dir: str, every: int = 0, keep: int = 3):
        self.ckpt_dir = ckpt_dir
        self.every = every
        self.keep = keep

    def _tree(self, st: TrainerState):
        return {"params": st.params, "opt_state": st.opt_state}

    def _sidecar(self, step: int):
        import pathlib
        return pathlib.Path(self.ckpt_dir) / f"state_{step:08d}.json"

    def on_train_start(self, trainer):
        import pathlib
        st = trainer.state
        meta = pathlib.Path(self.ckpt_dir) / "experiment.json"
        step = checkpoint.latest_step(self.ckpt_dir)
        if step is not None:
            saved_comp = saved_faults = None
            if meta.exists():
                saved = ExperimentConfig.from_dict(
                    json.loads(meta.read_text())).to_dict()
                here = trainer.cfg.to_dict()
                saved_comp = saved.get("compression")
                saved_faults = saved.get("faults")
                for k in self.RESUME_MUTABLE:
                    saved.pop(k, None)
                    here.pop(k, None)
                if saved != here:
                    diff = sorted(k for k in here if saved.get(k) != here[k])
                    raise ValueError(
                        f"checkpoint in {self.ckpt_dir} was written by a "
                        f"different experiment config (fields {diff})")
            tree = checkpoint.restore(self.ckpt_dir, self._tree(st), step)
            st.params = tree["params"]
            st.opt_state = tree["opt_state"]
            st.round = step
            self._restore_comp_state(trainer, step, saved_comp)
            loop = json.loads(self._sidecar(step).read_text())
            self._restore_fault_state(trainer, step, saved_faults, loop)
            st.comm_bytes = loop["comm_bytes"]
            st.val_acc, st.test_acc = loop["val_acc"], loop["test_acc"]
            st.history = loop["history"]
            # restore the wall-clock baseline: offset t0 by the elapsed
            # seconds persisted at save time so 'seconds' in new history
            # entries continues monotonically from the restored ones
            # (older sidecars lack the field — fall back to the last
            # restored entry's timestamp)
            elapsed = loop.get("elapsed_seconds",
                               st.history[-1]["seconds"] if st.history
                               else 0.0)
            st.t0 = time.perf_counter() - elapsed
            # new sidecars carry the sampler's exact bit-generator state at
            # save time: restore it directly instead of the O(rounds)
            # sample_round() replay (the Trainer falls back to replay for
            # sidecars written before the field existed)
            rng_state = loop.get("sampler_rng")
            if rng_state is not None:
                trainer.sampler.rng.bit_generator.state = rng_state
                trainer.sampler_restored = True
        else:
            pathlib.Path(self.ckpt_dir).mkdir(parents=True, exist_ok=True)
            meta.write_text(json.dumps(trainer.cfg.to_dict(), indent=1))

    def _restore_comp_state(self, trainer, step: int, saved_comp):
        """Restore the compressed-exchange EF accumulators (resume-mutable).

        The ``compression`` block may change between resumes; accumulators
        are only restored when (a) the current run keeps one (EF enabled),
        (b) a ``comp_<step>.npz`` sidecar exists, and (c) the codec that
        wrote it is KNOWN to match the current one (``experiment.json``
        comparison — different codecs produce identically-shaped state
        trees, so a residual restored across a codec change would load
        silently and mean nothing). Otherwise — including a missing or
        unreadable meta file, i.e. unknown provenance — error feedback
        restarts from zeros, which is always a valid EF state.
        """
        import dataclasses
        import pathlib
        comp_state = getattr(trainer.backend, "comp_state", None)
        if not comp_state:               # compression off or stateless codec
            return
        comp_file = pathlib.Path(self.ckpt_dir) / f"comp_{step:08d}.npz"
        if not comp_file.exists():
            return                       # EF newly enabled: start from zeros
        if saved_comp != dataclasses.asdict(trainer.cfg.compression):
            return                       # codec changed/unknown: reset
        trainer.backend.comp_state = checkpoint.restore(
            self.ckpt_dir, comp_state, step, name="comp")

    def _restore_fault_state(self, trainer, step: int, saved_faults, loop):
        """Restore the stale-embedding caches + fault schedule (resume-mutable).

        Same provenance contract as the EF sidecar: the caches and the
        schedule's rng state are restored only when a ``fault_<step>.npz``
        sidecar AND a persisted schedule state exist and the fault block
        that wrote them matches the current one. A changed/unknown block —
        or a run that just turned faults on — starts a fresh schedule with
        zero caches, which is always a valid fault state (never-delivered
        slots carry weight 0). The sidecar follows ``core.checkpoint``'s
        loud-corruption contract; a truncated/garbled file raises rather
        than silently training against partial caches.
        """
        import dataclasses
        import pathlib
        fault_state = getattr(trainer.backend, "fault_state", None)
        if fault_state is None or trainer.fault_sched is None:
            return
        if saved_faults != dataclasses.asdict(trainer.cfg.faults):
            return                       # fault block changed/unknown: reset
        sched_state = loop.get("fault_sched")
        fault_file = pathlib.Path(self.ckpt_dir) / f"fault_{step:08d}.npz"
        if sched_state is None or not fault_file.exists():
            return                       # pre-fault sidecar: reset
        trainer.backend.fault_state = checkpoint.restore(
            self.ckpt_dir, fault_state, step, name="fault")
        trainer.fault_sched.load_state(sched_state)
        trainer.fault_sched_restored = True

    def _save(self, trainer):
        import pathlib
        st = trainer.state
        checkpoint.save(self.ckpt_dir, st.round, self._tree(st))
        comp_state = getattr(trainer.backend, "comp_state", None)
        if comp_state:                   # EF accumulators ride as a sidecar
            checkpoint.save(self.ckpt_dir, st.round, comp_state, name="comp")
        fault_state = getattr(trainer.backend, "fault_state", None)
        if fault_state is not None:      # stale caches ride as a sidecar
            checkpoint.save(self.ckpt_dir, st.round, fault_state,
                            name="fault")
        # the meta file records the config that WROTE the latest state —
        # updated at save time (not resume start), so a resume that dies
        # before its first save can't relabel an older codec's EF sidecar
        # as its own for the next resume's provenance comparison
        (pathlib.Path(self.ckpt_dir) / "experiment.json").write_text(
            json.dumps(trainer.cfg.to_dict(), indent=1))
        self._sidecar(st.round).write_text(json.dumps(
            {"comm_bytes": st.comm_bytes, "val_acc": st.val_acc,
             "test_acc": st.test_acc, "history": st.history,
             "elapsed_seconds": time.perf_counter() - st.t0,
             # exact resume point for the sampler stream: the generator bit
             # state after st.round rounds were drawn (json handles the
             # arbitrary-precision ints PCG64 carries)
             "sampler_rng": st.sampler_rng_state,
             # fault schedule after st.round rounds drawn (saves land on
             # step ends, where the host draw is exactly st.round deep)
             "fault_sched": trainer.fault_sched.state()
             if trainer.fault_sched is not None else None}))
        checkpoint.cleanup(self.ckpt_dir, keep=self.keep)
        live = {int(f.stem.split("_")[1])
                for f in pathlib.Path(self.ckpt_dir).glob("ckpt_*.npz")}
        for f in list(pathlib.Path(self.ckpt_dir).glob("state_*.json")) + \
                list(pathlib.Path(self.ckpt_dir).glob("comp_*.npz")) + \
                list(pathlib.Path(self.ckpt_dir).glob("fault_*.npz")):
            if int(f.stem.split("_")[1]) not in live:
                f.unlink()

    def on_round_end(self, trainer, metrics):
        if self.every and trainer.state.round % self.every == 0:
            self._save(trainer)

    def on_train_end(self, trainer):
        if trainer.state.round > 0:
            self._save(trainer)


class Trainer:
    """Run one experiment: dataset binding + backend + hook pipeline."""

    def __init__(self, cfg: ExperimentConfig, data=None,
                 backend: Optional[Backend] = None,
                 hooks: Sequence[Hook] = ()):
        self.cfg = cfg
        self.data = data if data is not None else self._make_data(cfg)
        self.model_cfg = cfg.glasu_config(self.data)
        self.sampler = GlasuSampler(self.data, cfg.sampler_config(),
                                    seed=cfg.seed)
        self.optimizer = cfg.make_optimizer()
        backend_kw = {"mesh_devices": cfg.mesh_devices} \
            if cfg.backend == "sharded" and cfg.mesh_devices else {}
        self.backend = backend if backend is not None \
            else make_backend(cfg.backend, **backend_kw)
        self.backend.bind(self.model_cfg, self.optimizer, self.sampler)
        # host-side fault schedule (None for fault-free runs): the Trainer
        # owns the sequential draw; backends only ever see per-round plans
        self.fault_sched = make_schedule(cfg.faults, self.model_cfg.n_clients)
        if self.fault_sched is not None and \
                not getattr(self.backend, "supports_faults", False):
            # fail at config time: a backend without the fault contract
            # would otherwise silently train fault-free (the faults kwarg
            # only reaches backends through the run_round/run_step protocol)
            raise ValueError(
                f"backend {self.backend.name!r} does not support the "
                "fault-tolerance protocol (supports_faults); drop the "
                "faults block or pick a fault-capable backend")
        self.hooks: List[Hook] = [CommMeterHook()]
        if self.fault_sched is not None:
            self.hooks.append(ParticipationHook())
        if cfg.eval_every > 0:
            # eval_every == 0 skips exact full-graph eval entirely — the
            # contract for streamed-store datasets (powerlaw-* profiles),
            # where _eval_tables would materialize all N feature rows
            self.hooks.append(EvalHook())
        if cfg.target_acc is not None:
            self.hooks.append(EarlyStopHook(cfg.target_acc))
        if cfg.ckpt_dir is not None:
            self.hooks.append(CheckpointHook(cfg.ckpt_dir, cfg.ckpt_every))
        self.hooks.extend(hooks)
        self.state = TrainerState()
        # set by CheckpointHook when a sidecar restored the sampler's rng
        # bit state directly (skips the O(rounds) replay loop on resume)
        self.sampler_restored = False
        # set by CheckpointHook when the fault sidecar restored the
        # schedule's rng/clock state (skips the O(rounds) draw replay)
        self.fault_sched_restored = False

    def _run_step(self, params, opt_state, batches, keys, faults=None):
        """Dispatch one multi-round step; backends written against the
        older run_round-only protocol fall back to K audited sequential
        rounds (same helper the simulation backend uses)."""
        run_step = getattr(self.backend, "run_step", None)
        if run_step is not None:
            if faults is not None:
                return run_step(params, opt_state, batches, keys,
                                faults=faults)
            return run_step(params, opt_state, batches, keys)
        from .backends import run_step_sequential
        return run_step_sequential(self.backend, params, opt_state,
                                   batches, keys, faults=faults)

    @staticmethod
    def _make_data(cfg: ExperimentConfig):
        data = make_vfl_dataset(cfg.dataset, n_clients=cfg.n_clients,
                                seed=cfg.seed)
        if cfg.method == "centralized":
            data = make_centralized_dataset(data)
        return data

    def run(self) -> TrainResult:
        """Drive the device-resident round engine.

        Rounds advance in multi-round *steps*: ``cfg.rounds_per_step``
        pre-sampled rounds are stacked on a leading axis and dispatched as
        ONE jitted ``lax.scan`` (``Backend.run_step``) with params/opt_state
        donated. The step schedule is cut at every eval/checkpoint cadence
        boundary, so every hook that inspects ``state.params`` fires at a
        step end and sees exactly what the per-round loop would have shown
        it; mid-step rounds still dispatch ``on_round_end`` with their own
        loss row and byte count. Sampling runs in a ``PrefetchSampler``
        worker thread that fills round-stacked generation buffers while the
        device computes the previous step (a hook requesting a stop
        mid-step takes effect once the already-computed step finishes
        dispatching its round metrics).
        """
        cfg, st = self.cfg, self.state
        key = jax.random.PRNGKey(cfg.seed)
        st.params = glasu.init_params(key, self.model_cfg)
        st.opt_state = self.optimizer.init(st.params)
        st.t0 = time.perf_counter()
        for h in self.hooks:
            h.on_train_start(self)          # CheckpointHook may fast-forward
        if st.round and not self.sampler_restored:
            # replay the consumed sampler stream so a resumed run sees the
            # same batch sequence as an uninterrupted one — fallback for
            # sidecars that predate the persisted rng bit state
            for _ in range(st.round):
                self.sampler.sample_round()
        if st.round and self.fault_sched is not None \
                and not self.fault_sched_restored:
            # same replay for the fault draw: a resume without a restored
            # schedule state (fresh/changed fault block keeps zero caches,
            # but the DRAW must stay aligned with the round counter)
            for _ in range(st.round):
                self.fault_sched.next_round()
        st.sampler_rng_state = copy.deepcopy(
            self.sampler.rng.bit_generator.state)
        # every CheckpointHook's cadence cuts the schedule — a save must
        # land on a step end so its sidecar's rng state matches st.round
        ckpt_cadences = tuple(h.every for h in self.hooks
                              if isinstance(h, CheckpointHook))
        schedule = step_schedule(st.round, cfg.rounds, cfg.rounds_per_step,
                                 (cfg.eval_every,) + ckpt_cadences)
        prefetch = PrefetchSampler(self.sampler, schedule,
                                   n_buffers=cfg.prefetch_buffers,
                                   start=st.round) if schedule else None
        # a backend's own staging hand-off (the sharded mesh), if it has one
        stage = getattr(self.backend, "stage", None)
        try:
            t = st.round
            for _ in schedule:
                # spans (repro.obs), keyed by the step's first round:
                # wait_sample, stage, dispatch, wait_device (in retire)
                # and hook, inside step
                with obs.span("step", key=t + 1):
                    with obs.span("wait_sample"):
                        step = prefetch.get()
                    k = step.rounds
                    with obs.span("stage"):
                        keys = _fold_keys(key, jnp.arange(t, t + k))
                        batches = jax.device_put(step.data) \
                            if stage is None else stage(step.data)
                        plans = self.fault_sched.draw_step(k) \
                            if self.fault_sched is not None else None
                        obs.count("stage_bytes", sum(
                            x.nbytes for x in jax.tree.leaves(step.data)))
                    with obs.span("dispatch"):
                        out = self._run_step(st.params, st.opt_state,
                                             batches, keys, faults=plans)
                    st.params, st.opt_state = out.params, out.opt_state
                    st.sampler_rng_state = step.rng_state_after
                    # recycles the oldest generation, blocking on ITS
                    # compute only — the step just dispatched keeps running
                    prefetch.retire(step, out.losses)
                    logs = out.message_logs
                    per_round_bytes = out.comm_bytes_rounds
                    with obs.span("hook"):
                        for i in range(k):
                            st.round = t + i + 1
                            # a device row, not a host value: nothing
                            # blocks until EvalHook pulls it at eval cadence
                            st.last_losses = out.losses[i]
                            metrics = {
                                "round": st.round, "losses": out.losses[i],
                                "comm_bytes_round":
                                    per_round_bytes[i]
                                    if per_round_bytes is not None
                                    else out.comm_bytes_round,
                                "message_log": logs[i] if logs else None,
                                "fault_plan": plans[i] if plans else None}
                            for h in self.hooks:
                                h.on_round_end(self, metrics)
                    obs.count("rounds", k)
                t += k
                if st.should_stop:
                    break
        finally:
            if prefetch is not None:
                prefetch.close()
        st.wall_seconds = time.perf_counter() - st.t0
        for h in self.hooks:
            h.on_train_end(self)
        return TrainResult(
            test_acc=st.test_acc, val_acc=st.val_acc, history=st.history,
            comm_bytes=st.comm_bytes, rounds_run=st.round,
            wall_seconds=st.wall_seconds, params=st.params)
