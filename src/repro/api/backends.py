"""Pluggable execution backends: same Trainer, swappable substrate.

``VmappedBackend`` is the fast path — clients are a stacked leading axis and
one jitted scanned step (``core.glasu.make_multi_round_fn``) advances all of
them at once; communication is *metered* analytically via the sampler's cost
model. ``SimulationBackend`` replays the identical round as literal
client/server messages (``fed.simulation``) — the deployment topology of the
paper's Fig. 1 — and *audits* the analytic meter against the message log
every round: a divergence raises instead of silently mis-reporting bytes.
``ShardedBackend`` places each client (block) on its own mesh device
(``shard_map`` over a 'clients' axis;
``core.glasu.make_sharded_multi_round_fn``):
client compute is device-local, aggregation is a real cross-device
collective, and the byte meter is read off the collectives recorded at
trace time — audited at bind against the message-passing log instead of
trusting the analytic model.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..comm.compression import make_compressor
from ..core import glasu
from ..core.glasu import GlasuConfig
from ..fed import faults as faults_lib
from ..fed import simulation
from ..graph.prefetch import unstack_round
from ..graph.sampler import GlasuSampler, SampledBatch
from ..optim import optimizers as opt_lib


@dataclass
class RoundResult:
    """Output of one GLASU round, backend-independent."""
    params: Any
    opt_state: Any
    losses: Any                                   # (Q,) per-microstep losses
    comm_bytes: int                               # bytes this round
    message_log: Optional[simulation.MessageLog] = None


@dataclass
class StepResult:
    """Output of one multi-round step (K rounds in one dispatch)."""
    params: Any
    opt_state: Any
    losses: Any                                   # (K, Q) per-round rows
    comm_bytes_round: int                         # bytes per round (analytic)
    message_logs: Optional[list] = None           # per-round, simulation only
    # fault-tolerant steps only: delivered-only bytes for EACH of the K
    # rounds (uploads dropped or late price as zero). ``comm_bytes_round``
    # then still carries the fault-free per-round price for comparison.
    comm_bytes_rounds: Optional[tuple] = None


@runtime_checkable
class Backend(Protocol):
    """Execution substrate for one GLASU round (Alg 1 body).

    ``supports_faults`` is the explicit fault-capability contract: a
    backend that can run deadline rounds (accepting ``faults=`` on
    run_round/run_step) declares it ``True``. The Trainer checks the flag
    at CONFIG time — a fault-tolerant experiment on a backend without it
    fails loudly before the first round instead of silently training
    fault-free (all three built-in backends support faults; the flag
    exists for external/older backends written against the run_round-only
    protocol).

    A backend may also define ``stage(host_batch)``: the ``Trainer`` then
    hands it each step's round-stacked host batch and dispatches what it
    returns, in place of its own ``jax.device_put`` onto the default
    device (``ShardedBackend`` puts it straight onto its client mesh).
    """

    name: str
    supports_faults: bool

    def bind(self, model_cfg: GlasuConfig, optimizer: opt_lib.Optimizer,
             sampler: GlasuSampler) -> None:
        """Specialize to a model/optimizer/sampler before the first round."""
        ...

    def run_round(self, params, opt_state, batch: SampledBatch,
                  key, faults=None) -> RoundResult:
        """``faults`` (a ``fed.faults.RoundPlan``) runs the fault-tolerant
        exchange; requires a fault-tolerant bind (``cfg.fault_tolerant``)."""
        ...

    def run_step(self, params, opt_state, batches: SampledBatch,
                 keys, faults=None) -> StepResult:
        """K rounds in one call; ``batches``/``keys`` carry a leading round
        axis. params/opt_state may be donated — callers treat them as
        consumed. ``faults``: K ``RoundPlan``s (fault-tolerant binds only).
        """
        ...

    def joint_logits(self, params, batch: SampledBatch, key=None):
        """JointInference logits (M, S, C) — the cross-backend parity probe."""
        ...


def run_step_sequential(backend, params, opt_state, batches: SampledBatch,
                        keys, faults=None) -> StepResult:
    """K sequential ``run_round`` calls presented as one step.

    Used by ``SimulationBackend`` (message fidelity over throughput) and as
    the Trainer's fallback for backends written against the older
    run_round-only protocol. ``StepResult`` carries ONE per-round byte
    count, so a backend whose rounds diverge raises loudly instead of
    letting ``CommMeterHook`` mis-accumulate — EXCEPT under ``faults``
    (K ``RoundPlan``s), where per-round delivered bytes legitimately vary
    with the draw and ride in ``comm_bytes_rounds``.

    Fault contract: ``faults=`` is forwarded only to backends that declare
    ``supports_faults`` — a plan handed to a backend without the flag
    raises here rather than vanishing into a ``**kwargs`` sink (the
    Trainer already rejects that pairing at config time; this guard covers
    direct callers).
    """
    if faults is not None and not getattr(backend, "supports_faults", False):
        raise ValueError(
            f"backend {getattr(backend, 'name', type(backend).__name__)!r} "
            "does not declare supports_faults; it cannot run the "
            "fault-tolerant exchange (the plans would be dropped and the "
            "run would silently train fault-free)")
    losses, logs, per_round = [], [], []
    comm = None
    for i in range(len(keys)):
        # faults= is omitted when no plan is active so run_round-only
        # backends (supports_faults declared or not) keep working fault-free
        kw = {} if faults is None else {"faults": faults[i]}
        out = backend.run_round(params, opt_state,
                                unstack_round(batches, i), keys[i], **kw)
        params, opt_state = out.params, out.opt_state
        losses.append(out.losses)
        logs.append(out.message_log)
        per_round.append(out.comm_bytes)
        if faults is not None:
            continue
        if comm is None:
            comm = out.comm_bytes
        elif out.comm_bytes != comm:
            raise RuntimeError(
                "per-round byte counts diverged within a multi-round step; "
                "run this backend with rounds_per_step=1")
    if faults is not None:
        return StepResult(params, opt_state, jnp.stack(losses),
                          getattr(backend, "bytes_per_round", 0),
                          message_logs=logs
                          if any(l is not None for l in logs) else None,
                          comm_bytes_rounds=tuple(per_round))
    return StepResult(params, opt_state, jnp.stack(losses),
                      comm if comm is not None else 0,
                      message_logs=logs if any(l is not None for l in logs)
                      else None)


def _analytic_bytes(cfg: GlasuConfig, sampler: GlasuSampler,
                    compressor=None, n_uploads: Optional[int] = None) -> int:
    """Paper §3.2/§3.4 cost model; zero when nothing actually crosses
    clients. With a compressor, embedding messages are priced at their
    exact wire size (the int32 index sync is codec-independent). With
    ``n_uploads`` only that many uplink messages are priced (fault rounds:
    dropped/late uploads never reach the server)."""
    if cfg.agg_layers and cfg.n_clients > 1:
        return sampler.comm_bytes_per_joint_inference(cfg.hidden, cfg.agg,
                                                      compressor=compressor,
                                                      n_uploads=n_uploads)
    return 0


def _step_faults(plans) -> Optional["glasu.RoundFaults"]:
    """Device-side (K, M) masks for a step's K ``RoundPlan``s; ``None``
    off the fault path."""
    if plans is None:
        return None
    present, weight = faults_lib.stack_plans(plans)
    return glasu.RoundFaults(jnp.asarray(present), jnp.asarray(weight))


def run_round_as_step(backend, params, opt_state, batch, key,
                      faults=None) -> RoundResult:
    """One round as the backend's K = 1 step. The batch, the key and the
    plan get a leading round axis of 1, and the losses lose it again;
    ``x[None]`` and ``jnp.squeeze`` keep a device leaf on its device,
    where ``losses[0]`` would upload its index. params/opt_state are
    consumed as by ``run_step``."""
    out = backend.run_step(params, opt_state,
                           jax.tree.map(lambda x: x[None], batch), key[None],
                           faults=None if faults is None else [faults])
    comm = out.comm_bytes_round if faults is None \
        else out.comm_bytes_rounds[0]
    return RoundResult(out.params, out.opt_state,
                       jnp.squeeze(out.losses, 0), comm)


def _check_fault_args(cfg: GlasuConfig, fault_state, faults):
    if faults is not None and fault_state is None:
        raise ValueError(
            "faults passed to a backend bound without cfg.fault_tolerant; "
            "set the ExperimentConfig 'faults' block (or GlasuConfig."
            "fault_tolerant) before bind")
    if faults is None and fault_state is not None:
        raise ValueError(
            "backend bound fault-tolerant but no fault plan passed: every "
            "round of a fault-tolerant run takes its RoundPlan (a degraded "
            "FaultConfig() draws all-present plans)")


class VmappedBackend:
    """Stacked-axis fast path: one jitted scanned step_fn (K rounds per
    dispatch, donated params/opt_state), analytic byte meter.

    With ``model_cfg.compression`` active the backend owns the
    error-feedback carry (``self.comp_state``): it is threaded (and
    donated) through every round/step alongside the optimizer state, and
    the Trainer checkpoints/restores it via the backend attribute. The
    fault-tolerant stale-embedding cache (``self.fault_state``) is owned
    the same way; the step takes both as one ``glasu.RoundCarry``.
    """

    name = "vmapped"
    supports_faults = True

    def bind(self, model_cfg, optimizer, sampler):
        self.cfg = model_cfg
        self.optimizer = optimizer
        self.sampler = sampler
        self.compressor = make_compressor(model_cfg.compression)
        self.comp_state = glasu.init_comp_state(model_cfg,
                                                sampler.layer_sizes,
                                                self.compressor)
        self.fault_state = glasu.init_fault_state(model_cfg,
                                                  sampler.layer_sizes)
        self.bytes_per_round = _analytic_bytes(model_cfg, sampler,
                                               self.compressor)
        self.step_fn = glasu.make_multi_round_fn(model_cfg, optimizer)

    def _fault_bytes(self, plan) -> int:
        """Delivered-only price of one fault round (uplink × n_present)."""
        return _analytic_bytes(self.cfg, self.sampler, self.compressor,
                               n_uploads=plan.n_present)

    run_round = run_round_as_step

    def run_step(self, params, opt_state, batches, keys, faults=None):
        _check_fault_args(self.cfg, self.fault_state, faults)
        params, opt_state, losses, carry = self.step_fn(
            params, opt_state, batches, keys,
            glasu.RoundCarry(self.comp_state, self.fault_state),
            _step_faults(faults))
        self.comp_state, self.fault_state = carry
        return StepResult(params, opt_state, losses, self.bytes_per_round,
                          comm_bytes_rounds=None if faults is None else tuple(
                              self._fault_bytes(p) for p in faults))

    def joint_logits(self, params, batch, key=None):
        logits, _ = glasu.joint_inference(params, batch, self.cfg, key)
        return logits


class SimulationBackend:
    """Explicit message-passing path; audits the meter against the log."""

    name = "simulation"
    supports_faults = True

    def bind(self, model_cfg, optimizer, sampler):
        if model_cfg.agg != "mean":
            raise ValueError("SimulationBackend implements mean aggregation "
                             "only")
        if model_cfg.secure_agg or model_cfg.dp_sigma > 0.0:
            raise ValueError("SimulationBackend does not implement the §3.6 "
                             "privacy hooks")
        self.cfg = model_cfg
        self.optimizer = optimizer
        self.sampler = sampler
        self.compressor = make_compressor(model_cfg.compression)
        self.comp_state = glasu.init_comp_state(model_cfg,
                                                sampler.layer_sizes,
                                                self.compressor)
        self.fault_state = glasu.init_fault_state(model_cfg,
                                                  sampler.layer_sizes)
        self.bytes_per_round = _analytic_bytes(model_cfg, sampler,
                                               self.compressor)

    def run_round(self, params, opt_state, batch, key, faults=None):
        _check_fault_args(self.cfg, self.fault_state, faults)
        if self.fault_state is not None:
            if self.compressor is not None:
                (params, opt_state, losses, log, self.fault_state,
                 self.comp_state) = simulation.simulate_fault_round(
                    params, opt_state, batch, self.cfg, self.optimizer,
                    self.fault_state, faults, compressor=self.compressor,
                    comp_state=self.comp_state)
            else:
                params, opt_state, losses, log, self.fault_state = \
                    simulation.simulate_fault_round(params, opt_state, batch,
                                                    self.cfg, self.optimizer,
                                                    self.fault_state, faults)
            # delivered-only audit: the log minus dropped messages must
            # price exactly as the analytic model with n_present uploads —
            # compressed payloads priced at their wire size for present
            # clients only
            measured = log.total_bytes(delivered_only=True)
            expected = _analytic_bytes(self.cfg, self.sampler,
                                       compressor=self.compressor,
                                       n_uploads=faults.n_present)
            if measured != expected:
                raise RuntimeError(
                    f"fault-round byte-meter audit failed: delivered "
                    f"messages carry {measured} B but the cost model with "
                    f"{faults.n_present} delivered uploads predicts "
                    f"{expected} B")
            return RoundResult(params, opt_state, losses, measured,
                               message_log=log)
        params, opt_state, losses, log, comp_state = \
            simulation.simulate_round(params, opt_state, batch, self.cfg,
                                      self.optimizer, self.compressor,
                                      self.comp_state)
        if self.compressor is not None:
            self.comp_state = comp_state
        measured = log.total_bytes()
        if self.cfg.n_clients > 1 and self.cfg.agg_layers \
                and measured != self.bytes_per_round:
            raise RuntimeError(
                f"byte-meter audit failed: message log carries {measured} B "
                f"but the sampler cost model predicts {self.bytes_per_round} B")
        comm = measured if self.cfg.n_clients > 1 else 0
        return RoundResult(params, opt_state, losses, comm, message_log=log)

    def run_step(self, params, opt_state, batches, keys, faults=None):
        """Sequential replay: the simulation path is about message fidelity,
        not throughput, so a step is literally K audited rounds."""
        return run_step_sequential(self, params, opt_state, batches, keys,
                                   faults=faults)

    def joint_logits(self, params, batch, key=None):
        logits, _ = simulation.simulate_joint_inference(params, batch,
                                                        self.cfg)
        return logits


def _placed_as(x, sharding) -> bool:
    """``x`` is a committed jax array laid out as ``sharding`` says, however
    the spec is spelled (a step's ``P('clients',)`` output is placed as a
    ``P('clients', None)`` target, though the two do not compare equal)."""
    return (isinstance(x, jax.Array) and x.committed
            and x.sharding.is_equivalent_to(sharding, x.ndim))


def _put(tree, shardings):
    """``tree`` on ``shardings``: a leaf already placed so passes through as
    the very same array, the others go in one batched ``jax.device_put``.
    Returns the tree and how many leaves moved and were kept."""
    leaves, treedef = jax.tree.flatten(tree)
    targets = treedef.flatten_up_to(shardings)
    move = [i for i, (x, sh) in enumerate(zip(leaves, targets))
            if not _placed_as(x, sh)]
    if move:
        moved = jax.device_put([leaves[i] for i in move],
                               [targets[i] for i in move])
        for i, x in zip(move, moved):
            leaves[i] = x
    return treedef.unflatten(leaves), len(move), len(leaves) - len(move)


class ShardedBackend:
    """Device-sharded client parallelism over a ``('clients',)`` mesh.

    Each device holds an even block of clients (params, optimizer state,
    batch slices, all placed via ``launch.sharding`` client rules) and runs
    the trunk locally; aggregation is an ``all_gather`` collective along the
    client axis — the only cross-device traffic, exactly where the paper
    places communication. ``run_step`` is the same scanned K-round contract
    as the vmapped engine (one collective program, donated buffers).

    Byte metering: the aggregation collectives recorded while tracing the
    round body are priced under the paper's star topology and AUDITED at
    bind against a message-by-message log (``fed.simulation``'s index-sync
    + upload/broadcast replay) — this path never uses the sampler's
    analytic estimate.
    """

    name = "sharded"
    supports_faults = True

    def __init__(self, mesh=None, mesh_devices: Optional[int] = None):
        self._mesh = mesh
        self._mesh_devices = mesh_devices

    def bind(self, model_cfg, optimizer, sampler):
        if model_cfg.labels_at_client is not None:
            raise ValueError(
                "ShardedBackend does not implement labels_at_client (the "
                "Alg 6 owner gradient indexes the global client axis); use "
                "the vmapped backend")
        from ..launch import sharding as shd
        from ..launch.mesh import make_client_mesh

        self.cfg = model_cfg
        self.optimizer = optimizer
        self.sampler = sampler
        self.mesh = self._mesh if self._mesh is not None else \
            make_client_mesh(model_cfg.n_clients,
                             max_devices=self._mesh_devices)
        self.compressor = make_compressor(model_cfg.compression)
        self.comp_state = glasu.init_comp_state(model_cfg,
                                                sampler.layer_sizes,
                                                self.compressor)
        self.fault_state = glasu.init_fault_state(model_cfg,
                                                  sampler.layer_sizes)

        # placement shardings for inputs that arrive from off-mesh (init,
        # checkpoint restore, the host sampler): client-stacked leading dim
        params_abs = jax.eval_shape(
            lambda k: glasu.init_params(k, model_cfg), jax.random.PRNGKey(0))
        opt_abs = jax.eval_shape(optimizer.init, params_abs)
        pspecs = shd.client_param_specs(params_abs, self.mesh)
        self.param_sh = shd.tree_shardings(pspecs, self.mesh)
        self.opt_sh = shd.tree_shardings(
            shd.opt_state_specs(opt_abs, pspecs, self.mesh), self.mesh)
        self.comp_sh = None if self.comp_state is None else \
            shd.tree_shardings(
                shd.client_comp_state_specs(self.comp_state, self.mesh),
                self.mesh)
        # composed with compression the cache holds the server's decoded
        # view, recomputed on every device from the gathered payload —
        # replicated, not client-sharded
        self.fault_sh = None if self.fault_state is None else \
            shd.tree_shardings(
                shd.client_fault_state_specs(
                    self.fault_state, self.mesh,
                    replicated=self.compressor is not None),
                self.mesh)

        # byte meter: record the aggregation collectives from an abstract
        # trace of the step on a one-round shell (the scan traces its round
        # body once), then audit them message-by-message.
        # Fault-tolerant binds trace with all-present masks: the mesh
        # collective is shape-static (it always ships M blocks), and the
        # full-participation audit pins the meter; per-round fault prices
        # then come from the SAME audited model with n_present uploads.
        shell = sampler.shape_shell_batch()
        records = []
        trace_fn = glasu.make_sharded_multi_round_fn(
            model_cfg, optimizer, self.mesh, record=records.append,
            jit=False)
        ones = None if self.fault_state is None else glasu.RoundFaults(
            jnp.ones((1, model_cfg.n_clients)),
            jnp.ones((1, model_cfg.n_clients)))
        jax.eval_shape(trace_fn, params_abs, opt_abs,
                       jax.tree.map(lambda x: x[None], shell),
                       jax.random.PRNGKey(0)[None],
                       glasu.RoundCarry(self.comp_state, self.fault_state),
                       ones)
        self.collectives = tuple(records)
        self.bytes_per_round = self._audited_bytes(shell)

        self.step_fn = glasu.make_sharded_multi_round_fn(
            model_cfg, optimizer, self.mesh)
        self._joint_fn = None
        self._batch_shs = {}
        self._host_memory = self.mesh.devices.flat[0].platform == "cpu"

    def _audited_bytes(self, shell: SampledBatch) -> int:
        """Collective meter vs message log, or raise. Returns bytes/round."""
        cfg = self.cfg
        measured = sum(r.star_bytes() for r in self.collectives)
        log = simulation.MessageLog()
        simulation.log_index_sync(log, shell, cfg)
        simulation.log_agg_traffic(log, shell, cfg, compressor=self.compressor)
        expected_act = (log.total_bytes("upload")
                        + log.total_bytes("broadcast"))
        if measured != expected_act:
            raise RuntimeError(
                f"collective byte-meter audit failed: traced collectives "
                f"move {measured} B but the message log carries "
                f"{expected_act} B of uploads+broadcasts")
        if not (cfg.agg_layers and cfg.n_clients > 1):
            return 0          # nothing actually crosses clients
        # index-set coordination (Alg 2) runs host-side in the sampler; its
        # traffic comes from the same message log, not the collectives
        return measured + log.total_bytes("index_sync")

    def _batch_sh(self, batch, round_stacked: bool):
        """The client shardings of a batch (``client_batch_specs``), built
        once per tree structure, leaf shapes and ``round_stacked``."""
        leaves, treedef = jax.tree.flatten(batch)
        key = (treedef, tuple(np.shape(x) for x in leaves), round_stacked)
        sh = self._batch_shs.get(key)
        if sh is None:
            from ..launch import sharding as shd
            sh = self._batch_shs[key] = shd.tree_shardings(
                shd.client_batch_specs(batch, self.mesh,
                                       round_stacked=round_stacked),
                self.mesh)
        return sh

    def stage(self, host_batch):
        """The Trainer's hand-off of a step's round-stacked host batch: one
        batched ``jax.device_put`` from the host straight onto the client
        shardings, so the batch never lands on one chip first.

        The result must not alias the host buffers, which the prefetch
        worker refills once the step is retired. A CPU device's memory is
        the host's, and its client aliases an aligned numpy buffer instead
        of copying it, so there the batch is copied first."""
        if self._host_memory:
            host_batch = jax.tree.map(np.copy, host_batch)
        return jax.device_put(host_batch,
                              self._batch_sh(host_batch, round_stacked=True))

    def _fault_bytes(self, plan) -> int:
        """Delivered-only price of one fault round on the federated wire.

        The mesh all_gather is shape-static (M blocks regardless of the
        draw), so the TRAFFIC of a fault round is priced by the audited
        cost model with n_present uploads, not re-read off collectives.
        """
        return _analytic_bytes(self.cfg, self.sampler, self.compressor,
                               n_uploads=plan.n_present)

    def _placed(self, params, opt_state, batch, round_stacked: bool):
        """Every mesh placement of a step (params, optimizer state, batch
        and the compression and fault carries), under the ``place`` span.
        A step's own outputs and a staged batch pass through; what arrives
        from off the mesh (init, a restore, a host batch) moves. Counts the
        leaves in ``place_moved`` and ``place_kept``."""
        with obs.span("place"):
            placed, moved, kept = _put(
                (params, opt_state, batch, self.comp_state,
                 self.fault_state),
                (self.param_sh, self.opt_sh,
                 self._batch_sh(batch, round_stacked), self.comp_sh,
                 self.fault_sh))
            obs.count("place_moved", moved)
            obs.count("place_kept", kept)
        return placed

    run_round = run_round_as_step

    def run_step(self, params, opt_state, batches, keys, faults=None):
        _check_fault_args(self.cfg, self.fault_state, faults)
        params, opt_state, batches, comp_state, fault_state = self._placed(
            params, opt_state, batches, round_stacked=True)
        params, opt_state, losses, carry = self.step_fn(
            params, opt_state, batches, keys,
            glasu.RoundCarry(comp_state, fault_state), _step_faults(faults))
        self.comp_state, self.fault_state = carry
        if faults is not None:
            per_round = tuple(self._fault_bytes(p) for p in faults)
            obs.count("exchange_bytes", sum(per_round))
            return StepResult(params, opt_state, losses, self.bytes_per_round,
                              comm_bytes_rounds=per_round)
        # the audited price of each round's exchange; len(keys) is a shape
        obs.count("exchange_bytes", self.bytes_per_round * len(keys))
        return StepResult(params, opt_state, losses, self.bytes_per_round)

    def joint_logits(self, params, batch, key=None):
        if self._joint_fn is None:
            self._joint_fn = glasu.make_sharded_joint_fn(self.cfg, self.mesh)
        (params, batch), _, _ = _put(
            (params, batch),
            (self.param_sh, self._batch_sh(batch, round_stacked=False)))
        return self._joint_fn(params, batch, key)


_BACKENDS = {"vmapped": VmappedBackend, "simulation": SimulationBackend,
             "sharded": ShardedBackend}


def make_backend(name: str, **kwargs) -> Backend:
    """Instantiate a registered backend. ``kwargs`` (e.g. ``mesh``,
    ``mesh_devices`` for the sharded backend) go to the constructor."""
    try:
        cls = _BACKENDS[name]
    except KeyError:
        raise ValueError(f"unknown backend {name!r}; expected one of "
                         f"{tuple(_BACKENDS)}") from None
    return cls(**kwargs)
