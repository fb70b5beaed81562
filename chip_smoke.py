"""Bring-up smoke run of GLASU on TPU, through the public entry points.

    python chip_smoke.py            # one chip: train, CPU check, fused kernels, serve
    python chip_smoke.py --chips 4  # sharded backend, one party per chip, vs vmapped

Everything runs in this one process. It exits non-zero, printing no result,
when JAX finds no TPU; any failed check raises. The last line of standard
output is ``{"ok": true, "device": {"platform", "kind", "count"}}``.

Default run, at the paper's widths (``cora-gcnii-glasu``: M=3, L=4,
hidden 64, K=2, Q=4):

1. ``Trainer`` on the preset as shipped (vmapped backend, XLA path), a few
   dozen rounds in multi-round steps, one exact eval and a checkpoint:
   losses must be finite and falling; the eval's layers take the resident
   aggregation kernel, which must match XLA's ``gather_mean`` on the chip
   within ``RESIDENT_RTOL`` (whether bitwise is printed).
2. The first round again on the host CPU backend: its losses must agree
   with the chip's within ``CPU_RTOL``.
3. One round of each backbone (gcn, gcnii, gat) with ``use_pallas=True``
   against the XLA path's losses for the same round, within
   ``FUSED_RTOL``.
4. ``InferenceSession`` restored from phase 1's checkpoint answers a few
   cold batches, then the same batches warm: equal logits, 0 wire bytes.

``--chips 4`` runs only the sharded backend with one party per chip
(``n_clients=4``), checks the client mesh spans 4 devices and the
collective byte meter, checks that the exact eval, split by party over
the chips, kept XLA's gather (XLA cannot split the resident kernel) and
gives the logits of the same params on one chip, where the resident
kernel runs, within ``EVAL_RTOL``, and compares its first round's losses
with the vmapped backend on one chip within ``SHARD_RTOL``.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

PRESET = "cora-gcnii-glasu"
ROUNDS = 32                 # phase 1: four steps of ROUNDS_PER_STEP rounds
ROUNDS_PER_STEP = 8
SERVE_BATCHES = 3           # phase 4: cold then warm batches of SERVE_BATCH
SERVE_BATCH = 16
SHARD_ROUNDS = 8

# Comparisons cover the Q microstep losses of the FIRST round only: its
# first loss is the forward pass on identical parameters, the next Q-1
# follow Adam updates. Adam's first steps move each weight by about
# lr * sign(gradient), so a coordinate whose gradient is near zero can
# flip under roundoff, and on 16-node batches the trajectories decorrelate
# within a few rounds (chip vs CPU: 1e-3 apart in round 2, 9e-2 in round
# 4, on the cora preset). Within the first round, losses must agree
# elementwise to |got - want| <= RTOL * |want| + LOSS_ATOL.
# The TPU computes float32 matmuls with one bfloat16 pass by default
# (8-bit significand, unit roundoff 2^-9 ~ 2e-3); the CPU backend rounds
# in float32. A loss sums a few hundred such products per layer over four
# layers, so the chip may differ from the CPU by several bfloat16
# roundoffs: RTOL = 2e-2 allows ten, and a wrong forward or gradient
# differs at the first decimal. Losses of a batch fitted after a few local
# steps fall near zero, where roundoff in the logits moves the loss by an
# absolute amount proportional to the loss itself; LOSS_ATOL (nats) keeps
# those from reading as large relative errors.
LOSS_ATOL = 1e-3
CPU_RTOL = 2e-2
# Fused kernels (Mosaic's default matmul precision) against the XLA path
# (XLA's default precision): the same roundoff argument.
FUSED_RTOL = 2e-2
# Sharded vs vmapped on the same chip type: identical math, different
# fusion and per-device batch widths, so only accumulation order differs.
SHARD_RTOL = 2e-2
# The resident aggregation kernel against XLA's gather_mean on the chip,
# as a share of the largest mean: float32 sums in another order at most.
RESIDENT_RTOL = 1e-6
# The exact eval split over the chips (XLA's gather) against one chip (the
# resident kernel), as a share of the largest logit: the same sums, which
# may round differently, and then the same one-pass bfloat16 matmuls.
EVAL_RTOL = 2e-2


def _fail(msg: str):
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def _compile_counts():
    """(compile seconds, persistent-cache hits, misses) so far, from the
    program's compile counter (``repro.obs``)."""
    from repro import obs
    c = obs.counters()
    return (c.get("compile_s", 0.0), c.get("compile_cache_hits", 0),
            c.get("compile_cache_misses", 0))


def _report(phase: str, since):
    (s, h, m), (s1, h1, m1) = since, _compile_counts()
    print(f"[{phase}] compile_s={s1 - s:.2f} "
          f"cache_hits={h1 - h} cache_misses={m1 - m}")


def _run(cfg):
    """Trainer run recording every round's (Q,) microstep losses."""
    import numpy as np
    import jax
    from repro.api import Trainer
    from repro.api.trainer import Hook

    rows = []

    class LossRows(Hook):
        def on_round_end(self, trainer, metrics):
            rows.append(metrics["losses"])

    trainer = Trainer(cfg, hooks=[LossRows()])
    result = trainer.run()
    losses = np.asarray(jax.device_get(rows), np.float64)
    if losses.shape != (cfg.rounds, cfg.n_local_steps):
        _fail(f"{cfg.name}: loss rows {losses.shape}, expected "
              f"{(cfg.rounds, cfg.n_local_steps)}")
    if not np.isfinite(losses).all():
        _fail(f"{cfg.name}: non-finite losses {losses}")
    return trainer, result, losses


def _compare(name: str, got, want, rtol: float):
    """Check the first round's (Q,) losses; ``got``/``want`` are (R, Q)."""
    import numpy as np
    got, want = got[0], want[0]
    err = np.abs(got - want)
    bad = err > rtol * np.abs(want) + LOSS_ATOL
    print(f"[{name}] max_abs_diff={err.max():.3e} max_rel_diff="
          f"{(err / np.abs(want)).max():.3e} rtol={rtol:.0e} "
          f"atol={LOSS_ATOL:.0e} outside={int(bad.sum())}/{bad.size}")
    if bad.any():
        _fail(f"{name}: losses outside tolerance\n got  {got}\n want {want}")


def phase_train(ckpt_dir: str):
    import jax
    import numpy as np
    from repro.api import get_preset

    cfg = get_preset(PRESET).with_(
        rounds=ROUNDS, rounds_per_step=ROUNDS_PER_STEP, eval_every=ROUNDS,
        ckpt_dir=ckpt_dir)
    if cfg.use_pallas or cfg.backend != "vmapped":
        _fail(f"{PRESET} no longer ships as the vmapped XLA path")
    from repro import obs
    before = obs.counters()
    t0 = time.perf_counter()
    trainer, result, losses = _run(cfg)
    paths = {k: obs.counters().get(k, 0) - before.get(k, 0)
             for k in ("full_agg:resident", "full_agg:xla")}
    per_round = losses.mean(axis=1)
    head = float(per_round[:ROUNDS_PER_STEP].mean())
    tail = float(per_round[-ROUNDS_PER_STEP:].mean())
    print(f"[train] {PRESET} rounds={ROUNDS} rounds_per_step="
          f"{ROUNDS_PER_STEP} wall_s={time.perf_counter() - t0:.2f}")
    print(f"[train] loss first_step_mean={head:.4f} last_step_mean={tail:.4f}"
          f" round_means={np.round(per_round, 4).tolist()}")
    print(f"[train] eval val_acc={result.val_acc:.4f} "
          f"test_acc={result.test_acc:.4f} comm_bytes={result.comm_bytes}")
    if not tail < head:
        _fail(f"losses not falling: first step {head:.4f}, last {tail:.4f}")
    if len(result.history) != 1:
        _fail(f"expected one eval entry, got {len(result.history)}")
    # the exact eval's layers take the VMEM-resident mean on a TPU
    print(f"[train] eval layers resident={paths['full_agg:resident']} "
          f"xla={paths['full_agg:xla']}")
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu and (paths["full_agg:xla"] or not paths["full_agg:resident"]):
        _fail(f"exact eval took the XLA aggregation: {paths}")
    _check_resident_order(trainer)
    return cfg, losses


def _check_resident_order(trainer):
    """The resident kernel against ``gather_mean`` run by XLA on the chip,
    on the eval's own neighbour tables and on tables of other widths:
    equal to ``RESIDENT_RTOL``, and bitwise where the kernel's summation
    order is XLA's (printed, not required)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.train import _eval_neighbor_tables
    from repro.kernels import ops as kops
    from repro.models.gnn import gather_mean

    cfg = trainer.cfg
    idx, mask = _eval_neighbor_tables(trainer.data, cfg.eval_table_cap,
                                      cfg.seed)
    m, n, width = idx.shape
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(m, n, trainer.model_cfg.hidden)),
                    jnp.float32)
    tables = {width: (idx, mask)}
    for f in (5, 12):
        tables[f] = (jnp.asarray(rng.integers(0, n, (m, n, f)), jnp.int32),
                     jnp.asarray(rng.random((m, n, f)) < 0.8, jnp.float32))
    xla = jax.jit(jax.vmap(gather_mean))
    for f, (i, k) in tables.items():
        got = np.asarray(kops.resident_masked_mean(h, i, k))
        want = np.asarray(xla(h, i, k))
        rel = float(np.abs(got - want).max() / np.abs(want).max())
        print(f"[train] resident vs xla mean width={f}: max_rel={rel:.3e} "
              f"bitwise={bool((got == want).all())}")
        if not rel <= RESIDENT_RTOL:
            _fail(f"resident mean at width {f} off by {rel:.3e}")


def phase_cpu(cfg, chip_losses):
    import jax

    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        _, _, cpu_losses = _run(cfg.with_(rounds=1, rounds_per_step=1,
                                          eval_every=0, ckpt_dir=None))
    print(f"[cpu] round 1 on {cpu.platform}: {cpu_losses[0].tolist()} "
          f"chip: {chip_losses[0].tolist()}")
    _compare("cpu", chip_losses, cpu_losses, CPU_RTOL)


def phase_fused():
    from repro.api import get_preset
    from repro.kernels import ops

    if ops._interpret():
        _fail("Pallas kernels would run in interpret mode on this device")
    for backbone in ("gcn", "gcnii", "gat"):
        cfg = get_preset(f"cora-{backbone}-glasu").with_(
            rounds=1, rounds_per_step=1, eval_every=0)
        _, _, xla = _run(cfg)
        _, _, fused = _run(cfg.with_(use_pallas=True))
        print(f"[fused] {backbone} xla={xla[0].tolist()} "
              f"pallas={fused[0].tolist()}")
        _compare(f"fused/{backbone}", fused, xla, FUSED_RTOL)


def phase_serve(ckpt_dir: str):
    import numpy as np
    from repro.serve import InferenceSession, ServeConfig

    session = InferenceSession.from_checkpoint(
        ckpt_dir, serve=ServeConfig(max_batch=SERVE_BATCH))
    rng = np.random.default_rng(0)
    nodes = rng.choice(session.N, SERVE_BATCHES * SERVE_BATCH, replace=False)
    batches = np.split(nodes, SERVE_BATCHES)
    cold = [session.answer(b) for b in batches]
    warm = [session.answer(b) for b in batches]
    for i, (c, w) in enumerate(zip(cold, warm)):
        print(f"[serve] batch {i}: cold wire_bytes={c.wire_bytes} "
              f"latency_ms={c.latency_s * 1e3:.2f} | warm wire_bytes="
              f"{w.wire_bytes} latency_ms={w.latency_s * 1e3:.2f}")
        if not c.cold or w.cold:
            _fail(f"batch {i}: expected cold then warm, got cold={c.cold} "
                  f"then cold={w.cold}")
        if c.wire_bytes <= 0 or w.wire_bytes != 0:
            _fail(f"batch {i}: wire bytes cold {c.wire_bytes}, warm "
                  f"{w.wire_bytes} (warm must be 0)")
        if not np.array_equal(c.logits, w.logits):
            _fail(f"batch {i}: warm logits differ from cold logits")
        if not np.isfinite(c.logits).all():
            _fail(f"batch {i}: non-finite logits")


def _compare_eval(trainer):
    """The exact eval's logits on the trainer's party-split params (XLA's
    gather) against the same params, fresh on one chip (the resident
    kernel)."""
    import jax
    import numpy as np
    from repro import obs
    from repro.api.trainer import EvalHook

    hook = next(h for h in trainer.hooks if isinstance(h, EvalHook))
    params = trainer.state.params
    split = np.asarray(hook.eval_fn(params))
    before = obs.counters().get("full_agg:resident", 0)
    # from the host: an array moved between devices keeps its mesh type
    one = np.asarray(hook.eval_fn(
        jax.device_put(jax.device_get(params), jax.devices()[0])))
    resident = obs.counters().get("full_agg:resident", 0) - before
    err = float(np.abs(split - one).max())
    scale = float(np.abs(one).max())
    print(f"[sharded] eval logits split (xla) vs one chip (resident="
          f"{resident}): max_abs_diff={err:.3e} max_abs={scale:.3e}")
    if not resident:
        _fail("the one-chip exact eval did not take the resident kernel")
    if not err <= EVAL_RTOL * scale:
        _fail(f"split exact eval differs from one chip's by {err:.3e}")


def phase_sharded(n_chips: int):
    import jax
    from repro.api import get_preset

    cfg = get_preset(PRESET).with_(
        n_clients=n_chips, backend="sharded", rounds=SHARD_ROUNDS,
        rounds_per_step=ROUNDS_PER_STEP, eval_every=SHARD_ROUNDS)
    from repro import obs
    before = obs.counters()
    t0 = time.perf_counter()
    trainer, result, sharded = _run(cfg)
    paths = {k: obs.counters().get(k, 0) - before.get(k, 0)
             for k in ("full_agg:resident", "full_agg:xla")}
    backend = trainer.backend
    mesh_devices = int(backend.mesh.devices.size)
    print(f"[sharded] n_clients={n_chips} mesh={dict(backend.mesh.shape)} "
          f"devices={[d.id for d in backend.mesh.devices.flat]} "
          f"wall_s={time.perf_counter() - t0:.2f}")
    if mesh_devices != n_chips:
        _fail(f"client mesh spans {mesh_devices} devices, expected {n_chips}")
    # the audit itself runs at bind and raises on any mismatch between the
    # traced collectives and the message log; here its result is re-checked
    # against the vmapped backend's analytic cost model below
    print(f"[sharded] audited collectives={len(backend.collectives)} "
          f"bytes_per_round={backend.bytes_per_round} "
          f"comm_bytes={result.comm_bytes} val_acc={result.val_acc:.4f}")
    # XLA cannot split the resident kernel over the mesh: the exact eval,
    # split by party over the chips, keeps XLA's gather
    print(f"[sharded] eval layers resident={paths['full_agg:resident']} "
          f"xla={paths['full_agg:xla']}")
    if paths["full_agg:resident"] or not paths["full_agg:xla"]:
        _fail(f"split exact eval did not take XLA's aggregation: {paths}")
    _compare_eval(trainer)
    with jax.default_device(jax.devices()[0]):
        vm_trainer, _, vmapped = _run(cfg.with_(backend="vmapped"))
    if backend.bytes_per_round <= 0 or \
            backend.bytes_per_round != vm_trainer.backend.bytes_per_round:
        _fail(f"sharded meter {backend.bytes_per_round} B/round vs analytic "
              f"{vm_trainer.backend.bytes_per_round} B/round")
    if result.comm_bytes != SHARD_ROUNDS * backend.bytes_per_round:
        _fail(f"metered {result.comm_bytes} B over {SHARD_ROUNDS} rounds, "
              f"expected {SHARD_ROUNDS * backend.bytes_per_round}")
    print(f"[sharded] round 1 sharded: {sharded[0].tolist()} vmapped: "
          f"{vmapped[0].tolist()}")
    _compare("sharded_vs_vmapped", sharded, vmapped, SHARD_RTOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the single-chip phases; 4: only the sharded "
                         "backend across four chips and its vmapped twin")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        _fail(f"needs a TPU, JAX found {dev.platform!r} ({dev.device_kind})")
    if len(devices) < args.chips:
        _fail(f"--chips {args.chips} but JAX found {len(devices)} devices")
    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__}")

    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache dir={enable_compile_cache()}")
    t_start = time.perf_counter()

    if args.chips == 4:
        since = _compile_counts()
        phase_sharded(4)
        _report("sharded", since)
    else:
        ckpt_dir = tempfile.mkdtemp(prefix=".chip_smoke_ckpt_", dir=REPO)
        try:
            since = _compile_counts()
            cfg, losses = phase_train(ckpt_dir)
            _report("train", since)
            since = _compile_counts()
            phase_cpu(cfg, losses)
            _report("cpu", since)
            since = _compile_counts()
            phase_fused()
            _report("fused", since)
            since = _compile_counts()
            phase_serve(ckpt_dir)
            _report("serve", since)
        finally:
            shutil.rmtree(ckpt_dir, ignore_errors=True)

    seconds, hits, misses = _compile_counts()
    print(f"total compile_s={seconds:.2f} cache_hits={hits} "
          f"cache_misses={misses} wall_s="
          f"{time.perf_counter() - t_start:.2f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
