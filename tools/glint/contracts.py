"""Layer 2: jaxpr contract checker (GL2xx).

Traces every registered public entry point with shape shells (no real
compute: ``jax.eval_shape`` params, zero-stride sampler shells) and asserts
properties of the closed jaxpr / lowered IR that tier-1 unit tests cannot
see:

  GL201  no 64-bit values anywhere in the trace (x64 is off; a silently
         truncated f64 literal means someone *meant* a different number)
  GL202  no host-callback / device_put primitives on hot paths (a stray
         ``debug_print`` or implicit transfer serializes every dispatch)
  GL203  buffer donation effective: each donated leaf of the multi-round
         step fns produces an input-output aliasing in the lowered IR
         (broken donation doubles parameter HBM traffic per step) — run
         once per execution policy, so a policy whose extra carry (EF
         accumulators, fault caches) breaks aliasing fails here
  GL204  the sharded round body's embedding collectives match the byte
         meter term by term: per-client wire bytes summed over ``all_gather``
         eqns equal the sum of ``CollectiveRecord.up_bytes`` (a drifted
         meter is a static failure here, not a benchmark drift) — traced
         through the sharded builder at K = 1, once per execution policy
         (plain / compressed / fault-tolerant / composed)

Entry points register in ``ENTRY_POINTS``; adding a public round/serve/
kernel builder without registering it is itself a finding (GL200-style
coverage is enforced in ``tests/test_glint.py``), and every execution
policy of the unified round body (``core.glasu.ExecPolicy``) must ship a
registered traceable entry for both round builders
(``_check_policy_coverage``).
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, List, Tuple

from . import Finding

_X64 = ("float64", "int64", "uint64", "complex128")
_CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                   "callback", "outside_call", "device_put")


# ----------------------------------------------------------------- fixture
@functools.lru_cache(maxsize=None)
def _fixture():
    """Tiny shape-shell world shared by all contracts (built once)."""
    import jax
    import numpy as np
    from repro.core import glasu
    from repro.core.glasu import GlasuConfig
    from repro.graph.sampler import GlasuSampler, SamplerConfig
    from repro.graph.synth import make_vfl_dataset
    from repro.optim import optimizers as opt_lib

    m = 2
    data = make_vfl_dataset("tiny", n_clients=m, seed=0)
    d_in = max(c.feat_dim for c in data.clients)
    cfg = GlasuConfig(n_clients=m, n_layers=4, hidden=8,
                      n_classes=data.n_classes, d_in=d_in, backbone="gcn",
                      agg="mean", agg_layers=(1, 3), n_local_steps=1)
    scfg = SamplerConfig(n_layers=4, agg_layers=(1, 3), batch_size=4,
                         fanout=2, size_cap=32)
    sampler = GlasuSampler(data, scfg, seed=0)
    shell = sampler.shape_shell_batch()
    opt = opt_lib.sgd(0.1)
    params_abs = jax.eval_shape(lambda k: glasu.init_params(k, cfg),
                                jax.random.PRNGKey(0))
    opt_abs = jax.eval_shape(opt.init, params_abs)
    key_abs = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    batch_abs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype),
        shell)
    return dict(cfg=cfg, opt=opt, sampler=sampler, data=data,
                params=params_abs, opt_state=opt_abs, key=key_abs,
                batch=batch_abs, glasu=glasu)


def _stack_rounds(batch_abs, k: int):
    import jax
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((k,) + a.shape, a.dtype), batch_abs)


def _keys_abs(k: int):
    import jax
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    return jax.ShapeDtypeStruct((k,) + key.shape, key.dtype)


# ------------------------------------------------------------ jaxpr walking
def _walk_eqns(jaxpr):
    """Yield every eqn in ``jaxpr`` and all nested sub-jaxprs (pjit bodies,
    shard_map bodies, scan/cond branches, custom_vjp calls...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _walk_eqns(sub)


def _sub_jaxprs(v):
    import jax.extend.core as core
    if isinstance(v, core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, core.Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for item in v:
            yield from _sub_jaxprs(item)


def _all_avals(jaxpr):
    for eqn in _walk_eqns(jaxpr):
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(var, "aval", None)
            if aval is not None and hasattr(aval, "dtype"):
                yield eqn, aval


# ---------------------------------------------------------------- contracts
def _check_no_x64(name: str, closed, where: str) -> List[Finding]:
    out = []
    for eqn, aval in _all_avals(closed.jaxpr):
        if str(aval.dtype) in _X64:
            out.append(Finding(
                "GL201", where, 1,
                f"{name}: 64-bit value ({aval.dtype}) produced by "
                f"`{eqn.primitive.name}` in the traced jaxpr — x64 is "
                f"disabled repo-wide"))
            break
    for const in closed.consts:
        dt = getattr(const, "dtype", None)
        if dt is not None and str(dt) in _X64:
            out.append(Finding(
                "GL201", where, 1,
                f"{name}: 64-bit constant ({dt}) closed over by the jaxpr"))
            break
    return out


def _check_no_callbacks(name: str, closed, where: str) -> List[Finding]:
    out = []
    for eqn in _walk_eqns(closed.jaxpr):
        if eqn.primitive.name in _CALLBACK_PRIMS:
            out.append(Finding(
                "GL202", where, 1,
                f"{name}: `{eqn.primitive.name}` primitive on a hot path — "
                f"host callbacks/transfers serialize every dispatch"))
    return out


def _check_donation(name: str, jitted, args, n_donated_leaves: int,
                    where: str) -> List[Finding]:
    text = jitted.lower(*args).as_text()
    aliased = text.count("tf.aliasing_output")
    if aliased < n_donated_leaves:
        return [Finding(
            "GL203", where, 1,
            f"{name}: only {aliased} of {n_donated_leaves} donated leaves "
            f"are aliased input->output in the lowered IR — donation is "
            f"(partially) broken and parameter HBM traffic doubles")]
    return []


def _collect_gathers(closed):
    """(per_client_bytes, operand_ndim) per all_gather eqn in the trace."""
    out = []
    for eqn in _walk_eqns(closed.jaxpr):
        if eqn.primitive.name != "all_gather":
            continue
        aval = eqn.invars[0].aval
        # leading axis is the local client block; bytes of one client's
        # message = payload rows x width x itemsize
        per_client = (math.prod(aval.shape[1:]) * aval.dtype.itemsize
                      if aval.ndim >= 1 else aval.dtype.itemsize)
        out.append((per_client, aval.ndim))
    return out


# the ExecPolicy grid of the unified round body (core.glasu._round_body):
# identity/int8 codec x all-present/fault-tolerant participation. Every
# combination must ship a registered traceable entry for both round
# builders — _check_policy_coverage fails the run otherwise.
POLICY_COMBOS = ("plain", "int8", "faults", "int8+faults")


def _policy_cfg(policy: str):
    """The fixture config under one execution-policy combination."""
    import dataclasses
    from repro.comm.compression import CompressionConfig

    cfg = _fixture()["cfg"]
    if "int8" in policy:
        cfg = dataclasses.replace(
            cfg, compression=CompressionConfig(method="int8"))
    if "faults" in policy:
        cfg = dataclasses.replace(cfg, fault_tolerant=True)
    return cfg


def _policy_args(cfg, k: int = 2):
    """Abstract call args of a round builder's step under ``cfg``'s
    policy: ``params, opt_state, batches, keys, carry, faults`` with a
    leading round axis of ``k`` on the batches, keys and fault masks."""
    import jax

    fx = _fixture()
    glasu = fx["glasu"]
    layer_sizes = fx["sampler"].layer_sizes
    carry = glasu.RoundCarry(
        comp_state=jax.eval_shape(
            lambda: glasu.init_comp_state(cfg, layer_sizes)),
        fault_state=jax.eval_shape(
            lambda: glasu.init_fault_state(cfg, layer_sizes)))
    faults = None
    if cfg.fault_tolerant:
        mask = jax.ShapeDtypeStruct((k, cfg.n_clients), "float32")
        faults = glasu.RoundFaults(mask, mask)
    return (fx["params"], fx["opt_state"], _stack_rounds(fx["batch"], k),
            _keys_abs(k), carry, faults)


def _n_donated_leaves(args) -> int:
    """params + opt_state + the carry's active members (the donate_argnums
    set of both round builders)."""
    import jax
    params, opt_state, _, _, carry, _ = args
    return len(jax.tree.leaves((params, opt_state, carry)))


def _check_collectives_vs_meter(policy: str = "plain") -> List[Finding]:
    """GL204: trace the sharded builder's step at K = 1 under one execution
    policy, compare its all_gather set against the CollectiveRecords the
    byte meter emits for the same trace."""
    import jax
    from repro.launch.mesh import make_client_mesh

    fx = _fixture()
    glasu = fx["glasu"]
    cfg = _policy_cfg(policy)
    where = "src/repro/core/glasu.py"
    mesh = make_client_mesh(cfg.n_clients)
    records = []
    fn = glasu.make_sharded_multi_round_fn(cfg, fx["opt"], mesh,
                                           record=records.append, jit=False)
    args = _policy_args(cfg, k=1)
    with mesh:
        closed = jax.make_jaxpr(fn)(*args)

    name = f"make_sharded_multi_round_fn[{policy}]"
    out = []
    if not records:
        return [Finding("GL204", where, 1,
                        f"{name}: byte meter recorded no collectives")]
    # embedding exchanges are >=2-D payloads; the 1-D all_gather is the
    # Q-scalar loss diagnostic, explicitly unmetered (see the
    # local_update_steps docstring)
    payload = [b for b, nd in _collect_gathers(closed) if nd >= 2]
    metered = sum(r.up_bytes for r in records)
    traced = sum(payload)
    if traced != metered:
        out.append(Finding(
            "GL204", where, 1,
            f"{name}: traced embedding all_gathers move {traced} B/client "
            f"but the byte meter prices {metered} B/client — the meter "
            f"drifted from the compiled collectives"))
    if len(payload) < len(records):
        out.append(Finding(
            "GL204", where, 1,
            f"{name}: {len(records)} CollectiveRecords but only "
            f"{len(payload)} embedding all_gathers in the trace"))
    return out


# ------------------------------------------------------------- entry points
def _ep_multi_round_fn(policy: str = "plain"):
    import jax
    fx = _fixture()
    cfg = _policy_cfg(policy)
    fn = fx["glasu"].make_multi_round_fn(cfg, fx["opt"])
    args = _policy_args(cfg)
    closed = jax.make_jaxpr(fn)(*args)
    return closed, (fn, args, _n_donated_leaves(args))


def _ep_sharded_multi_round_fn(policy: str = "plain"):
    import jax
    from repro.launch.mesh import make_client_mesh
    fx = _fixture()
    cfg = _policy_cfg(policy)
    mesh = make_client_mesh(cfg.n_clients)
    fn = fx["glasu"].make_sharded_multi_round_fn(cfg, fx["opt"], mesh)
    args = _policy_args(cfg)
    with mesh:
        closed = jax.make_jaxpr(fn)(*args)
        findings = _check_donation(
            f"make_sharded_multi_round_fn[{policy}]", fn, args,
            _n_donated_leaves(args), "src/repro/core/glasu.py")
    return closed, ("inline", findings)


def _ep_sharded_joint_fn():
    import jax
    from repro.launch.mesh import make_client_mesh
    fx = _fixture()
    mesh = make_client_mesh(fx["cfg"].n_clients)
    fn = fx["glasu"].make_sharded_joint_fn(fx["cfg"], mesh)
    with mesh:
        closed = jax.make_jaxpr(fn)(fx["params"], fx["batch"], fx["key"])
    return closed, None


def _ep_sharded_serve_fn():
    import jax
    from repro.launch.mesh import make_client_mesh
    fx = _fixture()
    cfg = fx["cfg"]
    mesh = make_client_mesh(cfg.n_clients)
    fn = fx["glasu"].make_sharded_serve_fn(cfg, mesh)
    sizes = fx["sampler"].layer_sizes
    # cache-injection shells: keep mask (n_{l+1},) + replicated row stacks
    # (M, n_{l+1}, h) for every aggregation layer (the session always passes
    # the full key set; all-zero masks mean no injection)
    inject = {l: (jax.ShapeDtypeStruct((sizes[l + 1],), "float32"),
                  jax.ShapeDtypeStruct((cfg.n_clients, sizes[l + 1],
                                        cfg.hidden), "float32"))
              for l in cfg.agg_layers}
    with mesh:
        closed = jax.make_jaxpr(fn)(fx["params"], fx["batch"], inject)
    return closed, None


def _ep_serve_forward():
    import jax
    fx = _fixture()
    closed = jax.make_jaxpr(
        lambda p, b: fx["glasu"].serve_forward(p, b, fx["cfg"]))(
            fx["params"], fx["batch"])
    return closed, None


def _ep_full_forward():
    import jax
    fx = _fixture()
    cfg, data = fx["cfg"], fx["data"]
    m = cfg.n_clients
    n = min(c.n_nodes for c in data.clients)
    feats = jax.ShapeDtypeStruct((m, n, cfg.d_in), "float32")
    width = 4
    nbr = jax.ShapeDtypeStruct((m, n, width), "int32")
    nbm = jax.ShapeDtypeStruct((m, n, width), "float32")
    closed = jax.make_jaxpr(
        lambda p, f, i, k: fx["glasu"].full_forward(p, cfg, f, i, k,
                                                    chunk=16))(
            fx["params"], feats, nbr, nbm)
    return closed, None


def _ep_graph_agg_kernel():
    import jax
    from repro.kernels.graph_agg import graph_agg_pallas
    h = jax.ShapeDtypeStruct((32, 8), "float32")
    idx = jax.ShapeDtypeStruct((16, 3), "int32")
    mask = jax.ShapeDtypeStruct((16, 3), "float32")
    w = jax.ShapeDtypeStruct((8, 8), "float32")
    closed = jax.make_jaxpr(
        functools.partial(graph_agg_pallas, interpret=True))(h, idx, mask, w)
    return closed, None


def _ep_graph_agg_csr_kernel():
    import jax
    import numpy as np
    from repro.graph.csr_plan import plan_csr_slabs
    from repro.kernels.graph_agg import graph_agg_csr_pallas
    # a tiny concrete CSR: the slab planner is host-side, so the traced
    # entry is the kernel over the planned static-shape slab arrays
    indptr = np.array([0, 2, 2, 5, 6], np.int32)        # zero-degree row 1
    indices = np.array([1, 3, 0, 2, 3, 1], np.int32)
    idx_s, seg_s, ew_s, n_dst = plan_csr_slabs(indptr, indices)
    h = jax.ShapeDtypeStruct((4, 8), "float32")
    w = jax.ShapeDtypeStruct((8, 8), "float32")
    slabs = [jax.ShapeDtypeStruct(a.shape, a.dtype.name)
             for a in (idx_s, seg_s, ew_s)]
    closed = jax.make_jaxpr(
        lambda h_, i_, s_, e_, w_: graph_agg_csr_pallas(h_, i_, s_, e_, w_,
                                                        n_dst,
                                                        interpret=True))(
            h, *slabs, w)
    return closed, None


def _ep_gcnii_kernel():
    import jax
    from repro.kernels.graph_agg import gcnii_layer_pallas
    h = jax.ShapeDtypeStruct((32, 8), "float32")
    idx = jax.ShapeDtypeStruct((16, 4), "int32")
    mask = jax.ShapeDtypeStruct((16, 4), "float32")
    w = jax.ShapeDtypeStruct((8, 8), "float32")
    b = jax.ShapeDtypeStruct((8,), "float32")
    closed = jax.make_jaxpr(
        lambda *a: gcnii_layer_pallas(*a, alpha=0.1, beta=0.5,
                                      interpret=True))(
            h, h, idx, mask, w, b)
    return closed, None


def _ep_flash_kernel():
    import jax
    from repro.kernels.flash_attention import flash_attention_pallas
    q = jax.ShapeDtypeStruct((1, 64, 4, 8), "float32")
    k = jax.ShapeDtypeStruct((1, 64, 2, 8), "float32")   # GQA: 2 kv heads
    closed = jax.make_jaxpr(
        lambda q_, k_, v_: flash_attention_pallas(q_, k_, v_,
                                                  interpret=True))(q, k, k)
    return closed, None


def _ep_gat_kernel():
    import jax
    from repro.kernels.graph_agg import gat_layer_pallas
    h = jax.ShapeDtypeStruct((32, 8), "float32")
    idx = jax.ShapeDtypeStruct((16, 4), "int32")
    mask = jax.ShapeDtypeStruct((16, 4), "float32")
    w = jax.ShapeDtypeStruct((8, 2, 4), "float32")
    a_src = jax.ShapeDtypeStruct((2, 4), "float32")
    a_dst = jax.ShapeDtypeStruct((2, 4), "float32")
    b = jax.ShapeDtypeStruct((8,), "float32")
    closed = jax.make_jaxpr(
        functools.partial(gat_layer_pallas, interpret=True))(
            h, idx, mask, w, a_src, a_dst, b)
    return closed, None


def _ep_resident_masked_mean_kernel():
    import jax
    from repro.kernels.graph_agg import resident_masked_mean_pallas
    h = jax.ShapeDtypeStruct((2, 40, 64), "float32")
    idx = jax.ShapeDtypeStruct((2, 24, 5), "int32")
    mask = jax.ShapeDtypeStruct((2, 24, 5), "float32")
    closed = jax.make_jaxpr(
        functools.partial(resident_masked_mean_pallas, interpret=True,
                          vmem_limit_bytes=0))(h, idx, mask)
    return closed, None


# name -> (builder, repo-relative path of the code under contract)
ENTRY_POINTS: Dict[str, Tuple[Callable, str]] = {
    "make_multi_round_fn": (_ep_multi_round_fn, "src/repro/core/glasu.py"),
    "make_sharded_multi_round_fn": (_ep_sharded_multi_round_fn,
                                    "src/repro/core/glasu.py"),
    # non-plain ExecPolicy combinations of the unified round body: same
    # builders, extra carries (EF accumulators / fault caches) donated
    **{f"make_multi_round_fn[{_p}]": (
        functools.partial(_ep_multi_round_fn, _p),
        "src/repro/core/glasu.py") for _p in POLICY_COMBOS[1:]},
    **{f"make_sharded_multi_round_fn[{_p}]": (
        functools.partial(_ep_sharded_multi_round_fn, _p),
        "src/repro/core/glasu.py") for _p in POLICY_COMBOS[1:]},
    "make_sharded_joint_fn": (_ep_sharded_joint_fn,
                              "src/repro/core/glasu.py"),
    "make_sharded_serve_fn": (_ep_sharded_serve_fn,
                              "src/repro/core/glasu.py"),
    "serve_forward": (_ep_serve_forward, "src/repro/core/glasu.py"),
    "full_forward": (_ep_full_forward, "src/repro/core/glasu.py"),
    "graph_agg_pallas": (_ep_graph_agg_kernel,
                         "src/repro/kernels/graph_agg.py"),
    "graph_agg_csr_pallas": (_ep_graph_agg_csr_kernel,
                             "src/repro/kernels/graph_agg.py"),
    "gcnii_layer_pallas": (_ep_gcnii_kernel,
                           "src/repro/kernels/graph_agg.py"),
    "gat_layer_pallas": (_ep_gat_kernel, "src/repro/kernels/graph_agg.py"),
    "resident_masked_mean_pallas": (_ep_resident_masked_mean_kernel,
                                    "src/repro/kernels/graph_agg.py"),
    "flash_attention_pallas": (_ep_flash_kernel,
                               "src/repro/kernels/flash_attention.py"),
}


def _check_policy_coverage() -> List[Finding]:
    """Every ExecPolicy combination of the unified round body must ship a
    registered traceable entry for both round builders — the jit
    boundaries the Trainer actually dispatches. A policy added to
    ``POLICY_COMBOS`` without its entries is a finding, not a silent gap
    in contract coverage."""
    out = []
    for pol in POLICY_COMBOS:
        for base in ("make_multi_round_fn", "make_sharded_multi_round_fn"):
            key = base if pol == "plain" else f"{base}[{pol}]"
            if key not in ENTRY_POINTS:
                out.append(Finding(
                    "GL200", "tools/glint/contracts.py", 1,
                    f"execution policy {pol!r} ships without a registered "
                    f"traceable entry for {base} — GL203/GL204 never run "
                    f"against that combination"))
    return out


def run_contracts(names=None):
    """Run the GL2xx layer. Returns ``(findings, report)``."""
    findings: List[Finding] = []
    checked = []
    if names is None:
        findings.extend(_check_policy_coverage())
    for name, (builder, where) in ENTRY_POINTS.items():
        if names is not None and name not in names:
            continue
        closed, extra = builder()
        findings.extend(_check_no_x64(name, closed, where))
        findings.extend(_check_no_callbacks(name, closed, where))
        if extra == "skip-donation":
            pass
        elif isinstance(extra, tuple) and extra and extra[0] == "inline":
            findings.extend(extra[1])
        elif extra is not None:
            fn, args, n_leaves = extra
            findings.extend(_check_donation(name, fn, args, n_leaves,
                                            where))
        checked.append(name)
    if names is None or "collectives" in (names or ()):
        for pol in POLICY_COMBOS:
            findings.extend(_check_collectives_vs_meter(pol))
        checked.append("collectives-vs-meter")
    report = {"entry_points": checked}
    return findings, report
