"""GLASU: split-model VFL-GNN with lazy aggregation and stale updates.

Implements the paper's Algorithms 1 (training round), 3 (JointInference with
Extract) and 4 (LocalUpdate with stale cross-client representations), plus the
three baselines of §5.2 as special cases (§3.5):

  * centralized            -> M = 1
  * standalone [8]-style   -> agg_layers = () (clients never communicate)
  * simulated centralized [9] -> agg_layers = all layers, Q = 1
  * FedBCD [2]             -> A(E_m) = I (no graph; covered by unit test)

Execution model: the M clients are a stacked leading axis on every parameter
and activation leaf, and client-local compute is ``jax.vmap`` over that axis.
Server aggregation (parameter-free mean/concat, §3.1) is a cross-client
reduction — the only place information crosses the axis, exactly where the
paper places communication. ``CommMeter`` charges bytes for those crossings
using the paper's cost model.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from .. import obs
from ..comm import compression
from ..comm.compression import CompressionConfig, Compressor
from ..graph.sampler import SampledBatch
from ..models.gnn import BACKBONES, MASKED_MEAN
from ..optim import optimizers as opt_lib


@dataclass(frozen=True)
class GlasuConfig:
    n_clients: int = 3
    n_layers: int = 4
    hidden: int = 64
    n_classes: int = 7
    d_in: int = 478                       # padded per-client feature width
    backbone: str = "gcnii"
    agg: str = "mean"                     # 'mean' | 'concat' (parameter-free, §3.1)
    agg_layers: Sequence[int] = (1, 3)    # lazy aggregation index set I
    n_local_steps: int = 1                # Q (stale updates)
    gcnii_alpha: float = 0.1
    gcnii_beta: float = 0.5
    gat_heads: int = 2
    dp_sigma: float = 0.0                 # §3.6 DP hook (noise on uploads)
    secure_agg: bool = False              # §3.6 SA hook (cancelling masks)
    labels_at_client: Optional[int] = None  # Appendix B.2 (Alg 5-7): one label owner
    use_pallas: bool = False              # fused Pallas kernels (GCN/GCNII/GAT)
    compression: Optional[CompressionConfig] = None  # wire codec at the Agg boundary
    fault_tolerant: bool = False          # deadline rounds + stale-cache fallback

    def __post_init__(self):
        if self.agg_layers:
            assert (self.n_layers - 1) in self.agg_layers, \
                "prediction layer input must be aggregated (paper §3.1)"
        if self.agg == "concat":
            assert self.backbone == "gcn", "concat aggregation implemented for GCN"
        if self.compression is not None and self.compression.active:
            assert not self.secure_agg, \
                "secure_agg masks cancel only exactly; quantized/sparsified " \
                "uploads break the pairwise cancellation (disable one)"
        if self.fault_tolerant:
            assert self.agg_layers, \
                "fault tolerance shapes the aggregation exchange; a " \
                "standalone run has nothing to be tolerant about"
            assert not self.secure_agg and self.dp_sigma == 0.0, \
                "the §3.6 privacy hooks assume every round's uploads are " \
                "fresh; cached substitutes break mask cancellation / the " \
                "noise accounting — disable privacy hooks or faults"
            assert self.labels_at_client is None, \
                "labels_at_client (Alg 6) needs the owner's upload every " \
                "round; not supported with fault injection"

    def layer_in_dim(self, l: int) -> int:
        """Input width of layer l (concat widens post-aggregation layers)."""
        if l == 0:
            return self.hidden
        widened = self.agg == "concat" and (l - 1) in self.agg_layers
        return self.hidden * (self.n_clients if widened else 1)


def init_params(key, cfg: GlasuConfig):
    """Per-client stacked parameters: every leaf has leading dim M."""
    init_layer, _ = BACKBONES[cfg.backbone]
    keys = jax.random.split(key, cfg.n_layers + 2)

    def stack(fn, k):
        return jax.vmap(fn)(jax.random.split(k, cfg.n_clients))

    scale_in = jnp.sqrt(2.0 / cfg.d_in)
    params = {
        "inp": stack(lambda k: {"W": jax.random.normal(k, (cfg.d_in, cfg.hidden)) * scale_in,
                                "b": jnp.zeros((cfg.hidden,))}, keys[0]),
        "layers": [],
        "cls": None,
    }
    for l in range(cfg.n_layers):
        d_in = cfg.layer_in_dim(l)
        kw = {"n_heads": cfg.gat_heads} if cfg.backbone == "gat" else {}
        params["layers"].append(
            stack(lambda k, d=d_in, kw=kw: init_layer(k, d, cfg.hidden, **kw), keys[l + 1]))
    d_cls = cfg.hidden * (cfg.n_clients if cfg.agg == "concat" else 1)
    scale_c = jnp.sqrt(1.0 / d_cls)
    params["cls"] = stack(lambda k: {"W": jax.random.normal(k, (d_cls, cfg.n_classes)) * scale_c,
                                     "b": jnp.zeros((cfg.n_classes,))}, keys[-1])
    return params


# --------------------------------------------------------------------- layers
def _pallas_gcn_layer(p, h, h0, idx, mask):
    """GCN client sub-layer on the fused Pallas graph_agg kernel
    (one-hot gather-matmul + masked mean + MXU matmul in one pallas_call)."""
    from ..kernels import ops as kops
    out = kops.graph_agg(h, idx, mask, p["W"])
    return jax.nn.relu(out + p["b"])


def _pallas_gcnii_layer(p, h, h0, idx, mask, alpha, beta):
    """GCNII client sub-layer fully fused: gather-mean + initial residual +
    identity-map skip + matmul + relu in one pallas_call."""
    from ..kernels import ops as kops
    return kops.gcnii_layer(h, h0, idx, mask, p["W"], p["b"],
                            alpha=alpha, beta=beta)


def _pallas_gat_layer(p, h, h0, idx, mask):
    """GAT client sub-layer fully fused: per-head projection + masked softmax
    attention over the sampled fanout + head mix in one pallas_call."""
    from ..kernels import ops as kops
    return kops.gat_layer(h, idx, mask, p["W"], p["a_src"], p["a_dst"],
                          p["b"])


def _client_layer(cfg: GlasuConfig, l: int):
    """Resolve layer l's sub-layer fn; ``use_pallas=True`` covers all three
    paper backbones (GCN, GCNII, GAT) with fused kernels."""
    _, layer_fn = BACKBONES[cfg.backbone]
    if cfg.backbone == "gcnii":
        beta = cfg.gcnii_beta / (l + 1)   # beta_l = lambda / l decay as in [7]
        if cfg.use_pallas:
            return functools.partial(_pallas_gcnii_layer,
                                     alpha=cfg.gcnii_alpha, beta=beta)
        return functools.partial(layer_fn, alpha=cfg.gcnii_alpha, beta=beta)
    if cfg.backbone == "gcn" and cfg.use_pallas:
        return _pallas_gcn_layer
    if cfg.backbone == "gat" and cfg.use_pallas:
        return _pallas_gat_layer
    return layer_fn


def _aggregate(cfg: GlasuConfig, h_plus, key=None):
    """Server Agg (paper §3.1): parameter-free mean/concat across clients.

    h_plus: (M, n, h). Returns (agg, stale) where
      stale[m] = Extract(H[l+1], H_m^+[l])  — the "all-but-m" buffer (§3.3).
    Optional §3.6 hooks: pairwise-cancelling secure-agg masks and DP noise are
    applied to the *uploads*; the mean is unchanged by SA masks by design.
    """
    m = h_plus.shape[0]
    uploads = h_plus
    if cfg.secure_agg and key is not None:
        # masks and DP noise draw from DISTINCT derived subkeys; sampling
        # with the raw caller key would collide with any other consumer of
        # that key (glint GL002)
        mkey = jax.random.fold_in(key, 0)
        masks = jax.random.normal(mkey, h_plus.shape, h_plus.dtype)
        masks = masks - jnp.mean(masks, axis=0, keepdims=True)  # sum_m mask_m = 0
        uploads = uploads + masks
    if cfg.dp_sigma > 0.0 and key is not None:
        nkey = jax.random.fold_in(key, 1)
        uploads = uploads + cfg.dp_sigma * jax.random.normal(nkey, h_plus.shape, h_plus.dtype)
    if cfg.agg == "mean":
        agg = jnp.mean(uploads, axis=0)                      # (n, h)
        stale = agg[None] - uploads / m                      # Extract: H - H_m^+/M
        return jnp.broadcast_to(agg[None], h_plus.shape), stale
    # concat: (n, M*h); stale keeps other clients' blocks (own block zeroed)
    n, h = h_plus.shape[1], h_plus.shape[2]
    agg = jnp.transpose(uploads, (1, 0, 2)).reshape(n, m * h)
    own_block = jnp.eye(m, dtype=h_plus.dtype)               # (M, M)
    blockmask = jnp.repeat(1.0 - own_block, h, axis=1)       # (M, M*h)
    stale = agg[None] * blockmask[:, None, :]
    return jnp.broadcast_to(agg[None], (m, n, m * h)), stale


def _combine_with_stale(cfg: GlasuConfig, stale_l, h_plus_m, m_index=None,
                        w=None, denom=None):
    """Client-side Agg(H_{-m} (stale), H_m^{+} (fresh)) — Alg 4 line 6.

    ``w``/``denom`` carry the fault-tolerant round's participation weight
    for this client and the weighted-mean denominator; ``None`` (the
    default) is the legacy bit-identical path dividing by M.
    """
    if cfg.agg == "mean":
        if w is None:
            return stale_l + h_plus_m / cfg.n_clients
        return stale_l + w * h_plus_m / denom
    n, h = h_plus_m.shape
    own = jnp.zeros((n, cfg.n_clients, h), h_plus_m.dtype)
    own = own.at[:, m_index, :].set(h_plus_m if w is None else w * h_plus_m)
    return stale_l + own.reshape(n, cfg.n_clients * h)


# ------------------------------------------------------ compressed exchange
def init_comp_state(cfg: GlasuConfig, layer_sizes: Sequence[int],
                    compressor: Optional[Compressor] = None):
    """Error-feedback accumulators for the compressed embedding exchange.

    Returns ``None`` when compression is off (callers take the legacy code
    path), ``{}`` when compression is on without error feedback (stateless
    codecs thread an empty carry), else per aggregation layer one uplink
    accumulator (client-resident, shape ``(M, n_{l+1}, hidden)``) and one
    downlink accumulator (server-resident, ``(n_{l+1}, h_agg)``).
    ``layer_sizes`` is the sampler's static node-set size plan
    (``GlasuSampler.layer_sizes``, length L+1).
    """
    comp = compressor if compressor is not None else \
        compression.make_compressor(cfg.compression)
    if comp is None:
        return None
    if not comp.error_feedback:
        return {}
    down_h = cfg.hidden * (cfg.n_clients if cfg.agg == "concat" else 1)
    state = {}
    for l in cfg.agg_layers:  # glint: disable=GL004 init-time alloc over a static layer set, runs once
        n = layer_sizes[l + 1]
        state[l] = {
            "up": jnp.zeros((cfg.n_clients, n, cfg.hidden), jnp.float32),
            "down": jnp.zeros((n, down_h), jnp.float32)}
    return state


def _payload_msg_bytes(payload, lead_dims: int) -> int:
    """Static wire size of ONE message in a payload whose leaves carry
    ``lead_dims`` leading batch axes (0 = the payload IS one message)."""
    return sum(math.prod(leaf.shape[lead_dims:]) * jnp.dtype(leaf.dtype).itemsize
               for leaf in jax.tree.leaves(payload))


def _compressed_aggregate(cfg: GlasuConfig, comp: Compressor, h_plus, ef_l,
                          key=None, *, gather=None, i0=0, record=None,
                          layer: int = -1, cache_l=None,
                          faults: Optional["RoundFaults"] = None):
    """Server Agg (§3.1) with wire compression on both exchange legs.

    ``h_plus``: ``(m_blk, n, h)`` fresh client uploads — the full client
    stack on the vmapped path, the device-local block under ``shard_map``
    (then ``gather`` stacks payload leaves along the global client axis and
    ``i0`` is the block's global offset). ``ef_l`` is the layer's
    error-feedback entry (``{"up", "down"}``) or ``None``.

    Protocol (what a deployed system would do):
      1. client m adds DP noise (§3.6) and its carried residual, encodes,
         and uploads the wire payload;
      2. the server decodes all uploads, aggregates (`mean`/`concat` on the
         DEQUANTIZED values), adds the downlink residual, encodes, and
         broadcasts the compressed aggregate;
      3. client m decodes the broadcast, subtracts its own dequantized
         upload (Extract — it knows its own wire message exactly) to get
         the stale buffer H_{-m}, and continues forward with
         Agg(H_{-m}, H_m^+) — its exact fresh block plus the compressed
         view of everyone else.

    Composed fault-tolerant mode (``cache_l``/``faults`` given): the server
    keeps a cache of each client's last DELIVERED **decoded** block and
    substitutes it for absent clients, then aggregates with the round's
    participation weights (the same weighted mean as ``_fault_agg_math``,
    on dequantized values). Error feedback is slot-keyed per client and
    updated ONLY for clients whose upload was delivered this round — an
    absent client's residual is frozen, not decayed: it never transmitted,
    so there is nothing new to account for. ``cache_l`` is the full
    ``(M, n, h)`` decoded server view (replicated under ``shard_map``;
    every device recomputes it from the gathered payload).

    Returns ``(h, stale, new_ef_l, new_cache_l, denom)`` with ``h``/
    ``stale`` of shape ``(m_blk, n, h_agg)``; ``new_ef_l`` is ``None`` iff
    ``ef_l`` was, and ``new_cache_l``/``denom`` are ``None`` outside the
    composed mode. Decode is elementwise per row, so slicing the decoded
    global stack equals decoding the local payload — the local EF update
    relies on it.
    """
    m = cfg.n_clients
    m_blk = h_plus.shape[0]
    uploads = h_plus
    if cfg.dp_sigma > 0.0 and key is not None:
        # the global (M, n, h) draw is generated everywhere and sliced so
        # the sharded path adds bit-identical noise to the vmapped one
        nkey = jax.random.fold_in(key, 1)
        noise = cfg.dp_sigma * jax.random.normal(
            nkey, (m,) + h_plus.shape[1:], h_plus.dtype)
        if m_blk != m:
            noise = jax.lax.dynamic_slice_in_dim(noise, i0, m_blk, axis=0)
        uploads = uploads + noise
    ef_up = ef_l["up"] if ef_l is not None else None
    up_in = uploads if ef_up is None else uploads + ef_up
    payload = comp.encode(up_in)                        # client -> server
    wire = payload if gather is None else jax.tree.map(gather, payload)
    up_hat = comp.decode(wire, h_plus.shape[-1])        # (M, n, h) at server
    up_hat_blk = up_hat if m_blk == m else \
        jax.lax.dynamic_slice_in_dim(up_hat, i0, m_blk, axis=0)
    n, h = up_hat.shape[1], up_hat.shape[2]

    if faults is None:
        # the carried residual is decayed: accumulators are slot-keyed
        # while the sampled node set changes every round (not true
        # per-node EF) — see CompressionConfig.ef_decay for why undecayed
        # carry destabilizes
        new_ef_up = None if ef_up is None else \
            comp.ef_decay * (up_in - up_hat_blk)
        new_cache_l = denom = None
        eff_blk = up_hat_blk
        w_blk = None
        if cfg.agg == "mean":
            agg = jnp.mean(up_hat, axis=0)              # (n, h)
        else:
            agg = jnp.transpose(up_hat, (1, 0, 2)).reshape(n, m * h)
    else:
        p_blk = faults.present if m_blk == m else \
            jax.lax.dynamic_slice_in_dim(faults.present, i0, m_blk, axis=0)
        # absent clients never transmitted: their residual is frozen
        new_ef_up = None if ef_up is None else jnp.where(
            p_blk[:, None, None] > 0,
            comp.ef_decay * (up_in - up_hat_blk), ef_up)
        # server view: decoded fresh block where delivered, cache elsewhere
        eff = jnp.where(faults.present[:, None, None] > 0, up_hat, cache_l)
        new_cache_l = eff
        eff_blk = eff if m_blk == m else \
            jax.lax.dynamic_slice_in_dim(eff, i0, m_blk, axis=0)
        w = faults.weight[:, None, None].astype(up_hat.dtype)
        w_blk = faults.weight if m_blk == m else \
            jax.lax.dynamic_slice_in_dim(faults.weight, i0, m_blk, axis=0)
        w_blk = w_blk.astype(up_hat.dtype)
        if cfg.agg == "mean":
            denom = jnp.maximum(jnp.sum(faults.weight),
                                1.0).astype(up_hat.dtype)
            agg = jnp.sum(w * eff, axis=0) / denom      # (n, h)
        else:
            denom = jnp.asarray(1.0, up_hat.dtype)
            agg = jnp.transpose(w * eff, (1, 0, 2)).reshape(n, m * h)

    ef_down = ef_l["down"] if ef_l is not None else None
    down_payload, down_hat, new_ef_down = compression.roundtrip_with_ef(
        comp, agg, ef_down)                             # server -> clients

    if record is not None:
        record(CollectiveRecord(
            layer=layer, n_clients=m, n_rows=n, width_up=h,
            width_down=agg.shape[-1],
            itemsize=jnp.dtype(h_plus.dtype).itemsize,
            up_bytes=_payload_msg_bytes(payload, 1),
            down_bytes=_payload_msg_bytes(down_payload, 0)))

    if cfg.agg == "mean":
        if faults is None:
            stale = down_hat[None] - eff_blk / m        # Extract per client
        else:
            stale = down_hat[None] - w_blk[:, None, None] * eff_blk / denom
    else:
        own_block = jnp.eye(m, dtype=h_plus.dtype)
        blockmask = jnp.repeat(1.0 - own_block, h, axis=1)   # (M, M*h)
        if m_blk != m:
            blockmask = jax.lax.dynamic_slice_in_dim(blockmask, i0, m_blk,
                                                     axis=0)
        stale = down_hat[None] * blockmask[:, None, :]
    g_idx = i0 + jnp.arange(m_blk)
    if faults is None:
        h_out = jax.vmap(lambda s, hp, g: _combine_with_stale(cfg, s, hp, g))(
            stale, h_plus, g_idx)
    else:
        h_out = jax.vmap(
            lambda s, hp, g, wm: _combine_with_stale(cfg, s, hp, g, w=wm,
                                                     denom=denom))(
            stale, h_plus, g_idx, w_blk)
    new_ef_l = None if ef_l is None else {"up": new_ef_up,
                                          "down": new_ef_down}
    return h_out, stale, new_ef_l, new_cache_l, denom


# ------------------------------------------------- fault-tolerant exchange
class RoundFaults(NamedTuple):
    """Device-side view of one round's fault draw (``fed.faults.RoundPlan``).

    Two shape-static ``(M,)`` float32 vectors — the jitted/scanned round
    body never changes shape with the draw. Under ``lax.scan`` the leaves
    carry a leading round axis K and ride in the scan's xs.
    """
    present: Any      # 1.0 = the client's upload arrived before the deadline
    weight: Any       # 1.0 = fresh-or-valid-cache block enters the aggregate


def init_fault_state(cfg: GlasuConfig, layer_sizes: Sequence[int]):
    """Stale-embedding cache for the fault-tolerant exchange.

    ``None`` when fault tolerance is off; else per aggregation layer the
    last *delivered* upload stack, slot-keyed ``(M, n_{l+1}, hidden)``
    exactly like the PR-5 error-feedback accumulators (``layer_sizes`` is
    the sampler's static node-set plan, so the carry is shape-static and
    scan/donation-friendly). Starts at zeros; a never-delivered client's
    slot is excluded from the aggregate by its zero weight, never read.
    """
    if not cfg.fault_tolerant:
        return None
    return {l: jnp.zeros((cfg.n_clients, layer_sizes[l + 1], cfg.hidden),
                         jnp.float32)
            for l in cfg.agg_layers}  # glint: disable=GL004 init-time alloc over a static layer set, runs once


def _fault_agg_math(cfg: GlasuConfig, uploads, weight):
    """Weighted server Agg over effective (fresh-or-cached) uploads.

    ``uploads``: the full (M, n, h) effective stack; ``weight``: (M,)
    participation weights. Returns ``(h, stale, denom)`` with the same
    shapes/semantics as ``_aggregate``. At weight == 1 everywhere this is
    the legacy mean up to summation order (``sum(w*u)/M`` vs ``mean``),
    which is what the degraded-mode conformance rows pin down.
    """
    m = uploads.shape[0]
    w = weight[:, None, None].astype(uploads.dtype)
    if cfg.agg == "mean":
        # an all-zero weight row (every block aged out mid-crash) divides
        # by 1 instead of 0; the aggregate is zeros and weights exclude it
        denom = jnp.maximum(jnp.sum(weight), 1.0).astype(uploads.dtype)
        agg = jnp.sum(w * uploads, axis=0) / denom          # (n, h)
        stale = agg[None] - w * uploads / denom
        return jnp.broadcast_to(agg[None], uploads.shape), stale, denom
    # concat: zero-weight blocks are zeroed in place (documented: no
    # renormalization across the concatenated width)
    n, h = uploads.shape[1], uploads.shape[2]
    denom = jnp.asarray(1.0, uploads.dtype)
    agg = jnp.transpose(w * uploads, (1, 0, 2)).reshape(n, m * h)
    own_block = jnp.eye(m, dtype=uploads.dtype)
    blockmask = jnp.repeat(1.0 - own_block, h, axis=1)       # (M, M*h)
    stale = agg[None] * blockmask[:, None, :]
    return jnp.broadcast_to(agg[None], (m, n, m * h)), stale, denom


# -------------------------------------------------------- execution policy
class ExecPolicy(NamedTuple):
    """How one GLASU round executes — the three orthogonal axes the paper's
    round is invariant to, captured once so a single round body serves
    every builder:

      * aggregation transport: vmapped client stack (``axis_name=None``) vs
        per-device client blocks gathered with ``all_gather`` under
        ``shard_map`` (``axis_name``/``m_loc`` set);
      * exchange codec: identity (``compressor=None``) vs the PR-5 wire
        compressor at the Agg boundary;
      * participation: all-present vs deadline-round ``RoundPlan`` masks
        with the stale-embedding cache (``fault_tolerant``).

    ``record`` is the trace-time :class:`CollectiveRecord` hook of the
    byte meter. The policy is static Python state closed over at build
    time — it never crosses a jit boundary.
    """
    axis_name: Optional[str] = None   # None = vmapped; else shard_map axis
    m_loc: int = 0                    # clients per device (sharded only)
    compressor: Optional[Compressor] = None
    fault_tolerant: bool = False
    record: Any = None

    @property
    def sharded(self) -> bool:
        return self.axis_name is not None


def _policy(cfg: GlasuConfig, axis_name: Optional[str] = None,
            m_loc: int = 0, record=None) -> ExecPolicy:
    """Resolve ``cfg``'s codec/participation axes into an ExecPolicy."""
    return ExecPolicy(axis_name=axis_name, m_loc=m_loc,
                      compressor=compression.make_compressor(cfg.compression),
                      fault_tolerant=cfg.fault_tolerant, record=record)


class RoundCarry(NamedTuple):
    """The state a round threads besides params and optimizer state: one
    member per policy axis that keeps state across rounds. An inactive
    member is ``None``, an empty pytree — no argument leaf, no result leaf,
    nothing donated — so every policy shares one round signature.

      * ``comp_state``: the compressed exchange's error-feedback
        accumulators (``init_comp_state``; ``{}`` for a stateless codec);
      * ``fault_state``: the fault-tolerant exchange's stale-embedding
        cache (``init_fault_state``).
    """
    comp_state: Any = None
    fault_state: Any = None


def _record_dense(record, l: int, uploads, h_full):
    """Byte-meter record for an UNCOMPRESSED aggregation collective: wire
    size is the dense (n, h) block per message on both legs."""
    isz = jnp.dtype(uploads.dtype).itemsize
    record(CollectiveRecord(
        layer=l, n_clients=uploads.shape[0], n_rows=uploads.shape[1],
        width_up=uploads.shape[2], width_down=h_full.shape[-1],
        itemsize=isz,
        up_bytes=uploads.shape[1] * uploads.shape[2] * isz,
        down_bytes=uploads.shape[1] * h_full.shape[-1] * isz))


def _slice_block(pol: ExecPolicy, x, i0):
    """Device-local client block of a global (M, ...) stack; identity on
    the vmapped path where the block IS the full stack."""
    if not pol.sharded:
        return x
    return jax.lax.dynamic_slice_in_dim(x, i0, pol.m_loc, axis=0)


def _joint_inference_engine(params, batch: SampledBatch, cfg: GlasuConfig,
                            pol: ExecPolicy, key=None, comp_state=None,
                            fault_state=None,
                            faults: Optional[RoundFaults] = None):
    """Alg 3 (JointInference with Extract) under any :class:`ExecPolicy`.

    THE round-forward body — every public entry (``joint_inference``,
    ``fault_joint_inference``, ``sharded_joint_inference``) and every
    builder instantiates this one function; the policy only selects the
    transport (local stack vs gather), the codec (identity vs compressed
    exchange) and the participation rule (all-present vs masked with the
    stale-embedding cache).

    ``params``/``batch`` leaves carry the full client stack on the vmapped
    path and the device-local block under ``shard_map``; ``key``,
    ``faults`` and (with compression) the fault cache are replicated.

    Returns ``(logits, stale, new_comp_state, new_fault_state, denom)``;
    the two carries are ``{}`` when their axis is off, ``denom`` is the
    weighted-mean denominator of the fault aggregation (dtype-cast to the
    uploads exactly once, in ``_fault_agg_math`` /
    ``_compressed_aggregate`` — the vmapped/sharded drift this engine
    retired) and M when faults are off.
    """
    h = jax.vmap(lambda p, x: x @ p["W"] + p["b"])(params["inp"],
                                                   batch.feats)
    h0 = h
    stale: Dict[int, Any] = {}
    new_comp: Dict[int, Any] = {}
    new_cache: Dict[int, Any] = {}
    denom = jnp.asarray(cfg.n_clients, jnp.float32)
    i0 = jax.lax.axis_index(pol.axis_name) * pol.m_loc if pol.sharded else 0
    gather = (lambda x: _gather_clients(x, pol.axis_name)) if pol.sharded \
        else None
    for l in range(cfg.n_layers):  # glint: disable=GL004 static L-layer unroll; per-layer params are heterogeneous (widths change at agg boundaries)
        layer = _client_layer(cfg, l)
        h_plus = jax.vmap(layer)(params["layers"][l], h, h0,
                                 batch.gather_idx[l], batch.gather_mask[l])
        h0 = jax.vmap(lambda a, i: a[i])(h0, batch.self_pos[l])
        if l not in cfg.agg_layers:
            h = h_plus
            continue
        # fault rounds never consume the key: the §3.6 privacy hooks are
        # config-excluded with faults and the legacy fault engines never
        # folded it (trace identity for the golden rows)
        subkey = jax.random.fold_in(key, l) \
            if key is not None and not pol.fault_tolerant else None
        if pol.compressor is not None:
            ef_l = comp_state.get(l) if comp_state else None
            cache_l = fault_state[l] if pol.fault_tolerant else None
            h, stale[l], new_ef, cache, d = _compressed_aggregate(
                cfg, pol.compressor, h_plus, ef_l, subkey, gather=gather,
                i0=i0, record=pol.record, layer=l, cache_l=cache_l,
                faults=faults)
            if new_ef is not None:
                new_comp[l] = new_ef
            if pol.fault_tolerant:
                new_cache[l] = cache
                denom = d
        elif pol.fault_tolerant:
            # fresh where delivered, staleness-bounded cache elsewhere
            p_blk = _slice_block(pol, faults.present, i0)
            eff_blk = jnp.where(p_blk[:, None, None] > 0, h_plus,
                                fault_state[l])
            new_cache[l] = eff_blk
            uploads = eff_blk if gather is None else gather(eff_blk)
            h_full, stale_full, denom = _fault_agg_math(cfg, uploads,
                                                        faults.weight)
            if pol.record is not None:
                _record_dense(pol.record, l, uploads, h_full)
            h = _slice_block(pol, h_full, i0)
            stale[l] = _slice_block(pol, stale_full, i0)
        else:
            uploads = h_plus if gather is None else gather(h_plus)
            h_full, stale_full = _aggregate(cfg, uploads, subkey)
            if pol.record is not None:
                _record_dense(pol.record, l, uploads, h_full)
            h = _slice_block(pol, h_full, i0)
            stale[l] = _slice_block(pol, stale_full, i0)
    logits = jax.vmap(lambda p, x: x @ p["W"] + p["b"])(params["cls"], h)
    return logits, stale, new_comp, new_cache, denom


def fault_joint_inference(params, batch: SampledBatch, cfg: GlasuConfig,
                          fault_state, faults: RoundFaults):
    """Alg 3 under deadline-based partial participation.

    The server aggregates whatever uploads arrived before the deadline
    (``faults.present``) and substitutes each absent client's cached
    embedding; blocks whose cache aged past the staleness bound carry
    weight 0 (``faults.weight``) and are excluded. Returns
    ``(logits, stale, new_fault_state, denom)`` — the refreshed cache is
    threaded through the round carry next to the optimizer state.
    """
    logits, stale, _, new_cache, denom = _joint_inference_engine(
        params, batch, cfg, ExecPolicy(fault_tolerant=True),
        fault_state=fault_state, faults=faults)
    return logits, stale, new_cache, denom


# ------------------------------------------------------------------- forward
def _client_trunk(cfg: GlasuConfig, params_m, feats_m, batch: SampledBatch, m_index,
                  stale: Optional[Dict[int, Any]] = None,
                  return_hidden: bool = False, global_index=None,
                  fault_w=None, fault_denom=None):
    """One client's pass through all layers, aggregating via stale buffers.

    Used by LocalUpdate (Alg 4): server aggregation is replaced by the stored
    H_{-m} plus the client's fresh representation.

    ``m_index`` indexes the client-stacked batch arrays; ``global_index``
    (default: ``m_index``) is the client's position in the GLOBAL client
    order, which concat aggregation needs for its own-block placement. They
    differ only on the sharded backend, where each device holds a local
    block of the client axis and batch arrays are local blocks too.

    ``fault_w``/``fault_denom`` (fault-tolerant rounds only) weight the
    client's fresh block in the combine exactly as the server weighted it
    in the aggregate — a zero-weight client trains against the global
    aggregate with its own block excluded.
    """
    h = feats_m @ params_m["inp"]["W"] + params_m["inp"]["b"]
    h0 = h
    g_index = m_index if global_index is None else global_index
    for l in range(cfg.n_layers):
        layer = _client_layer(cfg, l)
        idx, mask = batch.gather_idx[l][m_index], batch.gather_mask[l][m_index]
        h_plus = layer(params_m["layers"][l], h, h0, idx, mask)
        h0 = h0[batch.self_pos[l][m_index]]
        if l in cfg.agg_layers:
            h = _combine_with_stale(cfg, stale[l], h_plus, g_index,
                                    w=fault_w, denom=fault_denom)
        else:
            h = h_plus
    if return_hidden:
        return h
    logits = h @ params_m["cls"]["W"] + params_m["cls"]["b"]
    return logits


def joint_inference(params, batch: SampledBatch, cfg: GlasuConfig, key=None,
                    compressor: Optional[Compressor] = None, comp_state=None):
    """Alg 3: full split-model forward with server aggregation at l in I.

    Returns (logits (M, S, C), stale {l: (M, n_{l+1}, h_agg)}). With a
    ``compressor``, the embedding exchange at every aggregation layer runs
    through the wire codec (see ``_compressed_aggregate``) and a third
    value — the updated error-feedback state — is returned. Callers that
    probe model math (``Backend.joint_logits``) pass no compressor and get
    the exact uncompressed forward.
    """
    logits, stale, new_state, _, _ = _joint_inference_engine(
        params, batch, cfg, ExecPolicy(compressor=compressor), key=key,
        comp_state=comp_state)
    if compressor is None:
        return logits, stale
    return logits, stale, new_state


def client_loss(params_m, feats_m, batch: SampledBatch, stale_m, labels,
                cfg: GlasuConfig, m_index, global_index=None,
                fault_w=None, fault_denom=None):
    """Client m's local objective (Alg 4 line 11) with stale buffers fixed."""
    logits = _client_trunk(cfg, params_m, feats_m, batch, m_index, stale_m,
                           global_index=global_index, fault_w=fault_w,
                           fault_denom=fault_denom)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]
    return jnp.mean(nll)


def label_owner_grad(params, batch: SampledBatch, stale, cfg: GlasuConfig):
    """Alg 6 (modified JointInference): the label owner computes
    grad_{H[L]} of ITS loss; the server broadcasts it to all clients."""
    m0 = cfg.labels_at_client

    def owner_loss(h):
        pm = jax.tree.map(lambda v: v[m0], params)
        logits = h @ pm["cls"]["W"] + pm["cls"]["b"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, batch.labels[:, None], axis=1)[:, 0]
        return jnp.mean(nll)

    pm = jax.tree.map(lambda v: v[m0], params)
    sm = {l: v[m0] for l, v in stale.items()}
    h_l = _client_trunk(cfg, pm, batch.feats[m0], batch, m0, sm,
                        return_hidden=True)
    return jax.grad(owner_loss)(h_l)


def local_update_steps(params, opt_state, batch: SampledBatch, stale,
                       cfg: GlasuConfig, optimizer: opt_lib.Optimizer,
                       g_hl=None, fault_w=None, fault_denom=None,
                       axis_name: Optional[str] = None, m_loc: int = 0):
    """Q iterations of Alg 4 under ``lax.scan`` (same mini-batch, stale H_{-m}).

    With ``labels_at_client`` set (Appendix B.2, Alg 7): only the owner
    evaluates the real loss; every other client trains on the surrogate
    <g_HL, H_m[L]> whose gradient equals the chain-rule product in eq. (3).

    On a fault-tolerant round ``fault_w`` is the participation-weight
    vector and ``fault_denom`` the weighted-mean denominator: each client
    combines its fresh block at the weight the server aggregated it with
    (Alg 4's stale-others + fresh-own structure, weighted).

    With ``axis_name``/``m_loc`` set (shard_map), every stacked input —
    params, opt state, batch, stale buffers, ``fault_w`` — holds the LOCAL
    client block. The update itself is device-local (the stale buffers
    already hold H_{-m}, so no communication — exactly the paper's
    client-side phase); only the reported mean loss crosses devices (an
    all_gather of Q scalars per round; diagnostics, not algorithm traffic,
    hence unmetered). Clients pass their GLOBAL index to the combine,
    which concat aggregation needs for own-block placement.
    """
    labels = batch.labels
    sharded = axis_name is not None
    m_ids = jnp.arange(m_loc if sharded else cfg.n_clients)
    m_global = jax.lax.axis_index(axis_name) * m_loc + m_ids if sharded \
        else None

    def one_step(carry, _):
        p, s = carry

        def per_client(params_m, feats_m, stale_m, m_index, *extra):
            extra = list(extra)
            g_index = extra.pop(0) if sharded else None
            w_m = extra.pop(0) if fault_w is not None else None
            if cfg.labels_at_client is None:
                return client_loss(params_m, feats_m, batch, stale_m, labels,
                                   cfg, m_index, global_index=g_index,
                                   fault_w=w_m, fault_denom=fault_denom)
            own = client_loss(params_m, feats_m, batch, stale_m, labels,
                              cfg, m_index)
            h_l = _client_trunk(cfg, params_m, feats_m, batch, m_index,
                                stale_m, return_hidden=True)
            surrogate = jnp.sum(jax.lax.stop_gradient(g_hl) * h_l)
            is_owner = m_index == cfg.labels_at_client
            # owner optimizes its real loss (incl. classifier); others the
            # broadcast-gradient surrogate (they own no classifier grads)
            return jnp.where(is_owner, own, surrogate)

        args = [p, batch.feats, stale, m_ids]
        if sharded:
            args.append(m_global)
        if fault_w is not None:
            args.append(fault_w)
        loss, grads = jax.vmap(jax.value_and_grad(per_client),
                               in_axes=(0,) * len(args))(*args)
        updates, s = optimizer.update(grads, s, p)
        p = opt_lib.apply_updates(p, updates)
        # sharded: gather to the global (M,) loss row so the reported mean
        # is the same reduction as the vmapped path's mean over all clients
        round_loss = jnp.mean(_gather_clients(loss, axis_name)) if sharded \
            else jnp.mean(loss)
        return (p, s), round_loss

    (params, opt_state), losses = jax.lax.scan(
        one_step, (params, opt_state), None, length=cfg.n_local_steps)
    return params, opt_state, losses


def _round_body(cfg: GlasuConfig, optimizer: opt_lib.Optimizer,
                pol: ExecPolicy, params, opt_state, batch: SampledBatch,
                key, carry: RoundCarry,
                faults: Optional[RoundFaults] = None):
    """One GLASU round (Alg 1 body): JointInference + Q LocalUpdates.

    THE round body — the only one. Both builders (vmapped / sharded, any
    carry combination) instantiate this function with their
    :class:`ExecPolicy`; there is no second copy to hand-sync. Returns
    ``(params, opt_state, losses, carry)``; the carry's inactive members
    pass through as given (``None``).
    """
    if pol.sharded and cfg.labels_at_client is not None:
        raise NotImplementedError(
            "labels_at_client requires indexing the global client axis "
            "(Alg 6 owner gradient); use the vmapped backend")
    fault_w = fault_denom = None
    if cfg.agg_layers:
        _, stale, new_comp, new_cache, denom = _joint_inference_engine(
            params, batch, cfg, pol, key=key, comp_state=carry.comp_state,
            fault_state=carry.fault_state, faults=faults)
        if pol.compressor is not None:
            carry = carry._replace(comp_state=new_comp)
        if pol.fault_tolerant:
            carry = carry._replace(fault_state=new_cache)
            i0 = jax.lax.axis_index(pol.axis_name) * pol.m_loc \
                if pol.sharded else 0
            fault_w = _slice_block(pol, faults.weight, i0)
            fault_denom = denom
    else:
        # standalone: no communication; zero stale buffers never used
        stale = {}
    g_hl = None
    if cfg.labels_at_client is not None:
        g_hl = label_owner_grad(params, batch, stale, cfg)
    params, opt_state, losses = local_update_steps(
        params, opt_state, batch, stale, cfg, optimizer, g_hl=g_hl,
        fault_w=fault_w, fault_denom=fault_denom,
        axis_name=pol.axis_name, m_loc=pol.m_loc)
    return params, opt_state, losses, carry


def _round_scan(cfg: GlasuConfig, optimizer: opt_lib.Optimizer,
                pol: ExecPolicy):
    """K rounds of ``_round_body`` under ``lax.scan``, in the round
    signature both builders wrap (jit / shard_map): ``(params, opt_state,
    batches, keys, carry, faults) -> (params, opt_state, losses, carry)``.
    Params, optimizer state and the :class:`RoundCarry` ride in the scan
    carry; the round-stacked batches, keys and (K, M) fault masks
    (``None`` off the fault path) ride in the xs; losses come back (K, Q).
    """
    def scan(params, opt_state, batches, keys, carry, faults):
        def body(state, xs):
            p, s, c = state
            batch, key, f = xs
            p, s, losses, c = _round_body(cfg, optimizer, pol, p, s, batch,
                                          key, c, f)
            return (p, s, c), losses

        (params, opt_state, carry), losses = jax.lax.scan(
            body, (params, opt_state, carry), (batches, keys, faults))
        return params, opt_state, losses, carry

    return scan


def _step(scan):
    """``scan`` under the public step signature, where ``carry`` and
    ``faults`` default to ``None``: a policy with neither takes the leading
    four arguments only."""
    def step_fn(params, opt_state, batches, keys, carry=None, faults=None):
        return scan(params, opt_state, batches, keys,
                    RoundCarry() if carry is None else carry, faults)

    return step_fn


# the step's own state, updated in place: params, opt_state and carry
_DONATED = (0, 1, 4)


def _checked(step_fn, rounds_per_step: int, what: str):
    """Reject a batch stack whose leading round axis disagrees with the
    static ``rounds_per_step`` hint loudly instead of silently scanning a
    different number of rounds. ``_jit`` exposes cache introspection."""
    def checked(*args, **kwargs):
        k = args[2].labels.shape[0]
        if k != rounds_per_step:
            raise ValueError(
                f"{what} built for rounds_per_step={rounds_per_step} "
                f"got a {k}-round batch stack")
        return step_fn(*args, **kwargs)

    checked._jit = step_fn                       # expose cache introspection
    return checked


def make_multi_round_fn(cfg: GlasuConfig, optimizer: opt_lib.Optimizer,
                        rounds_per_step: Optional[int] = None):
    """K GLASU rounds in one dispatch: ``lax.scan`` over round-stacked batches.

    ``step_fn(params, opt_state, batches, keys, carry=None, faults=None)
    -> (params, opt_state, losses, carry)``. ``batches`` is a
    ``SampledBatch`` whose every leaf carries a leading round axis K (see
    ``graph.prefetch.stack_rounds``) and ``keys`` is the matching (K, 2)
    stack of per-round PRNG keys. The scan compiles ONE round body
    regardless of K and replays it K times device-side — one host dispatch
    per K rounds instead of per round, which is where the per-round
    Python/runtime overhead of the Trainer loop goes. A single round is the
    step at K = 1.

    ``carry`` is the :class:`RoundCarry` of the policy's active axes:
    ``cfg.compression`` fills ``comp_state``, ``cfg.fault_tolerant`` fills
    ``fault_state`` and takes ``faults``, the round-stacked ``RoundFaults``
    of (K, M) leaves. Off those axes both stay ``None``.

    params, opt state and the carry are donated: the update is in-place at
    the XLA level, halving parameter-buffer HBM traffic per step. Callers
    must treat the passed-in trees as consumed (the Trainer immediately
    rebinds them).

    losses have shape (K, Q) — per-round rows, so hook cadence semantics
    (loss reporting, comm metering) are preserved exactly. K is read off
    the leading axis at trace time; distinct K values retrace (the Trainer
    cuts its schedule so a run uses one K, plus at most a tail/cadence
    remainder).

    ``rounds_per_step`` is an optional static hint: when given, a batch
    whose leading axis disagrees is rejected loudly instead of silently
    scanning a different number of rounds.
    """
    step_fn = jax.jit(_step(_round_scan(cfg, optimizer, _policy(cfg))),
                      donate_argnums=_DONATED)
    if rounds_per_step is None:
        return step_fn
    return _checked(step_fn, rounds_per_step, "multi-round step")


# ------------------------------------------------------- sharded execution
# Device-sharded client parallelism: each mesh device along the 'clients'
# axis holds an even block of m_loc = M / n_devices clients (params, opt
# state, batch slices) and runs `_client_trunk` device-local under
# ``shard_map``. The ONLY cross-device operation is server aggregation: the
# clients' uploads are ``all_gather``ed along the axis and the identical
# parameter-free Agg of §3.1 (`_aggregate`, including the §3.6 privacy
# hooks — the PRNG key is replicated, so masks/noise match the vmapped path
# bit-for-bit) runs on the gathered stack, exactly where the paper places
# communication. Each collective is recorded at trace time so the byte
# meter reports what the compiled program actually moves, priced under the
# paper's star topology (Fig 1: every client uploads its block, the server
# returns the aggregate).

class CollectiveRecord(NamedTuple):
    """One cross-client collective, recorded while tracing the round body.

    ``up_bytes``/``down_bytes`` are the WIRE sizes of one client upload and
    one server broadcast — equal to ``n_rows * width * itemsize`` for the
    uncompressed exchange, and read off the actual encoded payload leaves
    when a compressor runs (the ``all_gather`` then moves the compressed
    representation, so these are what the compiled collective ships).
    """
    layer: int          # aggregation layer index l
    n_clients: int      # M (global)
    n_rows: int         # n_{l+1} rows per upload
    width_up: int       # per-client upload width (hidden)
    width_down: int     # aggregate width broadcast back (hidden | M*hidden)
    itemsize: int       # logical (pre-compression) payload dtype bytes
    up_bytes: int       # wire bytes of ONE client upload message
    down_bytes: int     # wire bytes of ONE broadcast message

    def star_bytes(self) -> int:
        """Bytes under the paper's client<->server star topology (§3.2):
        M uploads + M downloads at their wire sizes."""
        return self.n_clients * (self.up_bytes + self.down_bytes)


def _gather_clients(x, axis_name: str):
    return jax.lax.all_gather(x, axis_name, axis=0, tiled=True)


def sharded_joint_inference(params, batch: SampledBatch, cfg: GlasuConfig,
                            key=None, *, axis_name: str, m_loc: int):
    """Alg 3 under shard_map: per-device client blocks, collective Agg.

    All array leaves of ``params``/``batch`` carry the LOCAL client block
    (leading dim m_loc); ``batch.labels`` and ``key`` are replicated. At
    every aggregation layer the local uploads are gathered to the full
    (M, n, h) stack and `_aggregate` runs verbatim on it — the same op on
    the same values as the vmapped path — then the device keeps its local
    slice of the broadcast aggregate and the Extract (stale) buffers.

    Returns (local logits (m_loc, S, C), stale {l: (m_loc, n_{l+1}, h_agg)}).
    The compressed and fault-tolerant exchanges run in the same engine
    from the round body: with a compressor the ``all_gather`` moves the
    encoded wire payload, and with faults each device substitutes cached
    blocks for absent clients BEFORE the gather (the mesh collective still
    ships M blocks; the wire meter prices only delivered uploads — see
    ``docs/FAULTS.md``).
    """
    pol = ExecPolicy(axis_name=axis_name, m_loc=m_loc)
    logits, stale, _, _, _ = _joint_inference_engine(params, batch, cfg, pol,
                                                     key=key)
    return logits, stale


def _client_axis_check(cfg: GlasuConfig, mesh, axis: str) -> int:
    d = mesh.shape[axis]
    if cfg.n_clients % d:
        raise ValueError(
            f"mesh axis {axis!r} has {d} devices, which does not divide "
            f"n_clients={cfg.n_clients}; build the mesh with "
            "launch.mesh.make_client_mesh (largest dividing axis) or pass "
            "one whose size divides the client count")
    return cfg.n_clients // d


def _sharded_specs(cfg: GlasuConfig, optimizer: opt_lib.Optimizer,
                   axis: str, round_stacked: bool = False):
    """(params, opt_state, batch) shard_map spec trees for the round body.

    These are the EXACT specs of the client-stacked layout (leading client
    dim on the ``clients`` axis); divisibility is enforced by
    `_client_axis_check`, unlike the guarded placement rules in
    launch.sharding which fall back to replication.
    """
    from jax.sharding import PartitionSpec as P

    cspec = P(*((None, axis) if round_stacked else (axis,)))
    params_abs = jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    pspecs = jax.tree.map(lambda _: P(axis), params_abs)
    opt_abs = jax.eval_shape(optimizer.init, params_abs)
    if isinstance(opt_abs, opt_lib.AdamState):
        ospecs = opt_lib.AdamState(P(), pspecs, pspecs)
    elif isinstance(opt_abs, opt_lib.SGDState):
        ospecs = opt_lib.SGDState(
            P(), pspecs if opt_abs.momentum is not None else None)
    else:
        raise ValueError(
            f"sharded GLASU supports sgd/momentum/adam/adamw states, got "
            f"{type(opt_abs).__name__}: factored second moments (adafactor) "
            "reduce across the client-stacked dim and would mix clients")
    per = tuple(cspec for _ in range(cfg.n_layers))
    bspecs = SampledBatch(feats=cspec, gather_idx=per, gather_mask=per,
                          row_valid=per, labels=P(), self_pos=per)
    return pspecs, ospecs, bspecs


def _comp_state_specs(cfg: GlasuConfig, comp: Optional[Compressor],
                      axis: str):
    """shard_map specs for the error-feedback carry: uplink accumulators
    are client-stacked (sharded over ``axis``), the downlink accumulator is
    server state (replicated). Shaped as ``init_comp_state``: ``None``
    without compression, ``{}`` for stateless codecs."""
    from jax.sharding import PartitionSpec as P

    if comp is None:
        return None
    if not comp.error_feedback:
        return {}
    return {l: {"up": P(axis), "down": P()} for l in cfg.agg_layers}


def _fault_state_specs(cfg: GlasuConfig, axis: str,
                       replicated: bool = False):
    """shard_map specs for the stale-embedding cache carry.

    Plain fault tolerance: each device holds its LOCAL client block of
    every per-layer cache stack (the same layout as the uplink
    error-feedback accumulators). Composed with compression
    (``replicated=True``): the cache holds the server's DECODED view,
    recomputed identically on every device from the gathered wire payload
    — replicated, not client-sharded.
    """
    from jax.sharding import PartitionSpec as P

    return {l: P() if replicated else P(axis) for l in cfg.agg_layers}


def _round_specs(cfg: GlasuConfig, optimizer: opt_lib.Optimizer,
                 pol: ExecPolicy, axis: str):
    """(in_specs, out_specs) for shard_mapping the round signature of
    ``_round_scan``. The carry's specs follow its members: an inactive
    member is ``None`` and has no spec. The PRNG keys, the (K, M) fault
    masks and the loss rows are replicated."""
    from jax.sharding import PartitionSpec as P

    pspecs, ospecs, bspecs = _sharded_specs(cfg, optimizer, axis,
                                            round_stacked=True)
    carry = RoundCarry(
        comp_state=_comp_state_specs(cfg, pol.compressor, axis),
        fault_state=_fault_state_specs(
            cfg, axis, replicated=pol.compressor is not None)
        if pol.fault_tolerant else None)
    return ((pspecs, ospecs, bspecs, P(), carry, P()),
            (pspecs, ospecs, P(), carry))


def make_sharded_multi_round_fn(cfg: GlasuConfig,
                                optimizer: opt_lib.Optimizer, mesh,
                                axis: str = "clients",
                                rounds_per_step: Optional[int] = None,
                                record=None, jit: bool = True):
    """K rounds with clients sharded over ``mesh``'s ``axis``:
    ``lax.scan`` INSIDE the shard_map, so one collective program advances
    all K rounds — the same step signature, donation, carry and
    round-stacked batch contract as ``make_multi_round_fn`` (the (K, M)
    fault-mask stacks ride the scan xs, replicated across devices).

    ``record`` (see ``CollectiveRecord``) observes the aggregation
    collectives at trace time, once per collective: the scan traces its
    round body once. ``jit=False`` returns the bare step, which is what the
    byte meter abstractly evaluates at bind on a one-round shell."""
    m_loc = _client_axis_check(cfg, mesh, axis)
    pol = _policy(cfg, axis_name=axis, m_loc=m_loc, record=record)
    in_specs, out_specs = _round_specs(cfg, optimizer, pol, axis)
    step_fn = _step(jax.shard_map(_round_scan(cfg, optimizer, pol),
                                  mesh=mesh, in_specs=in_specs,
                                  out_specs=out_specs, check_vma=False))
    if not jit:
        return step_fn
    step_fn = jax.jit(step_fn, donate_argnums=_DONATED)
    if rounds_per_step is None:
        return step_fn
    return _checked(step_fn, rounds_per_step, "sharded multi-round step")


def make_sharded_joint_fn(cfg: GlasuConfig, mesh, axis: str = "clients"):
    """JointInference logits with clients sharded over the mesh: returns the
    global (M, S, C) stack (assembled from per-device blocks)."""
    from jax.sharding import PartitionSpec as P

    m_loc = _client_axis_check(cfg, mesh, axis)
    # specs don't depend on the optimizer; borrow sgd for the helper
    pspecs, _, bspecs = _sharded_specs(cfg, opt_lib.sgd(0.0), axis)

    def body(params, batch, key):
        logits, _ = sharded_joint_inference(params, batch, cfg, key,
                                            axis_name=axis, m_loc=m_loc)
        return logits

    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(pspecs, bspecs, P()),
                                 out_specs=P(axis), check_vma=False))


# ---------------------------------------------------------------- evaluation
def full_forward(params, cfg: GlasuConfig, feats, nbr_idx, nbr_mask,
                 chunk: int = 4096, collect_agg: bool = False):
    """Exact full-graph inference, chunked over nodes (eval only).

    feats: (M, N, d); nbr_idx/mask: (M, N, D+1) padded neighbor tables.
    Aggregation across clients happens at the configured layers only — the
    eval-time model is exactly the trained split model.

    The chunk loop is a ``lax.map`` over chunk starts: the jit that wraps
    this (EvalHook) compiles ONE chunk body instead of unrolling
    ceil(N/chunk) copies of it. Destination tables are padded to a chunk
    multiple (pad rows gather node 0 under a zero mask and are sliced off),
    which also makes the chunk tiling exact when chunk does not divide N —
    the previous clamped-dynamic-slice concatenation silently re-read
    earlier rows in that case.

    For a masked-mean backbone (``models.gnn.MASKED_MEAN``) without
    ``use_pallas``, a layer whose source fits the TPU core's VMEM
    (``kernels.ops.resident_fits``: false off a TPU), on operands that sit
    on one device (``kernels.ops.on_one_device``: XLA cannot split the
    kernel over a mesh), instead takes its neighbour mean for all N nodes
    of every party from one ``resident_masked_mean`` kernel, and runs the
    layer's update on all N rows at once. Each layer traced counts
    ``full_agg:resident`` or ``full_agg:xla`` in ``repro.obs``.

    ``collect_agg=True`` additionally returns the post-aggregation stacks
    ``{l: (M, N, h_agg)}`` per aggregation layer — the serving cache's
    warm-fill source. Pad rows are sliced off BEFORE ``_aggregate`` runs
    (``[:, :n]`` above), so the collected stacks carry exactly the N real
    nodes regardless of whether ``chunk`` divides N; the hot-node cache
    can never be poisoned by chunk padding.
    """
    from ..kernels import ops as kops
    m, n = feats.shape[0], feats.shape[1]
    pad = (-n) % chunk
    idx_p, mask_p = nbr_idx, nbr_mask
    if pad:
        idx_p = jnp.pad(nbr_idx, ((0, 0), (0, pad), (0, 0)))
        mask_p = jnp.pad(nbr_mask, ((0, 0), (0, pad), (0, 0)))
    n_pad = n + pad
    h = jax.vmap(lambda p, x: x @ p["W"] + p["b"])(params["inp"], feats)
    h0 = h
    aggs: Dict[int, Any] = {}
    for l in range(cfg.n_layers):  # glint: disable=GL004 static L-layer unroll; the node axis is lax.map'd via chunk_fn below
        layer = _client_layer(cfg, l)

        def chunk_fn(lo, h_full=h, h0_full=h0, l=l, layer=layer):
            idx = jax.lax.dynamic_slice_in_dim(idx_p, lo, chunk, axis=1)
            mask = jax.lax.dynamic_slice_in_dim(mask_p, lo, chunk, axis=1)
            return jax.vmap(layer)(params["layers"][l], h_full, h0_full,
                                   idx, mask)

        if (cfg.backbone in MASKED_MEAN and not cfg.use_pallas
                and kops.resident_fits(n, h.shape[-1], nbr_idx.shape[-1])
                and kops.on_one_device(h, nbr_idx, nbr_mask)):
            obs.count("full_agg:resident")
            agg = kops.resident_masked_mean(h, nbr_idx, nbr_mask)
            h_plus = jax.vmap(
                lambda p, x, x0, i, k, a, layer=layer: layer(
                    p, x, x0, i, k, agg=a))(
                params["layers"][l], h, h0, nbr_idx, nbr_mask, agg)
        else:
            obs.count("full_agg:xla")
            if n_pad == chunk:
                h_plus = chunk_fn(0)[:, :n]
            else:
                starts = jnp.arange(0, n_pad, chunk)
                pieces = jax.lax.map(chunk_fn, starts)   # (C, M, chunk, h)
                h_plus = jnp.moveaxis(pieces, 0, 1).reshape(
                    m, n_pad, pieces.shape[-1])[:, :n]
        if l in cfg.agg_layers:
            h, _ = _aggregate(cfg, h_plus)
            if collect_agg:
                aggs[l] = h
        else:
            h = h_plus
        # h0 is node-aligned in full-graph mode (no subsetting)
    logits = jax.vmap(lambda p, x: x @ p["W"] + p["b"])(params["cls"], h)
    if collect_agg:
        return logits, aggs
    return logits  # (M, N, C)


# ------------------------------------------------------------------- serving
# Query-path forward for the serving subsystem (repro.serve). Differences
# from joint_inference: no PRNG key (the §3.6 privacy hooks are a training
# protocol — serving answers on the trained model), no error-feedback carry
# (queries are stateless; EF is a training-time variance-reduction loop),
# and a cache-injection hook at every aggregation layer: the session
# overwrites rows whose (node, layer, params_version) aggregate it already
# holds, so those rows skip the cross-client exchange — the serving-path
# analogue of §3.5 stale updates. Injection happens AFTER _aggregate /
# _compressed_aggregate; both aggregations are row-independent (mean /
# concat over clients per node), so garbage in a cached row's freshly
# computed value (its neighbor deps are pruned from the query plan) cannot
# contaminate any other row before it is overwritten.

def serve_forward(params, batch: SampledBatch, cfg: GlasuConfig,
                  compressor: Optional[Compressor] = None,
                  cache_inject: Optional[Dict[int, Any]] = None):
    """Cross-client forward for one served query plan (vmapped clients).

    ``cache_inject`` maps aggregation layer l to ``(keep, rows)``: ``keep``
    is a float (n_{l+1},) mask (1 = use the cached aggregate) and ``rows``
    the (M, n_{l+1}, h_agg) cached per-client stacks. The dict must carry
    the SAME key set on every call of one jitted trace (the session always
    passes all aggregation layers; all-zero masks mean no injection).

    Returns ``(h, aggs)``: the final (M, n_L, h_agg) representation the
    classifier consumes, and the post-injection aggregate stacks
    ``{l: (M, n_{l+1}, h_agg)}`` the session reads its cache fills from.
    """
    h = jax.vmap(lambda p, x: x @ p["W"] + p["b"])(params["inp"],
                                                   batch.feats)
    h0 = h
    aggs: Dict[int, Any] = {}
    for l in range(cfg.n_layers):  # glint: disable=GL004 static L-layer unroll; per-layer params are heterogeneous (widths change at agg boundaries)
        layer = _client_layer(cfg, l)
        h_plus = jax.vmap(layer)(params["layers"][l], h, h0,
                                 batch.gather_idx[l], batch.gather_mask[l])
        h0 = jax.vmap(lambda a, i: a[i])(h0, batch.self_pos[l])
        if l in cfg.agg_layers:
            if compressor is None:
                h, _ = _aggregate(cfg, h_plus)
            else:
                h = _compressed_aggregate(cfg, compressor, h_plus,
                                          None, layer=l)[0]
            if cache_inject is not None and l in cache_inject:
                keep, rows = cache_inject[l]
                h = jnp.where(keep[None, :, None] > 0, rows, h)
            aggs[l] = h
        else:
            h = h_plus
    return h, aggs


def sharded_serve_forward(params, batch: SampledBatch, cfg: GlasuConfig, *,
                          axis_name: str, m_loc: int,
                          compressor: Optional[Compressor] = None,
                          cache_inject: Optional[Dict[int, Any]] = None):
    """``serve_forward`` under shard_map: local client blocks, collective
    Agg (same layout contract as ``sharded_joint_inference``). The
    injection masks/rows arrive replicated; each device overwrites its
    local block of the aggregate."""
    h = jax.vmap(lambda p, x: x @ p["W"] + p["b"])(params["inp"],
                                                   batch.feats)
    h0 = h
    aggs: Dict[int, Any] = {}
    i0 = jax.lax.axis_index(axis_name) * m_loc
    for l in range(cfg.n_layers):  # glint: disable=GL004 static L-layer unroll; per-layer params are heterogeneous (widths change at agg boundaries)
        layer = _client_layer(cfg, l)
        h_plus = jax.vmap(layer)(params["layers"][l], h, h0,
                                 batch.gather_idx[l], batch.gather_mask[l])
        h0 = jax.vmap(lambda a, i: a[i])(h0, batch.self_pos[l])
        if l in cfg.agg_layers:
            if compressor is None:
                uploads = _gather_clients(h_plus, axis_name)
                h_full, _ = _aggregate(cfg, uploads)
                h = jax.lax.dynamic_slice_in_dim(h_full, i0, m_loc, axis=0)
            else:
                h = _compressed_aggregate(
                    cfg, compressor, h_plus, None,
                    gather=lambda x: _gather_clients(x, axis_name),
                    i0=i0, layer=l)[0]
            if cache_inject is not None and l in cache_inject:
                keep, rows = cache_inject[l]
                rows_blk = jax.lax.dynamic_slice_in_dim(rows, i0, m_loc,
                                                        axis=0)
                h = jnp.where(keep[None, :, None] > 0, rows_blk, h)
            aggs[l] = h
        else:
            h = h_plus
    return h, aggs


def make_sharded_serve_fn(cfg: GlasuConfig, mesh, axis: str = "clients",
                          compressor: Optional[Compressor] = None):
    """Jitted serving dispatch with clients sharded over the mesh.

    ``(params, batch, inject) -> (h, aggs)`` with the client axis of every
    output reassembled to the global (M, ...) stack; ``inject`` is the
    replicated ``{l: (keep, rows)}`` cache-injection dict (every
    aggregation layer present)."""
    from jax.sharding import PartitionSpec as P

    m_loc = _client_axis_check(cfg, mesh, axis)
    # specs don't depend on the optimizer; borrow sgd for the helper
    pspecs, _, bspecs = _sharded_specs(cfg, opt_lib.sgd(0.0), axis)
    ispecs = {l: (P(), P()) for l in cfg.agg_layers}

    def body(params, batch, inject):
        return sharded_serve_forward(params, batch, cfg, axis_name=axis,
                                     m_loc=m_loc, compressor=compressor,
                                     cache_inject=inject)

    out_specs = (P(axis), {l: P(axis) for l in cfg.agg_layers})
    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(pspecs, bspecs, ispecs),
                                 out_specs=out_specs, check_vma=False))


def accuracy_from_logits(logits, labels, idx, mode: str = "ensemble"):
    """'ensemble': average client logits (GLASU eval); 'per_client': mean of
    each client's own accuracy (standalone eval, paper §5.2)."""
    labels = jnp.asarray(labels)
    idx = jnp.asarray(idx)
    if mode == "ensemble":
        pred = jnp.argmax(jnp.mean(logits, axis=0)[idx], axis=-1)
        return jnp.mean((pred == labels[idx]).astype(jnp.float32))
    preds = jnp.argmax(logits[:, idx], axis=-1)
    accs = jnp.mean((preds == labels[idx][None]).astype(jnp.float32), axis=1)
    return jnp.mean(accs)
