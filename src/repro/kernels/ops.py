"""Jit'd public wrappers for the Pallas kernels.

On CPU (this container) kernels run in ``interpret=True`` mode — the kernel
body executes in Python/XLA for correctness validation; on TPU the same
``pallas_call`` lowers to Mosaic. The switch is automatic.

``use_pallas=True`` in the GLASU core routes all three paper backbones
(GCN, GCNII, GAT) through these fused kernels. ``pallas_call`` has no
reverse-mode rule, and GLASU *trains* through the client sub-layers
(Alg 4's LocalUpdate), so each graph op carries a ``custom_vjp``: the
forward pass is the fused kernel, the backward pass differentiates the
pure-jnp oracle in ``kernels/ref.py`` (bit-identical math, XLA-fused).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from ..graph.csr_plan import csr_slot_map, plan_csr_slabs
from . import ref
from .flash_attention import flash_attention_pallas
from .graph_agg import (ell_to_slabs, gat_layer_pallas, gcnii_layer_pallas,
                        graph_agg_csr_pallas, graph_agg_pallas,
                        resident_masked_mean_pallas, resident_pack,
                        resident_smem_bytes, resident_vmem_bytes)


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# Density heuristic for ``graph_agg``: the one-hot scatter matrix costs
# O(n_dst·n_src·d) MXU work and (128, n_src) VMEM per tile — unbeatable
# while the sampler caps n_src at size_cap (512), hopeless at graph scale.
# Above this source-set size the padded-fanout tables are re-laid out as
# CSR edge slabs in-trace (``ell_to_slabs``) and the segment-sum kernel
# runs instead. The threshold is deliberately ABOVE every shipped profile
# (largest eval source set: reddit, 8192 rows), so all existing golden /
# conformance fixtures stay on the dense path bitwise; kernel_bench
# measures the true crossover per shape and gates that CSR wins above it.
CSR_DISPATCH_MIN_SRC = 16384


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q, k, v, causal: bool = True, window: Optional[int] = None):
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  interpret=_interpret())


# ---------------------------------------------------------------- graph ops
@jax.custom_vjp
def _graph_agg(h, idx, mask, w):
    return graph_agg_pallas(h, idx, mask, w, interpret=_interpret())


def _graph_agg_fwd(h, idx, mask, w):
    out = graph_agg_pallas(h, idx, mask, w, interpret=_interpret())
    return out, (h, idx, mask, w)


def _graph_agg_bwd(res, g):
    _, vjp = jax.vjp(ref.graph_agg_ref, *res)
    return vjp(g)


_graph_agg.defvjp(_graph_agg_fwd, _graph_agg_bwd)


# sparse twin of ``_graph_agg``: same (h, idx, mask, w) contract, forward
# re-lays the fanout tables out as CSR edge slabs and runs the segment-sum
# kernel; backward differentiates the SAME dense oracle (identical algebra,
# so dense- and CSR-dispatched training produce matching gradients)
@jax.custom_vjp
def _graph_agg_sparse(h, idx, mask, w):
    idx_s, seg_s, ew_s, n_dst = ell_to_slabs(idx, mask)
    return graph_agg_csr_pallas(h, idx_s, seg_s, ew_s, w, n_dst,
                                interpret=_interpret())


def _graph_agg_sparse_fwd(h, idx, mask, w):
    return _graph_agg_sparse(h, idx, mask, w), (h, idx, mask, w)


_graph_agg_sparse.defvjp(_graph_agg_sparse_fwd, _graph_agg_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _graph_agg_csr(h, idx_slab, seg_slab, ew_slab, w, n_dst):
    return graph_agg_csr_pallas(h, idx_slab, seg_slab, ew_slab, w, n_dst,
                                interpret=_interpret())


def _graph_agg_csr_fwd(h, idx_slab, seg_slab, ew_slab, w, n_dst):
    out = graph_agg_csr_pallas(h, idx_slab, seg_slab, ew_slab, w, n_dst,
                               interpret=_interpret())
    return out, (h, idx_slab, seg_slab, ew_slab, w)


def _graph_agg_csr_bwd(n_dst, res, g):
    fn = lambda *a: ref.csr_slab_ref(*a, n_dst)
    _, vjp = jax.vjp(fn, *res)
    return vjp(g)


_graph_agg_csr.defvjp(_graph_agg_csr_fwd, _graph_agg_csr_bwd)


@functools.partial(jax.jit, static_argnames=("n_dst",))
def _graph_agg_csr_jit(h, idx_slab, seg_slab, ew_slab, w, n_dst):
    return _graph_agg_csr(h, idx_slab, seg_slab, ew_slab, w, n_dst)


def graph_agg_csr(h, indptr, indices, w, edge_weight=None):
    """Sparse aggregation over a host CSR: segment-mean of ``h`` rows per
    destination, fused with the weight matmul.

    ``indptr``/``indices`` are CONCRETE (numpy) — the slab planner runs on
    host, exactly like the sampler's table builds; the jitted kernel sees
    only the padded static-shape slab arrays (one compile per (shapes,
    n_dst) signature). Differentiable wrt ``h``/``w``/``edge_weight``; the
    backward pass differentiates ``ref.csr_slab_ref`` (the same segment-sum
    algebra, XLA-fused). Oracle: ``ref.graph_agg_csr_ref``.
    """
    idx_s, seg_s, ew_s, n_dst = plan_csr_slabs(indptr, indices)
    if edge_weight is not None:
        # keep the traced edge weights out of the host planner: scatter the
        # (nnz,) weights into the padded slab with the planner's slot map
        ew_s = _scatter_edge_weights(indptr, idx_s.shape[0], edge_weight)
    return _graph_agg_csr_jit(h, idx_s, seg_s, ew_s, w, n_dst)


def _scatter_edge_weights(indptr, total, edge_weight):
    """(nnz,) traced weights -> (total, 1) slab array via the concrete
    slot map (host planning in ``graph.csr_plan``, device scatter here)."""
    slot = csr_slot_map(indptr, total)
    ew = jnp.zeros((total,), jnp.float32)
    ew = ew.at[jnp.asarray(slot)].set(edge_weight.astype(jnp.float32))
    return ew[:, None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _gcnii_layer(alpha, beta, h, h0, idx, mask, w, b):
    return gcnii_layer_pallas(h, h0, idx, mask, w, b, alpha=alpha, beta=beta,
                              interpret=_interpret())


def _gcnii_layer_fwd(alpha, beta, h, h0, idx, mask, w, b):
    out = gcnii_layer_pallas(h, h0, idx, mask, w, b, alpha=alpha, beta=beta,
                             interpret=_interpret())
    return out, (h, h0, idx, mask, w, b)


def _gcnii_layer_bwd(alpha, beta, res, g):
    fn = lambda *a: ref.gcnii_layer_ref(*a, alpha, beta)
    _, vjp = jax.vjp(fn, *res)
    return vjp(g)


_gcnii_layer.defvjp(_gcnii_layer_fwd, _gcnii_layer_bwd)


@jax.custom_vjp
def _gat_layer(h, idx, mask, w, a_src, a_dst, b):
    return gat_layer_pallas(h, idx, mask, w, a_src, a_dst, b,
                            interpret=_interpret())


def _gat_layer_fwd(h, idx, mask, w, a_src, a_dst, b):
    out = gat_layer_pallas(h, idx, mask, w, a_src, a_dst, b,
                           interpret=_interpret())
    return out, (h, idx, mask, w, a_src, a_dst, b)


def _gat_layer_bwd(res, g):
    _, vjp = jax.vjp(ref.gat_layer_ref, *res)
    return vjp(g)


_gat_layer.defvjp(_gat_layer_fwd, _gat_layer_bwd)


@jax.jit
def graph_agg(h, idx, mask, w):
    """Masked-mean neighbor gather fused with the weight matmul (GCN core).

    Dispatches on the STATIC source-set size: small sets (every training /
    eval profile shipped today) run the one-hot scatter-matrix kernel;
    sets at or above ``CSR_DISPATCH_MIN_SRC`` run the CSR segment-sum
    kernel over in-trace edge slabs. Both paths share the dense oracle's
    backward, and the decision is a trace-time shape check — no runtime
    branch, no retrace beyond the usual shape signature.
    """
    if h.shape[0] >= CSR_DISPATCH_MIN_SRC:
        return _graph_agg_sparse(h, idx, mask, w)
    return _graph_agg(h, idx, mask, w)


@functools.partial(jax.jit, static_argnames=("alpha", "beta"))
def gcnii_layer(h, h0, idx, mask, w, b, alpha: float, beta: float):
    """Fused GCNII sub-layer: gather-mean + initial residual + identity map."""
    return _gcnii_layer(alpha, beta, h, h0, idx, mask, w, b)


@jax.jit
def gat_layer(h, idx, mask, w, a_src, a_dst, b):
    """Fused multi-head GAT sub-layer: projection + masked attention + mix."""
    return _gat_layer(h, idx, mask, w, a_src, a_dst, b)


# ------------------------------------------------- resident masked mean
# VMEM left to Mosaic's own scratch beside the resident source
RESIDENT_VMEM_RESERVE = 16 * 1024 * 1024


def _tpu_memory():
    """(VMEM, SMEM) bytes per core of the attached TPU, or (0, 0) off one."""
    if not pltpu.is_tpu_device():
        return 0, 0
    info = pltpu.get_tpu_info()
    return info.vmem_capacity_bytes, info.smem_capacity_bytes


def resident_fits(n_src: int, d: int, fanout: int) -> bool:
    """Whether ``resident_masked_mean`` can hold an (n_src, d) source in
    this device's VMEM, beside a reserve, and its (destination block,
    fanout) tables in half its SMEM (the rest is Mosaic's). False off a
    TPU and for widths that do not divide 128."""
    vmem, smem = _tpu_memory()
    return (bool(resident_pack(d))
            and resident_vmem_bytes(n_src, d) + RESIDENT_VMEM_RESERVE <= vmem
            and resident_smem_bytes(fanout) <= smem // 2)


def on_one_device(*xs) -> bool:
    """Whether no operand's type carries a mesh of more than one device.

    XLA's partitioner cannot split a Mosaic kernel, so a pass whose
    operands may span devices (the sharded backend's exact eval, a
    session holding its params) keeps XLA's gather. The type's mesh is
    the only placement a trace sees. It outlives a move of the array to
    one device, which then takes XLA too; and it is empty for operands
    that a caller's ``jax.jit(in_shardings=...)`` splits only as it
    lowers, where JAX refuses the kernel rather than run it wrong."""
    return all(jax.typeof(x).sharding.mesh.size <= 1 for x in xs)


@jax.jit
def resident_masked_mean(h, idx, mask):
    """Masked-mean aggregation of stacked parties, each party's source held
    in VMEM: h (M, n_src, d), idx/mask (M, n_dst, F) -> (M, n_dst, d),
    ``models.gnn.gather_mean`` per party, in the summation order XLA uses
    for it on a v5e (``kernels/graph_agg.py``). The exact full-graph pass
    calls it where ``resident_fits`` and ``on_one_device``; it has no
    gradient rule (the pass is eval only). Jitted, so the layers of one
    pass share one trace and lowering of the kernel."""
    return resident_masked_mean_pallas(h, idx, mask, interpret=_interpret(),
                                       vmem_limit_bytes=_tpu_memory()[0])
