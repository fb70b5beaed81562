"""Pallas TPU kernels: sampled-neighborhood aggregation for GLASU sub-layers.

These are the per-layer hotspots of the paper's split GNN (§3.1, Alg 3/4):

    GCN    H_m^+[l] = relu( (masked-mean nbrs of H) @ W + b )
    GCNII  z = (1-a)·mean + a·H0[self];  relu((1-b)·z + b·(z @ W) + b)
    GAT    per-head masked softmax attention over the sampled fanout

TPU adaptation (vs the CUDA gather-scatter formulation): destination nodes
are tiled in blocks of 128 (MXU/VREG lane alignment) and the fanout gather is
reformulated as a one-hot *scatter-matrix matmul*: for every destination tile
we build A in VREGs with

    A[r, s] = sum_f mask[r, f] * [idx[r, f] == s]        (BD x n_src)

so the masked gather-sum is ``A @ H`` — one MXU contraction instead of
128·F scalar DMAs per tile (the seed kernel's double ``fori_loop``).  The
masked mean and the weight matmul fuse behind it in the same program.

``d_out`` is tiled for real: the grid is (dst tiles, d_out tiles) and each
program writes one (128, DOUT_BLOCK) output tile, so weight/output VMEM stays
bounded for wide layers.  Each d_out tile recomputes the (cheap) scatter
matrix instead of caching it in scratch: the GLASU core ``jax.vmap``s these
kernels over the client axis, and Pallas batching *prepends* a grid axis,
which would shift every ``pl.program_id``-gated scratch reuse.  With the
usual hidden sizes (d_out <= 128) there is exactly one d_out tile and nothing
is recomputed.

Per-tile VMEM: scatter matrix (128 x n_src) + source rows (n_src x d) +
one weight tile (d x DOUT_BLOCK). With the sampler's n_src <= size_cap (512)
and d <= 512 the kernel compiles for v5e (tests/test_tpu_compile.py); the
v5e compiler runs out of VMEM at n_src = 8192 with d = 512, so full-graph
sweeps at that scale need narrow rows or the CSR path below.

Every entry point takes ``interpret`` without a default: ``kernels.ops``
decides it from the backend, and a direct caller must say which it wants.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DST_BLOCK = 128
DOUT_BLOCK = 128
LANE = 128
NEG_INF = -1e9


def _scatter_matrix(idx, mask, n_src):
    """One-hot accumulation matrix: A[r, s] = sum_f mask[r, f]·[idx[r, f]==s].

    ``A @ H`` is the masked gather-sum over the fanout — the whole gather
    runs on the MXU. The loop over fanout columns is a *Python* loop over a
    static, small F (3-64), unrolled at trace time; every op is 2D.
    """
    src = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], n_src), 1)
    a = jnp.zeros((idx.shape[0], n_src), jnp.float32)
    for f in range(idx.shape[1]):  # glint: disable=GL004 static fanout unroll at trace time (F is 3-64; see module docstring)
        a = a + jnp.where(idx[:, f:f + 1] == src, mask[:, f:f + 1], 0.0)
    return a


def _select_matrix(idx_col, n_src):
    """Unmasked one-hot row-select matrix for one (n, 1) index column."""
    src = jax.lax.broadcasted_iota(jnp.int32, (idx_col.shape[0], n_src), 1)
    return jnp.where(idx_col == src, 1.0, 0.0)


def _masked_mean(idx_ref, mask_ref, h_ref):
    """(BD, d) masked mean of gathered source rows, f32."""
    mask = mask_ref[...].astype(jnp.float32)
    a = _scatter_matrix(idx_ref[...], mask, h_ref.shape[0])
    denom = jnp.maximum(jnp.sum(mask, axis=1, keepdims=True), 1.0)
    s = jnp.dot(a, h_ref[...].astype(jnp.float32),
                preferred_element_type=jnp.float32)
    return s / denom


def _pad_rows(x, block):
    pad = (-x.shape[0]) % block
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x


def _dout_block(d_out: int) -> int:
    return d_out if d_out <= DOUT_BLOCK else DOUT_BLOCK


def _pad_cols(x, block):
    pad = (-x.shape[-1]) % block
    if pad:
        x = jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))
    return x


# ----------------------------------------------------------------- GCN / agg
def _graph_agg_kernel(idx_ref, mask_ref, h_ref, w_ref, out_ref):
    agg = _masked_mean(idx_ref, mask_ref, h_ref)
    out_ref[...] = jnp.dot(agg.astype(w_ref.dtype), w_ref[...],
                           preferred_element_type=jnp.float32
                           ).astype(out_ref.dtype)


def graph_agg_pallas(h, idx, mask, w, *, interpret: bool):
    """h: (n_src, d), idx/mask: (n_dst, F), w: (d, d_out) -> (n_dst, d_out)."""
    n_dst, fanout = idx.shape
    d = h.shape[1]
    d_out = w.shape[1]
    bo = _dout_block(d_out)
    idx = _pad_rows(idx, DST_BLOCK)
    mask = _pad_rows(mask, DST_BLOCK)
    wp = _pad_cols(w, bo)
    grid = (idx.shape[0] // DST_BLOCK, wp.shape[1] // bo)
    out = pl.pallas_call(
        _graph_agg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((DST_BLOCK, fanout), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),                   # idx tile
            pl.BlockSpec((DST_BLOCK, fanout), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),                   # mask
            pl.BlockSpec((h.shape[0], d), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),                   # sources
            pl.BlockSpec((d, bo), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),                   # W tile
        ],
        out_specs=pl.BlockSpec((DST_BLOCK, bo), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((idx.shape[0], wp.shape[1]), w.dtype),
        interpret=interpret,
    )(idx, mask, h, wp)
    return out[:n_dst, :d_out]


# ----------------------------------------------------------- CSR / sparse
# The one-hot kernel above builds a (128, n_src) scatter matrix per tile —
# O(n_dst·n_src·d) dense MXU work, perfect while the sampler caps n_src at
# size_cap (512) but quadratic-looking the moment the source set grows
# toward graph scale. The CSR path replaces it with a per-tile *edge slab*:
# the host planner lays the CSR out as (n_tiles, slab) edge blocks — tile i
# owns destination rows [128i, 128i+128) and exactly its own edges, padded
# to a uniform slab length — so each program touches O(slab·d) work
# regardless of n_src. Assignment runs as a (128, slab) comparison matrix
# against the LOCAL destination row of each edge (sentinel 128 = padding,
# matches no row), making the kernel grid-position-free: no program_id, no
# SMEM scalars, safe under the core's client-axis vmap exactly like the
# dense kernels. The source-row gather runs in XLA before the pallas_call
# (Mosaic has no vector gather over a VMEM-resident row block), so each
# program reads only its own tile's (slab, d) gathered rows — never the
# whole (n_src, d) source matrix, which at graph scale would not fit VMEM.

CSR_PAD_ROW = DST_BLOCK          # local-seg sentinel: matches no tile row


def ell_to_slabs(idx, mask):
    """Padded-fanout (ELL) tables -> the kernel's slab layout, traceable.

    idx/mask: (n_dst, F) — the sampler's gather tables. Every row owns
    exactly F slots, so the slab is the uniform 128·F and the conversion is
    pure reshapes/iota (jit-safe; this is the in-trace dispatch path of
    ``ops.graph_agg``). Masked-off entries become weight-0 edges — the
    denominator clamp keeps the masked-mean semantics bitwise.
    """
    n_dst, fanout = idx.shape
    idx = _pad_rows(idx, DST_BLOCK)
    mask = _pad_rows(mask, DST_BLOCK)
    n_pad = idx.shape[0]
    n_tiles = n_pad // DST_BLOCK
    slab = DST_BLOCK * fanout
    local = jnp.broadcast_to(
        (jnp.arange(n_pad, dtype=jnp.int32) % DST_BLOCK)[:, None],
        (n_pad, fanout))
    idx_slab = idx.astype(jnp.int32).reshape(n_tiles * slab, 1)
    seg_slab = local.reshape(n_tiles * slab, 1)
    ew_slab = mask.astype(jnp.float32).reshape(n_tiles * slab, 1)
    return idx_slab, seg_slab, ew_slab, n_dst


def _csr_agg_kernel(seg_ref, ew_ref, g_ref, w_ref, out_ref):
    """One (dst tile, d_out tile) program over the tile's edge slab."""
    seg = seg_ref[...]                                  # (1, slab) local row
    ew = ew_ref[...].astype(jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, (DST_BLOCK, seg.shape[1]), 0)
    a = jnp.where(rows == seg, ew, 0.0)                 # (128, slab)
    s = jnp.dot(a, g_ref[...], preferred_element_type=jnp.float32)
    denom = jnp.maximum(jnp.sum(a, axis=1, keepdims=True), 1.0)
    out_ref[...] = jnp.dot((s / denom).astype(w_ref.dtype), w_ref[...],
                           preferred_element_type=jnp.float32
                           ).astype(out_ref.dtype)


def graph_agg_csr_pallas(h, idx_slab, seg_slab, ew_slab, w, n_dst: int, *,
                         interpret: bool):
    """CSR segment-mean + matmul over the planned slab layout.

    h: (n_src, d); idx/seg/ew slabs: (n_tiles*slab, 1) from
    ``graph.csr_plan.plan_csr_slabs`` / ``ell_to_slabs``; w: (d, d_out)
    -> (n_dst, d_out).
    Grid is (dst tiles, d_out tiles); each program reads ONE tile's edge
    slab — its (slab, d) gathered source rows plus (1, slab) local rows and
    weights — and one weight tile. VMEM per program is slab·d·4B + the
    (128, slab) assignment matrix, independent of n_src.
    """
    d = h.shape[1]
    d_out = w.shape[1]
    bo = _dout_block(d_out)
    wp = _pad_cols(w, bo)
    n_tiles = max(1, -(-n_dst // DST_BLOCK))
    slab = idx_slab.shape[0] // n_tiles
    gathered = jnp.take(h.astype(jnp.float32), idx_slab[:, 0], axis=0)
    # lane-major (n_tiles, 1, slab) rows: a tile's block is one (1, slab)
    # row vector, compared against the sublane iota without a transpose
    seg = seg_slab.reshape(n_tiles, 1, slab)
    ew = ew_slab.reshape(n_tiles, 1, slab)
    grid = (n_tiles, wp.shape[1] // bo)
    out = pl.pallas_call(
        _csr_agg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((pl.Squeezed(), 1, slab), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),                # local rows
            pl.BlockSpec((pl.Squeezed(), 1, slab), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),                # weights
            pl.BlockSpec((slab, d), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),                # edge rows
            pl.BlockSpec((d, bo), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),                # W tile
        ],
        out_specs=pl.BlockSpec((DST_BLOCK, bo), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_tiles * DST_BLOCK, wp.shape[1]),
                                       w.dtype),
        interpret=interpret,
    )(seg, ew, gathered, wp)
    return out[:n_dst, :d_out]


# -------------------------------------------------------------------- GCNII
def _gcnii_kernel(idx_ref, mask_ref, h_ref, h0_ref, w_ref, b_ref, out_ref, *,
                  alpha):
    agg = _masked_mean(idx_ref, mask_ref, h_ref)
    # initial residual: H0 at the output node set (self column, unmasked —
    # mirrors the reference's plain h0[idx[:, 0]] gather)
    sel0 = _select_matrix(idx_ref[...][:, 0:1], h0_ref.shape[0])
    h0_sel = jnp.dot(sel0, h0_ref[...].astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    z = (1.0 - alpha) * agg + alpha * h0_sel                 # (BD, d)
    out = jnp.dot(z.astype(w_ref.dtype), w_ref[...],
                  preferred_element_type=jnp.float32)
    out_ref[...] = jax.nn.relu(
        out + b_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


def gcnii_layer_pallas(h, h0, idx, mask, w, b, *, alpha: float, beta: float,
                       interpret: bool):
    """Fused GCNII client sub-layer (constant width d == d_out).

    h/h0: (n_src, d); idx/mask: (n_dst, F+1) with self at column 0;
    w: (d, d); b: (d,) -> relu((1-β)z + β(z@W) + b), z = (1-α)·mean + α·h0.
    The identity-map skip is folded into the weight, (1-β)z + β(z@W) =
    z @ ((1-β)I + βW), so each (dst tile, d_out tile) program needs no
    column offset into z: the skip's columns arrive with the weight tile.
    """
    n_dst, fanout1 = idx.shape
    d = h.shape[1]
    bo = _dout_block(d)
    w_id = (1.0 - beta) * jnp.eye(d, dtype=w.dtype) + beta * w
    wp = _pad_cols(w_id, bo)
    bp = _pad_cols(b[None, :], bo)
    idx = _pad_rows(idx, DST_BLOCK)
    mask = _pad_rows(mask, DST_BLOCK)
    grid = (idx.shape[0] // DST_BLOCK, wp.shape[1] // bo)
    out = pl.pallas_call(
        functools.partial(_gcnii_kernel, alpha=alpha),
        grid=grid,
        in_specs=[
            pl.BlockSpec((DST_BLOCK, fanout1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((DST_BLOCK, fanout1), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((h.shape[0], d), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((h0.shape[0], d), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((d, bo), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bo), lambda i, j: (0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((DST_BLOCK, bo), lambda i, j: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((idx.shape[0], wp.shape[1]), w.dtype),
        interpret=interpret,
    )(idx, mask, h, h0, wp, bp)
    return out[:n_dst, :d]


# ---------------------------------------------------------------------- GAT
def _gat_kernel(idx_ref, mask_ref, h_ref, w_ref, asrc_ref, adst_ref, b_ref,
                out_ref):
    """One (dst tile, head) program: project, gather, masked softmax, mix.

    The fanout gather runs as per-column one-hot matmuls; attention logits
    are assembled column-by-column with an iota mask (all ops 2D, unrolled
    over the static fanout — no 3D tensors, no program_id)."""
    idx = idx_ref[...]
    mask = mask_ref[...].astype(jnp.float32)
    n_dst, f1 = idx.shape
    n_src = h_ref.shape[0]
    wh = jnp.dot(h_ref[...].astype(jnp.float32),
                 w_ref[...].astype(jnp.float32),
                 preferred_element_type=jnp.float32)          # (n_src, dh)
    e_dst = jnp.sum(wh * adst_ref[...].astype(jnp.float32),
                    axis=1, keepdims=True)                    # (n_src, 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n_dst, f1), 1)
    gathered = []
    e = jnp.zeros((n_dst, f1), jnp.float32)
    for f in range(f1):  # glint: disable=GL004 static fanout unroll at trace time (F is 3-64; see module docstring)
        sel = _select_matrix(idx[:, f:f + 1], n_src)
        gathered.append(jnp.dot(sel, wh, preferred_element_type=jnp.float32))
        ecol = jnp.dot(sel, e_dst, preferred_element_type=jnp.float32)
        e = e + jnp.where(cols == f, ecol, 0.0)
    e_src = jnp.sum(gathered[0] * asrc_ref[...].astype(jnp.float32),
                    axis=1, keepdims=True)                    # self = col 0
    e = jax.nn.leaky_relu(e_src + e, negative_slope=0.2)
    e = jnp.where(mask > 0, e, NEG_INF)
    att = jax.nn.softmax(e, axis=1) * mask
    out = jnp.zeros_like(gathered[0])
    for f in range(f1):
        out = out + att[:, f:f + 1] * gathered[f]
    out = out + b_ref[...].astype(jnp.float32)
    # elu spelled with exp: Mosaic has no expm1 lowering
    out = jnp.where(out > 0, out, jnp.exp(jnp.minimum(out, 0.0)) - 1.0)
    out_ref[...] = out.astype(out_ref.dtype)


def gat_layer_pallas(h, idx, mask, w, a_src, a_dst, b, *, interpret: bool):
    """Fused multi-head GAT client sub-layer.

    h: (n_src, d); idx/mask: (n_dst, F+1) with self at column 0;
    w: (d, H, dh); a_src/a_dst: (H, dh); b: (H*dh,) -> (n_dst, H*dh).
    Grid is (dst tiles, heads): each program handles one head's (128, dh)
    output block; the head axis rides the BlockSpec index maps so no head
    dimension is ever materialized in VMEM. Each head's width is
    zero-padded to a lane multiple (128) so its blocks tile the chip's
    (8, 128) layout: padded columns project to 0, add nothing to the
    attention logits, and are sliced off the output.
    """
    n_dst, fanout1 = idx.shape
    d, n_heads, dh = w.shape
    idx = _pad_rows(idx, DST_BLOCK)
    mask = _pad_rows(mask, DST_BLOCK)
    wp = _pad_cols(w, LANE)
    dhp = wp.shape[2]
    w2 = wp.reshape(d, n_heads * dhp)
    a_src2 = _pad_cols(a_src, LANE).reshape(1, n_heads * dhp)
    a_dst2 = _pad_cols(a_dst, LANE).reshape(1, n_heads * dhp)
    b2 = _pad_cols(b.reshape(n_heads, dh), LANE).reshape(1, n_heads * dhp)
    grid = (idx.shape[0] // DST_BLOCK, n_heads)
    out = pl.pallas_call(
        _gat_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((DST_BLOCK, fanout1), lambda i, k: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((DST_BLOCK, fanout1), lambda i, k: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((h.shape[0], d), lambda i, k: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((d, dhp), lambda i, k: (0, k),
                         memory_space=pltpu.VMEM),            # head's W
            pl.BlockSpec((1, dhp), lambda i, k: (0, k),
                         memory_space=pltpu.VMEM),            # head's a_src
            pl.BlockSpec((1, dhp), lambda i, k: (0, k),
                         memory_space=pltpu.VMEM),            # head's a_dst
            pl.BlockSpec((1, dhp), lambda i, k: (0, k),
                         memory_space=pltpu.VMEM),            # head's bias
        ],
        out_specs=pl.BlockSpec((DST_BLOCK, dhp), lambda i, k: (i, k),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((idx.shape[0], n_heads * dhp), h.dtype),
        interpret=interpret,
    )(idx, mask, h, w2, a_src2, a_dst2, b2)
    out = out.reshape(idx.shape[0], n_heads, dhp)[:n_dst, :, :dh]
    return out.reshape(n_dst, n_heads * dh)
