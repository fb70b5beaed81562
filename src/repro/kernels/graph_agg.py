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

The exact full-graph pass's neighbour mean (``resident_masked_mean_pallas``,
last section) keeps a party's whole source in VMEM instead: packed two
64-wide nodes to a 128-lane row, Reddit's 232,965 nodes take 59.6 MB of the
v5e core's 128 MiB, in one buffer loaded once per party and layer; the
tables stream through SMEM. ``kernels.ops.resident_fits`` allows it only
where the width divides 128 and the packed source, two output blocks and a
16 MiB reserve fit the VMEM that ``pltpu.get_tpu_info`` reports: on a v5e
up to 456,704 nodes 64 wide, 228,352 nodes 128 wide. Off a TPU, above
that, for GAT, under ``use_pallas`` and where the pass's operands span
several devices (XLA cannot split a Mosaic kernel: ``on_one_device`` in
``kernels.ops``) the pass keeps XLA's chunked row gather
(``core.glasu.full_forward``).

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


# ------------------------------------------------------ resident masked mean
# The exact full-graph pass aggregates every node of a party from the same
# (N, d) source. XLA runs that as a row gather from HBM, which costs a fixed
# time per row however narrow the row. Here the party's whole source is
# copied into VMEM once, and each neighbour costs one row load from VMEM.
# A 64-wide row would take a whole 128-lane row in VMEM's (8, 128) tiling,
# so the source is packed ``LANE // d`` nodes to a row: node i sits in row
# i % R, lane group i // R, for R rows rounded up to whole tiles. The
# kernel's tables carry (i % R) << 7 | the lane roll that brings that
# group to lanes 0..d-1.
#
# The sum keeps the order of XLA's TPU reduce of ``gather_mean``'s
# (n, F, d) products, whose F axis lies on the 8 sublanes: eight strided
# accumulators (neighbour f goes to f % 8, in turn), then a halving tree
# over them (0+4, 1+5, 2+6, 3+7; then +2; then +1). The division by the
# clamped mask sum runs in XLA, as ``gather_mean``'s does. That order was
# read off XLA's output on a v5e with JAX 0.9; no contract promises it.
# There the mean came out bitwise ``gather_mean``'s at F = 5, 12 and 33
# (``chip_smoke.py``), and a Reddit pass's logits bitwise the XLA path's;
# another chip or compiler may sum otherwise. A summation order that
# differs by one rounding flips the one-pass bf16 rounding of ``z @ W``
# operands and moves a logit by up to 0.7% of the logits' RMS.

RESIDENT_BLOCK = 512             # destinations per grid step
SUBLANE = 8
SMEM_TILE = 1024                 # words: the tiling of a 1-D SMEM block
ROLL_BITS = 7                    # a table entry is row << 7 | lane roll
UNROLLED = 4                     # destinations unrolled per loop step


def resident_pack(d: int) -> int:
    """Nodes per packed 128-lane row for source width ``d``, or 0 when the
    kernel cannot pack that width (it must divide 128)."""
    return LANE // d if 0 < d <= LANE and LANE % d == 0 else 0


def _resident_rows(n_src: int, pack: int) -> int:
    """Packed rows R, rounded up to whole (8, 128) tiles."""
    rows = -(-n_src // pack)
    return -(-rows // SUBLANE) * SUBLANE


def resident_vmem_bytes(n_src: int, d: int) -> int:
    """VMEM the kernel asks for: the packed source, single-buffered, and
    the two buffers of its (block, d) output, lane-padded."""
    packed = _resident_rows(n_src, resident_pack(d)) * LANE * 4
    return packed + 2 * RESIDENT_BLOCK * LANE * 4


def _table_stride(block: int, fanout: int) -> int:
    """Words per block of a 1-D SMEM table, padded to the 1-D tiling (a
    block of a 2-D SMEM array would need 8 rows)."""
    return -(-block * fanout // SMEM_TILE) * SMEM_TILE


def resident_smem_bytes(fanout: int) -> int:
    """SMEM the kernel's tables take: slots and mask, two buffers each."""
    return 4 * _table_stride(RESIDENT_BLOCK, fanout) * 4


def _halving_sum(parts):
    """Sum eight sublane partials as a halving tree. Absent ones (None,
    past a fanout under 8) add nothing, as XLA's zero padding adds 0."""
    k = len(parts) // 2
    while k:
        for i in range(k):  # glint: disable=GL004 static 7-add tree
            if parts[i + k] is not None:
                parts[i] = parts[i] + parts[i + k]
        k //= 2
    return parts[0]


def _resident_kernel(q_ref, w_ref, src, out_ref, *, fanout, pack, d):
    """One (party, destination block) program: masked sums, not means.

    ``src`` is the party's packed source, the same block for all of the
    party's programs, so it is copied into VMEM once per party.
    Destinations go eight at a time, one output tile, four to a loop step
    (on a v5e as fast as eight, and half the code to trace and compile):
    each neighbour's packed row is loaded, rolled by a dynamic amount that
    brings its lane group to lanes 0..d-1, scaled by its mask, and summed
    in XLA's order (section comment).
    """
    sub = jax.lax.broadcasted_iota(jnp.int32, (SUBLANE, LANE), 0)

    def neighbour(at):
        q = q_ref[at]
        x = src[pl.ds(q >> ROLL_BITS, 1), :]               # (1, 128)
        if pack > 1:    # its lane group to lanes 0..d-1
            x = pltpu.roll(x, q & (LANE - 1), 1)
        return x * w_ref[at]

    def rows(g, step, block):
        """Destinations 4·step .. 4·step + 3 of group g into ``block``."""
        first = g * (SUBLANE * fanout) + step * (UNROLLED * fanout)
        for r in range(UNROLLED):  # glint: disable=GL004 static 4-row unroll
            parts = [None] * SUBLANE
            for f in range(fanout):  # glint: disable=GL004 static fanout unroll at trace time
                x = neighbour(first + (r * fanout + f))
                k = f % SUBLANE
                parts[k] = x if parts[k] is None else parts[k] + x
            block = jnp.where(sub == step * UNROLLED + r, _halving_sum(parts),
                              block)
        return block

    def group(g, carry):
        block = jax.lax.fori_loop(0, SUBLANE // UNROLLED,
                                  functools.partial(rows, g),
                                  jnp.zeros((SUBLANE, LANE), jnp.float32))
        out_ref[pl.ds(pl.multiple_of(g * SUBLANE, SUBLANE), SUBLANE), :] = \
            block[:, :d]
        return carry

    jax.lax.fori_loop(0, out_ref.shape[0] // SUBLANE, group, 0)


def resident_masked_mean_pallas(h, idx, mask, *, interpret: bool,
                                vmem_limit_bytes: int):
    """Masked mean of each destination's neighbour rows, source in VMEM.

    h: (M, n_src, d) party sources; idx/mask: (M, n_dst, F) neighbour
    tables -> (M, n_dst, d) float32: ``models.gnn.gather_mean`` per party,
    in the summation order XLA used for it on a v5e (section comment).
    ``d`` must divide 128 (``resident_pack``). Indices are clipped into
    [0, n_src) as XLA's gather clips them; pad entries are idx 0 under
    mask 0.
    Grid is (parties, destination blocks). The source block changes only
    with the party, so it is fetched once per party, into one VMEM buffer
    (``pl.Buffered(1)``); the tables stream through SMEM a block at a
    time.
    """
    m, n_src, d = h.shape
    n_dst, fanout = idx.shape[1:]
    pack = resident_pack(d)
    if not pack:
        raise ValueError(f"source width {d} does not divide {LANE}")
    rows = _resident_rows(n_src, pack)
    hp = jnp.pad(h.astype(jnp.float32),
                 ((0, 0), (0, rows * pack - n_src), (0, 0)))
    src = jnp.swapaxes(hp.reshape(m, pack, rows, d), 1, 2).reshape(
        m, rows, LANE)
    block = min(RESIDENT_BLOCK, -(-n_dst // DST_BLOCK) * DST_BLOCK)
    pad = (-n_dst) % block
    idx = jnp.clip(idx.astype(jnp.int32), 0, n_src - 1)
    roll = (idx // rows * (LANE - d)) % LANE       # lane group to lanes 0..d-1
    q = jnp.pad((idx % rows) << ROLL_BITS | roll, ((0, 0), (0, pad), (0, 0)))
    w = jnp.pad(mask.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
    n_blocks = (n_dst + pad) // block
    stride = _table_stride(block, fanout)

    def table(x):
        x = x.reshape(m * n_blocks, block * fanout)
        return jnp.pad(x, ((0, 0), (0, stride - block * fanout))).reshape(-1)

    spec = pl.BlockSpec((stride,), lambda p, j: (p * n_blocks + j,),
                        memory_space=pltpu.SMEM)
    sums = pl.pallas_call(
        functools.partial(_resident_kernel, fanout=fanout, pack=pack, d=d),
        name="resident_masked_mean",
        grid=(m, n_blocks),
        in_specs=[
            spec,                                            # slots
            spec,                                            # mask
            pl.BlockSpec((None, rows, LANE), lambda p, j: (p, 0, 0),
                         memory_space=pltpu.VMEM,
                         pipeline_mode=pl.Buffered(1)),      # packed source
        ],
        out_specs=pl.BlockSpec((None, block, d), lambda p, j: (p, j, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((m, n_dst + pad, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(table(q), table(w), src)[:, :n_dst]
    return sums / jnp.maximum(jnp.sum(mask, axis=-1, keepdims=True), 1.0)
