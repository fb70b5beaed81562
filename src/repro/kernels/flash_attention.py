"""Pallas TPU kernel: flash attention (causal / sliding-window / bidirectional)
with native GQA (kv-head reuse via BlockSpec index maps — no materialized
head repeat).

Grid: (batch, q_heads, n_q_blocks). Each program owns one (BLOCK_Q, dh) query
tile in VMEM and streams (BLOCK_K, dh) key/value tiles with the online-
softmax running (m, l, acc) state. Causality and the sliding window are
enforced (a) coarsely by skipping fully-masked kv blocks via the loop bounds
and (b) exactly by an in-tile position mask. Block sizes default to 128
(MXU-aligned); dh must be a multiple of 8 (v5e VREG sublane).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, causal, window,
                  block_q, block_k, seq_k, scale):
    qi = pl.program_id(2)  # glint: disable=GL005 never vmapped: callers pass pre-batched (b, h, s, dh) and batch/head ride the grid
    q = q_ref[0, 0].astype(jnp.float32) * scale          # (BQ, dh)
    q_start = qi * block_q

    # kv block range actually visible to this q tile
    n_kv_blocks = (seq_k + block_k - 1) // block_k
    hi = n_kv_blocks if not causal else \
        jnp.minimum((q_start + block_q + block_k - 1) // block_k, n_kv_blocks)
    lo = 0
    if window is not None:
        lo = jnp.maximum(q_start - window + 1, 0) // block_k

    def body(kb, carry):
        acc, m_prev, l_prev = carry
        k_start = kb * block_k
        k = k_ref[0, 0, pl.dslice(k_start, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.dslice(k_start, block_k), :].astype(jnp.float32)
        s = q @ k.T                                     # (BQ, BK)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < seq_k
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_cur[:, None])
        alpha = jnp.exp(m_prev - m_cur)
        l_cur = l_prev * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + p @ v
        return acc, m_cur, l_cur

    acc0 = jnp.zeros((block_q, q_ref.shape[3]), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(lo, hi, body, (acc0, m0, l0))
    o_ref[0, 0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: Optional[int] = None,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool):
    """q: (B, S, H, dh); k/v: (B, T, Kv, dh) -> (B, S, H, dh)."""
    b, s, h, dh = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    pad_q = (-s) % block_q
    pad_k = (-t) % block_k
    qt = jnp.moveaxis(q, 2, 1)                       # (B, H, S, dh)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad_q:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    sp, tp = s + pad_q, t + pad_k

    grid = (b, h, sp // block_q)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, causal=causal, window=window,
                          block_q=block_q, block_k=block_k, seq_k=t,
                          scale=1.0 / (dh ** 0.5)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda bi, hi, qi: (bi, hi, qi, 0),
                         memory_space=pltpu.VMEM),
            # GQA: kv head = q head // group — no repeat materialization
            pl.BlockSpec((1, 1, tp, dh),
                         lambda bi, hi, qi: (bi, hi // g, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, tp, dh),
                         lambda bi, hi, qi: (bi, hi // g, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda bi, hi, qi: (bi, hi, qi, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, h, sp, dh), q.dtype),
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.moveaxis(out[:, :, :s], 1, 2)
