"""Flash-style sliding-window attention — Pallas TPU kernel.

TPU-native adaptation (DESIGN.md §2): instead of the CUDA flash-attention
warp layout, blocks are chosen for the MXU/VMEM hierarchy —
(block_q × head_dim) q tiles resident in VMEM, the kv window streamed in
block_q-sized tiles through the innermost sequential grid dimension with an
online-softmax accumulator in VMEM scratch.  All matmul dims are multiples
of 128 when head_dim is (the assigned archs use hd ∈ {64, 128}).

Grid: (B·H, n_q_blocks, n_window_blocks)   (last dim innermost/sequential)
Block shapes:
  q   (1, 1, bq, hd)   from (B, H, S, hd)
  k/v (1, 1, bq, hd)   from (B, Hkv, S, hd) — GQA folds h→h//G in index_map
  out (1, 1, bq, hd)
Scratch (VMEM): m (bq, 1), l (bq, 1), acc (bq, hd) — fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            window: int, block_q: int, num_win_blocks: int, scale: float):
    i = pl.program_id(1)          # q block
    j = pl.program_id(2)          # window block (innermost, sequential)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_block = i - (num_win_blocks - 1) + j               # true kv block index
    q = q_ref[0, 0].astype(jnp.float32)                   # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (bq, bq)

    qpos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_q), 0)
    kpos = kv_block * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_q), 1)
    mask = (qpos >= kpos) & (qpos - kpos < window) & (kv_block >= 0)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                   # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(p, v)
    m_ref[...] = m_new

    @pl.when(j == num_win_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def swa_attention_fwd(q, k, v, *, window: int, block_q: int = 128,
                      interpret: bool = False):
    """q: (B, H, S, hd); k, v: (B, Hkv, S, hd).  Causal sliding-window."""
    b, h, s, hd = q.shape
    hkv = k.shape[1]
    g = h // hkv
    block_q = min(block_q, s)
    while s % block_q:
        block_q //= 2
    # kv blocks covering (qpos − window, qpos] for every q in a block:
    # ceil(window / block_q) previous blocks + the diagonal block
    num_win_blocks = -(-window // block_q) + 1
    grid = (b * h, s // block_q, num_win_blocks)
    scale = hd ** -0.5

    def q_map(bh, i, j):
        return (bh // h, bh % h, i, 0)

    def kv_map(bh, i, j):
        kvb = i - (num_win_blocks - 1) + j
        return (bh // h, (bh % h) // g, jnp.maximum(kvb, 0), 0)

    kern = functools.partial(
        _kernel, window=window, block_q=block_q,
        num_win_blocks=num_win_blocks, scale=scale)

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), q_map),
            pl.BlockSpec((1, 1, block_q, hd), kv_map),
            pl.BlockSpec((1, 1, block_q, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), q_map),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
        name="swa_attention",
    )(q, k, v)
