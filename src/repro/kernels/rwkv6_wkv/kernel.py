"""RWKV6 WKV recurrence (data-dependent per-channel decay) — Pallas TPU
kernel ([arXiv:2404.05892], the attention-free core of rwkv6-1.6b).

Per head (K = V = head dim), with w_t ∈ (0,1)^K data-dependent:

  S_t = diag(w_t) S_{t-1} + k_t v_tᵀ
  y_t = r_tᵀ (S_{t-1} + diag(u) k_t v_tᵀ)

Chunked form (same algebra as models.rwkv6.wkv_chunked): within a chunk the
strictly-causal part is a (c×c) banded matmul with cumulative log-decay,
the diagonal carries the u bonus, and the (K×V) state is carried across
chunks.  TPU adaptation: the state lives in VMEM scratch across the
sequential chunk grid dim; every matmul maps to the MXU with c, K multiples
of (8, 128) at production sizes (c=128, K=64..128).

Grid: (B·H, n_chunks)   (chunks innermost — state carry)
Blocks (inputs pre-reshaped to (B, nc, c, H, K)):
  r/k/v/logw (1, 1, c, 1, K);  u (1, K);  s0 (1, 1, K, K)
Outputs: y (1, 1, c, 1, K);  s_final (1, 1, K, K)
Scratch: S (K, K) fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sf_ref, s_ref, *,
            nchunks: int):
    kidx = pl.program_id(1)

    @pl.when(kidx == 0)
    def _init():
        s_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0, :, 0].astype(jnp.float32)          # (c, K)
    k = k_ref[0, 0, :, 0].astype(jnp.float32)
    v = v_ref[0, 0, :, 0].astype(jnp.float32)
    logw = w_ref[0, 0, :, 0].astype(jnp.float32)       # ≤ 0
    u = u_ref[0].astype(jnp.float32)                   # (K,)
    c = r.shape[0]

    cs = jnp.cumsum(logw, axis=0)                      # (c, K) inclusive
    excl = cs - logw                                   # exclusive
    rd = r * jnp.exp(excl)
    kd = k * jnp.exp(-cs)
    att = jax.lax.dot_general(rd, kd, (((1,), (1,)), ((), ())))   # (c, c)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
           > jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))      # strict
    att = jnp.where(tri, att, 0.0)
    y = jax.lax.dot(att, v)                                        # (c, K)
    # diagonal with u bonus
    y += jnp.sum(r * u[None] * k, axis=-1, keepdims=True) * v
    # inter-chunk
    y += jax.lax.dot(rd, s_ref[...])                               # (c,K)·(K,V)
    y_ref[0, 0, :, 0] = y.astype(y_ref.dtype)

    # state update
    end = cs[-1]                                                   # (K,)
    s_new = s_ref[...] * jnp.exp(end)[:, None] + jax.lax.dot_general(
        k * jnp.exp(end[None] - cs), v, (((0,), (0,)), ((), ())))  # (K, V)
    s_ref[...] = s_new

    @pl.when(kidx == nchunks - 1)
    def _final():
        sf_ref[0, 0] = s_new.astype(sf_ref.dtype)


def wkv_scan_fwd(r, k, v, logw, u, s0, *, chunk: int = 64,
                 interpret: bool = False):
    """r, k, v, logw: (B, S, H, K); u: (H, K); s0: (B, H, K, K) fp32.
    Returns y (B, S, H, K) fp32 and s_final (B, H, K, K) fp32."""
    bsz, s, h, dk = r.shape
    chunk = min(chunk, s)
    while s % chunk:
        chunk //= 2
    nc = s // chunk

    resh = lambda t: t.reshape(bsz, nc, chunk, h, dk)
    grid = (bsz * h, nc)
    kern = functools.partial(_kernel, nchunks=nc)

    io_spec = pl.BlockSpec((1, 1, chunk, 1, dk),
                           lambda bh, kk: (bh // h, kk, 0, bh % h, 0))
    st_spec = pl.BlockSpec((1, 1, dk, dk),
                           lambda bh, kk: (bh // h, bh % h, 0, 0))

    y, sf = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[io_spec, io_spec, io_spec, io_spec,
                  pl.BlockSpec((1, dk), lambda bh, kk: (bh % h, 0)),
                  st_spec],
        out_specs=[io_spec, st_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, nc, chunk, h, dk), jnp.float32),
            jax.ShapeDtypeStruct((bsz, h, dk, dk), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dk), jnp.float32)],
        interpret=interpret,
        name="wkv_scan",
    )(resh(r), resh(k), resh(v), resh(logw), u, s0.astype(jnp.float32))
    return y.reshape(bsz, s, h, dk), sf
