"""Mamba2 SSD chunked scan — Pallas TPU kernel (zamba2's recurrent core).

One grid cell computes one (batch, head, chunk) tile of the SSD recurrence:

  intra-chunk   M[t,s] = (C_t·B_s) · exp(cs_t − cs_s)   (s ≤ t, banded matmul)
  inter-chunk   y_t   += exp(cs_t) · C_t · h_prev
  state carry   h     ← exp(cs_end) h_prev + Σ_s exp(cs_end − cs_s) B_s ⊗ x_s

TPU adaptation (DESIGN.md §2): the chunk dim is the MXU matmul dim — three
(c×c)/(c×N)/(c×P) matmuls per tile with c a multiple of 128; the running
state h (P×N fp32) lives in VMEM scratch and is carried across the
innermost sequential grid dimension (the chunk index), so HBM traffic is
one read of x/B/C and one write of y per token — the recurrence never
round-trips state through HBM.

Grid: (B·H, n_chunks)   (chunks innermost/sequential — state carry)
Blocks (inputs pre-reshaped to (B, nc, c, ...)):
  x   (1, 1, c, 1, P)   adt (1, 1, c, 1)    b/c (1, 1, c, N)
  h0  (1, 1, P, N)
Outputs: y (1, 1, c, 1, P);  h_final (1, 1, P, N)
Scratch: h (P, N) fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _kernel(x_ref, a_ref, b_ref, c_ref, h0_ref, y_ref, hf_ref, h_ref, *,
            nchunks: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        h_ref[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0, :, 0].astype(jnp.float32)          # (c, P)
    adt = a_ref[0, 0].astype(jnp.float32)              # (c,)
    bm = b_ref[0, 0].astype(jnp.float32)               # (c, N)
    cm = c_ref[0, 0].astype(jnp.float32)               # (c, N)
    cseq = x.shape[0]

    cs = jnp.cumsum(adt)                               # (c,) inclusive
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())))   # (c, c)
    decay = jnp.exp(cs[:, None] - cs[None, :])
    tri = (jax.lax.broadcasted_iota(jnp.int32, (cseq, cseq), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (cseq, cseq), 1))
    m = jnp.where(tri, decay, 0.0) * cb
    y = jax.lax.dot(m, x)                              # (c, P) intra
    # inter-chunk: exp(cs_t) · C_t · h_prev    (h: (P, N))
    y += jnp.exp(cs)[:, None] * jax.lax.dot_general(
        cm, h_ref[...], (((1,), (1,)), ((), ())))      # (c, N)·(P, N)ᵀ
    y_ref[0, 0, :, 0] = y.astype(y_ref.dtype)

    # state update
    end = cs[-1]
    w = jnp.exp(end - cs)                              # (c,)
    h_new = h_ref[...] * jnp.exp(end) + jax.lax.dot_general(
        x, bm * w[:, None], (((0,), (0,)), ((), ())))  # (P, N)
    h_ref[...] = h_new

    @pl.when(k == nchunks - 1)
    def _final():
        hf_ref[0, 0] = h_new.astype(hf_ref.dtype)


def ssd_scan_fwd(x, adt, b, c, h0, *, chunk: int = 128,
                 interpret: bool = False):
    """x: (B, S, H, P) Δ-weighted input; adt: (B, S, H) = a·Δ (≤ 0);
    b, c: (B, S, N); h0: (B, H, P, N) fp32.
    Returns y (B, S, H, P) fp32 (no D-skip) and h_final (B, H, P, N) fp32.
    """
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    chunk = min(chunk, s)
    while s % chunk:
        chunk //= 2
    nc = s // chunk

    xr = x.reshape(bsz, nc, chunk, h, p)
    ar = adt.reshape(bsz, nc, chunk, h)
    br = b.reshape(bsz, nc, chunk, n)
    cr = c.reshape(bsz, nc, chunk, n)

    grid = (bsz * h, nc)
    kern = functools.partial(_kernel, nchunks=nc)

    y, hf = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, 1, p), lambda bh, k: (bh // h, k, 0, bh % h, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda bh, k: (bh // h, k, 0, bh % h)),
            pl.BlockSpec((1, 1, chunk, n), lambda bh, k: (bh // h, k, 0, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda bh, k: (bh // h, k, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bh, k: (bh // h, bh % h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, 1, p), lambda bh, k: (bh // h, k, 0, bh % h, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bh, k: (bh // h, bh % h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, nc, chunk, h, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(xr, ar.reshape(bsz, nc, chunk, h), br, cr, h0.astype(jnp.float32))
    return y.reshape(bsz, s, h, p), hf
