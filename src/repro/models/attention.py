"""Attention: GQA with optional sliding window; blockwise-exact prefill and
ring-buffer KV-cache decode.

The model-level implementation is pure jnp and memory-bounded (online-softmax
over KV blocks, never materializing an (S, S) score matrix).  The
perf-critical SWA path has a Pallas kernel twin in ``repro.kernels.swa_attention``
validated against ``ref.py`` == this module's math.

FLOPs note for the roofline: the full-attention path computes all (q, kv)
blocks and masks above the diagonal, so HLO FLOPs count the non-causal 2x —
the same convention as a dense softmax(QK^T)V baseline.  The SWA path is
banded (linear in S).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Array = jax.Array

NEG_INF = -1e30


def _grouped(q, hkv):
    """(B, S, Hq, hd) -> (B, S, Hkv, G, hd)."""
    b, s, hq, hd = q.shape
    return q.reshape(b, s, hkv, hq // hkv, hd)


def attention(
    q: Array,              # (B, Sq, Hq, hd)
    k: Array,              # (B, Skv, Hkv, hd)
    v: Array,              # (B, Skv, Hkv, hd)
    *,
    causal: bool = True,
    window: int | None = None,
    q_block: int = 1024,
    kv_block: int = 1024,
    use_pallas: bool = False,
) -> Array:
    """Blockwise-exact attention; O(S·w) for sliding window, else O(S²)."""
    b, sq, hq, hd = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    scale = hd ** -0.5
    qg = _grouped(q, hkv)

    q_block = min(q_block, sq)
    while sq % q_block:
        q_block //= 2
    nq = sq // q_block

    if window is not None:
        if use_pallas and sq == skv:
            from repro.kernels import interpret_mode
            from repro.kernels.swa_attention.ops import swa_attention
            return swa_attention(
                q, k, v, window=window, block_q=min(q_block, 128),
                interpret=interpret_mode())
        return _swa(qg, k, v, window=window, q_block=q_block, scale=scale)

    kv_block = min(kv_block, skv)
    while skv % kv_block:
        kv_block //= 2
    nkv = skv // kv_block

    kb = k.reshape(b, nkv, kv_block, hkv, hd)
    vb = v.reshape(b, nkv, kv_block, hkv, hd)
    qb = qg.reshape(b, nq, q_block, hkv, hq // hkv, hd)

    def per_q_block(qi, qcur):
        # online softmax over kv blocks
        m0 = jnp.full((b, hkv, hq // hkv, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, hq // hkv, q_block), jnp.float32)
        a0 = jnp.zeros((b, q_block, hkv, hq // hkv, hd), jnp.float32)

        def kv_step(carry, inputs):
            m, l, acc = carry
            ki, kcur, vcur = inputs
            s = jnp.einsum("bqkgd,bskd->bkgqs", qcur.astype(jnp.float32),
                           kcur.astype(jnp.float32)) * scale
            if causal:
                qpos = qi * q_block + jnp.arange(q_block)
                kpos = ki * kv_block + jnp.arange(kv_block)
                mask = qpos[:, None] >= kpos[None, :]
                s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bqkgd", p, vcur.astype(jnp.float32))
            acc_new = acc * jnp.moveaxis(corr, -1, 1)[..., None] + pv
            return (m_new, l_new, acc_new), None

        ks_in = (jnp.arange(nkv), jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0))
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), ks_in)
        out = acc / jnp.maximum(jnp.moveaxis(l, -1, 1), 1e-30)[..., None]
        return out

    def q_step(_, inputs):
        qi, qcur = inputs
        return None, per_q_block(qi, qcur)

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), jnp.moveaxis(qb, 1, 0)))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, sq, hq, hd)
    return out.astype(q.dtype)


def _swa(qg, k, v, *, window: int, q_block: int, scale: float):
    """Banded causal attention: each q block sees the previous `window` keys."""
    b, sq, hkv, g, hd = qg.shape
    skv = k.shape[1]
    nq = sq // q_block
    span = window + q_block          # kv needed per q block
    # pad kv on the left by `window` so the slice start is always >= 0
    kp = jnp.pad(k, ((0, 0), (window, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (window, 0), (0, 0), (0, 0)))
    qb = qg.reshape(b, nq, q_block, hkv, g, hd)

    def q_step(_, inputs):
        qi, qcur = inputs
        start = qi * q_block         # in padded coords == qpos - window
        kcur = jax.lax.dynamic_slice_in_dim(kp, start, span, axis=1)
        vcur = jax.lax.dynamic_slice_in_dim(vp, start, span, axis=1)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qcur.astype(jnp.float32),
                       kcur.astype(jnp.float32)) * scale
        qpos = qi * q_block + jnp.arange(q_block)              # absolute
        kpos = start + jnp.arange(span) - window               # absolute (may be <0)
        mask = (qpos[:, None] >= kpos[None, :]) \
            & (qpos[:, None] - kpos[None, :] < window) \
            & (kpos[None, :] >= 0)
        s = jnp.where(mask, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgqs,bskd->bqkgd", p, vcur.astype(jnp.float32))
        return None, out

    _, outs = jax.lax.scan(q_step, None, (jnp.arange(nq), jnp.moveaxis(qb, 1, 0)))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, sq, hkv * g, hd)
    return out.astype(k.dtype)


# -- decode ------------------------------------------------------------------
def cache_length(seq_len: int, window: int | None) -> int:
    return seq_len if window is None else min(seq_len, window)


def decode_attention(
    q: Array,              # (B, 1, Hq, hd)
    k_cache: Array,        # (B, L, Hkv, hd)  (already includes the new token)
    v_cache: Array,
    pos: Array,            # scalar int32: absolute position of the new token
    *,
    ring: bool,
) -> Array:
    b, _, hq, hd = q.shape
    l, hkv = k_cache.shape[1], k_cache.shape[2]
    qg = _grouped(q, hkv)[:, 0]                                  # (B, Hkv, G... ) -> (B, Hkv? )
    # qg: (B, Hkv, G, hd) after dropping seq axis
    s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) * hd ** -0.5
    slots = jnp.arange(l)
    if ring:
        valid = jnp.where(pos + 1 >= l, jnp.ones((l,), bool), slots <= pos)
    else:
        valid = slots <= pos
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, hq, hd).astype(q.dtype)


def cache_insert(k_cache: Array, v_cache: Array, k_new: Array, v_new: Array, pos: Array, *, ring: bool):
    """Insert one token's K/V at slot pos (ring: pos % L)."""
    l = k_cache.shape[1]
    slot = pos % l if ring else pos
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new.astype(k_cache.dtype), slot, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new.astype(v_cache.dtype), slot, axis=1)
    return k_cache, v_cache


# -- multi-head latent attention (DeepSeek-V2) --------------------------------
def mla_attention(q: Array, k: Array, v: Array, scale: float) -> Array:
    """Full-sequence causal attention whose values are narrower than its
    queries and keys: q, k (B, S, H, dqk), v (B, S, H, dv) -> (B, S, H, dv).
    Scores and softmax in f32.  It holds the (S, S) scores: it serves the
    training loss and ``prefill``; the served path is
    :func:`mla_decode_attention`."""
    s = jnp.einsum("bqhe,bkhe->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    pos = jnp.arange(q.shape[1])
    s = jnp.where(pos[:, None] >= pos[None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhe->bqhe", p, v.astype(jnp.float32))
    return out.astype(v.dtype)


def mla_decode_attention(q_cat: Array, ckv: Array, pos: Array,
                         scale: float) -> Array:
    """The absorbed decode's attention over the latent cache.

    ``q_cat`` (B, 1, H, r + rope) is each head's query in the cache's
    coordinates: the nope query absorbed into the latent (q_nope W_UK^T)
    beside the roped query.  ``ckv`` (B, L, r + rope) holds per position the
    normed latent and the roped key all heads share, so one product gives
    (q_nope W_UK^T) . c + q_rope . k_rope.  The operands stay in the cache's
    type; scores and softmax are f32.  Returns p . ckv (B, 1, H, r + rope)
    f32, whose first r columns are the latent output (the last rope columns
    are the price of not slicing the cache)."""
    s = jnp.einsum("bshc,btc->bhst", q_cat.astype(ckv.dtype), ckv,
                   preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(ckv.shape[1]) <= pos
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhst,btc->bshc", p.astype(ckv.dtype), ckv,
                      preferred_element_type=jnp.float32)


def reference_attention(q, k, v, *, causal=True, window=None):
    """Naive O(S^2) oracle used by tests (and kernels/swa_attention/ref.py)."""
    b, sq, hq, hd = q.shape
    hkv = k.shape[2]
    qg = _grouped(q, hkv)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32), k.astype(jnp.float32)) * hd ** -0.5
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((sq, k.shape[1]), bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= qpos - kpos < window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, hd).astype(q.dtype)
