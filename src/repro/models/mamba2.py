"""Mamba2 (SSD) block — chunked parallel scan for train/prefill, O(1)-state
recurrent step for decode.  [arXiv:2405.21060 as used by zamba2, arXiv:2411.15242]

State: h ∈ (B, H, P, N) with P = head dim, N = ssm state size.
    h_t = exp(a_h Δ_t) h_{t-1} + Δ_t B_t ⊗ x_t
    y_t = C_t · h_t + D x_t
B_t, C_t shared across heads (ngroups = 1), a_h scalar per head.

The chunked algorithm (chunk c): within a chunk the contribution is an
attention-like banded matmul M[t,s] = C_t·B_s · exp(cs_t − cs_s) · Δ_s (s ≤ t),
across chunks the state is carried by a short lax.scan.  The Pallas kernel in
``repro.kernels.mamba2_scan`` implements the same math per chunk.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import dense_init

Array = jax.Array


def mamba_dims(cfg):
    d_in = cfg.ssm_expand * cfg.d_model
    nheads = d_in // cfg.ssm_head_dim
    return d_in, nheads


def init_mamba_block(key, cfg, dtype):
    d = cfg.d_model
    d_in, nheads = mamba_dims(cfg)
    n = cfg.ssm_state_size
    conv_dim = d_in + 2 * n
    ks = jax.random.split(key, 5)
    return {
        "in_proj": dense_init(ks[0], (d, 2 * d_in + 2 * n + nheads), dtype),
        "conv_w": dense_init(ks[1], (cfg.ssm_conv_width, conv_dim), dtype, scale=0.5),
        "out_proj": dense_init(ks[2], (d_in, d), dtype),
        "a_log": jnp.zeros((nheads,), jnp.float32),
        "d_skip": jnp.ones((nheads,), jnp.float32),
        "dt_bias": (jax.random.uniform(ks[3], (nheads,), jnp.float32) * 2 - 4.0),
    }


def _split_proj(zxbcdt, cfg):
    d_in, nheads = mamba_dims(cfg)
    n = cfg.ssm_state_size
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * n], axis=-1)
    return z, xbc, dt


def _causal_conv(xbc: Array, conv_w: Array) -> Array:
    """Depthwise causal conv over time.  xbc: (B, S, C); conv_w: (W, C)."""
    w = conv_w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (w - 1, 0), (0, 0)))
    out = sum(pad[:, i : i + xbc.shape[1]] * conv_w[i] for i in range(w))
    return jax.nn.silu(out)


def ssd_chunked(x, dt, a, b, c, d_skip, *, chunk: int, h0=None):
    """Chunked SSD scan.

    x: (B, S, H, P); dt: (B, S, H); a: (H,) negative; b, c: (B, S, N).
    Returns y: (B, S, H, P) and final state (B, H, P, N).
    """
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    chunk = min(chunk, s)
    while s % chunk:
        chunk //= 2
    nc = s // chunk

    adt = a[None, None, :] * dt                                  # (B,S,H) ≤ 0
    xr = (x * dt[..., None]).reshape(bsz, nc, chunk, h, p)       # Δ-weighted input
    ar = adt.reshape(bsz, nc, chunk, h)
    br = b.reshape(bsz, nc, chunk, n)
    cr = c.reshape(bsz, nc, chunk, n)

    if h0 is None:
        h0 = jnp.zeros((bsz, h, p, n), jnp.float32)

    def chunk_step(hprev, inputs):
        xc, ac, bc, cc = inputs                                  # (B,c,H,P) (B,c,H) (B,c,N)
        cs = jnp.cumsum(ac, axis=1)                              # (B,c,H) inclusive
        # intra-chunk: M[t,s] = (C_t·B_s) exp(cs_t - cs_s) for s<=t
        cb = jnp.einsum("btn,bsn->bts", cc, bc)                  # (B,c,c)
        decay = cs[:, :, None, :] - cs[:, None, :, :]            # (B,t,s,H)
        mask = jnp.tril(jnp.ones((xc.shape[1], xc.shape[1]), bool))
        m = jnp.where(mask[None, :, :, None], jnp.exp(decay), 0.0) * cb[..., None]
        y_intra = jnp.einsum("btsh,bshp->bthp", m, xc.astype(jnp.float32))
        # inter-chunk: y_t += C_t · (exp(cs_t) h_prev)
        y_inter = jnp.einsum("btn,bhpn,bth->bthp", cc, hprev, jnp.exp(cs))
        # state update: h = exp(cs_end) h_prev + Σ_s exp(cs_end - cs_s) B_s x_s
        end = cs[:, -1:, :]                                      # (B,1,H)
        w = jnp.exp(end - cs)                                    # (B,c,H)
        h_new = hprev * jnp.exp(end)[:, 0, :, None, None] + jnp.einsum(
            "bsh,bsn,bshp->bhpn", w, bc, xc.astype(jnp.float32))
        return h_new, y_intra + y_inter

    ins = tuple(jnp.moveaxis(t, 1, 0) for t in (xr, ar, br, cr))
    h_final, ys = jax.lax.scan(chunk_step, h0, ins)
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, s, h, p)
    y = y + x.astype(jnp.float32) * d_skip[None, None, :, None]
    return y.astype(x.dtype), h_final


def ssd_reference(x, dt, a, b, c, d_skip, h0=None):
    """Token-by-token oracle (lax.scan over time)."""
    bsz, s, h, p = x.shape
    n = b.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((bsz, h, p, n), jnp.float32)

    def step(hprev, inputs):
        xt, dtt, bt, ct = inputs                                 # (B,H,P) (B,H) (B,N)
        decay = jnp.exp(a[None] * dtt)                           # (B,H)
        hnew = hprev * decay[..., None, None] + jnp.einsum(
            "bhp,bn,bh->bhpn", xt.astype(jnp.float32), bt, dtt)
        yt = jnp.einsum("bn,bhpn->bhp", ct, hnew)
        return hnew, yt

    ins = tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c))
    hf, ys = jax.lax.scan(step, h0, ins)
    y = jnp.moveaxis(ys, 0, 1) + x.astype(jnp.float32) * d_skip[None, None, :, None]
    return y.astype(x.dtype), hf


def mamba_block_apply(params, cfg, x: Array, *, chunk: int = 256):
    """Full-sequence mamba2 block.  x: (B, S, d) -> (B, S, d)."""
    d_in, nheads = mamba_dims(cfg)
    n = cfg.ssm_state_size
    zxbcdt = jnp.einsum("bsd,de->bse", x, params["in_proj"])
    z, xbc, dt = _split_proj(zxbcdt, cfg)
    xbc = _causal_conv(xbc, params["conv_w"])
    xin, b, c = jnp.split(xbc, [d_in, d_in + n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    a = -jnp.exp(params["a_log"])
    xh = xin.reshape(*xin.shape[:2], nheads, cfg.ssm_head_dim)
    if cfg.use_pallas_kernels:
        from repro.kernels import interpret_mode
        from repro.kernels.mamba2_scan.ops import ssd_chunked_pallas
        y, _ = ssd_chunked_pallas(xh, dt, a, b, c, params["d_skip"],
                                  chunk=chunk,
                                  interpret=interpret_mode())
    else:
        y, _ = ssd_chunked(xh, dt, a, b, c, params["d_skip"], chunk=chunk)
    y = y.reshape(*x.shape[:2], d_in) * jax.nn.silu(z)
    return jnp.einsum("bse,ed->bsd", y, params["out_proj"])


def init_mamba_cache(cfg, batch: int, dtype):
    d_in, nheads = mamba_dims(cfg)
    n = cfg.ssm_state_size
    return {
        "h": jnp.zeros((batch, nheads, cfg.ssm_head_dim, n), jnp.float32),
        "conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, d_in + 2 * n), dtype),
    }


def mamba_block_decode(params, cfg, x: Array, cache):
    """One-token step.  x: (B, 1, d) -> (B, 1, d), new cache."""
    d_in, nheads = mamba_dims(cfg)
    n = cfg.ssm_state_size
    zxbcdt = jnp.einsum("bsd,de->bse", x, params["in_proj"])
    z, xbc, dt = _split_proj(zxbcdt, cfg)
    # conv over the rolling buffer
    hist = jnp.concatenate([cache["conv"], xbc.astype(cache["conv"].dtype)], axis=1)
    conv_out = jnp.einsum("bwc,wc->bc", hist, params["conv_w"])[:, None]
    conv_out = jax.nn.silu(conv_out)
    xin, b, c = jnp.split(conv_out, [d_in, d_in + n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])[:, 0]  # (B,H)
    a = -jnp.exp(params["a_log"])
    xh = xin[:, 0].reshape(x.shape[0], nheads, cfg.ssm_head_dim)
    decay = jnp.exp(a[None] * dt)
    h = cache["h"] * decay[..., None, None] + jnp.einsum(
        "bhp,bn,bh->bhpn", xh.astype(jnp.float32), b[:, 0], dt)
    y = jnp.einsum("bn,bhpn->bhp", c[:, 0], h)
    y = y + xh.astype(jnp.float32) * params["d_skip"][None, :, None]
    y = y.reshape(x.shape[0], 1, d_in).astype(x.dtype) * jax.nn.silu(z)
    out = jnp.einsum("bse,ed->bsd", y, params["out_proj"])
    new_cache = {"h": h, "conv": hist[:, 1:]}
    return out, new_cache
