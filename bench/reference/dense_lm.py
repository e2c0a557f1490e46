"""Plain float32 reference of the dense decoder the cells run: RMSNorm,
rotary positions (rotate-half), grouped-query causal attention with an
optional sliding window, SwiGLU, untied unembedding.  Straight ``jax.numpy``
at the highest matmul precision, no kernels, no cache, no batching tricks:
one full forward over whole sequences.  It imports nothing of the program.

``fp8=True`` is the control: every matmul's operands are rounded to
float8 e4m3 (one scale per tensor) before the product, the step below the
configuration's bfloat16.  Gradients pass the rounding straight through.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from bench.weights import dims

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
NEG_INF = -1e30


def fake_fp8(x):
    """Round ``x`` to e4m3 with one per-tensor scale; identity gradient."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, 1e-30) / E4M3_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec: str, a, b, fp8: bool):
    if fp8:
        a, b = fake_fp8(a), fake_fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    """x (B, S, H, hd); rotate-half with frequencies theta^(-2i/hd)."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[:, None].astype(jnp.float32) * freqs[None]     # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(params, tokens, model: Dict, *, fp8: bool = False):
    """tokens (B, S) int32 -> logits (B, S, V) float32."""
    n = dims(model)
    eps, theta = model["norm_eps"], model["rope_theta"]
    window = model.get("sliding_window")
    group = n["H"] // n["Hkv"]
    s = tokens.shape[1]
    pos = jnp.arange(s)
    allowed = pos[:, None] >= pos[None, :]
    if window is not None:
        allowed &= pos[:, None] - pos[None, :] < window
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)

    def layer(x, lp):
        lp = f32(lp)
        a = lp["attn"]
        h = _rms(x, lp["ln_attn"], eps)
        q = _rope(_mm("bsd,dhe->bshe", h, a["wq"], fp8), pos, theta)
        k = _rope(_mm("bsd,dhe->bshe", h, a["wk"], fp8), pos, theta)
        v = _mm("bsd,dhe->bshe", h, a["wv"], fp8)
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        sc = _mm("bqhe,bkhe->bhqk", q, k, fp8) * n["hd"] ** -0.5
        sc = jnp.where(allowed[None, None], sc, NEG_INF)
        o = _mm("bhqk,bkhe->bqhe", jax.nn.softmax(sc, axis=-1), v, fp8)
        x = x + _mm("bshe,hed->bsd", o, a["wo"], fp8)
        h = _rms(x, lp["ln_ffn"], eps)
        f = lp["ffn"]
        g = jax.nn.silu(_mm("bsd,df->bsf", h, f["w_gate"], fp8)) \
            * _mm("bsd,df->bsf", h, f["w_up"], fp8)
        return x + _mm("bsf,fd->bsd", g, f["w_down"], fp8), None

    x = params["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])
    x = _rms(x, params["ln_f"].astype(jnp.float32), eps)
    unembed = params.get("unembed")
    if unembed is None:
        unembed = params["embed"].T
    return _mm("bsd,dv->bsv", x, unembed.astype(jnp.float32), fp8)


def loss(params, batch, model: Dict, *, fp8: bool = False):
    """Mean next-token cross entropy over every position of the batch."""
    logits = forward(params, batch["tokens"], model, fp8=fp8)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)
