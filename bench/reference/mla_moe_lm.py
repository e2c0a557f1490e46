"""Plain float32 reference of the latent-attention expert model the
DeepSeek-V2-Lite cell serves: one full forward over whole sequences,
straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no absorption.  It imports nothing of the program.

Per layer: RMSNorm; multi-head latent attention with keys and values made
per head from the latent (k = [c W_UK, k_rope], v = c W_UV, c the normed
latent), YaRN rope on the rope parts, scale (nope + rope)^-1/2 times
mscale(factor, mscale_all_dim)^2, causal softmax; RMSNorm; the first
``first_k_dense`` layers a dense SwiGLU, the others the expert layer: a
softmax over all router outputs, the greedy top-k, renormalized only if
``norm_topk_prob``, times ``routed_scaling_factor``; of the routed experts
only those held (0 .. n_routed_experts-1) are computed, each weighting its
output by its gate; the shared SwiGLU on every token.  Untied unembedding.

Departures from DeepSeek's modeling code (``modeling_deepseek.py``):

- the rope dimensions rotate in halves (rotate-half) where DeepSeek pairs
  interleaved dimensions: a fixed permutation of the rope columns of the
  query and latent projections, which random weights cannot tell apart;
- experts held elsewhere contribute nothing (the chip's share of an
  8-chip expert-parallel layer, as the program computes it);
- no auxiliary balance loss and no dropout (inference only);
- the router is f32 and computed in f32 (DeepSeek stores it in the model's
  type and upcasts for the gate).

``fp8=True`` is the control: every matmul's operands rounded to float8
e4m3 (``dense_lm.fake_fp8``), the step below the configuration's bfloat16.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense_lm import NEG_INF, _mm, _rms


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, yarn: Dict) -> np.ndarray:
    """DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` frequencies."""
    def correction_dim(rotations):
        return (dim * math.log(yarn["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(yarn["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    extrapolate = 1.0 - ramp
    freq_extra = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    freq_inter = 1.0 / (yarn["factor"] * theta ** (np.arange(0, dim, 2) / dim))
    return freq_inter * (1 - extrapolate) + freq_extra * extrapolate


def rope_terms(model: Dict):
    """(inverse frequencies, cos/sin factor, attention scale)."""
    dim, theta = model["qk_rope_head_dim"], model["rope_theta"]
    scale = (model["qk_nope_head_dim"] + dim) ** -0.5
    y = model.get("yarn")
    if not y:
        return 1.0 / theta ** (np.arange(0, dim, 2) / dim), 1.0, scale
    if y["mscale_all_dim"]:
        scale *= yarn_mscale(y["factor"], y["mscale_all_dim"]) ** 2
    rotation = (yarn_mscale(y["factor"], y["mscale"])
                / yarn_mscale(y["factor"], y["mscale_all_dim"]))
    return yarn_inv_freq(dim, theta, y), rotation, scale


def _rope(x, inv_freq, rotation):
    """x (B, S, H, dim), rotate-half at positions 0 .. S-1."""
    ang = (jnp.arange(x.shape[1], dtype=jnp.float32)[:, None]
           * jnp.asarray(inv_freq, jnp.float32)[None])
    cos = (jnp.cos(ang) * rotation)[None, :, None]
    sin = (jnp.sin(ang) * rotation)[None, :, None]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(h, f, fp8, spec_in="bsd,df->bsf", spec_out="bsf,fd->bsd"):
    g = jax.nn.silu(_mm(spec_in, h, f["w_gate"], fp8)) * _mm(
        spec_in, h, f["w_up"], fp8)
    return _mm(spec_out, g, f["w_down"], fp8)


def _experts(h, moe, model: Dict, fp8: bool):
    """The held share of the expert layer plus the shared SwiGLU."""
    probs = jax.nn.softmax(_mm("bsd,de->bse", h, moe["router"], fp8), -1)
    gates, chosen = jax.lax.top_k(probs, model["experts_per_token"])
    if model["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)
    gates = gates * model["routed_scaling_factor"]
    held = moe["w_gate"].shape[0]
    onehot = jax.nn.one_hot(chosen, model["num_experts"])[..., :held]
    weight = jnp.einsum("bske,bsk->bse", onehot, gates)        # (B, S, held)
    g = _mm("bsd,edf->bsef", h, moe["w_gate"], fp8)
    u = _mm("bsd,edf->bsef", h, moe["w_up"], fp8)
    y = _mm("bsef,efd->bsed", jax.nn.silu(g) * u, moe["w_down"], fp8)
    out = jnp.einsum("bsed,bse->bsd", y, weight)
    if "shared" in moe:
        out = out + _swiglu(h, moe["shared"], fp8)
    return out


def forward(params, tokens, model: Dict, *, fp8: bool = False):
    """tokens (B, S) int32 -> logits (B, S, V) float32."""
    with jax.default_matmul_precision("highest"):
        return _forward(params, tokens, model, fp8)


def _forward(params, tokens, model, fp8):
    eps = model["norm_eps"]
    r, nope = model["kv_lora_rank"], model["qk_nope_head_dim"]
    inv_freq, rotation, scale = rope_terms(model)
    s = tokens.shape[1]
    pos = jnp.arange(s)
    causal = pos[:, None] >= pos[None, :]
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)

    def layer(x, lp):
        lp = f32(lp)
        a = lp["attn"]
        h = _rms(x, lp["ln_attn"], eps)
        q = _mm("bsd,dhe->bshe", h, a["wq"], fp8)
        q = jnp.concatenate([q[..., :nope],
                             _rope(q[..., nope:], inv_freq, rotation)], -1)
        kv = _mm("bsd,dc->bsc", h, a["wkv_a"], fp8)
        c = _rms(kv[..., :r], a["kv_norm"], eps)
        k_rope = _rope(kv[..., None, r:], inv_freq, rotation)      # one head
        k_nope = _mm("bsc,chn->bshn", c, a["w_uk"], fp8)
        v = _mm("bsc,chv->bshv", c, a["w_uv"], fp8)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, k_nope.shape[:3]
                                      + k_rope.shape[-1:])], -1)
        sc = _mm("bqhe,bkhe->bhqk", q, k, fp8) * scale
        sc = jnp.where(causal[None, None], sc, NEG_INF)
        o = _mm("bhqk,bkhe->bqhe", jax.nn.softmax(sc, axis=-1), v, fp8)
        x = x + _mm("bshe,hed->bsd", o, a["wo"], fp8)
        h = _rms(x, lp["ln_ffn"], eps)
        if "ffn" in lp:
            return x + _swiglu(h, lp["ffn"], fp8), None
        return x + _experts(h, lp["moe"], model, fp8), None

    x = params["embed"][tokens].astype(jnp.float32)
    for stack in ("dense_layers", "layers"):
        if stack in params:
            x, _ = jax.lax.scan(layer, x, params[stack])
    x = _rms(x, params["ln_f"].astype(jnp.float32), eps)
    return _mm("bsd,dv->bsv", x, params["unembed"].astype(jnp.float32), fp8)
