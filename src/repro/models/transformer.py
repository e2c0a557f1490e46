"""Decoder-only transformer backbone (dense / MoE / VLM families).

Layers are stacked (leading axis L) and applied with lax.scan so the HLO is
depth-independent; each layer body is rematerialized in the loss path.

A configuration with ``kv_lora_rank`` (DeepSeek-V2) takes multi-head latent
attention and DeepSeek's FFN: its ``first_k_dense`` leading layers
(``params["dense_layers"]``, a dense SwiGLU) and its expert layers
(``params["layers"]``, the held-expert layer with shared experts) are two
scanned stacks.  Its decode is absorbed: the cache holds per position and
layer only the normed latent and the roped shared key.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MOE, VLM, ModelConfig
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models.common import (
    apply_mrope,
    apply_rope,
    chunked_softmax_xent,
    dense_init,
    dtype_of,
    embed_init,
    init_swiglu,
    rms_norm,
    rope_freqs,
    rope_rotate,
    swiglu,
    yarn_inv_freq,
    yarn_mscale,
)

Array = jax.Array


# -- parameter init ----------------------------------------------------------
def init_attn(key, cfg: ModelConfig, dtype):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, cfg.num_heads, hd), dtype),
        "wk": dense_init(ks[1], (d, cfg.num_kv_heads, hd), dtype),
        "wv": dense_init(ks[2], (d, cfg.num_kv_heads, hd), dtype),
        "wo": dense_init(ks[3], (cfg.num_heads, hd, d), dtype),
    }


def init_mla(key, cfg: ModelConfig, dtype):
    d, h = cfg.d_model, cfg.num_heads
    r, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    nope, v = cfg.qk_nope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 5)

    def init(k, shape, fan_in):
        return dense_init(k, shape, dtype, scale=fan_in ** -0.5)

    return {
        "wq": init(ks[0], (d, h, nope + rope), d),
        "wkv_a": init(ks[1], (d, r + rope), d),
        "kv_norm": jnp.ones((r,), jnp.float32),
        "w_uk": init(ks[2], (r, h, nope), r),
        "w_uv": init(ks[3], (r, h, v), r),
        "wo": init(ks[4], (h, v, d), h * v),
    }


def init_mla_layer(key, cfg: ModelConfig, dtype, *, dense: bool):
    """A DeepSeek layer: MLA, then a dense SwiGLU of ``dense_d_ff``
    (``dense``) or the held-expert layer."""
    ks = jax.random.split(key, 2)
    d = cfg.d_model
    p = {"attn": init_mla(ks[0], cfg, dtype),
         "ln_attn": jnp.ones((d,), jnp.float32),
         "ln_ffn": jnp.ones((d,), jnp.float32)}
    if dense:
        p["ffn"] = init_swiglu(ks[1], d, cfg.dense_d_ff, dtype)
    else:
        p["moe"] = moe_lib.init_held_moe(
            ks[1], d, cfg.d_ff, cfg.num_experts, cfg.n_routed_experts,
            cfg.num_shared_experts * cfg.d_ff, dtype)
    return p


def init_layer(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 3)
    p = {
        "attn": init_attn(ks[0], cfg, dtype),
        "ln_attn": jnp.ones((cfg.d_model,), jnp.float32),
        "ln_ffn": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if cfg.family == MOE:
        p["moe"] = moe_lib.init_moe(ks[1], cfg.d_model, cfg.d_ff, cfg.num_experts, dtype)
    else:
        from repro.models.common import init_swiglu

        p["ffn"] = init_swiglu(ks[1], cfg.d_model, cfg.d_ff, dtype)
    return p


def init_params(key, cfg: ModelConfig):
    dtype = dtype_of(cfg)
    k_emb, k_layers, k_out = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    if cfg.kv_lora_rank:
        k = cfg.first_k_dense
        layers = jax.vmap(lambda key: init_mla_layer(
            key, cfg, dtype, dense=False))(layer_keys[k:])
    else:
        layers = jax.vmap(lambda k: init_layer(k, cfg, dtype))(layer_keys)
    params = {
        "embed": embed_init(k_emb, (cfg.vocab_size, cfg.d_model), dtype),
        "layers": layers,
        "ln_f": jnp.ones((cfg.d_model,), jnp.float32),
    }
    if cfg.kv_lora_rank and cfg.first_k_dense:
        params["dense_layers"] = jax.vmap(lambda key: init_mla_layer(
            key, cfg, dtype, dense=True))(layer_keys[:cfg.first_k_dense])
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(k_out, (cfg.d_model, cfg.vocab_size), dtype)
    return params


def unembed_of(params):
    return params.get("unembed", params["embed"].T)


# -- layer application -------------------------------------------------------
def _qkv(layer, cfg, x, positions):
    q = jnp.einsum("bsd,dhe->bshe", x, layer["attn"]["wq"])
    k = jnp.einsum("bsd,dhe->bshe", x, layer["attn"]["wk"])
    v = jnp.einsum("bsd,dhe->bshe", x, layer["attn"]["wv"])
    if cfg.mrope_sections is not None:
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def layer_apply(layer, cfg: ModelConfig, x: Array, positions) -> tuple[Array, Array]:
    """Full-sequence layer.  Returns (x, moe_aux_loss)."""
    h = rms_norm(x, layer["ln_attn"], cfg.norm_eps)
    q, k, v = _qkv(layer, cfg, h, positions)
    o = attn.attention(q, k, v, causal=True, window=cfg.sliding_window,
                       use_pallas=cfg.use_pallas_kernels)
    x = x + jnp.einsum("bshe,hed->bsd", o, layer["attn"]["wo"])

    h = rms_norm(x, layer["ln_ffn"], cfg.norm_eps)
    if cfg.family == MOE:
        f, aux = moe_lib.moe_ffn(
            h, layer["moe"], top_k=cfg.experts_per_token,
            capacity_factor=cfg.moe_capacity_factor)
    else:
        from repro.models.common import swiglu

        f = swiglu(h, layer["ffn"]["w_gate"], layer["ffn"]["w_up"], layer["ffn"]["w_down"])
        aux = jnp.zeros((), jnp.float32)
    return x + f, aux


def layer_decode(layer, cfg: ModelConfig, x: Array, kcache, vcache, pos) -> tuple[Array, Array, Array]:
    """One-token layer step.  x: (B, 1, d); kcache/vcache: (B, L, Hkv, hd)."""
    ring = cfg.sliding_window is not None
    h = rms_norm(x, layer["ln_attn"], cfg.norm_eps)
    positions = jnp.full((x.shape[0], 1), pos)
    if cfg.mrope_sections is not None:
        positions = jnp.broadcast_to(pos, (3, x.shape[0], 1))
    q, k, v = _qkv(layer, cfg, h, positions)
    kcache, vcache = attn.cache_insert(kcache, vcache, k, v, pos, ring=ring)
    o = attn.decode_attention(q, kcache, vcache, pos, ring=ring)
    x = x + jnp.einsum("bshe,hed->bsd", o, layer["attn"]["wo"])

    h = rms_norm(x, layer["ln_ffn"], cfg.norm_eps)
    if cfg.family == MOE:
        f, _ = moe_lib.moe_ffn(
            h, layer["moe"], top_k=cfg.experts_per_token,
            capacity_factor=float(cfg.num_experts) / max(cfg.experts_per_token, 1))
    else:
        from repro.models.common import swiglu

        f = swiglu(h, layer["ffn"]["w_gate"], layer["ffn"]["w_up"], layer["ffn"]["w_down"])
    return x + f, kcache, vcache


# -- multi-head latent attention layers (DeepSeek-V2) ---------------------------
def mla_rope(cfg: ModelConfig):
    """(inverse frequencies of the rope part, the factor on its cos and
    sin, the attention scale): YaRN's where the configuration scales rope,
    whose scale gains mscale(factor, mscale_all_dim) squared."""
    dim, theta = cfg.qk_rope_head_dim, cfg.rope_theta
    scale = (cfg.qk_nope_head_dim + dim) ** -0.5
    y = cfg.yarn
    if y is None:
        return rope_freqs(dim, theta), 1.0, scale
    rotation = yarn_mscale(y.factor, y.mscale) / yarn_mscale(
        y.factor, y.mscale_all_dim)
    if y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return yarn_inv_freq(dim, theta, y), rotation, scale


def _mla_project(ap, cfg: ModelConfig, h, positions):
    """q (B, S, H, nope + rope), its rope part roped, and ckv (B, S, r +
    rope): per position the normed latent and the roped shared key."""
    inv_freq, rotation, _ = mla_rope(cfg)
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhe->bshe", h, ap["wq"])
    q = jnp.concatenate(
        [q[..., :nope], rope_rotate(q[..., nope:], positions, inv_freq,
                                    rotation)], axis=-1)
    kv = jnp.einsum("bsd,dc->bsc", h, ap["wkv_a"])
    c = rms_norm(kv[..., :r], ap["kv_norm"], cfg.norm_eps)
    k_rope = rope_rotate(kv[..., None, r:], positions, inv_freq, rotation)
    return q, jnp.concatenate([c, k_rope[..., 0, :]], axis=-1)


def mla_full(ap, cfg: ModelConfig, h, positions):
    """MLA over a whole sequence, keys and values made per head from the
    latent."""
    with jax.named_scope("model.attn.mla"):
        q, ckv = _mla_project(ap, cfg, h, positions)
        r = cfg.kv_lora_rank
        c, k_rope = ckv[..., :r], ckv[..., r:]
        k_nope = jnp.einsum("bsc,chn->bshn", c, ap["w_uk"])
        v = jnp.einsum("bsc,chv->bshv", c, ap["w_uv"])
        k_rope = jnp.broadcast_to(k_rope[:, :, None],
                                  k_nope.shape[:3] + k_rope.shape[-1:])
        k = jnp.concatenate([k_nope, k_rope], axis=-1)
        o = attn.mla_attention(q, k, v, mla_rope(cfg)[2])
        return jnp.einsum("bshv,hvd->bsd", o, ap["wo"])


def mla_decode(ap, cfg: ModelConfig, h, ckv_cache, pos):
    """The absorbed one-token MLA step: writes this position's latent and
    roped key into ``ckv_cache`` (B, L, r + rope), absorbs W_UK into the
    query and W_UV after the latent output, so no per-head key or value
    is made.  Returns (output (B, 1, d), cache)."""
    with jax.named_scope("model.attn.mla"):
        positions = jnp.full((h.shape[0], 1), pos)
        q, ckv = _mla_project(ap, cfg, h, positions)
        ckv_cache = jax.lax.dynamic_update_slice_in_dim(
            ckv_cache, ckv.astype(ckv_cache.dtype), pos, axis=1)
        nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
        q_lat = jnp.einsum("bshn,chn->bshc", q[..., :nope], ap["w_uk"])
        q_cat = jnp.concatenate([q_lat, q[..., nope:]], axis=-1)
        o = attn.mla_decode_attention(q_cat, ckv_cache, pos,
                                      mla_rope(cfg)[2])[..., :r]
        o = jnp.einsum("bshc,chv->bshv", o.astype(h.dtype), ap["w_uv"])
        return jnp.einsum("bshv,hvd->bsd", o, ap["wo"]), ckv_cache


def _mla_ffn(layer, cfg: ModelConfig, h):
    """(output, token-slots routed to held experts) of a DeepSeek layer's
    FFN: the dense SwiGLU of a leading layer, else the held-expert layer."""
    if "ffn" in layer:
        f = layer["ffn"]
        return (swiglu(h, f["w_gate"], f["w_up"], f["w_down"]),
                jnp.zeros((), jnp.int32))
    return moe_lib.held_moe_ffn(
        h, layer["moe"], held=jnp.arange(cfg.n_routed_experts),
        top_k=cfg.experts_per_token, norm_topk=cfg.norm_topk_prob,
        scaling=cfg.routed_scaling_factor)


def mla_layer_apply(layer, cfg: ModelConfig, x: Array, positions) -> Array:
    h = rms_norm(x, layer["ln_attn"], cfg.norm_eps)
    x = x + mla_full(layer["attn"], cfg, h, positions)
    h = rms_norm(x, layer["ln_ffn"], cfg.norm_eps)
    return x + _mla_ffn(layer, cfg, h)[0]


def mla_layer_decode(layer, cfg: ModelConfig, x: Array, ckv, pos):
    """One-token DeepSeek layer: (x, ckv cache, routed token-slots)."""
    h = rms_norm(x, layer["ln_attn"], cfg.norm_eps)
    o, ckv = mla_decode(layer["attn"], cfg, h, ckv, pos)
    x = x + o
    h = rms_norm(x, layer["ln_ffn"], cfg.norm_eps)
    f, routed = _mla_ffn(layer, cfg, h)
    return x + f, ckv, routed


#: the two stacks of a DeepSeek model, in order, with their cache leaves
MLA_STACKS = (("dense_layers", "ckv_dense"), ("layers", "ckv"))


def _mla_forward(params, cfg: ModelConfig, batch, remat: bool):
    x = embed_inputs(params, cfg, batch)
    positions = _positions_for(cfg, batch, x.shape[1])

    def body(x, layer):
        return mla_layer_apply(layer, cfg, x, positions), None

    if remat:
        body = jax.checkpoint(body)
    for stack, _ in MLA_STACKS:
        if stack in params:
            x, _ = jax.lax.scan(body, x, params[stack])
    return rms_norm(x, params["ln_f"], cfg.norm_eps), jnp.zeros((), jnp.float32)


def _mla_decode_step(params, cfg: ModelConfig, tokens: Array, cache):
    pos = cache["pos"]
    x = jnp.take(params["embed"], tokens, axis=0)
    new = {"pos": pos + 1}
    routed = jnp.zeros((), jnp.int32)

    def body(carry, inputs):
        x, n = carry
        layer, ckv = inputs
        x, ckv, r = mla_layer_decode(layer, cfg, x, ckv, pos)
        return (x, n + r), ckv

    for stack, leaf in MLA_STACKS:
        if stack in params:
            (x, routed), new[leaf] = jax.lax.scan(
                body, (x, routed), (params[stack], cache[leaf]))
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32),
                        unembed_of(params).astype(jnp.float32))
    return logits, new, {"expert_slots": routed}


def _mla_init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype):
    """Per position and layer the normed latent and the roped shared key
    (``kv_lora_rank + qk_rope_head_dim`` values)."""
    width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
    k = cfg.first_k_dense
    cache = {"ckv": jnp.zeros((cfg.num_layers - k, batch, seq_len, width), dtype),
             "pos": jnp.zeros((), jnp.int32)}
    if k:
        cache["ckv_dense"] = jnp.zeros((k, batch, seq_len, width), dtype)
    return cache


# -- full model --------------------------------------------------------------
def _positions_for(cfg, batch, seq):
    if cfg.mrope_sections is not None:
        return batch["positions"]                    # (3, B, S) provided (M-RoPE)
    b = batch["tokens"].shape[0]
    return jnp.broadcast_to(jnp.arange(seq)[None], (b, seq))


def embed_inputs(params, cfg: ModelConfig, batch) -> Array:
    tok = jnp.take(params["embed"], batch["tokens"], axis=0)
    if cfg.family == VLM and cfg.num_media_tokens:
        media = batch["media"].astype(tok.dtype)     # (B, M, d) stubbed frontend
        tok = jnp.concatenate([media, tok], axis=1)
    return tok


def forward(params, cfg: ModelConfig, batch, *, remat: bool = True):
    """Returns final hidden states (B, S, d) and total moe aux loss."""
    if cfg.kv_lora_rank:
        return _mla_forward(params, cfg, batch, remat)
    x = embed_inputs(params, cfg, batch)
    positions = _positions_for(cfg, batch, x.shape[1])

    def body(carry, layer):
        x, aux = carry
        x2, a = layer_apply(layer, cfg, x, positions)
        return (x2, aux + a), None

    if remat:
        body = jax.checkpoint(body)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), params["layers"])
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    return x, aux


def loss_fn(params, cfg: ModelConfig, batch):
    h, aux = forward(params, cfg, batch)
    labels = batch["labels"]
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)
        if cfg.family == VLM and cfg.num_media_tokens:
            mask = mask.at[:, : cfg.num_media_tokens].set(0.0)
    xent = chunked_softmax_xent(h, unembed_of(params), labels, mask, cfg.xent_chunk)
    return xent + cfg.router_aux_loss_coef * aux, {"xent": xent, "moe_aux": aux}


def init_cache(cfg: ModelConfig, batch: int, seq_len: int, dtype=None):
    dtype = dtype or dtype_of(cfg)
    if cfg.kv_lora_rank:
        return _mla_init_cache(cfg, batch, seq_len, dtype)
    lc = attn.cache_length(seq_len, cfg.sliding_window)
    hd = cfg.resolved_head_dim
    shape = (cfg.num_layers, batch, lc, cfg.num_kv_heads, hd)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "pos": jnp.zeros((), jnp.int32),
    }


def decode_step(params, cfg: ModelConfig, tokens: Array, cache):
    """tokens: (B, 1) -> logits (B, 1, V), new cache."""
    if cfg.kv_lora_rank:
        return _mla_decode_step(params, cfg, tokens, cache)[:2]
    pos = cache["pos"]
    x = jnp.take(params["embed"], tokens, axis=0)

    def body(x, inputs):
        layer, kc, vc = inputs
        x, kc, vc = layer_decode(layer, cfg, x, kc, vc, pos)
        return x, (kc, vc)

    x, (knew, vnew) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32),
                        unembed_of(params).astype(jnp.float32))
    return logits, {"k": knew, "v": vnew, "pos": pos + 1}


def decode_step_stats(params, cfg: ModelConfig, tokens: Array, cache):
    """``decode_step`` and its per-step counters: a DeepSeek model counts
    the token-slots routed to held experts (``expert_slots``, all layers);
    other configurations count nothing."""
    if cfg.kv_lora_rank:
        return _mla_decode_step(params, cfg, tokens, cache)
    return (*decode_step(params, cfg, tokens, cache), {})


def prefill(params, cfg: ModelConfig, batch):
    """Teacher-forced full forward returning last-position logits (serving path)."""
    h, _ = forward(params, cfg, batch, remat=False)
    logits = jnp.einsum("bd,dv->bv", h[:, -1].astype(jnp.float32),
                        unembed_of(params).astype(jnp.float32))
    return logits
