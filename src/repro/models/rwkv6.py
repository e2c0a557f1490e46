"""RWKV6 ("Finch") block — attention-free, data-dependent per-channel decay.
[arXiv:2404.05892]

Time-mix recurrence per head (K = V = head dim):
    S_t = diag(w_t) S_{t-1} + k_t v_tᵀ          S ∈ R^{K×V}
    y_t = (S_{t-1} + diag(u) k_t v_tᵀ)ᵀ r_t
with w_t ∈ (0,1)^K data-dependent (low-rank projection of the shifted input).

Train/prefill uses a chunked formulation (same shape of algorithm as SSD):
within-chunk banded matmul with cumulative log-decay, state carried across
chunks by lax.scan.  The Pallas kernel in ``repro.kernels.rwkv6_wkv``
implements the per-chunk computation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import dense_init

Array = jax.Array


def rwkv_dims(cfg):
    nheads = cfg.d_model // cfg.rwkv_head_dim
    return nheads, cfg.rwkv_head_dim


def init_rwkv_block(key, cfg, dtype):
    d = cfg.d_model
    nheads, hd = rwkv_dims(cfg)
    lora = max(32, d // 16)
    ks = jax.random.split(key, 12)
    return {
        # time-mix
        "mu": (jax.random.uniform(ks[0], (5, d), jnp.float32)),   # r,k,v,g,w shifts
        "w_r": dense_init(ks[1], (d, d), dtype),
        "w_k": dense_init(ks[2], (d, d), dtype),
        "w_v": dense_init(ks[3], (d, d), dtype),
        "w_g": dense_init(ks[4], (d, d), dtype),
        "w_o": dense_init(ks[5], (d, d), dtype),
        "w_decay_a": dense_init(ks[6], (d, lora), dtype),
        "w_decay_b": dense_init(ks[7], (lora, d), dtype, scale=0.1),
        "decay_base": jnp.full((d,), -6.0, jnp.float32),
        "u_bonus": jax.random.normal(ks[8], (d,), jnp.float32) * 0.1,
        "ln_x": jnp.ones((d,), jnp.float32),
        # channel-mix
        "mu_cm": jax.random.uniform(ks[9], (2, d), jnp.float32),
        "cm_k": dense_init(ks[10], (d, cfg.d_ff), dtype),
        "cm_v": dense_init(ks[11], (cfg.d_ff, d), dtype),
        "cm_r": dense_init(jax.random.fold_in(key, 99), (d, d), dtype),
    }


def _token_shift(x: Array, x_prev: Array | None = None):
    """x: (B, S, d) -> previous token's x (zeros / x_prev at position 0)."""
    shifted = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    if x_prev is not None:
        shifted = shifted.at[:, 0].set(x_prev.astype(shifted.dtype))
    return shifted


def wkv_chunked(r, k, v, w, u, *, chunk: int, s0=None):
    """Chunked WKV.  r,k,v,w: (B, S, H, K); u: (H, K); w = per-step decay in (0,1).

    Returns y (B, S, H, K) and final state (B, H, K, K) [k-dim, v-dim].
    """
    bsz, s, h, dk = r.shape
    chunk = min(chunk, s)
    while s % chunk:
        chunk //= 2
    nc = s // chunk

    logw = jnp.log(w.astype(jnp.float32))                        # ≤ 0
    rr = r.reshape(bsz, nc, chunk, h, dk)
    kk = k.reshape(bsz, nc, chunk, h, dk)
    vv = v.reshape(bsz, nc, chunk, h, dk)
    ww = logw.reshape(bsz, nc, chunk, h, dk)

    if s0 is None:
        s0 = jnp.zeros((bsz, h, dk, dk), jnp.float32)

    def chunk_step(sprev, inputs):
        rc, kc, vc, wc = inputs                                  # (B,c,H,K)
        cs = jnp.cumsum(wc, axis=1)                              # inclusive cumulative log decay
        excl = cs - wc                                           # exclusive (Π up to t-1)
        rf = rc.astype(jnp.float32)
        kf = kc.astype(jnp.float32)
        vf = vc.astype(jnp.float32)
        # intra-chunk, strictly lower triangular (s < t):
        # k_s v_sᵀ reaches y_t decayed by steps s+1..t-1 = exp(excl_t - cs_s)
        att = jnp.einsum("bthk,bshk->bhts",
                         rf * jnp.exp(excl), kf * jnp.exp(-cs))
        c = rc.shape[1]
        mask = jnp.tril(jnp.ones((c, c), bool), k=-1)
        att = jnp.where(mask[None, None], att, 0.0)
        y = jnp.einsum("bhts,bshv->bthv", att, vf)
        # diagonal (current token) with u bonus:
        y = y + jnp.sum(rf * u[None, None] * kf, axis=-1, keepdims=True) * vf
        # inter-chunk: y_t += r_t · (exp(excl_t) S_prev)
        y = y + jnp.einsum("bthk,bhkv->bthv", rf * jnp.exp(excl), sprev)
        # state update: S_new = diag(Πw) S_prev + Σ_s exp(cs_end - cs_s) k_s v_sᵀ
        end = cs[:, -1]                                          # (B,H,K)
        snew = sprev * jnp.exp(end)[..., None] + jnp.einsum(
            "bshk,bshv->bhkv", kf * jnp.exp(end[:, None] - cs), vf)
        return snew, y

    ins = tuple(jnp.moveaxis(t, 1, 0) for t in (rr, kk, vv, ww))
    s_final, ys = jax.lax.scan(chunk_step, s0, ins)
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, s, h, dk)
    return y.astype(r.dtype), s_final


def wkv_reference(r, k, v, w, u, s0=None):
    """Token-by-token oracle."""
    bsz, s, h, dk = r.shape
    if s0 is None:
        s0 = jnp.zeros((bsz, h, dk, dk), jnp.float32)

    def step(sprev, inputs):
        rt, kt, vt, wt = (t.astype(jnp.float32) for t in inputs)  # (B,H,K)
        yt = jnp.einsum("bhk,bhkv->bhv", rt, sprev) + \
            jnp.sum(rt * u[None] * kt, axis=-1, keepdims=True) * vt
        snew = sprev * wt[..., None] + jnp.einsum("bhk,bhv->bhkv", kt, vt)
        return snew, yt

    ins = tuple(jnp.moveaxis(t, 1, 0) for t in (r, k, v, w))
    sf, ys = jax.lax.scan(step, s0, ins)
    return jnp.moveaxis(ys, 0, 1).astype(r.dtype), sf


def _time_mix_inputs(params, x, shifted):
    mu = params["mu"]
    mix = [x + (shifted - x) * jax.nn.sigmoid(mu[i])[None, None].astype(x.dtype)
           for i in range(5)]
    xr, xk, xv, xg, xw = mix
    r = jnp.einsum("bsd,de->bse", xr, params["w_r"])
    k = jnp.einsum("bsd,de->bse", xk, params["w_k"])
    v = jnp.einsum("bsd,de->bse", xv, params["w_v"])
    g = jnp.einsum("bsd,de->bse", xg, params["w_g"])
    lora = jnp.einsum("bsd,dl,le->bse", xw, params["w_decay_a"], params["w_decay_b"])
    w = jnp.exp(-jnp.exp(params["decay_base"][None, None] + lora.astype(jnp.float32)))
    return r, k, v, g, w


def time_mix(params, cfg, x: Array, *, chunk: int = 256):
    nheads, hd = rwkv_dims(cfg)
    b, s, d = x.shape
    shifted = _token_shift(x)
    r, k, v, g, w = _time_mix_inputs(params, x, shifted)
    to_h = lambda t: t.reshape(b, s, nheads, hd)
    u = params["u_bonus"].reshape(nheads, hd)
    if cfg.use_pallas_kernels:
        from repro.kernels import interpret_mode
        from repro.kernels.rwkv6_wkv.ops import wkv_chunked_pallas
        y, _ = wkv_chunked_pallas(
            to_h(r), to_h(k), to_h(v), to_h(w.astype(x.dtype)), u,
            chunk=chunk, interpret=interpret_mode())
    else:
        y, _ = wkv_chunked(to_h(r), to_h(k), to_h(v), to_h(w.astype(x.dtype)),
                           u, chunk=chunk)
    y = y.reshape(b, s, d)
    # group norm per head (ln_x)
    yh = y.reshape(b, s, nheads, hd).astype(jnp.float32)
    yh = (yh - yh.mean(-1, keepdims=True)) * jax.lax.rsqrt(yh.var(-1, keepdims=True) + 1e-5)
    y = (yh.reshape(b, s, d) * params["ln_x"][None, None]).astype(x.dtype)
    y = y * jax.nn.silu(g)
    return jnp.einsum("bsd,de->bse", y, params["w_o"])


def channel_mix(params, cfg, x: Array):
    mu = params["mu_cm"]
    shifted = _token_shift(x)
    xk = x + (shifted - x) * jax.nn.sigmoid(mu[0])[None, None].astype(x.dtype)
    xr = x + (shifted - x) * jax.nn.sigmoid(mu[1])[None, None].astype(x.dtype)
    k = jnp.square(jax.nn.relu(jnp.einsum("bsd,df->bsf", xk, params["cm_k"])))
    kv = jnp.einsum("bsf,fd->bsd", k, params["cm_v"])
    return jax.nn.sigmoid(jnp.einsum("bsd,de->bse", xr, params["cm_r"])) * kv


def init_rwkv_cache(cfg, batch: int, dtype):
    nheads, hd = rwkv_dims(cfg)
    return {
        "s": jnp.zeros((batch, nheads, hd, hd), jnp.float32),
        "x_tm": jnp.zeros((batch, cfg.d_model), dtype),
        "x_cm": jnp.zeros((batch, cfg.d_model), dtype),
    }


def time_mix_decode(params, cfg, x: Array, cache):
    """x: (B, 1, d)."""
    nheads, hd = rwkv_dims(cfg)
    b, _, d = x.shape
    shifted = cache["x_tm"][:, None]
    r, k, v, g, w = _time_mix_inputs(params, x, shifted)
    to_h = lambda t: t[:, 0].reshape(b, nheads, hd).astype(jnp.float32)
    rt, kt, vt, wt = to_h(r), to_h(k), to_h(v), to_h(w)
    u = params["u_bonus"].reshape(nheads, hd)
    sprev = cache["s"]
    yt = jnp.einsum("bhk,bhkv->bhv", rt, sprev) + \
        jnp.sum(rt * u[None] * kt, axis=-1, keepdims=True) * vt
    snew = sprev * wt[..., None] + jnp.einsum("bhk,bhv->bhkv", kt, vt)
    yh = (yt - yt.mean(-1, keepdims=True)) * jax.lax.rsqrt(yt.var(-1, keepdims=True) + 1e-5)
    y = (yh.reshape(b, 1, d) * params["ln_x"][None, None]).astype(x.dtype)
    y = y * jax.nn.silu(g)
    out = jnp.einsum("bsd,de->bse", y, params["w_o"])
    return out, {"s": snew, "x_tm": x[:, 0]}


def channel_mix_decode(params, cfg, x: Array, cache):
    mu = params["mu_cm"]
    shifted = cache["x_cm"][:, None].astype(x.dtype)
    xk = x + (shifted - x) * jax.nn.sigmoid(mu[0])[None, None].astype(x.dtype)
    xr = x + (shifted - x) * jax.nn.sigmoid(mu[1])[None, None].astype(x.dtype)
    k = jnp.square(jax.nn.relu(jnp.einsum("bsd,df->bsf", xk, params["cm_k"])))
    kv = jnp.einsum("bsf,fd->bsd", k, params["cm_v"])
    out = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", xr, params["cm_r"])) * kv
    return out, {"x_cm": x[:, 0]}


# -- full model ---------------------------------------------------------------
def init_params(key, cfg):
    from repro.models.common import dtype_of, embed_init
    dtype = dtype_of(cfg)
    ks = jax.random.split(key, 4)
    layer_keys = jax.random.split(ks[0], cfg.num_layers)

    def init_layer(k):
        return {
            "block": init_rwkv_block(k, cfg, dtype),
            "ln_tm": jnp.ones((cfg.d_model,), jnp.float32),
            "ln_cm": jnp.ones((cfg.d_model,), jnp.float32),
        }

    return {
        "embed": embed_init(ks[1], (cfg.vocab_size, cfg.d_model), dtype),
        "ln_in": jnp.ones((cfg.d_model,), jnp.float32),
        "layers": jax.vmap(init_layer)(layer_keys),
        "ln_f": jnp.ones((cfg.d_model,), jnp.float32),
        "unembed": dense_init(ks[2], (cfg.d_model, cfg.vocab_size), dtype),
    }


def forward(params, cfg, batch, *, remat: bool = True):
    from repro.models.common import rms_norm
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    x = rms_norm(x, params["ln_in"], cfg.norm_eps)

    def body(x, lp):
        x = x + time_mix(lp["block"], cfg, rms_norm(x, lp["ln_tm"], cfg.norm_eps))
        x = x + channel_mix(lp["block"], cfg, rms_norm(x, lp["ln_cm"], cfg.norm_eps))
        return x, None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["layers"])
    return rms_norm(x, params["ln_f"], cfg.norm_eps), jnp.zeros((), jnp.float32)


def loss_fn(params, cfg, batch):
    from repro.models.common import chunked_softmax_xent
    h, _ = forward(params, cfg, batch)
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones(batch["labels"].shape, jnp.float32)
    xent = chunked_softmax_xent(h, params["unembed"], batch["labels"], mask, cfg.xent_chunk)
    return xent, {"xent": xent}


def init_cache(cfg, batch: int, seq_len: int, dtype=None):
    from repro.models.common import dtype_of
    dtype = dtype or dtype_of(cfg)
    one = init_rwkv_cache(cfg, batch, dtype)
    stacked = jax.tree.map(
        lambda t: jnp.zeros((cfg.num_layers, *t.shape), t.dtype), one)
    stacked["pos"] = jnp.zeros((), jnp.int32)
    return stacked


def decode_step(params, cfg, tokens, cache):
    from repro.models.common import rms_norm
    pos = cache["pos"]
    x = jnp.take(params["embed"], tokens, axis=0)
    x = rms_norm(x, params["ln_in"], cfg.norm_eps)

    def body(x, inputs):
        lp, c = inputs
        o, tm_new = time_mix_decode(
            lp["block"], cfg, rms_norm(x, lp["ln_tm"], cfg.norm_eps),
            {"s": c["s"], "x_tm": c["x_tm"]})
        x = x + o
        o, cm_new = channel_mix_decode(
            lp["block"], cfg, rms_norm(x, lp["ln_cm"], cfg.norm_eps),
            {"x_cm": c["x_cm"]})
        x = x + o
        return x, {"s": tm_new["s"], "x_tm": tm_new["x_tm"], "x_cm": cm_new["x_cm"]}

    layer_cache = {k: cache[k] for k in ("s", "x_tm", "x_cm")}
    x, new_lc = jax.lax.scan(body, x, (params["layers"], layer_cache))
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32),
                        params["unembed"].astype(jnp.float32))
    new_lc["pos"] = pos + 1
    return logits, new_lc


def prefill(params, cfg, batch):
    h, _ = forward(params, cfg, batch, remat=False)
    logits = jnp.einsum("bd,dv->bv", h[:, -1].astype(jnp.float32),
                        params["unembed"].astype(jnp.float32))
    return logits
