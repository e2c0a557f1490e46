"""DeepSeek-V2's pieces at CPU size against a plain float32 copy of their
mathematics: YaRN rope, multi-head latent attention (the full-sequence
forward and the absorbed decode through the latent cache), the held-expert
layer (gates, drop-free routing, shares that add up to the uncut layer),
and the slot engine serving the model."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import MOE, ModelConfig, YarnScaling
from repro.models import moe, transformer
from repro.models.common import yarn_inv_freq, yarn_mscale
from repro.models.model import build_model

YARN = YarnScaling(factor=40.0, original_max_position_embeddings=32,
                   beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                   mscale_all_dim=0.707)
CFG = ModelConfig(
    name="tiny-mla", family=MOE, num_layers=3, d_model=64, num_heads=4,
    num_kv_heads=4, d_ff=24, vocab_size=128, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, yarn=YARN,
    num_experts=16, experts_per_token=3, n_routed_experts=16,
    num_shared_experts=2, first_k_dense=1, dense_d_ff=48,
    norm_topk_prob=False, routed_scaling_factor=1.0, norm_eps=1e-6,
    dtype="float32", max_seq_len=64)
HI = jax.lax.Precision.HIGHEST


# -- a plain float32 copy of the mathematics ---------------------------------
def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, w, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * w


def _yarn(dim, theta, y):
    """DeepSeek-V2's yarn frequencies, written out."""
    def corr(rot):
        return dim * math.log(y.original_max_position_embeddings
                              / (rot * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(corr(y.beta_fast)), 0)
    high = min(math.ceil(corr(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    extra = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    return extra / y.factor * ramp + extra * (1 - ramp)


def _rope(x, inv):
    ang = np.arange(x.shape[1])[:, None] * inv[None]
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    h = x.shape[-1] // 2
    return np.concatenate([x[..., :h] * cos - x[..., h:] * sin,
                           x[..., h:] * cos + x[..., :h] * sin], -1)


def _swiglu(h, f):
    g = _mm("bsd,df->bsf", h, f["w_gate"])
    return _mm("bsf,fd->bsd", jax.nn.silu(g) * _mm("bsd,df->bsf", h,
                                                     f["w_up"]), f["w_down"])


def ref_moe(h, mp, cfg, held):
    """The experts ``held`` of the layer (all, for the uncut layer) and the
    shared experts; returns (out, routes to held experts)."""
    probs = jax.nn.softmax(_mm("bsd,de->bse", h, mp["router"]), -1)
    gates, chosen = jax.lax.top_k(probs, cfg.experts_per_token)
    out = 0.0
    for j, e in enumerate(held):
        w = jnp.sum(jnp.where(chosen == e, gates, 0.0), -1)
        f = {k: mp[k][j] for k in ("w_gate", "w_up", "w_down")}
        out = out + _swiglu(h, f) * w[..., None]
    if "shared" in mp:
        out = out + _swiglu(h, mp["shared"])
    return out, int(np.sum(np.isin(np.asarray(chosen), held)))


def ref_forward(params, tokens, cfg):
    """Non-absorbed MLA (per-head keys and values from the latent), f32.
    Returns (logits, routes to held experts at each position)."""
    r, nope = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    inv = _yarn(cfg.qk_rope_head_dim, cfg.rope_theta, cfg.yarn)
    m = 0.1 * 0.707 * math.log(40.0) + 1.0
    scale = (nope + cfg.qk_rope_head_dim) ** -0.5 * m * m
    p = jax.tree.map(lambda a: np.asarray(a, np.float32), params)
    x = p["embed"][np.asarray(tokens)]
    s = x.shape[1]
    causal = np.tril(np.ones((s, s), bool))
    routed = np.zeros(s, np.int64)
    for stack in ("dense_layers", "layers"):
        for i in range(p[stack]["ln_attn"].shape[0]):
            lp = jax.tree.map(lambda a: a[i], p[stack])
            a = lp["attn"]
            h = _rms(x, lp["ln_attn"], cfg.norm_eps)
            q = _mm("bsd,dhe->bshe", h, a["wq"])
            q = np.concatenate([q[..., :nope], _rope(q[..., nope:], inv)], -1)
            kv = _mm("bsd,dc->bsc", h, a["wkv_a"])
            c = _rms(kv[..., :r], a["kv_norm"], cfg.norm_eps)
            kr = _rope(kv[..., None, r:], inv)
            kn = _mm("bsc,chn->bshn", c, a["w_uk"])
            k = np.concatenate([kn, np.broadcast_to(kr, kn.shape[:3] + kr.shape[-1:])], -1)
            v = _mm("bsc,chv->bshv", c, a["w_uv"])
            sc = np.where(causal, _mm("bqhe,bkhe->bhqk", q, k) * scale, -1e30)
            o = _mm("bhqk,bkhe->bqhe", jax.nn.softmax(sc, -1), v)
            x = x + _mm("bshe,hed->bsd", o, a["wo"])
            h = _rms(x, lp["ln_ffn"], cfg.norm_eps)
            if "ffn" in lp:
                x = x + _swiglu(h, lp["ffn"])
            else:
                held = list(range(cfg.n_routed_experts))
                for t in range(s):
                    routed[t] += ref_moe(h[:, t:t + 1], lp["moe"], cfg, held)[1]
                x = x + ref_moe(h, lp["moe"], cfg, held)[0]
    x = _rms(x, p["ln_f"], cfg.norm_eps)
    return np.asarray(_mm("bsd,dv->bsv", x, p["unembed"])), routed


def _model(cfg=CFG, seed=0):
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(seed))


# -- YaRN -------------------------------------------------------------------------
@pytest.mark.parametrize("dim,theta,orig", [(8, 10_000.0, 32), (64, 10_000.0, 4096),
                                            (64, 1e6, 4096), (16, 500.0, 64)])
def test_yarn_frequencies_follow_the_formula(dim, theta, orig):
    y = YarnScaling(40.0, orig, 32.0, 1.0, 0.707, 0.707)
    got = yarn_inv_freq(dim, theta, y)
    np.testing.assert_allclose(got, _yarn(dim, theta, y), rtol=1e-12)
    extra = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    assert got[0] == pytest.approx(extra[0])                # fast: kept
    assert got[-1] == pytest.approx(extra[-1] / 40.0)       # slow: /factor


def test_yarn_mscale_and_the_published_attention_scale():
    assert yarn_mscale(40.0, 0.707) == pytest.approx(0.1 * 0.707 * math.log(40) + 1)
    assert yarn_mscale(40.0, 0.707) ** 2 == pytest.approx(1.5896, abs=1e-4)
    assert yarn_mscale(1.0, 0.707) == 1.0
    inv, rotation, scale = transformer.mla_rope(get_config("deepseek-v2-lite"))
    assert rotation == 1.0                   # mscale == mscale_all_dim
    assert scale == pytest.approx(192 ** -0.5 * yarn_mscale(40.0, 0.707) ** 2)
    # dims 0-9 keep theta^(-2i/64), 23-31 are divided by 40, a ramp between
    extra = 1.0 / 10_000.0 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:10], extra[:10])
    np.testing.assert_allclose(inv[23:], extra[23:] / 40.0)


# -- MLA: full sequence and the absorbed decode ----------------------------------
@pytest.mark.parametrize("held", [16, 4])
def test_prefill_and_absorbed_decode_match_the_reference(held):
    cfg = dataclasses.replace(CFG, n_routed_experts=held)
    model, params = _model(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 12), 0, cfg.vocab_size)
    want, _ = ref_forward(params, toks, cfg)
    h, _ = transformer.forward(params, cfg, {"tokens": toks}, remat=False)
    full = np.asarray(h @ params["unembed"])
    np.testing.assert_allclose(full, want, atol=2e-4, rtol=2e-4)
    cache = model.init_cache(2, 16)
    assert cache["ckv"].shape == (2, 2, 16, 32 + 8)        # latent + rope key
    step = jax.jit(model.decode_step)
    for t in range(12):
        logits, cache = step(params, toks[:, t:t + 1], cache)
        np.testing.assert_allclose(np.asarray(logits[:, 0]), want[:, t],
                                   atol=2e-4, rtol=2e-4)


def test_absorbed_decode_keeps_no_per_head_key_or_value():
    """The cache is the latent and the rope key (576 values per position and
    layer at the published widths), and the decode's program holds no
    (positions, heads, head width) key or value."""
    cfg = get_config("deepseek-v2-lite")
    model = build_model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(1, 64))
    assert cache["ckv"].shape == (26, 1, 64, 576)
    assert cache["ckv_dense"].shape == (1, 1, 64, 576)
    params = model.param_shapes()
    text = jax.jit(model.decode_step).lower(
        params, jax.ShapeDtypeStruct((1, 1), jnp.int32), cache).as_text()
    assert "64x16x128" not in text and "64x16x192" not in text


# -- the held-expert layer ----------------------------------------------------------
def _layer_params(seed=0):
    _, params = _model(seed=seed)
    return jax.tree.map(lambda a: a[0], params["layers"]["moe"])


def test_gates_are_a_softmax_over_all_experts_top_k_not_renormalized():
    mp = _layer_params()
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 64))
    gates, chosen = moe.held_gates(x, mp["router"], 3, norm_topk=False,
                                   scaling=1.0)
    probs = jax.nn.softmax(x @ mp["router"], -1)
    want, idx = jax.lax.top_k(probs, 3)
    np.testing.assert_array_equal(chosen, idx)
    np.testing.assert_allclose(gates, want, rtol=1e-6)
    assert np.all(np.asarray(gates.sum(-1)) < 1.0)
    normed, _ = moe.held_gates(x, mp["router"], 3, norm_topk=True, scaling=2.5)
    np.testing.assert_allclose(normed.sum(-1), 2.5, rtol=1e-6)


def test_shares_add_up_to_the_uncut_layer():
    """Four devices holding 4 experts each: their routed parts, with the
    shared experts counted once, give the uncut layer; the routes they
    count add up to tokens x top-k."""
    mp = _layer_params()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 7, 64))
    whole, _ = ref_moe(x, mp, CFG, list(range(16)))
    routed_only = {k: v for k, v in mp.items() if k != "shared"}
    total, routes = 0.0, 0
    for i in range(4):
        share = dict(routed_only, **{k: mp[k][4 * i:4 * i + 4]
                                     for k in ("w_gate", "w_up", "w_down")})
        out, n = moe.held_moe_ffn(x, share, held=jnp.arange(4) + 4 * i,
                                  top_k=3, norm_topk=False)
        total, routes = total + out, routes + int(n)
    total = total + _swiglu(x, mp["shared"])
    np.testing.assert_allclose(total, whole, atol=1e-5, rtol=1e-5)
    assert routes == 2 * 7 * 3


def test_routing_is_drop_free_when_every_token_picks_one_expert():
    mp = _layer_params()
    router = np.asarray(mp["router"]).copy()
    router[:, 5] = 10.0                       # positive tokens all pick 5
    mp = dict(mp, router=jnp.asarray(router))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(4), (1, 40, 64)))
    share = {k: (v[4:8] if k in ("w_gate", "w_up", "w_down") else v)
             for k, v in mp.items()}
    out, n = moe.held_moe_ffn(x, share, held=jnp.arange(4, 8), top_k=3,
                              norm_topk=False)
    want, routes = ref_moe(x, share, CFG, [4, 5, 6, 7])
    np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)
    _, chosen = moe.held_gates(x, mp["router"], 3, norm_topk=False, scaling=1.0)
    assert np.all(np.asarray(chosen)[..., 0] == 5)
    assert int(n) == routes >= 40              # every token's route to 5 kept


# -- the slot engine ------------------------------------------------------------------
def test_engine_serves_the_model_as_the_reference_does():
    """A handful of requests through ``ServingEngine``: each served token is
    the reference's best at its position, and the engine's per-step count
    of routes to held experts adds up to the reference's over every token
    fed."""
    from repro.core.serving import ServingConfig, ServingEngine, build_lane
    cfg = dataclasses.replace(CFG, n_routed_experts=4)
    model, params = _model(cfg)
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (5, 6), 0,
                                            cfg.vocab_size), np.int32)
    plens, budgets = [6, 3, 5, 2, 4], [4, 6, 3, 5, 2]
    engine = ServingEngine(model, ServingConfig(slots=3, max_new=6, steps=40,
                                                cache_len=12), prompts)
    lane = build_lane(n_requests=5, prompt_lens=plens, max_new=budgets,
                      steps=40, n_nodes=2, balances=[100.0], load=0.5)
    res = engine.run(params, lane)
    assert res.done.all()
    fed = 0
    for j in range(5):
        seq = np.concatenate([prompts[j, :plens[j]], res.tokens[j, :budgets[j]]])
        logits, routed = ref_forward(params, seq[None, :-1], cfg)
        served = logits[0, plens[j] - 1:][np.arange(budgets[j]),
                                          res.tokens[j, :budgets[j]]]
        best = logits[0, plens[j] - 1:].max(-1)
        np.testing.assert_allclose(served, best, atol=1e-4)
        fed += int(routed.sum())
    assert set(res.stats) == {"expert_slots"}
    assert int(res.stats["expert_slots"].sum()) == fed
