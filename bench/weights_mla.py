"""Random weights from the seed for the latent-attention expert model
(DeepSeek-V2's layout as ``models/transformer.py`` builds it), made on the
device in one jitted call, in the type the configuration serves them in.

The tree: ``embed``; ``dense_layers`` (the leading dense layers) and
``layers`` (the expert layers), each a stack of MLA (``wq``, ``wkv_a``,
``kv_norm``, ``w_uk``, ``w_uv``, ``wo``), two norms and a dense SwiGLU or
the held-expert layer (f32 ``router`` over every expert, the held experts'
``w_gate``/``w_up``/``w_down``, the ``shared`` SwiGLU); ``ln_f``;
``unembed``.  The harness checks it against the program's
``param_shapes()`` before handing it over; the plain reference reads it too.
"""
from __future__ import annotations

import math
from typing import Dict

from bench.traffic import seed_words


def mla_dims(model: Dict) -> Dict[str, int]:
    return {"L": model["num_layers"], "K": model["first_k_dense"],
            "d": model["d_model"], "H": model["num_heads"],
            "r": model["kv_lora_rank"], "rope": model["qk_rope_head_dim"],
            "nope": model["qk_nope_head_dim"], "v": model["v_head_dim"],
            "E": model["num_experts"], "held": model["n_routed_experts"],
            "f": model["d_ff"], "S": model["num_shared_experts"] * model["d_ff"],
            "Fd": model["dense_d_ff"], "V": model["vocab_size"]}


def make_params(config: Dict, seed: int):
    import jax
    import jax.numpy as jnp
    m = config["model"]
    n = mla_dims(m)
    d, H, r, rope, nope, v = (n[k] for k in ("d", "H", "r", "rope", "nope", "v"))
    dt = jnp.dtype(m["dtype"])
    f32 = jnp.float32

    def matrix(key, shape, fan_in, dtype=dt):
        x = jax.random.truncated_normal(key, -2.0, 2.0, shape, f32)
        return (x / math.sqrt(fan_in)).astype(dtype)

    def swiglu(key, lead, width):
        ks = jax.random.split(key, 3)
        return {"w_gate": matrix(ks[0], lead + (d, width), d),
                "w_up": matrix(ks[1], lead + (d, width), d),
                "w_down": matrix(ks[2], lead + (width, d), width)}

    def stack(key, count, dense):
        ks = jax.random.split(key, 8)
        lead = (count,)
        p = {"attn": {"wq": matrix(ks[0], lead + (d, H, nope + rope), d),
                      "wkv_a": matrix(ks[1], lead + (d, r + rope), d),
                      "kv_norm": jnp.ones(lead + (r,), f32),
                      "w_uk": matrix(ks[2], lead + (r, H, nope), r),
                      "w_uv": matrix(ks[3], lead + (r, H, v), r),
                      "wo": matrix(ks[4], lead + (H, v, d), H * v)},
             "ln_attn": jnp.ones(lead + (d,), f32),
             "ln_ffn": jnp.ones(lead + (d,), f32)}
        if dense:
            p["ffn"] = swiglu(ks[5], lead, n["Fd"])
        else:
            moe = swiglu(ks[5], lead + (n["held"],), n["f"])
            moe = {"w_gate": moe["w_gate"], "w_up": moe["w_up"],
                   "w_down": moe["w_down"],
                   "router": matrix(ks[6], lead + (d, n["E"]), d, f32)}
            if n["S"]:
                moe["shared"] = swiglu(ks[7], lead, n["S"])
            p["moe"] = moe
        return p

    @jax.jit
    def init(key):
        ks = jax.random.split(key, 4)
        params = {
            "embed": (jax.random.normal(ks[0], (n["V"], d), f32) * 0.02).astype(dt),
            "layers": stack(ks[1], n["L"] - n["K"], dense=False),
            "ln_f": jnp.ones((d,), f32),
            "unembed": matrix(ks[3], (d, n["V"]), d),
        }
        if n["K"]:
            params["dense_layers"] = stack(ks[2], n["K"], dense=True)
        return params

    return init(jax.random.PRNGKey(int(seed_words(seed, 4)[3])))
