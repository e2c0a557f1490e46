"""Random weights from the seed, made on the device in one jitted call, in
the type the configuration serves them in.

The tree has the layout of the repo's dense decoder (``models/
transformer.py``): the harness checks it against the program's own
``param_shapes()`` before handing it over.  The benchmark makes the weights,
so the plain reference may read them too.
"""
from __future__ import annotations

import math
from typing import Dict

from bench.traffic import seed_words


def dims(model: Dict) -> Dict[str, int]:
    d, h = model["d_model"], model["num_heads"]
    return {"L": model["num_layers"], "d": d, "H": h,
            "Hkv": model["num_kv_heads"], "hd": model.get("head_dim") or d // h,
            "F": model["d_ff"], "V": model["vocab_size"]}


def make_params(config: Dict, seed: int):
    import jax
    import jax.numpy as jnp
    m = config["model"]
    n = dims(m)
    L, d, H, Hkv, hd, F, V = (n[k] for k in ("L", "d", "H", "Hkv", "hd", "F", "V"))
    dt = jnp.dtype(m["dtype"])
    f32 = jnp.float32

    def matrix(key, shape, fan_in):
        x = jax.random.truncated_normal(key, -2.0, 2.0, shape, f32)
        return (x / math.sqrt(fan_in)).astype(dt)

    @jax.jit
    def init(key):
        ks = jax.random.split(key, 9)
        params = {
            "embed": (jax.random.normal(ks[0], (V, d), f32) * 0.02).astype(dt),
            "layers": {
                "attn": {"wq": matrix(ks[1], (L, d, H, hd), d),
                         "wk": matrix(ks[2], (L, d, Hkv, hd), d),
                         "wv": matrix(ks[3], (L, d, Hkv, hd), d),
                         "wo": matrix(ks[4], (L, H, hd, d), H * hd)},
                "ln_attn": jnp.ones((L, d), f32),
                "ln_ffn": jnp.ones((L, d), f32),
                "ffn": {"w_gate": matrix(ks[5], (L, d, F), d),
                        "w_up": matrix(ks[6], (L, d, F), d),
                        "w_down": matrix(ks[7], (L, F, d), F)},
            },
            "ln_f": jnp.ones((d,), f32),
        }
        if not m["tie_embeddings"]:
            params["unembed"] = matrix(ks[8], (d, V), d)
        return params

    return init(jax.random.PRNGKey(int(seed_words(seed, 4)[3])))
