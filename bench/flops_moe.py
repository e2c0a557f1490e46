"""Operations of the latent-attention expert model's serving step, from its
shapes alone (the algorithm's counts, as ``bench/flops.py`` keeps them for
the dense model)."""
from __future__ import annotations

from typing import Dict

from bench.weights_mla import mla_dims


def mla_attention_params(model: Dict) -> int:
    """Matmul weights of one MLA layer a decoded token passes through: the
    query, the latent projection, W_UK and W_UV (absorbed into the query
    and the latent output, each used once a token and head) and the
    output projection."""
    n = mla_dims(model)
    d, H, r, rope = n["d"], n["H"], n["r"], n["rope"]
    return (d * H * (n["nope"] + rope) + d * (r + rope)
            + r * H * (n["nope"] + n["v"]) + H * n["v"] * d)


def shared_matmul_params(model: Dict) -> int:
    """Matmul weights every token passes through, routed experts left out:
    attention in every layer, the leading dense SwiGLUs, each expert
    layer's router and shared SwiGLU, the unembedding."""
    n = mla_dims(model)
    d = n["d"]
    moe_layers = n["L"] - n["K"]
    return (n["L"] * mla_attention_params(model) + n["K"] * 3 * d * n["Fd"]
            + moe_layers * (d * n["E"] + 3 * d * n["S"]) + d * n["V"])


def routed_expert_params(model: Dict) -> int:
    """Weights of one routed expert (gate, up, down)."""
    n = mla_dims(model)
    return 3 * n["d"] * n["f"]


def decode_flops(model: Dict, tokens: int, expert_slots: int) -> int:
    """2 per matmul weight: ``tokens`` decoded or prefilled tokens through
    the shared weights, and ``expert_slots`` token-slots through a held
    routed expert.  Attention over the cache is left out (at the cell's
    mean position, about a tenth of a token's FLOPs)."""
    return 2 * (tokens * shared_matmul_params(model)
                + expert_slots * routed_expert_params(model))
