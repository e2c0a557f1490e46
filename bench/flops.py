"""Operations and bytes each cell's work needs, from its shapes alone.

These are the algorithm's counts, not the compiler's: a recomputed forward
(rematerialisation) or an idle slot the engine computes anyway does not
count.  The per-layer metrics divide them by device or window time.
"""
from __future__ import annotations

from typing import Dict

from bench.weights import dims


def matmul_params(model: Dict) -> int:
    """Weights that take part in a matmul per token: the layers' attention
    and SwiGLU projections and the unembedding (the embedding is a gather)."""
    n = dims(model)
    d, hd = n["d"], n["hd"]
    attn = d * hd * (2 * n["H"] + 2 * n["Hkv"])
    ffn = 3 * d * n["F"]
    return n["L"] * (attn + ffn) + d * n["V"]


def train_flops_per_sequence(model: Dict, seq_len: int) -> int:
    """Forward and backward FLOPs of one sequence: 6 per matmul weight per
    token, plus the attention scores and their weighted sum over the whole
    sequence (12 L d_attn S per token; the PaLM convention)."""
    n = dims(model)
    per_token = 6 * matmul_params(model) \
        + 12 * n["L"] * n["H"] * n["hd"] * seq_len
    return per_token * seq_len


def swarm_round_flops(model: Dict, traffic: Dict) -> int:
    """The gradient FLOPs of one round: every node's batch (the engine
    computes inactive and slashed nodes' gradients too, but only the
    gradients count)."""
    rows = len(traffic["roster"]) * traffic["seqs_per_node"]
    return rows * train_flops_per_sequence(model, traffic["seq_len"])


def decode_flops_per_token(model: Dict) -> int:
    """2 per matmul weight for one decoded token (attention over the cache
    is left out: under 4% of a token's FLOPs at the cells' 384 positions)."""
    return 2 * matmul_params(model)


def centered_clip_kernel_bytes(n_nodes: int, d: int, iters: int) -> Dict[str, int]:
    """HBM bytes of the fused CenteredClip kernels on an (n_nodes, d) f32
    stack: the median warm start reads the stack and the mask and writes
    one row; each iteration streams the stack and the centre twice (the
    norm phase, then the clipped-mean phase) and writes the new centre."""
    stack, row = 4 * n_nodes * d, 4 * d
    return {"median": stack + 4 * n_nodes + row,
            "iteration": 2 * (stack + row) + row,
            "round": stack + 4 * n_nodes + row + iters * (2 * (stack + row) + row)}


def batcher_pairs(n: int) -> int:
    """Compare-exchanges of Batcher's odd-even merge sort over the next
    power of two at or above ``n``: (k^2 - k + 4) 2^(k-2) - 1 for 2^k."""
    k = max(1, (n - 1).bit_length())
    return (k * k - k + 4) * 2 ** (k - 2) - 1 if k >= 2 else 1


def centered_clip_kernel_flops(n_nodes: int, d: int, iters: int) -> int:
    """Arithmetic of one round's kernels: per column the median network's
    compare-exchanges (a min and a max each), and per element and iteration
    the norm phase's difference, square and sum and the mean phase's
    difference, scale, mask and sum."""
    return 2 * batcher_pairs(n_nodes) * d + iters * 7 * n_nodes * d
