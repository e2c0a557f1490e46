"""Shared building blocks for the model zoo (pure JAX, functional)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


def dtype_of(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


# -- initialisation ----------------------------------------------------------
def dense_init(key, shape, dtype, scale: float | None = None):
    """Truncated-normal fan-in init (matches common LLM practice)."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


def split_keys(key, names):
    keys = jax.random.split(key, len(names))
    return dict(zip(names, keys))


# -- norms -------------------------------------------------------------------
def rms_norm(x: Array, scale: Array, eps: float) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


# -- RoPE --------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim))


def apply_rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(hd, theta), jnp.float32)          # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs        # (..., S, hd/2)
    angles = angles[..., None, :]                                    # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's temperature factor: 0.1 mscale ln(factor) + 1 above a factor
    of 1 (DeepSeek-V2's ``yarn_get_mscale``)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, yarn) -> np.ndarray:
    """YaRN inverse frequencies of a ``dim``-wide rotary part: dimensions
    that turn more than ``beta_fast`` times over the original context keep
    theta^(-2i/dim), those that turn fewer than ``beta_slow`` times are
    divided by ``factor``, with a linear ramp between."""
    expo = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / theta ** expo
    inter = 1.0 / (yarn.factor * theta ** expo)

    def turns_at(rotations):
        return (dim * math.log(yarn.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_at(yarn.beta_fast)), 0)
    high = min(math.ceil(turns_at(yarn.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return inter * ramp + extra * (1.0 - ramp)


def rope_rotate(x: Array, positions: Array, inv_freq, scale: float = 1.0) -> Array:
    """Rotate-half RoPE with given inverse frequencies, cos and sin times
    ``scale``.  x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    freqs = jnp.asarray(inv_freq, jnp.float32)                       # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs        # (..., S, hd/2)
    angles = angles[..., None, :]                                    # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles) * scale, jnp.sin(angles) * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: Array, positions: Array, theta: float, sections) -> Array:
    """Qwen2-VL multimodal RoPE.

    x: (B, S, H, hd); positions: (3, B, S) — (temporal, height, width) ids.
    ``sections`` partitions the hd/2 rotary frequencies into (t, h, w) groups;
    each group rotates by its own position id. [arXiv:2409.12191]
    """
    hd = x.shape[-1]
    assert sum(sections) == hd // 2, (sections, hd)
    freqs = jnp.asarray(rope_freqs(hd, theta), jnp.float32)          # (hd/2,)
    # angle per section: pick which of the 3 position streams drives each freq
    sec_id = np.concatenate([np.full(s, i) for i, s in enumerate(sections)])
    pos_sel = positions[sec_id]                                      # (hd/2, B, S)
    angles = jnp.moveaxis(pos_sel, 0, -1).astype(jnp.float32) * freqs  # (B, S, hd/2)
    angles = angles[..., None, :]                                    # (B, S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -- FFN ---------------------------------------------------------------------
def swiglu(x: Array, w_gate: Array, w_up: Array, w_down: Array) -> Array:
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    return jnp.einsum("...f,fd->...d", jax.nn.silu(g) * u, w_down)


def init_swiglu(key, d: int, f: int, dtype):
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (d, f), dtype),
        "w_up": dense_init(ks[1], (d, f), dtype),
        "w_down": dense_init(ks[2], (f, d), dtype),
    }


# -- losses ------------------------------------------------------------------
def chunked_softmax_xent(
    h: Array,            # (B, S, d) final hidden states
    unembed: Array,      # (d, V)
    labels: Array,       # (B, S) int32
    mask: Array,         # (B, S) float — 1 where the label counts
    chunk: int,
) -> Array:
    """Cross-entropy without materializing (B, S, V) logits.

    The sequence axis is processed in chunks under jax.checkpoint so the peak
    live logits tensor is (B, chunk, V).  This is the big-vocab trick that
    makes 151k-vocab training fit (DESIGN.md §4).
    """
    B, S, _ = h.shape
    chunk = min(chunk, S)
    n = S // chunk
    rem = S - n * chunk

    def chunk_loss(args):
        hc, yc, mc = args
        logits = jnp.einsum("bsd,dv->bsv", hc.astype(jnp.float32), unembed.astype(jnp.float32))
        logz = jax.nn.logsumexp(logits, axis=-1)
        # the gold logit as a masked sum over the vocab, not a gather: equal
        # for finite logits, and it partitions like logsumexp where the
        # vocab is model-sharded (a gather there trips an XLA SPMD check
        # inside the multi-pod step's manual "pod" region; docs/architecture.md)
        vocab = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
        gold = jnp.sum(jnp.where(vocab == yc[..., None], logits, 0.0), axis=-1)
        return jnp.sum((logz - gold) * mc)

    chunk_loss = jax.checkpoint(chunk_loss)

    hs = h[:, : n * chunk].reshape(B, n, chunk, -1).swapaxes(0, 1)
    ys = labels[:, : n * chunk].reshape(B, n, chunk).swapaxes(0, 1)
    ms = mask[:, : n * chunk].reshape(B, n, chunk).swapaxes(0, 1)

    def body(carry, args):
        return carry + chunk_loss(args), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hs, ys, ms))
    if rem:
        total = total + chunk_loss((h[:, n * chunk:], labels[:, n * chunk:], mask[:, n * chunk:]))
    denom = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
    return total / denom


def full_softmax_xent(h, unembed, labels, mask):
    """Reference (materializes logits) — used by tests to validate chunking."""
    logits = jnp.einsum("bsd,dv->bsv", h.astype(jnp.float32), unembed.astype(jnp.float32))
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
    return jnp.sum((logz - gold) * mask) / denom
