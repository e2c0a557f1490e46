"""Gradient compression (paper §3.1): the communication-efficiency substrate.

- QSGD stochastic quantization (fixed compression, Alistarh et al. [2]);
  Pallas kernel twin in ``repro.kernels.qsgd``.
- Top-k sparsification with error feedback (the standard adaptive scheme
  the paper cites as [19]-style).
- PowerSGD-style low-rank compression (rank-r outer product) — included as
  the beyond-survey option for 2-D tensors.

All compressors return a ``Compressed`` payload plus the bits-on-wire count
so benchmarks can report exact compression ratios, and a ``decompress``
path used by tests to bound reconstruction error.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

Array = jax.Array


@dataclass(frozen=True)
class Compressed:
    kind: str
    payload: Dict[str, Any]
    bits: int          # exact bits on the wire
    orig_shape: tuple
    orig_bits: int


def _nbits(x) -> int:
    return int(x.size * jnp.dtype(x.dtype).itemsize * 8)


# -- QSGD ---------------------------------------------------------------------
def qsgd_compress(key, x: Array, *, levels: int = 16,
                  bucket_size: int = 1024) -> Compressed:
    """Stochastic uniform quantization to ``levels`` levels per |x|/norm.

    Bucketed as in Alistarh et al. [2]: one fp32 L2 norm per
    ``bucket_size`` elements + a sign+magnitude code per element.  Without
    bucketing the relative error grows as √d/levels — unusable at
    million-dim gradients (observed: a 5M-dim LM gradient quantized
    against a single global norm carries 35× the signal in noise).
    Unbiased: E[decompress(compress(x))] = x.
    """
    shape = x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    padded = bucketed(flat, bucket_size)
    norms = jnp.linalg.norm(padded, axis=1, keepdims=True)   # (nb, 1)
    scaled = jnp.abs(padded) / jnp.maximum(norms, 1e-30) * levels
    lower = jnp.floor(scaled)
    p = scaled - lower
    rnd = jax.random.uniform(key, padded.shape)
    q = (lower + (rnd < p)).astype(jnp.int32)            # in [0, levels]
    sign = jnp.signbit(padded)
    # levels is static, so the wire width is plain Python math — keeps the
    # codec traceable under jit/vmap (the batched swarm engine vmaps it).
    bits_per_el = math.ceil(math.log2(levels + 1)) + 1
    return Compressed(
        kind="qsgd",
        payload={"q": q, "sign": sign, "norms": norms, "levels": levels,
                 "size": flat.size},
        bits=32 * -(-flat.size // bucket_size) + flat.size * bits_per_el,
        orig_shape=shape,
        orig_bits=_nbits(x),
    )


#: bucket rows are padded to a multiple of this (the TPU sublane count)
BUCKET_ROW_MULTIPLE = 8


def bucketed(flat: Array, bucket_size: int) -> Array:
    """Zero-pad a flat vector into (nb, bucket_size) rows, nb a multiple of
    :data:`BUCKET_ROW_MULTIPLE`.  The extra all-zero buckets change no
    result: their norms are 0 and their codes decode to 0, and threefry
    draws over the padded shape extend those over the unpadded one (jax's
    default partitionable threefry indexes draws by flat position).
    Without the row padding the TPU compiler emits code for the per-bucket
    norm reduction that grows with the bucket count — hundreds of MB at
    100M+ elements."""
    rows = -(-flat.size // bucket_size)
    rows = -(-rows // BUCKET_ROW_MULTIPLE) * BUCKET_ROW_MULTIPLE
    return jnp.pad(flat, (0, rows * bucket_size - flat.size)).reshape(
        rows, bucket_size)


def qsgd_decompress(c: Compressed) -> Array:
    p = c.payload
    mag = p["q"].astype(jnp.float32) / p["levels"] * p["norms"]
    out = jnp.where(p["sign"], -mag, mag).reshape(-1)[:p["size"]]
    return out.reshape(c.orig_shape)


# -- top-k with error feedback --------------------------------------------------
def topk_compress(x: Array, *, k_frac: float = 0.01) -> Compressed:
    flat = x.reshape(-1).astype(jnp.float32)
    k = max(1, int(flat.size * k_frac))
    vals, idx = jax.lax.top_k(jnp.abs(flat), k)
    vals = flat[idx]
    return Compressed(
        kind="topk",
        payload={"vals": vals, "idx": idx, "size": flat.size},
        bits=k * (32 + 32),
        orig_shape=x.shape,
        orig_bits=_nbits(x),
    )


def topk_decompress(c: Compressed) -> Array:
    p = c.payload
    out = jnp.zeros((p["size"],), jnp.float32).at[p["idx"]].set(p["vals"])
    return out.reshape(c.orig_shape)


def topk_with_error_feedback(x: Array, error: Array, *, k_frac: float = 0.01):
    """Returns (compressed, new_error).  error accumulates what wasn't sent."""
    corrected = x + error
    c = topk_compress(corrected, k_frac=k_frac)
    new_error = corrected - topk_decompress(c)
    return c, new_error


# -- PowerSGD (rank-r) -----------------------------------------------------------
def powersgd_compress(key, x: Array, *, rank: int = 4, iters: int = 1) -> Compressed:
    """Low-rank (subspace-iteration) approximation of a 2-D tensor."""
    assert x.ndim == 2, "powersgd applies to matrices"
    if iters < 1:
        # the left factor p only exists after the first projection — iters=0
        # used to escape the loop with p unbound (UnboundLocalError)
        raise ValueError(f"powersgd needs iters >= 1, got {iters}")
    m, n = x.shape
    xf = x.astype(jnp.float32)
    q = jax.random.normal(key, (n, rank), jnp.float32)
    for _ in range(iters):
        p = xf @ q                                       # (m, r)
        p, _ = jnp.linalg.qr(p)
        q = xf.T @ p                                     # (n, r)
    return Compressed(
        kind="powersgd",
        payload={"p": p, "q": q},
        bits=(m + n) * rank * 32,
        orig_shape=x.shape,
        orig_bits=_nbits(x),
    )


def powersgd_decompress(c: Compressed) -> Array:
    return (c.payload["p"] @ c.payload["q"].T).reshape(c.orig_shape)


WIRE_CODECS = (None, "qsgd", "topk", "powersgd")


def roundtrip(kind: Optional[str], key, x: Array, **kwargs) -> Array:
    """Lossy wire round-trip: what the receiver reconstructs from ``x``.

    ``kind=None`` is the uncompressed wire (identity).  Pure function of
    ``(kind, key, x)`` — jit- and vmap-safe, so the batched swarm engine
    round-trips all N node gradients in one ``jax.vmap`` call over per-node
    keys.  The key seeds QSGD's stochastic rounding and PowerSGD's subspace
    init; top-k ignores it.

    PowerSGD natively compresses matrices; non-2-D payloads (the swarm's
    flat gradients) are zero-padded onto the squarest 2-D grid, compressed,
    and sliced back — sizes are static, so this stays jit/vmap-safe.
    """
    if kind is None:
        return x
    if kind == "qsgd":
        return qsgd_decompress(qsgd_compress(key, x, **kwargs))
    if kind == "topk":
        return topk_decompress(topk_compress(x, **kwargs))
    if kind == "powersgd":
        if x.ndim == 2:
            return powersgd_decompress(powersgd_compress(key, x, **kwargs))
        flat = x.reshape(-1)
        d = flat.size
        cols = int(math.ceil(math.sqrt(d)))
        rows = int(math.ceil(d / cols))
        grid = jnp.pad(flat, (0, rows * cols - d)).reshape(rows, cols)
        out = powersgd_decompress(powersgd_compress(key, grid, **kwargs))
        return out.reshape(-1)[:d].reshape(x.shape)
    raise ValueError(f"unknown wire codec: {kind!r} "
                     f"(roundtrip carries: {WIRE_CODECS})")


DECOMPRESSORS = {
    "qsgd": qsgd_decompress,
    "topk": topk_decompress,
    "powersgd": powersgd_decompress,
}


def decompress(c: Compressed) -> Array:
    return DECOMPRESSORS[c.kind](c)


def compression_ratio(c: Compressed) -> float:
    return c.orig_bits / c.bits
