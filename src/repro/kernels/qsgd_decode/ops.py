"""Wire-format QSGD payloads and the fused decode-accumulate entry points.

``wire_encode`` is bit-compatible with ``core.compression.qsgd_compress``
— same bucketing, same norms, same stochastic-rounding draws from the
same key — but stores the code as one **signed int8** per element
(sign folded into the magnitude) instead of the reference's int32 + bool
pair, so the payload a fused round keeps live between compress and
aggregate is ~4.5 bytes/element smaller.  ``wire_decode(wire_encode(k, x))``
equals ``compression.roundtrip("qsgd", k, x)`` except that true-sign zero
codes decode to +0.0 rather than −0.0 (numerically equal; every
arithmetic consumer is unaffected).

``QsgdPayload`` is a registered pytree with static (levels, size,
bucket_size) aux data, so ``jax.vmap(wire_encode)`` batches the per-node
payloads into a stack the fused aggregators consume directly.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.compression import bucketed
from repro.kernels import interpret_mode
from repro.kernels.qsgd_decode.kernel import qsgd_decode_accumulate_fwd

Array = jax.Array


@jax.tree_util.register_pytree_node_class
class QsgdPayload:
    """codes (…, nb, B) int8 signed magnitudes, norms (…, nb, 1) f32 bucket
    L2 norms, nb padded to a multiple of 8 with zero buckets
    (``compression.bucketed``); levels/size/bucket_size are static aux
    (vmap-/jit-safe)."""

    def __init__(self, codes: Array, norms: Array, *, levels: int,
                 size: int, bucket_size: int):
        self.codes = codes
        self.norms = norms
        self.levels = levels
        self.size = size
        self.bucket_size = bucket_size

    def tree_flatten(self):
        return (self.codes, self.norms), (self.levels, self.size,
                                          self.bucket_size)

    @classmethod
    def tree_unflatten(cls, aux, children):
        levels, size, bucket_size = aux
        codes, norms = children
        return cls(codes, norms, levels=levels, size=size,
                   bucket_size=bucket_size)

    def wire_bits(self) -> int:
        """Same accounting as ``compression.qsgd_compress``."""
        import math
        bits_per_el = math.ceil(math.log2(self.levels + 1)) + 1
        nb = -(-self.size // self.bucket_size)
        return 32 * nb + self.size * bits_per_el


def wire_encode(key, x: Array, *, levels: int = 16,
                bucket_size: int = 1024) -> QsgdPayload:
    """QSGD-quantize ``x`` (any shape) into a signed-int8 wire payload.

    Every intermediate up to the code integers matches
    ``compression.qsgd_compress`` expression-for-expression, so the
    stochastic rounding consumes identical uniform draws and the decoded
    values agree bitwise (modulo signed zeros).  ``levels`` must fit a
    signed byte.
    """
    if levels > 127:
        raise ValueError(f"int8 wire codes need levels <= 127, got {levels}")
    flat = x.reshape(-1).astype(jnp.float32)
    padded = bucketed(flat, bucket_size)
    norms = jnp.linalg.norm(padded, axis=1, keepdims=True)
    scaled = jnp.abs(padded) / jnp.maximum(norms, 1e-30) * levels
    lower = jnp.floor(scaled)
    p = scaled - lower
    rnd = jax.random.uniform(key, padded.shape)
    q = (lower + (rnd < p)).astype(jnp.int32)
    sign = jnp.signbit(padded)
    codes = jnp.where(sign, -q, q).astype(jnp.int8)
    return QsgdPayload(codes, norms, levels=levels, size=flat.size,
                       bucket_size=bucket_size)


def wire_decode(payload: QsgdPayload) -> Array:
    """Dequantize a (possibly vmapped) payload back to flat f32 updates."""
    # associate exactly like compression.qsgd_decompress — (q/levels)·norm —
    # so the reconstruction is bit-equal, not merely within an ulp
    dec = (payload.codes.astype(jnp.float32)
           / payload.levels * payload.norms)
    lead = payload.codes.shape[:-2]
    return dec.reshape(lead + (-1,))[..., :payload.size]


def wire_roundtrip(key, x: Array, *, levels: int = 16,
                   bucket_size: int = 1024) -> Array:
    """decode(encode(x)) — the fused twin of
    ``compression.roundtrip("qsgd", ...)``, equal modulo signed zeros."""
    out = wire_decode(wire_encode(key, x, levels=levels,
                                  bucket_size=bucket_size))
    return out.reshape(x.shape)


def decode_accumulate(payload: QsgdPayload, weights: Array, *,
                      use_kernel: bool = False, block_d: int = 4096,
                      interpret: Optional[bool] = None) -> Array:
    """Σᵢ wᵢ · decode(payloadᵢ) without a materialized decoded stack.

    ``payload`` is a node-batched QsgdPayload (codes (N, nb, B)); returns
    the (size,) f32 accumulator.  The jnp path writes the dequantize as an
    elementwise expression feeding the node-sum so XLA fuses it into one
    pass; ``use_kernel=True`` runs the Pallas tile kernel instead.
    """
    n, nb, b = payload.codes.shape
    if use_kernel:
        acc = qsgd_decode_accumulate_fwd(
            payload.codes.reshape(n, nb * b),
            payload.norms.reshape(n, nb),
            weights, levels=payload.levels, bucket_size=b,
            block_d=block_d, interpret=interpret_mode(interpret))
    else:
        dec = (payload.codes.astype(jnp.float32)
               / payload.levels * payload.norms)            # (N, nb, B)
        w = weights.astype(jnp.float32)[:, None, None]
        acc = jnp.sum(dec * w, axis=0).reshape(-1)
    return acc[:payload.size]
