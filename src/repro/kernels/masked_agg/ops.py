"""Fused masked-aggregation entry points: the round engine's fast path.

Each fused aggregator is a drop-in twin of its ``aggregation.masked_*``
counterpart — same name, same keyword surface — that additionally accepts
a node-batched :class:`~repro.kernels.qsgd_decode.ops.QsgdPayload` in
place of the fp32 (N, D) stack, so a compressed round feeds wire payloads
straight into aggregation.

Two implementations sit behind each twin:

- ``use_kernel=False`` (the default off-TPU): restructured jnp with
  **identical op-level arithmetic** to the reference, so fused == unfused
  bit-for-bit (pinned by tests/test_kernel_conformance.py).  The speed
  comes from two algorithm swaps, not looser numerics:
  (1) the coordinate-median warm start runs as a Batcher odd-even merge
  network over the N node rows — pure min/max, bit-equal to ``nanmedian``
  including its even-k interpolation, and ~6x faster than XLA's generic
  sort of the (N, D) stack at N=16, D=1M on CPU;
  (2) krum's pairwise distances accumulate in gram form
  (‖xᵢ‖² + ‖xⱼ‖² − 2·xᵢᵀxⱼ, one matmul) instead of the broadcast
  (N, N, D) difference tensor (~15x).  Gram d2 is *not* bit-equal to the
  broadcast d2 (cancellation at ~1e-6 relative), but krum's output is an
  argmin **selection** — equal except at exact score ties.
- ``use_kernel=True`` (the default on TPU backends — ``repro.kernels``
  decides): the Pallas kernels from ``kernel.py``, which additionally
  keep every D-sized intermediate in VMEM tiles.  Tiled norm accumulation reorders float sums, so the kernel
  path carries the same documented ~1e-5 relative divergence as the
  centralized centered_clip kernel.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import aggregation
from repro.kernels import interpret_mode, use_kernels
from repro.kernels.masked_agg import kernel as _k
from repro.kernels.qsgd_decode import ops as qdec

Array = jax.Array

# make_round_fn auto-selects the fused path once the fp32 update stack
# (N·D·4 bytes) crosses this; below it the unfused path compiles faster and
# the sort being replaced is already cheap.
FUSED_MIN_BYTES = 4 << 20


def _as_f32_stack(updates) -> Array:
    """(N, D) f32 view of either a dense stack or a QsgdPayload batch."""
    if isinstance(updates, qdec.QsgdPayload):
        return qdec.wire_decode(updates)
    return updates.astype(jnp.float32)


def masked_median_net(updates: Array, mask: Array) -> Array:
    """Masked coordinate median via the odd-even merge network — bit-equal
    to ``aggregation._masked_median`` for mask.sum() >= 1."""
    n = updates.shape[0]
    rows = [jnp.where(mask[i], updates[i], jnp.inf) for i in range(n)]
    k = jnp.sum(mask.astype(jnp.int32))
    return _k._masked_rank_interp(_k._sorted_rows(rows), k)


def masked_centered_clip_fused(updates, mask: Array, *,
                               clip_tau=None, iters: int = 3, v0=None,
                               use_kernel: Optional[bool] = None,
                               block_d: Optional[int] = None,
                               interpret: Optional[bool] = None) -> Array:
    x = _as_f32_stack(updates)
    if use_kernels(use_kernel):
        interpret = interpret_mode(interpret)
        v = (v0.astype(jnp.float32) if v0 is not None
             else _k.masked_median_fwd(x, mask, block_d=block_d,
                                       interpret=interpret))
        for _ in range(iters):
            v = _k.masked_cc_iter_fwd(x, v, mask, clip_tau=clip_tau,
                                      block_d=block_d, interpret=interpret)
        out = v
    else:
        warm = v0 if v0 is not None else masked_median_net(x, mask)
        # delegate to the reference with the network warm start — every
        # iteration op is then literally the reference's, hence bit-equal
        out = aggregation.masked_centered_clip(
            x, mask, clip_tau=clip_tau, iters=iters, v0=warm)
    return jnp.where(jnp.any(mask), out, jnp.zeros_like(out))


def masked_krum_fused(updates, mask: Array, *, f: int = 1,
                      use_kernel: Optional[bool] = None,
                      block_d: Optional[int] = None,
                      interpret: Optional[bool] = None) -> Array:
    x = _as_f32_stack(updates)
    if use_kernels(use_kernel):
        d2 = _k.masked_krum_d2_fwd(x, block_d=block_d,
                                   interpret=interpret_mode(interpret))
    else:
        sq = jnp.sum(x * x, axis=1)
        d2 = sq[:, None] + sq[None, :] - 2.0 * (x @ x.T)
    scores = aggregation._krum_scores_from_d2(d2, mask, f)
    row = x[jnp.argmin(scores)]
    return jnp.where(jnp.any(mask), row, jnp.zeros_like(row))


def masked_mean_fused(updates, mask: Array, *,
                      use_kernel: Optional[bool] = None,
                      block_d: int = 4096,
                      interpret: Optional[bool] = None) -> Array:
    if isinstance(updates, qdec.QsgdPayload):
        k = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
        acc = qdec.decode_accumulate(
            updates, mask.astype(jnp.float32),
            use_kernel=use_kernels(use_kernel), block_d=block_d,
            interpret=interpret_mode(interpret))
        return acc / k
    return aggregation.masked_mean(updates, mask)


FUSED_MASKED_AGGREGATORS: Dict[str, Callable] = {
    "mean": masked_mean_fused,
    "krum": masked_krum_fused,
    "centered_clip": masked_centered_clip_fused,
}


def get_fused_aggregator(name: str, **defaults) -> Callable:
    """Fused twin of ``aggregation.get_masked_aggregator`` — same names,
    same keyword routing; raises KeyError for aggregators without a fused
    implementation (the engine falls back to the unfused path)."""
    fn = FUSED_MASKED_AGGREGATORS[name]
    return functools.partial(fn, **defaults) if defaults else fn
