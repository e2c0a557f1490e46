"""Fused QSGD dequantize-and-accumulate — Pallas TPU kernel (paper §3.1+§3.3).

The unfused round decodes every node's QSGD payload into a full fp32
(N, D) stack before aggregation touches it — 4 bytes/element of HBM
traffic for data that lived on the wire at ~0.56 bytes/element (int8
sign+magnitude codes plus one fp32 norm per bucket).  This kernel
consumes the wire payloads directly: each grid step loads an
(N, block_d) tile of int8 codes and the matching groups of 8 bucket
norms per node, dequantizes in VMEM, and accumulates the weighted
per-node sum straight into the aggregation accumulator.  The decoded
stack never exists in HBM.

The weight vector folds in whatever the aggregator needs — the masked
mean uses ``mask / k``; CenteredClip-style iterations can pass
per-node clip scales.  Columns are independent, so the grid is a plain
(n_d_blocks,) sweep with no cross-tile state.

``block_d`` covers whole groups of 8 buckets (the norm layout is
per-bucket); the wrapper enforces ``bucket_size % 128 == 0``, and the
wire codec pads D to a multiple of 8 buckets.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.compression import BUCKET_ROW_MULTIPLE

#: buckets per norm column group — the row multiple the wire codec pads the
#: buckets to (the TPU sublane count), so a norms tile is (…, N, 8): both
#: trailing dims equal the array's, as Mosaic requires
NORM_GROUP = BUCKET_ROW_MULTIPLE


def _decode_acc_kernel(c_ref, n_ref, w_ref, o_ref, *, bucket: int,
                       levels: int):
    """codes tile (N, bd) int8; norms tile (g, N, 8): norm of bucket
    8·gi + b of node i at [gi, i, b]; weights (N, 1).  Every bucket is a
    static lane-aligned slice, scaled by a static norm column."""
    w = w_ref[...]                                      # (N, 1)
    for gi in range(n_ref.shape[0]):
        norms = n_ref[gi]                               # (N, 8)
        for b in range(NORM_GROUP):
            lo = (gi * NORM_GROUP + b) * bucket
            codes = c_ref[:, lo:lo + bucket].astype(jnp.float32) / levels
            dec = codes * norms[:, b:b + 1]             # (N, bucket)
            o_ref[:, lo:lo + bucket] = jnp.sum(dec * w, axis=0,
                                               keepdims=True)


def qsgd_decode_accumulate_fwd(codes, norms, weights, *, levels: int,
                               bucket_size: int, block_d: int = 4096,
                               interpret: bool = False):
    """weights ⋅ dequantize(codes, norms): (N, L) int8 codes, (N, L/bucket)
    norms, (N,) weights -> (L,) f32 accumulator, one streamed pass.

    The bucket count L/bucket must be a multiple of 8
    (``compression.bucketed`` pads it so).  The norms are regrouped to
    (nb/8, N, 8) — a pass over 4 bytes per bucket — so each grid step
    reads whole groups of 8 bucket norms per node."""
    n, l = codes.shape
    group = NORM_GROUP * bucket_size
    if bucket_size % 128 or l % group:
        raise ValueError(
            f"decode_accumulate needs lane-aligned buckets in groups of "
            f"{NORM_GROUP}: bucket_size={bucket_size}, L={l}")
    block_d = max(group, min(block_d, l)) // group * group
    while l % block_d:
        block_d -= group
    grouped = norms.reshape(n, l // group, NORM_GROUP).transpose(1, 0, 2)
    kern = functools.partial(_decode_acc_kernel, bucket=bucket_size,
                             levels=levels)
    out = pl.pallas_call(
        kern,
        grid=(l // block_d,),
        in_specs=[
            pl.BlockSpec((n, block_d), lambda j: (0, j)),
            pl.BlockSpec((block_d // group, n, NORM_GROUP),
                         lambda j: (j, 0, 0)),
            pl.BlockSpec((n, 1), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, l), jnp.float32),
        interpret=interpret,
        name="qsgd_decode_acc",
    )(codes, grouped, weights.reshape(n, 1).astype(jnp.float32))
    return out.reshape(l)
