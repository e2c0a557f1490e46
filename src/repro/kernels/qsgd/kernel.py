"""QSGD stochastic gradient quantization — Pallas TPU kernel (paper §3.1, [2]).

The wire-compression hot loop of the Protocol Learning gradient path: scale
by the global L2 norm, stochastically round to ``levels`` magnitude buckets,
emit a signed int8 code per element.  E[decode(encode(x))] = x.

TPU adaptation (DESIGN.md §2): the gradient is viewed as (rows, 128) lanes;
each grid step quantizes one (block_rows, 128) VMEM tile on the VPU.  The
global norm is a scalar prefactor computed by XLA (a cheap fused reduction)
and prefetched to SMEM; randomness enters as a uniform tile generated by
the JAX PRNG outside the kernel, so the kernel is bit-deterministic given
its inputs (required by the §4.2 verification path: validators recompute
codes exactly).

Grid: (n_row_blocks,)
Blocks: x (block_rows, 128) · rnd (block_rows, 128) -> q int8 (block_rows, 128)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

LANE = 128


def _kernel(norm_ref, x_ref, rnd_ref, q_ref, *, levels: int):
    x = x_ref[...].astype(jnp.float32)
    norm = norm_ref[0]
    scaled = jnp.abs(x) / jnp.maximum(norm, 1e-30) * levels
    lower = jnp.floor(scaled)
    p = scaled - lower
    q = lower + (rnd_ref[...] < p).astype(jnp.float32)       # in [0, levels]
    q = jnp.where(jnp.signbit(x), -q, q)
    q_ref[...] = q.astype(jnp.int8)


def qsgd_encode_fwd(x2d, rnd2d, norm, *, levels: int = 64,
                    block_rows: int = 256, interpret: bool = False):
    """x2d, rnd2d: (R, 128) fp32; norm: () fp32.  Returns int8 codes (R, 128)."""
    assert x2d.ndim == 2 and x2d.shape[1] == LANE, x2d.shape
    assert levels <= 127, "codes must fit int8"
    r = x2d.shape[0]
    block_rows = min(block_rows, r)
    while r % block_rows:
        block_rows //= 2
    grid = (r // block_rows,)

    kern = functools.partial(_kernel, levels=levels)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((block_rows, LANE), lambda i, norm: (i, 0)),
                pl.BlockSpec((block_rows, LANE), lambda i, norm: (i, 0)),
            ],
            out_specs=pl.BlockSpec((block_rows, LANE), lambda i, norm: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, jnp.int8),
        interpret=interpret,
        name="qsgd_encode",
    )(norm.reshape(1), x2d, rnd2d)
