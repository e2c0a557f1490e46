"""CenteredClip byzantine-robust aggregation — Pallas TPU kernel ([40], §3.3).

One CenteredClip iteration:  v ← v + mean_i clip(x_i − v, τ), where the clip
is by each node's FULL-vector L2 norm ‖x_i − v‖ over all D coordinates.

TPU adaptation (DESIGN.md §2): D is huge (the flattened gradient) and N is
small (the node count), so the kernel streams (N, block_d) VMEM tiles twice
along a two-phase grid — phase 0 accumulates per-node squared norms into a
persistent (N, 1) VMEM scratch (cross-tile reduction), phase 1 re-streams
the tiles and applies the clipped mean.  The updates matrix is read twice
from HBM; nothing of size D is ever resident.

Grid: (2, n_d_blocks)   (phase outermost, tiles innermost/sequential)
Blocks: x (N, bd) · v (1, bd) -> v_new (1, bd);  scratch sq (N, 1) fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _kernel(x_ref, v_ref, o_ref, sq_ref, *, tau: float):
    ph = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((ph == 0) & (j == 0))
    def _init():
        sq_ref[...] = jnp.zeros_like(sq_ref)

    diff = x_ref[...].astype(jnp.float32) - v_ref[...].astype(jnp.float32)

    @pl.when(ph == 0)
    def _accumulate():
        sq_ref[...] += jnp.sum(diff * diff, axis=1, keepdims=True)
        o_ref[...] = v_ref[...]                       # placeholder write

    @pl.when(ph == 1)
    def _apply():
        norm = jnp.sqrt(sq_ref[...])                  # (N, 1)
        scale = jnp.minimum(1.0, tau / jnp.maximum(norm, 1e-12))
        o_ref[...] = v_ref[...] + jnp.mean(diff * scale, axis=0, keepdims=True)


def centered_clip_iter_fwd(updates, v, *, clip_tau: float = 1.0,
                           block_d: int = 2048, interpret: bool = False):
    """One CC iteration.  updates: (N, D) fp32; v: (D,) fp32 -> (D,)."""
    n, d = updates.shape
    block_d = min(block_d, d)
    while d % block_d:
        block_d //= 2
    grid = (2, d // block_d)

    kern = functools.partial(_kernel, tau=clip_tau)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((n, block_d), lambda ph, j: (0, j)),
            pl.BlockSpec((1, block_d), lambda ph, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda ph, j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, 1), jnp.float32)],
        interpret=interpret,
        name="centered_clip_iter",
    )(updates, v.reshape(1, d))
    return out.reshape(d)
