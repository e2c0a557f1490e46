"""SWARM-style pipeline parallelism (paper §3.2, Ryabinin et al. [71]).

The paper's preferred internet-scale sharding: the model is split layerwise
into P stages; activations flow stage-to-stage (point-to-point, cheap),
never all-to-all.  Expressed natively with shard_map + lax.ppermute:

- stage s holds layers [s·L/P, (s+1)·L/P) — params sharded over the
  ``pipe`` mesh axis on their stacked layer dim;
- GPipe-style fill/drain schedule over M microbatches: M + P − 1 ticks,
  activation hand-off by collective_permute each tick;
- jax.grad differentiates straight through the ppermute schedule, so the
  same code trains (the backward permutes run in reverse) — no hand-written
  backward pipeline.

The square-cube claim the paper cites from [71] — per-stage comm/compute
ratio shrinks as d_model grows — is measured in benchmarks/bench_pipeline_scaling.py
with this exact implementation.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

Array = jax.Array


def num_ticks(num_micro: int, num_stages: int) -> int:
    return num_micro + num_stages - 1


def spmd_pipeline(stage_fn: Callable, stage_params, xs: Array, *, axis: str = "pipe"):
    """Run inside shard_map over ``axis``.

    stage_fn(local_params, x) -> x : applies this stage's layers.
    stage_params: this stage's shard (leading layer axis already local).
    xs: (M, mb, ...) microbatches (same on every stage).
    Returns ys: (M, mb, ...) — valid on the LAST stage, zeros elsewhere.
    """
    p = jax.lax.axis_size(axis)
    stage = jax.lax.axis_index(axis)
    m = xs.shape[0]
    ticks = num_ticks(m, p)
    perm = [(i, i + 1) for i in range(p - 1)]

    def tick_fn(carry, t):
        recv, ys = carry
        mb_idx = jnp.clip(t, 0, m - 1)
        first_in = jnp.where(t < m, 1.0, 0.0) * xs[mb_idx]
        x = jnp.where(stage == 0, first_in, recv)
        out = stage_fn(stage_params, x)
        # last stage: commit the microbatch that finished at this tick
        done_idx = jnp.clip(t - (p - 1), 0, m - 1)
        commit = (stage == p - 1) & (t >= p - 1)
        ys = jax.lax.dynamic_update_index_in_dim(
            ys, jnp.where(commit, out, ys[done_idx]), done_idx, 0)
        recv = jax.lax.ppermute(out, axis, perm)
        return (recv, ys), None

    recv0 = jax.lax.pvary(jnp.zeros_like(xs[0]), (axis,))
    ys0 = jax.lax.pvary(jnp.zeros_like(xs), (axis,))
    (recv, ys), _ = jax.lax.scan(tick_fn, (recv0, ys0), jnp.arange(ticks))
    # broadcast final outputs from the last stage to everyone
    mask = (stage == p - 1).astype(ys.dtype)
    return jax.lax.psum(ys * mask, axis)


def make_pipeline_apply(layer_fn: Callable, mesh: Mesh, *, axis: str = "pipe"):
    """Build jit-ready pipelined apply: (stacked_params, xs) -> ys.

    layer_fn(layer_params, x) -> x for ONE layer; layers are scanned within
    a stage.  stacked_params leaves have leading dim L (L % P == 0).
    """

    def stage_fn(local_params, x):
        def body(x, lp):
            return layer_fn(lp, x), None
        x, _ = jax.lax.scan(body, x, local_params)
        return x

    def apply(stacked_params, xs):
        fn = functools.partial(spmd_pipeline, stage_fn, axis=axis)
        spec_params = jax.tree.map(lambda _: P(axis), stacked_params)
        # the mesh context lets the caller's single-device arrays enter
        # the stage-sharded program
        with jax.set_mesh(mesh):
            return jax.shard_map(
                fn, mesh=mesh,
                in_specs=(spec_params, P()),
                out_specs=P(),
            )(stacked_params, xs)

    return apply


def pipeline_comm_bytes(num_micro: int, num_stages: int, act_bytes: int) -> int:
    """Activation bytes crossing stage boundaries per forward pass."""
    return num_ticks(num_micro, num_stages) * (num_stages - 1) * act_bytes


def pipeline_compute_flops(num_micro: int, layers_per_stage: int,
                           flops_per_layer_mb: int) -> int:
    """Useful FLOPs per stage per forward pass."""
    return num_micro * layers_per_stage * flops_per_layer_mb


def bubble_fraction(num_micro: int, num_stages: int) -> float:
    """GPipe bubble: (P-1)/(M+P-1) of ticks are fill/drain idle."""
    return (num_stages - 1) / num_ticks(num_micro, num_stages)
