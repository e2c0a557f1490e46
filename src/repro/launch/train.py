"""Distributed train-step builder + a runnable single-host trainer.

Two regimes, selected by the mesh and ``TrainOptions.pod_sync``:

- ``dense`` (or no pod axis): one global pjit program; the batch shards
  over (pod, data), XLA inserts the exact gradient all-reduces.  This is
  the centralized baseline the paper compares against.
- ``qsgd`` / ``gossip`` / ``centered_clip``: the Protocol Learning regime.
  The step is a ``shard_map`` manual over the ``pod`` axis only
  (``axis_names={"pod"}``) — data/model sharding inside each pod stays
  automatic (pjit), while gradients crossing the pod boundary go through
  the explicit ``core.hierarchical`` collectives: int8-on-the-wire
  quantized all-gather, ring gossip (exact at 2 pods), or byzantine-robust
  CenteredClip.  The dry-run HLO shows the wire dtype/schedule directly.

Also provides grad-accumulation microbatching (perf knob for the memory
roofline term) and the ``python -m repro.launch.train`` driver used by the
examples.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.hierarchical import get_pod_sync
from repro.launch import mesh as mesh_lib
from repro.models import sharding as shrules
from repro.models.model import Model


class TrainState(NamedTuple):
    params: Any
    opt_state: Any


@dataclass(frozen=True)
class TrainOptions:
    pod_sync: str = "dense"              # dense|qsgd|gossip|centered_clip
    sync_kwargs: Dict = field(default_factory=dict)
    microbatches: int = 1                # grad accumulation steps
    donate: bool = True
    # FSDP-style compute gather: weights are STORED (data, model)-sharded
    # (so optimizer state fits) but gathered over ``data`` for the forward/
    # backward.  Without this, XLA sharding propagation keeps weights
    # d_model-sharded over ``data`` and instead un-shards the *activations*
    # over the batch — materializing full-batch O(S²) attention residuals
    # (observed: 124 GB/device temps on tinyllama train_4k).  See
    # EXPERIMENTS.md §Perf iteration 0.
    param_gather: str = "fsdp"           # fsdp|none


# -- sharding trees -----------------------------------------------------------
def state_pspecs(model: Model, mesh: Mesh):
    sizes = mesh_lib.axis_sizes(mesh)
    return shrules.param_pspecs(model.param_shapes(), model.cfg, sizes)


def _strip_axes(spec: P, drop=("data",)) -> P:
    out = []
    for e in spec:
        if e is None:
            out.append(None)
        elif isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a not in drop)
            out.append(kept if kept else None)
        else:
            out.append(None if e in drop else e)
    return P(*out)


def compute_pspecs(pspec_tree):
    """Model-axis-only specs: the FSDP gather target for the forward pass."""
    return jax.tree.map(lambda s: _strip_axes(s), pspec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def make_param_gather(model: Model, mesh: Mesh, mode: str, *,
                      bare_specs: bool = False):
    """params -> params resharded for compute (identity when mode='none').

    ``bare_specs=True`` constrains with raw PartitionSpecs (resolved against
    the context mesh) — required inside the partial-manual pod shard_map,
    where a NamedSharding built on the fully-Auto mesh would not match the
    Manual-pod context mesh.
    """
    if mode == "none" or mesh_lib.axis_sizes(mesh).get("data", 1) == 1:
        return lambda p: p
    gathered = compute_pspecs(state_pspecs(model, mesh))
    if not bare_specs:
        gathered = jax.tree.map(lambda s: NamedSharding(mesh, s), gathered,
                                is_leaf=lambda x: isinstance(x, P))

    def gather(params):
        return jax.tree.map(jax.lax.with_sharding_constraint, params, gathered,
                            is_leaf=lambda x: isinstance(x, P))
    return gather


def train_state_shardings(model: Model, optimizer, mesh: Mesh):
    """NamedShardings for TrainState(params, opt_state)."""
    pspec = state_pspecs(model, mesh)
    opt_state_shapes = jax.eval_shape(
        lambda: optimizer.init(model.param_shapes()))
    opt_pspec = jax.tree_util.tree_map_with_path(
        lambda path, leaf: _match_param_spec(path, leaf, pspec),
        opt_state_shapes)
    to_ns = lambda spec_tree: jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
    return TrainState(params=to_ns(pspec), opt_state=to_ns(opt_pspec))


def _match_param_spec(path, leaf, pspec):
    """Optimizer-state leaf -> spec of the parameter it mirrors (or P())."""
    # AdamState paths look like ('m', <param path...>) / ('v', ...) / ('step',)
    keys = [getattr(e, "key", getattr(e, "idx", getattr(e, "name", None)))
            for e in path]
    sub = pspec
    try:
        for k in keys[1:]:
            if isinstance(sub, (dict,)):
                sub = sub[k]
            elif isinstance(sub, (list, tuple)):
                sub = sub[int(k)]
            else:
                return P()
        if isinstance(sub, P):
            return sub
    except (KeyError, IndexError, TypeError, ValueError):
        pass
    return P()


def batch_shardings(model: Model, shape: ShapeConfig, mesh: Mesh):
    sizes = mesh_lib.axis_sizes(mesh)
    extra = ("pod",) if mesh_lib.has_pod_axis(mesh) else ()
    specs = shrules.batch_pspecs(model.batch_specs(shape), sizes,
                                 extra_batch_axes=extra)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def _pod_batch_specs(batch_tree):
    """Batch specs naming ONLY the pod axis (for partial-manual shard_map)."""
    def leaf(path, l):
        keys = [str(getattr(e, "key", getattr(e, "idx", e))) for e in path]
        if keys and keys[-1] == "positions":
            return P(None, "pod")
        return P("pod")
    return jax.tree_util.tree_map_with_path(leaf, batch_tree)


# -- microbatching ------------------------------------------------------------
def _split_micro(batch, m: int):
    """(B, ...) -> (m, B/m, ...) on the batch axis of every leaf.

    The reshape breaks SPMD batch-sharding propagation (observed: granite
    train_4k with mb=8 compiled to 8× the FLOPs — every device ran the
    full global batch), so when the launch layer has declared activation
    batch axes we re-pin the new batch dim explicitly.
    """
    from repro.models import sharding as shrules
    axes = shrules._ACT_BATCH_AXES

    def leaf(path, l):
        keys = [str(getattr(e, "key", getattr(e, "idx", e))) for e in path]
        if keys and keys[-1] == "positions":        # (3, B, S) -> (m, 3, B/m, S)
            b = l.shape[1]
            out = jnp.moveaxis(
                l.reshape(l.shape[0], m, b // m, *l.shape[2:]), 1, 0)
            if axes is not None:
                out = jax.lax.with_sharding_constraint(
                    out, P(None, None, axes, *([None] * (out.ndim - 3))))
            return out
        b = l.shape[0]
        out = l.reshape(m, b // m, *l.shape[1:])
        if axes is not None:
            out = jax.lax.with_sharding_constraint(
                out, P(None, axes, *([None] * (out.ndim - 2))))
        return out
    return jax.tree_util.tree_map_with_path(leaf, batch)


def _grad_fn(model: Model, microbatches: int, gather=lambda p: p):
    """Returns grad_fn(params, batch) -> (loss, grads) with accumulation."""
    def loss_of(params, batch):
        loss, _ = model.loss(gather(params), batch)
        return loss

    vg = jax.value_and_grad(loss_of)

    if microbatches == 1:
        return vg

    def accum(params, batch):
        micro = _split_micro(batch, microbatches)

        def body(carry, mb):
            loss_sum, gsum = carry
            l, g = vg(params, mb)
            gsum = jax.tree.map(jnp.add, gsum, g)
            return (loss_sum + l, gsum), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss_sum, gsum), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), zeros), micro)
        inv = 1.0 / microbatches
        return loss_sum * inv, jax.tree.map(lambda g: g * inv, gsum)

    return accum


# -- the train step ------------------------------------------------------------
def make_train_step(model: Model, optimizer, mesh: Mesh,
                    opts: TrainOptions = TrainOptions()):
    """Returns ``step(state, batch) -> (state, metrics)`` (un-jitted)."""
    use_pod_sync = mesh_lib.has_pod_axis(mesh) and opts.pod_sync != "dense"
    gather = make_param_gather(model, mesh, opts.param_gather,
                               bare_specs=use_pod_sync)
    grad_fn = _grad_fn(model, opts.microbatches, gather)

    def apply_update(state, loss, grads):
        params, opt_state = optimizer.update(grads, state.opt_state, state.params)
        from repro.optim.optimizer import global_norm
        metrics = {"loss": loss, "grad_norm": global_norm(grads)}
        return TrainState(params, opt_state), metrics

    if not use_pod_sync:
        def step(state, batch):
            loss, grads = grad_fn(state.params, batch)
            return apply_update(state, loss, grads)
        return step

    sync = get_pod_sync(opts.pod_sync, **opts.sync_kwargs)
    # Inside the manual-pod region the batch's pod dim is already local, so
    # activation constraints must not name the manual "pod" axis.
    inner_batch_axes = tuple(a for a in mesh_lib.batch_axes(mesh)
                             if a != "pod")

    def per_pod(state, batch):
        # batch is this pod's local shard; data/model axes remain automatic
        with shrules.activation_sharding(
                inner_batch_axes,
                model_axis_size=mesh_lib.axis_sizes(mesh).get("model", 1)):
            loss, grads = grad_fn(state.params, batch)
        grads = sync(grads, "pod")
        loss = jax.lax.pmean(loss, "pod")
        return apply_update(state, loss, grads)

    def step(state, batch):
        batch_specs = _pod_batch_specs(batch)
        state_specs = jax.tree.map(lambda _: P(), state)
        return jax.shard_map(
            per_pod,
            mesh=mesh,
            in_specs=(state_specs, batch_specs),
            out_specs=(state_specs, {"loss": P(), "grad_norm": P()}),
            axis_names=frozenset({"pod"}),
            check_vma=False,
        )(state, batch)

    return step


# -- serving step (decode shapes) ----------------------------------------------
def make_serve_step(model: Model):
    """One decode tick: (params, tokens(B,1), cache) -> (logits, cache)."""
    def serve_step(params, tokens, cache):
        return model.decode_step(params, tokens, cache)
    return serve_step


def serve_param_shardings(model: Model, mesh: Mesh):
    """Serving weight layout: replicated over `data`, sharded over `model`
    (Megatron TP).  There is no optimizer state to amortize at inference,
    and keeping d_model sharded over `data` makes XLA all-gather expert/
    attention weights PER DECODE TOKEN (mixtral: 10.9 GB/token —
    EXPERIMENTS.md §Perf pair A3)."""
    pspec = compute_pspecs(state_pspecs(model, mesh))
    return jax.tree.map(lambda s: NamedSharding(mesh, s), pspec,
                        is_leaf=lambda x: isinstance(x, P))


def serve_shardings(model: Model, shape: ShapeConfig, mesh: Mesh):
    sizes = mesh_lib.axis_sizes(mesh)
    extra = ("pod",) if mesh_lib.has_pod_axis(mesh) else ()
    tokens_sds, cache_sds = model.decode_specs(shape)
    b = tokens_sds.shape[0]
    btotal = 1
    for a in (*extra, "data"):
        btotal *= sizes[a]
    tok_spec = P((*extra, "data")) if b % btotal == 0 else P()
    cache_spec = shrules.cache_pspecs(cache_sds, model.cfg, sizes,
                                      extra_batch_axes=extra)
    ns = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                                   is_leaf=lambda x: isinstance(x, P))
    return ns(tok_spec), ns(cache_spec)


# -- driver ---------------------------------------------------------------------
def main(argv=None):
    import argparse

    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, model_batch
    from repro.models.model import build_model
    from repro.optim.optimizer import AdamW, cosine_schedule

    ap = argparse.ArgumentParser(description="trainer")
    ap.add_argument("--arch", default="protocol-125m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="train the tiny same-family variant (default); "
                         "--no-reduced trains the published width")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    cfg = cfg.reduced(max_seq_len=args.seq) if args.reduced else cfg
    model = build_model(cfg)
    opt = AdamW(lr=cosine_schedule(args.lr, 10, args.steps), weight_decay=0.01)

    params = model.init(jax.random.PRNGKey(0))
    state = TrainState(params, opt.init(params))
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch)

    host = mesh_lib.make_host_mesh()
    step_fn = jax.jit(make_train_step(
        model, opt, host, TrainOptions(microbatches=args.microbatches)))

    import time
    t0 = time.time()
    for step in range(args.steps):
        batch = model_batch(cfg, dcfg, step)
        state, metrics = step_fn(state, batch)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d}  loss {float(metrics['loss']):8.4f}  "
                  f"gnorm {float(metrics['grad_norm']):8.3f}  "
                  f"({time.time() - t0:6.1f}s)", flush=True)
    return state


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
