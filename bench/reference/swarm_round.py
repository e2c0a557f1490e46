"""Plain reference of the centralized swarm round, step by step in Python.

Per round: every node's gradient of the mean next-token loss on its own
batch (``dense_lm``, float32); byzantine corruption (the inner-product
attack submits ``-scale`` times the mean true gradient of the active
nodes); stake/slash audits (a node is audited when its uniform draw from
the round's key schedule is under ``p_check``, and caught when its
submission differs from the recomputed gradient by more than the relative
``tolerance``); the masked aggregate of the kept submissions (mean, or
CenteredClip from the coordinate median with the adaptive radius); global-
norm clipping and AdamW, all on float32 parameters.

The audit leaves out the simulated cross-stack noise (``numeric_noise``,
1e-5 relative): an honest submission differs from its recompute by that
noise alone, a corrupted one by orders of magnitude more than the
tolerance, so no decision depends on it.

It imports nothing of the program: the key schedule (``fold_in`` of
purpose, round and node into the run's key) is written out here.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic as traffic_mod
from bench.reference import dense_lm
from bench.weights import make_params

AUDIT_SELECT = 2          # the round's key-schedule purpose of audit draws


def leaf_norms(tree) -> np.ndarray:
    return np.asarray(jax.jit(lambda t: jnp.stack(
        [jnp.linalg.norm(l.astype(jnp.float32).ravel())
         for l in jax.tree.leaves(t)]))(tree), np.float64)


def _shape(s):
    return (-1,) + (1,) * (s.ndim - 1)


def _rows(s, idx, true_mean, attack):
    """Rows ``idx`` of a leaf's submissions: an attacker (``attack`` > 0)
    submits ``-attack`` times the mean true gradient of the active nodes,
    an honest node its own gradient.  Built per leaf, so the corrupted
    stack is never held whole."""
    a = jnp.take(attack, idx)
    return jnp.where((a > 0).reshape(_shape(s)),
                     -a.reshape(_shape(s)) * true_mean[None],
                     jnp.take(s, idx, axis=0))


@jax.jit
def _true_mean(s, active):
    w = active.astype(jnp.float32) / jnp.maximum(jnp.sum(active), 1)
    return jnp.tensordot(w, s, axes=1)


@jax.jit
def _audit_terms(s, true_mean, attack):
    """Per node: squared distance of its submission to its true gradient,
    and the squared norm of the true gradient, over this leaf."""
    flat = s.reshape(s.shape[0], -1)
    sub = jnp.where(attack[:, None] > 0, -attack[:, None] * true_mean.ravel(),
                    flat)
    return jnp.sum(jnp.square(sub - flat), 1), jnp.sum(jnp.square(flat), 1)


@jax.jit
def _median(s, idx, true_mean, attack):
    return jnp.median(_rows(s, idx, true_mean, attack), axis=0)


@jax.jit
def _mean(s, idx, true_mean, attack):
    return jnp.mean(_rows(s, idx, true_mean, attack), axis=0)


@jax.jit
def _sq_dist(s, idx, true_mean, attack, v):
    d = _rows(s, idx, true_mean, attack) - v[None]
    return jnp.sum(jnp.square(d).reshape(d.shape[0], -1), axis=1)


@jax.jit
def _clip_step(s, idx, true_mean, attack, v, scale):
    d = _rows(s, idx, true_mean, attack) - v[None]
    return v + jnp.mean(d * scale.reshape(_shape(d)), axis=0)


def centered_clip(leaves, idx, iters: int):
    """CenteredClip over the kept rows: start at the coordinate median, then
    ``iters`` times move by the mean of the differences clipped to the
    median distance.  ``leaves`` holds (stack, true mean, attack) per leaf."""
    v = [_median(leaf[0], idx, *leaf[1:]) for leaf in leaves]
    for _ in range(iters):
        dist = jnp.sqrt(sum(_sq_dist(leaf[0], idx, *leaf[1:], l)
                            for leaf, l in zip(leaves, v)))
        scale = jnp.minimum(1.0, jnp.median(dist) / jnp.maximum(dist, 1e-12))
        v = [_clip_step(leaf[0], idx, *leaf[1:], l, scale)
             for leaf, l in zip(leaves, v)]
    return v


@jax.jit
def _adamw(params, m, v, g, step, hp):
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(l)) for l in jax.tree.leaves(g)))
    g = jax.tree.map(lambda l: l * jnp.minimum(1.0, hp["clip_norm"]
                                               / jnp.maximum(norm, 1e-12)), g)
    m = jax.tree.map(lambda a, b: hp["b1"] * a + (1 - hp["b1"]) * b, m, g)
    v = jax.tree.map(lambda a, b: hp["b2"] * a + (1 - hp["b2"]) * b * b, v, g)
    bc1, bc2 = 1 - hp["b1"] ** step, 1 - hp["b2"] ** step
    params = jax.tree.map(
        lambda p, a, b: p - hp["lr"] * ((a / bc1) / (jnp.sqrt(b / bc2) + hp["eps"])
                                        + hp["weight_decay"] * p),
        params, m, v)
    return params, m, v, g


def run(config: Dict, traffic: Dict, seed: int, steps: int, *,
        fp8: bool = False, half_batch: bool = False) -> Dict:
    """The first ``steps`` rounds from the seed.  Returns per-leaf norms of
    the first clipped gradient (``grad``), of the parameters' change after
    the last round (``change``), each round's aggregate norm (``agg_norm``)
    and caught mask (``caught``).  ``half_batch`` is a planted fault: each
    node's loss over the first half of its rows only."""
    model = config["model"]
    roster = traffic["roster"]
    n = len(roster)
    hp = {k: jnp.float32(v) for k, v in traffic["optimizer"].items()}
    audit = traffic.get("verification")
    iters = traffic["agg_kwargs"].get("iters", 3)
    node_fn, _ = traffic_mod.swarm_batches(traffic, model["vocab_size"], seed)
    base = jax.random.PRNGKey(traffic["swarm_seed"])
    for r in roster:
        if r.get("byzantine") not in (None, "inner_product"):
            raise ValueError(f"reference has no {r['byzantine']!r} corruption")
    attack = jnp.asarray([r.get("byzantine_scale", 0.0) if r.get("byzantine")
                          else 0.0 for r in roster], jnp.float32)

    params0 = jax.tree.map(lambda a: a.astype(jnp.float32),
                           make_params(config, seed))
    params = params0
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)

    def node_loss(p, b):
        if half_batch:
            b = jax.tree.map(lambda x: x[: x.shape[0] // 2], b)
        return dense_lm.loss(p, b, model, fp8=fp8)

    @jax.jit
    def grads_of(p, rnd):
        batches = jax.vmap(lambda i: node_fn(i, rnd))(jnp.arange(n))
        return jax.lax.map(lambda b: jax.grad(node_loss)(p, b), batches)

    slashed = np.zeros(n, bool)
    out = {"agg_norm": [], "caught": []}
    for rnd in range(steps):
        active = np.asarray([r.get("join_round", 0) <= rnd
                             and rnd < (r.get("leave_round") or 1 << 30)
                             for r in roster]) & ~slashed
        g, treedef = jax.tree.flatten(grads_of(params, rnd))  # (N, ...) each
        act = jnp.asarray(active)
        leaves = [(s, _true_mean(s, act), attack) for s in g]
        terms = [_audit_terms(*leaf) for leaf in leaves]
        mismatch = np.sqrt(np.asarray(sum(t[0] for t in terms))) / np.maximum(
            np.sqrt(np.asarray(sum(t[1] for t in terms))), 1e-30)
        caught = np.zeros(n, bool)
        if audit:
            for i in np.flatnonzero(active):
                key = jax.random.fold_in(jax.random.fold_in(
                    jax.random.fold_in(base, AUDIT_SELECT), rnd), int(i))
                if float(jax.random.uniform(key)) < audit["p_check"]:
                    caught[i] = mismatch[i] > audit["tolerance"]
        idx = jnp.asarray(np.flatnonzero(active & ~caught))
        if traffic["aggregator"] == "centered_clip":
            agg = centered_clip(leaves, idx, iters)
        elif traffic["aggregator"] == "mean":
            agg = [_mean(leaf[0], idx, *leaf[1:]) for leaf in leaves]
        else:
            raise ValueError(f"reference has no {traffic['aggregator']!r}")
        del g, leaves
        agg = jax.tree.unflatten(treedef, agg)
        out["agg_norm"].append(float(np.sqrt(np.sum(leaf_norms(agg) ** 2))))
        params, m, v, clipped = _adamw(params, m, v, agg,
                                       jnp.float32(rnd + 1), hp)
        if rnd == 0:
            out["grad"] = leaf_norms(clipped)
        out["caught"].append(caught)
        slashed |= caught
    out["change"] = leaf_norms(jax.tree.map(lambda a, b: a - b, params, params0))
    return out
