"""Mixture-of-Experts FFN with capacity-based scatter/gather dispatch.

Dispatch is sort-free: per group (= batch row) we compute each routed
token-slot's rank within its expert via a scatter-counted prefix, then
scatter hidden states into an (E, C, d) buffer, run all experts as one
batched einsum, and gather back with the gate weights.  Tokens overflowing
an expert's capacity are dropped (standard capacity-factor semantics).

Expert-parallel sharding: the E axis shards over the mesh "model" axis when
divisible (qwen3: 128/16), otherwise the per-expert FFN dim shards
(mixtral: 8 experts on 16-way model parallelism).  See models/sharding.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import dense_init, init_swiglu, swiglu

Array = jax.Array


def init_moe(key, d: int, f: int, num_experts: int, dtype):
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d, num_experts), jnp.float32),
        "w_gate": dense_init(ks[1], (num_experts, d, f), dtype),
        "w_up": dense_init(ks[2], (num_experts, d, f), dtype),
        "w_down": dense_init(ks[3], (num_experts, f, d), dtype),
    }


def capacity(seq: int, top_k: int, num_experts: int, factor: float) -> int:
    c = int(seq * top_k / num_experts * factor)
    return max(8, ((c + 7) // 8) * 8)  # round up to 8 for clean tiling


def route(x: Array, router: Array, top_k: int):
    """x: (..., d) -> (gates (..., k), experts (..., k) int32, aux_loss)."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balance auxiliary loss
    e = router.shape[-1]
    me = jnp.mean(probs.reshape(-1, e), axis=0)
    counts = jax.nn.one_hot(experts, e, dtype=jnp.float32).sum(axis=-2)  # (..., E)
    ce = jnp.mean(counts.reshape(-1, e), axis=0) / top_k
    aux = e * jnp.sum(me * ce)
    return gates, experts, aux


def _dispatch_one_group(x, experts, gates, num_experts: int, cap: int):
    """x: (S, d); experts/gates: (S, k).  Returns buffer (E, C, d), meta."""
    s, d = x.shape
    k = experts.shape[-1]
    flat_e = experts.reshape(-1)                      # (S*k,)
    flat_g = gates.reshape(-1)

    # rank of each routed slot within its expert, in token order
    # prefix count: rank[i] = #{j < i : e_j == e_i}
    onehot = jax.nn.one_hot(flat_e, num_experts, dtype=jnp.int32)   # (S*k, E)
    rank = (jnp.cumsum(onehot, axis=0) - onehot)                    # exclusive
    rank = jnp.take_along_axis(rank, flat_e[:, None], axis=1)[:, 0]
    keep = rank < cap
    slot_e = jnp.where(keep, flat_e, 0)
    slot_c = jnp.where(keep, rank, 0)

    buf = jnp.zeros((num_experts, cap, d), x.dtype)
    src = jnp.repeat(x, k, axis=0) * keep[:, None].astype(x.dtype)
    buf = buf.at[slot_e, slot_c].add(src)
    return buf, (slot_e, slot_c, flat_g * keep.astype(flat_g.dtype))


def _combine_one_group(buf_out, meta, s: int, k: int):
    slot_e, slot_c, g = meta
    gathered = buf_out[slot_e, slot_c]                # (S*k, d)
    gathered = gathered * g[:, None].astype(gathered.dtype)
    return gathered.reshape(s, k, -1).sum(axis=1)


def moe_ffn(x: Array, params, *, top_k: int, capacity_factor: float):
    """x: (B, S, d) -> (B, S, d), aux_loss.  Group = batch row."""
    b, s, d = x.shape
    e = params["router"].shape[-1]
    cap = capacity(s, top_k, e, capacity_factor)

    gates, experts, aux = route(x, params["router"], top_k)

    from repro.models.sharding import (constrain_batch, constrain_ffn,
                                       model_axis_size)

    def dispatch_group(xg, eg, gg):
        return _dispatch_one_group(xg, eg, gg, e, cap)

    buf, meta = jax.vmap(dispatch_group)(x, experts, gates.astype(x.dtype))
    # keep the capacity buffer batch-sharded: without this pin, SPMD
    # propagation replicates the vmap'd scatter across the data axis and
    # every device computes the GLOBAL batch's expert FFNs (§Perf mixtral)
    buf = constrain_batch(buf)

    # pin f only when experts are f-sharded (same rule as param_pspecs:
    # experts shard over `model` when E divides it, else d_ff does)
    shard_f = e % max(model_axis_size(), 1) != 0

    def experts_group(h_in):
        g_act = jnp.einsum("ecd,edf->ecf", h_in, params["w_gate"])
        u = jnp.einsum("ecd,edf->ecf", h_in, params["w_up"])
        if shard_f:
            g_act, u = constrain_ffn(g_act), constrain_ffn(u)
        return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g_act) * u,
                          params["w_down"])

    h_out = jax.vmap(experts_group)(buf)
    h_out = constrain_batch(h_out)

    out = jax.vmap(lambda ho, m: _combine_one_group(ho, m, s, top_k))(
        h_out, meta)
    return constrain_batch(out).astype(x.dtype), aux


def moe_ffn_reference(x: Array, params, *, top_k: int):
    """Oracle: every expert on every token, masked combine (no capacity drops).

    Tests compare moe_ffn against this with capacity_factor large enough that
    nothing is dropped.
    """
    gates, experts, _ = route(x, params["router"], top_k)
    e = params["router"].shape[-1]
    g_act = jnp.einsum("bsd,edf->bsef", x, params["w_gate"])
    u = jnp.einsum("bsd,edf->bsef", x, params["w_up"])
    h = jnp.einsum("bsef,efd->bsed", jax.nn.silu(g_act) * u, params["w_down"])
    onehot = jax.nn.one_hot(experts, e, dtype=h.dtype)                # (B,S,k,E)
    w = jnp.einsum("bske,bsk->bse", onehot, gates.astype(h.dtype))
    return jnp.einsum("bsed,bse->bsd", h, w).astype(x.dtype)


# -- the held-expert layer (one device's share of an expert-parallel layer) ----
def init_held_moe(key, d: int, f: int, num_experts: int, held: int,
                  shared_f: int, dtype):
    """The router over all ``num_experts``, the first ``held`` routed
    experts, and (``shared_f`` > 0) the shared experts as one SwiGLU."""
    ks = jax.random.split(key, 5)
    out = {
        "router": dense_init(ks[0], (d, num_experts), jnp.float32),
        "w_gate": dense_init(ks[1], (held, d, f), dtype),
        "w_up": dense_init(ks[2], (held, d, f), dtype),
        "w_down": dense_init(ks[3], (held, f, d), dtype),
    }
    if shared_f:
        out["shared"] = init_swiglu(ks[4], d, shared_f, dtype)
    return out


def held_gates(x: Array, router: Array, top_k: int, *, norm_topk: bool,
               scaling: float):
    """DeepSeek's gate: softmax over every router output in f32, the greedy
    top ``top_k``, renormalized only if ``norm_topk``, times ``scaling``.
    Returns (gates (..., k) f32, experts (..., k) int32)."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        router.astype(jnp.float32))
    gates, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if norm_topk:
        gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True),
                                    1e-9)
    return gates * scaling, experts


def held_moe_ffn(x: Array, params, *, held: Array, top_k: int,
                 norm_topk: bool = True, scaling: float = 1.0):
    """The part of an expert layer that one device of an expert-parallel
    deployment computes: x (..., d) -> (out (..., d), token-slots routed to
    the held experts).

    The router keeps its full width: every token picks its ``top_k`` of all
    the router's experts.  Only the experts ``held`` (ids into the router's
    outputs, matching the leading axis of the expert weights) are computed,
    for the tokens routed to them, each weighted by its gate; a token's
    routes to experts held elsewhere add nothing here (the exchange that
    would add them is not simulated).  The shared experts run on every
    token.  Summing the outputs of devices that hold disjoint shares, with
    the shared part counted once, gives the whole layer.

    Drop-free by construction: every held expert runs on every token and
    the result is masked by the token's gate for it (zero where not
    routed), so no capacity limits how many tokens an expert takes, however
    uneven the routing.  At decode sizes (one token per slot) that costs
    less than gathering; a long prefill would want a grouped dispatch.
    """
    with jax.named_scope("model.moe.route"):
        gates, experts = held_gates(x, params["router"], top_k,
                                    norm_topk=norm_topk, scaling=scaling)
        hit = experts[..., None] == held                    # (..., k, held)
        weight = jnp.sum(jnp.where(hit, gates[..., None], 0.0), axis=-2)
        routed = jnp.sum(hit).astype(jnp.int32)
    with jax.named_scope("model.moe.experts"):
        g = jnp.einsum("...d,edf->...ef", x, params["w_gate"])
        u = jnp.einsum("...d,edf->...ef", x, params["w_up"])
        y = jnp.einsum("...ef,efd->...ed", jax.nn.silu(g) * u,
                       params["w_down"])
        out = jnp.einsum("...ed,...e->...d", y.astype(jnp.float32), weight)
        out = out.astype(x.dtype)
    if "shared" in params:
        with jax.named_scope("model.moe.shared"):
            s = params["shared"]
            out = out + swiglu(x, s["w_gate"], s["w_up"], s["w_down"])
    return out, routed
