"""Protocol serving engine (paper §4.1 meets §5): continuous-batching
inference over custody shards, with serving as a campaign axis.

This module is to *inference* what ``core.swarm`` is to training.  A
Protocol Model is defined by what callers can and cannot get at serving
time — logits yes, weights no — and the paper's no-off question has an
inference-time twin: **who can refuse or halt serving** when custody
holders churn or defect?  Three layers answer it:

1. **Scanned decoding** — :func:`greedy_decode` replaces the per-token
   python loop of the old serving driver with two device programs (a
   scanned prefill via ``Model.decode_scan`` and a ``lax.scan`` over
   ``decode_step``), bit-identical tokens at a fraction of the dispatch
   cost.  The old loop survives as :func:`greedy_decode_loop`, the
   reference oracle the engine is equivalence-tested (and benchmarked)
   against.

2. **The continuous-batching engine** — :class:`ServingEngine` steps a
   fixed pool of decode *slots* through one ``lax.scan``
   (:func:`make_serve_step`): every step each occupied slot advances one
   token (mid-prompt slots feed the next prompt token — prefill and decode
   are the same step function, which is what keeps shapes fixed), finished
   slots retire, and free slots admit queued requests by arrival order —
   all via masks, so admission/retirement under load never changes shapes
   and the program **never recompiles**.  Requests live in arrival/length
   arrays (:class:`ServeLane`); generated tokens land in a per-request
   output buffer via masked scatters.

3. **Protocol coupling + the campaign axis** — the PR-4 custody matrix
   rides through serving: per-step node availability (churn, defection)
   gates the live shard coverage, and the engine **halts exactly when
   coverage < 1** (no admissions, no token progress — nobody holds the
   full model, so nobody can serve it).  Credential balances (the
   vectorized :class:`~repro.core.ledger.Ledger` view) gate admission on
   device with the same strict ``balance - fee > min_shares`` boundary as
   ``Ledger.can_infer``.  :func:`sweep` vmaps whole *serving lanes* —
   traced load / churn / redundancy / coalition axes from a
   ``scenarios.ServingGrid`` — into ONE compiled program and renders the
   throughput-vs-availability phase diagram
   (:meth:`ServingResult.availability_table`), mirroring
   ``derailment.sweep``.

The no-off-at-inference finding this machinery measures: below full
redundancy, serving inherits an off-switch nobody designed — any custody
coalition whose departure uncovers a shard can refuse the entire swarm's
inference, and at redundancy 1 every single holder holds that veto
(``docs/serving.md``).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.placement import MeshPlan

Array = jax.Array

_FAR = np.iinfo(np.int32).max


# ============================ scanned greedy decoding ===========================
@dataclass
class ServeStats:
    prefill_s: float
    decode_s: float
    tokens_out: int
    batch: int

    @property
    def tok_per_s(self) -> float:
        return self.tokens_out * self.batch / max(self.decode_s, 1e-9)


@functools.lru_cache(maxsize=32)
def _greedy_programs(model, batch: int, prompt_len: int, max_new: int,
                     cache_len: int):
    """The two jitted programs of the scanned greedy decoder, cached per
    (model, shape) so repeated calls never retrace.  LRU-bounded: a
    long-lived server decoding many distinct request shapes must not
    accumulate compiled executables without bound."""

    @jax.jit
    def prefill(params, prompts):
        cache = model.init_cache(batch, cache_len)
        logits, cache = model.decode_scan(params, prompts, cache)
        tok0 = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return tok0, cache

    @jax.jit
    def decode(params, tok0, cache):
        def body(carry, _):
            tok, c = carry
            logits, c = model.decode_step(params, tok[:, None], c)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return (nxt, c), tok

        (_, cache), toks = jax.lax.scan(body, (tok0, cache), None,
                                        length=max_new)
        return jnp.moveaxis(toks, 0, 1)                       # (B, max_new)

    return prefill, decode


def greedy_decode(model, params, prompts: Array, max_new: int,
                  *, cache_len: Optional[int] = None):
    """Scanned greedy decoding: prompts (B, S0) int32 -> (B, max_new) tokens.

    Exactly the math of :func:`greedy_decode_loop` (prefill by stepping the
    prompt through ``decode_step`` — exact for every family including the
    recurrent ones — then argmax feedback), but the token loops run inside
    two compiled programs instead of one python dispatch per token."""
    b, s0 = prompts.shape
    cache_len = cache_len or (s0 + max_new)
    prefill, decode = _greedy_programs(model, b, s0, max_new, cache_len)

    t0 = time.perf_counter()
    tok0, cache = jax.block_until_ready(prefill(params, prompts))
    prefill_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    gen = jax.block_until_ready(decode(params, tok0, cache))
    decode_s = time.perf_counter() - t0
    return gen, ServeStats(prefill_s, decode_s, max_new, b)


@functools.lru_cache(maxsize=8)
def _loop_decode_step(model):
    # one jitted decode_step per model, shared across greedy_decode_loop
    # calls: the ORIGINAL driver re-jitted (hence re-traced) every call —
    # caching here gives the baseline its best steady-state behaviour, so
    # benchmark speedups never include the baseline's tracing time
    return jax.jit(model.decode_step)


def greedy_decode_loop(model, params, prompts: Array, max_new: int,
                       *, cache_len: Optional[int] = None):
    """The replaced per-token python loop — kept as the readable reference
    oracle :func:`greedy_decode` (and the continuous-batching engine) are
    equivalence-tested against, and as the benchmark baseline."""
    b, s0 = prompts.shape
    cache_len = cache_len or (s0 + max_new)
    cache = model.init_cache(b, cache_len)

    decode = _loop_decode_step(model)

    t0 = time.perf_counter()
    logits = None
    for i in range(s0):
        logits, cache = decode(params, prompts[:, i:i + 1], cache)
    jax.block_until_ready(logits)
    prefill_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    outs: List[Array] = []
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    for _ in range(max_new):
        outs.append(tok)
        logits, cache = decode(params, tok, cache)
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    gen = jax.block_until_ready(jnp.concatenate(outs, axis=1))
    decode_s = time.perf_counter() - t0
    return gen, ServeStats(prefill_s, decode_s, max_new, b)


# ======================== the continuous-batching engine ========================
@dataclass(frozen=True)
class ServingConfig:
    """Static engine shape: slot-pool size, per-request decode budget, scan
    horizon, and the admission boundary.  ``min_shares`` uses the same
    strict ``>`` boundary as ``Ledger.can_infer``: a holder whose balance
    after the fee would not *exceed* ``min_shares`` is refused.  (The fee
    itself is NOT static — it rides :class:`ServeLane` as a traced value,
    so a campaign can sweep pricing.)"""
    slots: int = 4
    max_new: int = 8
    steps: int = 64
    min_shares: float = 0.0
    cache_len: Optional[int] = None       # default: prompt_len + max_new


class ServeLane(NamedTuple):
    """Per-run traced serving parameters — the inference twin of
    ``swarm.LaneParams``.  Every field is an array, so a *campaign* is a
    ServeLane whose leaves carry a leading lane axis (``stack_serve_lanes``)
    vmapped by :meth:`ServingEngine.run_many`.

    Request fields have shape (R,); ``balances`` is the vectorized Ledger
    view (H credential holders); ``node_down_from``/``node_down_until``
    are the custody roster's *outage windows* — node n is offline while
    ``down_from <= t < down_until``.  One window expresses every serving
    churn shape: a permanent defection is ``[defect_step, FAR)``, a node
    that joins late is ``[0, join_step)``, a transient outage heals
    (which is what makes the "degraded" regime — coverage gaps that stall
    serving and then recover — reachable at all; the swarm engine's
    join/leave membership windows are the complement convention).
    ``custody`` is the (N, S) shard-custody matrix from
    ``core.unextractable`` (``None`` = un-sharded serving, never halts;
    all lanes of a campaign must agree)."""
    arrivals: Array        # (R,) int32 — step at which request r arrives
    holders: Array         # (R,) int32 — credential-holder index per request
    prompt_lens: Array     # (R,) int32
    max_new: Array         # (R,) int32 — per-request decode budget
                           #   (<= ServingConfig.max_new, the buffer width;
                           #   slots retire the moment THEIR request is done
                           #   — no head-of-line padding to the batch max)
    balances: Array        # (H,) f32 — initial credential balances
    node_down_from: Array  # (N,) int32 — outage start (inclusive; _FAR = never)
    node_down_until: Array # (N,) int32 — outage end (exclusive)
    fee: Array             # ()  f32 — credentials spent per admission
    custody: Optional[Array] = None   # (N, S) bool | None


class ServeState(NamedTuple):
    """The carry of the scanned serve step — the whole serving frontier
    lives on device, so a run never round-trips to the host."""
    caches: Any           # model cache pytree, leading slot axis
    slot_req: Array       # (S,) int32 — occupying request id; R = free
    slot_t: Array         # (S,) int32 — tokens fed so far for the occupant
    last_tok: Array       # (S,) int32 — the occupant's previous output
    admitted: Array       # (R,) bool
    done: Array           # (R,) bool — all max_new tokens delivered
    balances: Array       # (H,) f32 — live credential balances
    out_tokens: Array     # (R, max_new) int32 — delivered tokens
    admit_step: Array     # (R,) int32 — the step that admitted it; -1 never


class ServeRecord(NamedTuple):
    """Per-step outputs stacked by ``lax.scan`` (leading step axis)."""
    coverage: Array       # () f32 — live shard coverage (1.0 un-sharded)
    live: Array           # () bool — coverage complete; serving possible
    n_active: Array       # () int32 — occupied slots after admission
    n_admitted: Array     # () int32 — requests admitted this step
    new_tokens: Array     # () int32 — tokens delivered this step
    queued: Array         # () int32 — arrived, unadmitted, fundable after
                          #   this step (credential-refused waiters are
                          #   not counted as demand)
    stats: Dict[str, Array]  # the model's per-step counters
                          #   (``Model.decode_step_stats``) summed over the
                          #   advancing slots; empty for most models


def stack_serve_lanes(lanes: Sequence[ServeLane]) -> ServeLane:
    """Stack single-run lanes into a campaign (leading lane axis on every
    leaf).  All lanes must share R/H/N and agree on ``custody`` (all None,
    or all same-shaped matrices)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *lanes)


def make_serve_step(model, cfg: ServingConfig, prompt_shape: Tuple[int, int],
                    *, has_custody: bool) -> Tuple[Callable, Callable]:
    """Build the pure serve step — returns ``(step, init_state)`` where
    ``step(params, prompts, lane, state, t) -> (state, ServeRecord)`` and
    ``init_state(lane) -> ServeState`` is the matching empty pool.
    ``prompts`` is a traced (R, P) argument (only its shape is baked), so
    one compiled program serves any prompt batch of that shape.

    Static structure (slot count, horizon, whether the custody gate exists)
    is baked here; everything per-run rides in ``lane`` as traced arrays,
    so one trace serves every lane of a campaign.  The step is four masked
    stages — availability, admission, decode, retire — with fixed shapes
    throughout:

    - **availability**: nodes online outside their outage window; live shard
      coverage from the custody matrix; ``live = every shard held`` (the
      serving twin of ``RoundRecord.coverage``).  A dead step admits
      nothing and advances nothing — serving is halted, not degraded
      gracefully: with a shard missing there is no model to run.
    - **admission**: arrived, unadmitted requests whose holder can afford
      the fee (strict ``balance - fee > min_shares``, the
      ``Ledger.can_infer`` boundary, counting same-step same-holder
      siblings so a burst can never overdraw a balance) fill free slots in
      arrival order; fees are deducted on device.  Newly admitted slots
      get a pristine cache (masked reset), so a recycled slot never leaks
      its previous occupant's KV state.
    - **decode**: every slot advances one token through a vmapped
      ``decode_step`` (B=1 per slot — each slot sits at its own position).
      Mid-prompt slots feed the next prompt token; finished-prompt slots
      feed their previous argmax.  Idle slots compute and discard — the
      fixed-shape price, exactly the swarm engine's inactive-lane trade.
    - **retire**: the token produced at prompt position ``plen-1+i`` is
      generated token ``i``; token ``max_new-1`` completes the request,
      frees the slot, and marks ``done``.
    """
    n_req, p_max = prompt_shape
    slots, max_new = cfg.slots, cfg.max_new
    cache_len = cfg.cache_len or (p_max + max_new)
    template = model.init_cache(1, cache_len)

    def decode_all(params, toks, caches):
        return jax.vmap(model.decode_step_stats,
                        in_axes=(None, 0, 0))(params, toks, caches)

    def step(params, prompts: Array, lane: ServeLane, state: ServeState, t):
        # each stage under its scope (``jax.named_scope``, op metadata only):
        # ``repro.analysis.stages`` joins a profiler's device time to them
        with jax.named_scope("serve.admit"):
            # -- availability: who holds the model right now ----------------
            online = ~((lane.node_down_from <= t) & (t < lane.node_down_until))
            if has_custody:
                covered = jnp.any(lane.custody & online[:, None], axis=0)
                coverage = jnp.mean(covered.astype(jnp.float32))
                live = jnp.all(covered)
            else:
                coverage = jnp.ones((), jnp.float32)
                live = jnp.ones((), bool)

            # -- admission: queued requests fill free slots in arrival order 
            occ = state.slot_req < n_req
            waiting = (~state.admitted) & (lane.arrivals <= t)
            # funding is strict (balance - fee > min_shares, the can_infer
            # boundary) and accounts for waiting same-holder siblings: the
            # k-th waiting request of a holder (by request index) must afford
            # k+1 fees.  Any same-step admitted subset of a holder then needs
            # at least |subset| fees — a burst can never drive a balance past
            # the boundary, whatever order admission picks.  The index-prefix
            # rule is deliberately deterministic: when a holder cannot fund
            # every waiting sibling, the LOWEST-index ones stay fundable (a
            # documented tie-break, not a fairness guarantee).
            idx = jnp.arange(n_req)
            prior_same = jnp.sum(
                (lane.holders[:, None] == lane.holders[None, :])
                & waiting[None, :] & (idx[:, None] > idx[None, :]), axis=1)
            funded = (state.balances[lane.holders]
                      - (prior_same + 1).astype(jnp.float32) * lane.fee
                      > cfg.min_shares)
            cand = waiting & funded & live
            # FIFO: priority by (arrival step, request index) — a request
            # that has waited longer is admitted first, whatever its index
            # (ties and the monotone-arrival builders reduce to request
            # order)
            fifo = lane.arrivals * n_req + idx                     # (R,)
            rank = jnp.sum(cand[None, :]
                           & (fifo[None, :] < fifo[:, None]), axis=1)
            admit = cand & (rank < jnp.sum(~occ))
            free_first = jnp.argsort(occ)        # free slots, in slot order
            slot_of = free_first[jnp.clip(rank, 0, slots - 1)]
            scatter_to = jnp.where(admit, slot_of, slots)
            upd = jnp.full((slots,), -1, jnp.int32).at[scatter_to].set(
                jnp.arange(n_req, dtype=jnp.int32), mode="drop")
            newly = upd >= 0
            slot_req = jnp.where(newly, upd, state.slot_req)
            slot_t = jnp.where(newly, 0, state.slot_t)
            caches = jax.tree.map(
                lambda init, c: jnp.where(
                    newly.reshape((slots,) + (1,) * init.ndim),
                    init[None], c),
                template, state.caches)
            balances = state.balances.at[
                jnp.where(admit, lane.holders, lane.balances.shape[0])
            ].add(-lane.fee, mode="drop")
            admitted = state.admitted | admit
            admit_step = jnp.where(admit, t, state.admit_step)
            occ = slot_req < n_req

        with jax.named_scope("serve.decode"):
            # -- decode: every slot advances one token ----------------------
            req = jnp.minimum(slot_req, n_req - 1)
            plen = lane.prompt_lens[req]
            tok_in = jnp.where(slot_t < plen,
                               prompts[req, jnp.clip(slot_t, 0, p_max - 1)],
                               state.last_tok)
            logits, new_caches, stats = decode_all(
                params, tok_in[:, None, None], caches)
            next_tok = jnp.argmax(logits[:, 0, -1], axis=-1).astype(jnp.int32)

        with jax.named_scope("serve.retire"):
            # -- record / retire --------------------------------------------
            advance = occ & live
            gen_i = slot_t - (plen - 1)
            budget = lane.max_new[req]
            rec = advance & (gen_i >= 0) & (gen_i < budget)
            out_tokens = state.out_tokens.at[
                jnp.where(rec, req, n_req), jnp.clip(gen_i, 0, max_new - 1)
            ].set(next_tok, mode="drop")
            finished = rec & (gen_i == budget - 1)
            done = state.done.at[jnp.where(finished, req, n_req)].set(
                True, mode="drop")
            slot_t = jnp.where(advance, slot_t + 1, slot_t)
            last_tok = jnp.where(advance, next_tok, state.last_tok)
        with jax.named_scope("serve.cache_write"):
            caches = jax.tree.map(
                lambda new, old: jnp.where(
                    advance.reshape((slots,) + (1,) * (new.ndim - 1)),
                    new, old),
                new_caches, caches)
        with jax.named_scope("serve.retire"):
            slot_req = jnp.where(finished, n_req, slot_req)

            new_state = ServeState(
                caches=caches, slot_req=slot_req, slot_t=slot_t,
                last_tok=last_tok, admitted=admitted, done=done,
                balances=balances, out_tokens=out_tokens,
                admit_step=admit_step)
            record = ServeRecord(
                coverage=coverage, live=live,
                n_active=jnp.sum(occ).astype(jnp.int32),
                n_admitted=jnp.sum(admit).astype(jnp.int32),
                new_tokens=jnp.sum(rec).astype(jnp.int32),
                # serviceable backlog only: credential-refused waiters are
                # not demand (they would otherwise poison the availability
                # metric — and hence the served/degraded classification —
                # forever)
                queued=(jnp.sum(waiting & funded)
                        - jnp.sum(admit)).astype(jnp.int32),
                stats={k: jnp.sum(jnp.where(advance, v, 0)).astype(v.dtype)
                       for k, v in sorted(stats.items())})
        return new_state, record

    def init_state(lane: ServeLane) -> ServeState:
        caches = jax.tree.map(
            lambda l: jnp.broadcast_to(l[None], (slots,) + l.shape), template)
        return ServeState(
            caches=caches,
            slot_req=jnp.full((slots,), n_req, jnp.int32),
            slot_t=jnp.zeros((slots,), jnp.int32),
            last_tok=jnp.zeros((slots,), jnp.int32),
            admitted=jnp.zeros((n_req,), bool),
            done=jnp.zeros((n_req,), bool),
            balances=lane.balances.astype(jnp.float32),
            out_tokens=jnp.zeros((n_req, max_new), jnp.int32),
            admit_step=jnp.full((n_req,), -1, jnp.int32))

    return step, init_state


@dataclass
class ServeResult:
    """One lane's host-side outcome.  ``wall_s`` is the measured wall time
    of the lane's program (for ``run_many`` campaigns: the shared program's
    wall split evenly across lanes, so per-lane ``tok_per_s`` is an
    amortized rate)."""
    tokens: np.ndarray        # (R, max_new) int32
    done: np.ndarray          # (R,) bool
    admitted: np.ndarray      # (R,) bool
    balances: np.ndarray      # (H,) f32 — final credential balances
    coverage: np.ndarray      # (T,) f32
    live: np.ndarray          # (T,) bool
    n_active: np.ndarray      # (T,) int32
    n_admitted: np.ndarray    # (T,) int32
    new_tokens: np.ndarray    # (T,) int32
    queued: np.ndarray        # (T,) int32
    admit_step: np.ndarray    # (R,) int32 — admission step, -1 = never:
                              #   the first token comes out at step
                              #   admit_step + prompt_len - 1
    wall_s: float = 0.0
    stats: Dict[str, np.ndarray] = field(default_factory=dict)  # (T,)
                              #   each: ``ServeRecord.stats`` per step

    @property
    def tokens_served(self) -> int:
        return int(self.new_tokens.sum())

    @property
    def tok_per_s(self) -> float:
        return self.tokens_served / max(self.wall_s, 1e-9)

    @property
    def availability(self) -> float:
        """Fraction of *demand* steps (work queued or in flight) on which
        serving was live.  1.0 when there was never demand."""
        demand = (self.n_active > 0) | (self.queued > 0)
        if not demand.any():
            return 1.0
        return float((self.live & demand).sum() / demand.sum())


def settle_fees(ledger, holders: Sequence[str], result: ServeResult,
                fee: float) -> Dict[str, float]:
    """Mirror a serving lane's device-side fee spending back onto the host
    :class:`~repro.core.ledger.Ledger`, closing the §4.1 inference-market
    loop: admission fees deducted on device (``ServeState.balances``) become
    ``Ledger.charge_fee`` events, and the accumulated pool is paid out to
    stakers pro-rata by stake (``Ledger.distribute_fees``) — serving income
    flows to the capital that keeps the model held.

    The lane must have been built from this ledger's balances
    (``ledger.balance_vector(holders)`` → ``ServeLane.balances``); each
    holder's spend is recovered as an integer number of fees (device
    balances are f32 — rounding squashes the accumulation noise), so the
    ledger's conservation invariant survives the round-trip bit-exactly.
    Returns the per-staker payouts."""
    init = ledger.balance_vector(holders)
    for name, b0, b1 in zip(holders, init, result.balances):
        spent = fee * round(float(b0 - b1) / fee) if fee > 0 else 0.0
        if spent > 0:
            ledger.charge_fee(name, spent)
    return ledger.distribute_fees()


def _result_from_device(state: ServeState, recs: ServeRecord,
                        wall_s: float = 0.0) -> ServeResult:
    return ServeResult(
        tokens=np.asarray(state.out_tokens),
        done=np.asarray(state.done),
        admitted=np.asarray(state.admitted),
        balances=np.asarray(state.balances),
        coverage=np.asarray(recs.coverage),
        live=np.asarray(recs.live),
        n_active=np.asarray(recs.n_active),
        n_admitted=np.asarray(recs.n_admitted),
        new_tokens=np.asarray(recs.new_tokens),
        queued=np.asarray(recs.queued),
        admit_step=np.asarray(state.admit_step),
        wall_s=wall_s,
        stats={k: np.asarray(v) for k, v in recs.stats.items()})


class ServingEngine:
    """Device-resident continuous-batching server: one compiled
    ``lax.scan`` of :func:`make_serve_step` per (lane-shape, custody)
    signature, cached so repeated runs (tests, benchmarks, property
    examples) never retrace.

    ``run`` serves one :class:`ServeLane`; ``run_many`` vmaps a stacked
    campaign of lanes through the same scan — ONE program for a whole
    (load × churn × redundancy × coalition) grid.  ``prompts`` given at
    construction are the default workload; ``run``/``run_many`` accept a
    same-shaped override without retracing (prompts are a traced program
    argument).

    ``plan`` (a :class:`~repro.core.placement.MeshPlan`) shards
    ``run_many``'s lane axis over the plan's mesh (bit-exact — lanes are
    embarrassingly parallel) and the shared params over its within-lane
    axes (allclose); single-lane ``run`` has no lane axis to shard and
    ignores it."""

    def __init__(self, model, cfg: ServingConfig, prompts: Array,
                 plan: Optional[MeshPlan] = None):
        self.model = model
        self.cfg = cfg
        self.prompts = jnp.asarray(prompts, jnp.int32)
        self.plan = plan
        self._programs: Dict[Tuple[bool, bool], Callable] = {}
        self._executables: Dict[Tuple, Callable] = {}

    def _program(self, has_custody: bool, vmapped: bool) -> Callable:
        key = (has_custody, vmapped)
        if key not in self._programs:
            step, init_state = make_serve_step(
                self.model, self.cfg, tuple(self.prompts.shape),
                has_custody=has_custody)

            def run(params, prompts, lane):
                def body(st, t):
                    return step(params, prompts, lane, st, t)
                return jax.lax.scan(body, init_state(lane),
                                    jnp.arange(self.cfg.steps))

            if vmapped and self.plan is not None:
                fn = jax.vmap(run, in_axes=(None, None, 0),
                              spmd_axis_name=self.plan.lanes_axis)
            elif vmapped:
                fn = jax.vmap(run, in_axes=(None, None, 0))
            else:
                fn = run
            self._programs[key] = jax.jit(fn)
        return self._programs[key]

    def program(self, *, has_custody: bool, vmapped: bool) -> Callable:
        """THE engine program for this (custody, vmapped) signature — the
        jitted ``fn(params, prompts, lane(s))`` that :meth:`run` /
        :meth:`run_many` execute, straight from the program cache.  Public
        so ``analysis.jaxpr_audit`` traces the real serve scan (and so
        callers can pre-lower it) instead of a reimplementation."""
        return self._program(has_custody, vmapped)

    def _check(self, lane: ServeLane,
               prompts: Optional[Array]) -> Array:
        budgets = np.asarray(lane.max_new)
        if budgets.max() > self.cfg.max_new or budgets.min() < 1:
            raise ValueError(
                "per-request max_new must lie in [1, "
                f"{self.cfg.max_new}] (the engine's decode budget) — a "
                "zero budget would wedge its slot for the whole horizon")
        plens = np.asarray(lane.prompt_lens)
        if plens.max() > self.prompts.shape[-1] or plens.min() < 1:
            raise ValueError(
                f"prompt_lens must lie in [1, {self.prompts.shape[-1]}] "
                "(the engine's prompt buffer width) — a longer prompt "
                "would silently re-feed the last buffered token")
        if prompts is None:
            return self.prompts
        prompts = jnp.asarray(prompts, jnp.int32)
        if prompts.shape != self.prompts.shape:
            raise ValueError(
                f"prompts override must match the engine's compiled shape "
                f"{self.prompts.shape}, got {prompts.shape}")
        return prompts

    @staticmethod
    def _signature(*args) -> Tuple:
        leaves, tree = jax.tree.flatten(args)
        return tree, tuple((jnp.shape(x), jnp.result_type(x)) for x in leaves)

    def compile(self, params, lane: ServeLane,
                prompts: Optional[Array] = None) -> None:
        """Compile :meth:`run`'s program for these arguments' shapes ahead of
        the first episode; ``run`` then calls that executable for every lane
        of the same shapes and types (others take the jitted program).  A
        warm-up need not serve a whole episode."""
        p = self._check(lane, prompts)
        self._executables[self._signature(params, p, lane)] = self._program(
            lane.custody is not None, False).lower(params, p, lane).compile()

    def run(self, params, lane: ServeLane,
            prompts: Optional[Array] = None) -> ServeResult:
        """Serve one lane.  Host spans (free when no profiler runs):
        ``repro.serve.run`` around the call, ``.wait`` for the device to
        finish the episode, ``.readback`` for its results."""
        with jax.profiler.TraceAnnotation("repro.serve.run"):
            p = self._check(lane, prompts)
            fn = (self._executables.get(self._signature(params, p, lane))
                  or self._program(lane.custody is not None, False))
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("repro.serve.wait"):
                state, recs = jax.block_until_ready(fn(params, p, lane))
            wall_s = time.perf_counter() - t0
            with jax.profiler.TraceAnnotation("repro.serve.readback"):
                return _result_from_device(state, recs, wall_s)

    def run_many(self, params, lanes: ServeLane,
                 prompts: Optional[Array] = None) -> List[ServeResult]:
        p = self._check(lanes, prompts)
        fn = self._program(lanes.custody is not None, True)
        t0 = time.perf_counter()
        if self.plan is not None:
            lanes = self.plan.place_lanes(lanes)
            params = self.plan.place_params(params)
            with self.plan.mesh:
                state, recs = jax.block_until_ready(fn(params, p, lanes))
        else:
            state, recs = jax.block_until_ready(fn(params, p, lanes))
        wall = time.perf_counter() - t0
        n = int(lanes.arrivals.shape[0])
        out = []
        for i in range(n):
            out.append(_result_from_device(
                jax.tree.map(lambda x: x[i], state),
                jax.tree.map(lambda x: x[i], recs),
                wall / n))
        return out


# ============================== lane building ==================================
def build_lane(*, n_requests: int, prompt_lens: Sequence[int],
               max_new, steps: int, n_nodes: int,
               balances: Sequence[float], fee: float = 1.0,
               load: Optional[float] = None,
               arrivals: Optional[Sequence[int]] = None,
               holders: Optional[Sequence[int]] = None,
               custody: Optional[np.ndarray] = None,
               churn_rate: float = 0.0,
               coalition_fraction: float = 0.0,
               defect_step: Optional[int] = None,
               seed: int = 0) -> ServeLane:
    """Host-side :class:`ServeLane` builder — the serving twin of
    ``derailment._sweep_lane``.

    ``max_new`` is the per-request decode budget — a scalar broadcast to
    all requests or a length-R sequence (mixed budgets are what continuous
    batching exists for: slots retire per-request, no head-of-line
    padding).  ``load`` (requests per step) spaces arrivals as
    ``floor(r / load)`` unless explicit ``arrivals`` are given.
    ``coalition_fraction`` marks
    the *last* ``ceil(fraction * N)`` roster slots (the same tail
    convention as ``CustodyConfig``) as a defecting coalition that goes
    down at ``defect_step`` and never returns — the inference no-off
    attack.  ``churn_rate`` makes that fraction of the remaining nodes
    transient: each gets one staggered mid-horizon *outage window* (down,
    then back up), so redundancy-starved shards open coverage gaps that
    later heal — the "degraded" regime.  Drawn with ``seed`` (numpy),
    deliberately separate from any model seed: sweeping serving seeds
    varies churn, never the custody draw."""
    if arrivals is None:
        if load is None or load <= 0:
            raise ValueError("pass either arrivals or a positive load")
        arrivals = np.floor(np.arange(n_requests) / load).astype(np.int32)
    arrivals = np.asarray(arrivals, np.int32)
    prompt_lens = np.asarray(prompt_lens, np.int32)
    max_new = np.broadcast_to(np.asarray(max_new, np.int32),
                              (n_requests,)).copy()
    if arrivals.shape != (n_requests,) or prompt_lens.shape != (n_requests,):
        raise ValueError("arrivals / prompt_lens must have shape (n_requests,)")
    balances = np.asarray(balances, np.float32)
    if holders is None:
        holders = np.arange(n_requests, dtype=np.int32) % balances.shape[0]
    holders = np.asarray(holders, np.int32)

    down_from = np.full(n_nodes, _FAR, np.int32)
    down_until = np.full(n_nodes, _FAR, np.int32)
    n_coal = int(np.ceil(coalition_fraction * n_nodes))
    if n_coal:
        down_from[n_nodes - n_coal:] = (steps // 3 if defect_step is None
                                        else defect_step)
    if churn_rate > 0:
        rng = np.random.default_rng(seed)
        rest = np.arange(n_nodes - n_coal)
        k = min(len(rest), int(np.ceil(churn_rate * len(rest))))
        picked = rng.choice(rest, size=k, replace=False)
        lo, hi = max(1, steps // 4), max(2, (3 * steps) // 4)
        dur = max(2, steps // 6)
        for j, node in enumerate(sorted(int(i) for i in picked)):
            at = lo + (j * max(1, (hi - lo) // max(1, k))) % max(1, hi - lo)
            down_from[node] = at
            down_until[node] = at + dur
    return ServeLane(
        arrivals=jnp.asarray(arrivals),
        holders=jnp.asarray(holders),
        prompt_lens=jnp.asarray(prompt_lens),
        max_new=jnp.asarray(max_new),
        balances=jnp.asarray(balances),
        node_down_from=jnp.asarray(down_from),
        node_down_until=jnp.asarray(down_until),
        fee=jnp.asarray(fee, jnp.float32),
        custody=None if custody is None else jnp.asarray(custody))


# ============================ the serving campaign ==============================
@dataclass(frozen=True)
class ServingCell:
    """One lane of a serving sweep, classified."""
    load: float
    churn_rate: float
    redundancy: int
    coalition_fraction: float
    seed: int
    n_requests: int
    completed: int
    refused: int              # unadmitted for lack of credentials
    tokens_served: int
    availability: float       # live fraction of demand steps
    final_coverage: float

    @property
    def regime(self) -> str:
        """The serving twin of ``DerailmentResult.extractability``:

        - ``halted``: credentialed work left unserved after coverage loss
          stalled serving (``availability < 1``).  A healed outage that
          consumed the horizon still counts — the coverage loss, not the
          load, spent the capacity; when both overload and an outage
          contribute, attribution goes to the outage;
        - ``backlogged``: work left unserved with every demand step live —
          offered load exceeded capacity within the horizon (a load
          regime, not a no-off one);
        - ``degraded``: everything served, but coverage gaps stalled
          serving on some demand steps (availability < 1);
        - ``served``: everything served, every demand step live.
        """
        pending = self.n_requests - self.completed - self.refused
        if pending > 0:
            return "halted" if self.availability < 1.0 else "backlogged"
        if self.availability < 1.0:
            return "degraded"
        return "served"


@dataclass
class ServingResult:
    """Every cell of a ``scenarios.ServingGrid``, plus how it was compiled
    (one program for ``n_runs`` lanes) and the aggregate decode rate."""
    grid: Any                 # scenarios.ServingGrid
    cells: List[ServingCell]
    n_programs: int
    n_runs: int
    wall_s: float
    tokens_total: int
    n_devices: int = 1        # devices the sweep's mesh plan spanned

    @property
    def runs_per_s(self) -> float:
        return self.n_runs / max(self.wall_s, 1e-9)

    @property
    def tok_per_s(self) -> float:
        return self.tokens_total / max(self.wall_s, 1e-9)

    def availability_table(self) -> str:
        """The serving phase diagram: one row per (redundancy [, coalition
        fraction], churn rate), one column per load; each cell shows the
        regime letter per seed — S = served, D = degraded, H = halted,
        B = backlogged — plus the mean availability."""
        loads = sorted({c.load for c in self.cells})
        coal = len({c.coalition_fraction for c in self.cells}) > 1
        rows = sorted({(c.redundancy, c.coalition_fraction, c.churn_rate)
                       for c in self.cells})
        labels = [f"r={r}" + (f" coal={cf:.2f}" if coal else "")
                  + f" churn={ch:.2f}" for r, cf, ch in rows]
        width = max([22] + [len(l) + 2 for l in labels])
        head = "serving".ljust(width) + "".join(f"load={l:.2f}".rjust(16)
                                                for l in loads)
        code = {"served": "S", "degraded": "D", "halted": "H",
                "backlogged": "B"}
        lines = [head]
        for (r, cf, ch), label in zip(rows, labels):
            cells = []
            for l in loads:
                cell = [c for c in self.cells
                        if (c.redundancy, c.coalition_fraction,
                            c.churn_rate) == (r, cf, ch)
                        and abs(c.load - l) < 1e-9]
                if not cell:
                    cells.append("-".rjust(16))
                    continue
                marks = "".join(code[c.regime] for c in cell)
                avail = sum(c.availability for c in cell) / len(cell)
                cells.append(f"{marks} a={avail:.2f}".rjust(16))
            lines.append(label.ljust(width) + "".join(cells))
        lines.append("(S=served  D=degraded  H=halted  B=backlogged, one "
                     "letter per seed; a = availability)")
        return "\n".join(lines)


def sweep(model, params, grid, *, prompts: Optional[Array] = None,
          plan: Optional[MeshPlan] = None) -> ServingResult:
    """Measure a whole serving phase diagram — every (load × churn ×
    redundancy × coalition × seed) cell of a ``scenarios.ServingGrid`` —
    as **one** compiled device program, mirroring ``derailment.sweep``.

    Load rides in the traced ``arrivals`` lane, churn and coalition
    defection in the ``node_down_from``/``node_down_until`` outage lanes,
    redundancy in the traced ``custody`` lane; prompts and the engine
    program are shared
    by every cell.  Each lane reproduces the single-run
    :meth:`ServingEngine.run` for the same parameters (one scan, vmapped).

    ``plan`` (e.g. ``MeshPlan.from_grid(grid)``) shards the lane axis over
    the plan's mesh — bit-exact (pinned in
    ``tests/test_campaign_sharded.py``) — and the shared model params over
    its within-lane axes (allclose).
    """
    from repro.core.unextractable import assign_matrix

    r, p = grid.n_requests, grid.prompt_len
    if prompts is None:
        prompts = jax.random.randint(jax.random.PRNGKey(0), (r, p), 0,
                                     model.cfg.vocab_size)
    # varied prompt lengths exercise mixed prefill/decode slot states
    prompt_lens = (p // 2 + np.arange(r) % (p - p // 2 + 1)).astype(np.int32)
    cfg = ServingConfig(slots=grid.slots, max_new=grid.max_new,
                        steps=grid.steps)
    balances = np.full(grid.n_holders, grid.fee * grid.n_requests + 1.0,
                       np.float32)
    custody_for = {
        red: assign_matrix(grid.n_nodes, grid.num_shards, red, seed=0,
                           max_fraction=grid.max_fraction)
        for red in grid.redundancies}

    engine = ServingEngine(model, cfg, prompts, plan=plan)
    lanes, metas = [], []
    for load in grid.loads:
        for churn in grid.churn_rates:
            for red in grid.redundancies:
                for cf in grid.coalition_fractions:
                    for seed in grid.seeds:
                        lanes.append(build_lane(
                            n_requests=r, prompt_lens=prompt_lens,
                            max_new=grid.max_new,
                            steps=grid.steps, n_nodes=grid.n_nodes,
                            balances=balances, fee=grid.fee, load=load,
                            custody=custody_for[red], churn_rate=churn,
                            coalition_fraction=cf,
                            defect_step=grid.defect_step, seed=seed))
                        metas.append((load, churn, red, cf, seed))

    t0 = time.perf_counter()
    results = engine.run_many(params, stack_serve_lanes(lanes))
    wall = time.perf_counter() - t0

    cells = []
    for (load, churn, red, cf, seed), lane, res in zip(metas, lanes, results):
        pending = ~res.done
        # a pending request counts as credential-refused only when serving
        # never halted in its lane — in a halted lane the coverage loss,
        # not the balance, explains unserved work (balances only decrease,
        # so an exhausted balance at the end does not prove the request
        # was ever refused while serving was live)
        refused = pending & ~res.admitted & res.live.all() & (
            res.balances[np.asarray(lane.holders)] - grid.fee
            <= cfg.min_shares)
        cells.append(ServingCell(
            load=load, churn_rate=churn, redundancy=red,
            coalition_fraction=cf, seed=seed, n_requests=r,
            completed=int(res.done.sum()), refused=int(refused.sum()),
            tokens_served=res.tokens_served,
            availability=res.availability,
            final_coverage=float(res.coverage[-1])))
    return ServingResult(grid=grid, cells=cells, n_programs=1,
                         n_runs=len(lanes), wall_s=wall,
                         tokens_total=sum(c.tokens_served for c in cells),
                         n_devices=plan.n_devices if plan is not None else 1)
