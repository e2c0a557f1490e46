"""Swarm simulator: the paper's five §3 properties in one runnable system.

Simulates N protocol participants training one model:
  1. communication efficiency — optional on-the-wire compression (lossy,
     round-tripped through core.compression);
  2. model sharding — the model itself runs sharded under pjit in
     launch/train.py; the swarm layer treats a node as a *logical* gradient
     contributor (a node may be a whole cluster — paper §2 last paragraph);
  3. elastic membership — nodes join/leave on a schedule, aggregation only
     sees currently-active nodes;
  4. byzantine tolerance — per-node corruption behaviours + robust
     aggregation from core.aggregation;
  5. heterogeneous capacity — per-node speed scales both contributed batch
     count and minted shares.

Plus the §4 mechanisms: stake/slash verification audits and the ownership
ledger.  Runs on CPU with a real (small) model; the aggregation math is
identical at any scale.

The round itself is a **pure functional core**: :class:`SwarmState` (params,
optimizer state, slashed mask, per-node contribution counters) advanced by
the ``round_fn`` built with :func:`make_round_fn`, parameterized by a
:class:`LaneParams` pytree of per-run traced values (behaviour codes,
byzantine scales, membership windows, PRNG base key, audit rate/tolerance,
and any traced aggregator kwargs).  The core has **no host round-trips** —
slashing and contribution minting happen on device, and the host-side
:class:`~repro.core.ledger.Ledger` is reconstructed from the device counters
after a run.  That makes two compositions possible:

- :func:`scan_rounds` — ``lax.scan`` the round over the round axis, so a
  whole training run is one device program;
- :func:`run_campaign` — additionally ``vmap`` over a leading *campaign*
  axis of stacked :class:`LaneParams`, so a full parameter sweep (attacker
  fractions × scales × seeds, per aggregator regime) is **one** compiled
  program (see ``core.derailment.sweep``).

Two engines share one API (``step``/``run``/``history``/``ledger``):

- :class:`Swarm` — the default **batched engine**, now a thin wrapper over
  the functional core: ``step`` invokes one jitted core round; ``run``
  dispatches the scanned core when the data function is traceable.
- :class:`SequentialSwarm` — the original per-node Python loop, kept as the
  readable reference oracle the batched engine is equivalence-tested against.

Both engines draw every random number from the same per-(purpose, round,
node) ``fold_in`` schedule, so with the same seed they produce the *same*
corruption noise, wire-codec realizations, audit selections, and therefore
the same ``agg_norm`` history (within fp32 reduction-order tolerance).

**Decentralized mode** (paper §3.2 meets §5.5): when a round is built with
``decentralized=True`` (``SwarmConfig.topology`` on the engine,
``LaneParams.mixing`` on the functional core), there is *no central
aggregator*.  ``SwarmState.params`` carries a leading node axis — one model
replica per node — and each round every node (1) computes its gradient at
its **own** replica, (2) robust-aggregates the submitted gradients of its
*neighborhood* (the rows of the mixing matrix, via the same masked
aggregators with a per-node neighbor ∧ keep mask), (3) applies the result
to its replica with its own optimizer state, and (4) gossip-mixes replicas
``params ← W @ params``.  ``RoundRecord.consensus_err`` tracks the maximum
replica deviation from the swarm mean after mixing.  A fully-connected
mixing matrix makes every neighborhood global and every replica identical,
which reproduces the centralized engine exactly (property-tested in
``tests/test_topology.py``).  ``mixing`` may also be a (T, N, N) stack —
time-varying or churn-coupled graphs from ``core.topology`` — indexed by
``round % T`` inside the scanned round.

**Custody lane** (paper §4.1 meets §5.5): ``SwarmConfig.custody`` (a
``core.unextractable.CustodyConfig``; ``LaneParams.custody`` /
``LaneParams.coalition`` on the functional core) rides the Protocol-Model
custody matrix through the compiled round as a pure *observability* layer —
it never perturbs the training math, which is what makes a fully-redundant
custody lane reproduce the plain engine bit-exactly (property-tested in
``tests/test_custody.py``).  Each round records
``RoundRecord.coverage`` — the fraction of shards held by at least one
*active* node, i.e. the live extraction frontier: custody-coupled churn
zeroes a shard's availability once every holder has left or been slashed
(the custody analogue of ``churn_coupled_mixing``).  At eval time a
campaign with a custody lane additionally runs the **reconstruct-attack
eval** inside the program: the coalition's shards are reassembled
(``masked_reconstruct``) and evaluated next to the honest params, so the
final losses come back as an (honest, extracted) pair per lane
(``core.derailment.sweep`` turns this into the extractability phase
table).
"""
from __future__ import annotations

import functools
import inspect
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Set

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation, compression, economy
from repro.core.economy import EconomyConfig, EconParams
from repro.core.ledger import Ledger
from repro.core.placement import MeshPlan
from repro.core.unextractable import (
    CustodyConfig,
    assign_matrix,
    coalition_tail_mask,
    masked_reconstruct,
    shards_covered,
)
from repro.core.verification import VerificationConfig, audit_batch, audit_flat
from repro.kernels.masked_agg import ops as masked_agg_ops
from repro.kernels.qsgd_decode import ops as qsgd_decode_ops

Array = jax.Array

#: Byzantine behaviours, indexed by the code used in the vectorized
#: corruption table (``_corrupt_all``).  Code 0 is honest (identity).
BEHAVIOURS = ("honest", "sign_flip", "scale", "noise", "zero", "inner_product")
BEHAVIOUR_CODES: Dict[str, int] = {name: i for i, name in enumerate(BEHAVIOURS)}

# Key-schedule purposes.  Every random draw in a round is keyed by
# (seed, purpose, round, node_index) via fold_in — engine-independent, which
# is what makes the sequential reference and the batched engine bit-identical
# in their randomness (and keeps the batched round free of host-side key
# chains that would serialize it).
_CORRUPT, _WIRE, _AUDIT_SEL, _AUDIT_NOISE, _DELAY = range(5)

_FAR = np.iinfo(np.int32).max


def _node_key(base: Array, purpose: int, rnd, node_idx) -> Array:
    k = jax.random.fold_in(base, purpose)
    k = jax.random.fold_in(k, rnd)
    return jax.random.fold_in(k, node_idx)


@dataclass(frozen=True)
class NodeSpec:
    node_id: str
    speed: float = 1.0
    byzantine: Optional[str] = None      # None|sign_flip|scale|noise|zero|inner_product
    byzantine_scale: float = 10.0
    join_round: int = 0
    leave_round: Optional[int] = None
    #: max gradient staleness (rounds) this node may run behind — only read
    #: when the config sets ``staleness_bound > 0``, and clamped to it; the
    #: *realized* per-round delay is drawn uniformly in [0, min(delay,
    #: bound, round)] from the (seed, _DELAY, round, node) key schedule.
    #: ``None`` (the default) derives the delay from ``speed`` — slow nodes
    #: are stale nodes (see :meth:`effective_delay`); an explicit value
    #: always overrides the derivation.
    delay: Optional[int] = None

    @property
    def effective_delay(self) -> int:
        """The staleness cap async rounds read.  Explicit ``delay`` wins;
        otherwise it is derived from ``speed``: a node running at 1/s of
        the reference speed needs ~s rounds per unit of work, so it may
        lag ``ceil(1/speed) - 1`` rounds (speed ≥ 1 → 0, 0.5 → 1,
        0.25 → 3) — the async twin of the ledger's speed-weighted
        minting."""
        if self.delay is not None:
            return self.delay
        return max(int(np.ceil(1.0 / max(self.speed, 1e-9))) - 1, 0)

    def active(self, rnd: int) -> bool:
        return self.join_round <= rnd and (self.leave_round is None or rnd < self.leave_round)

    @property
    def behaviour_code(self) -> int:
        kind = self.byzantine or "honest"
        if kind not in BEHAVIOUR_CODES:
            raise ValueError(f"unknown byzantine behaviour: {kind!r} "
                             f"(known: {BEHAVIOURS})")
        return BEHAVIOUR_CODES[kind]


@dataclass(frozen=True)
class SwarmConfig:
    aggregator: str = "centered_clip"
    agg_kwargs: Dict = field(default_factory=dict)
    verification: Optional[VerificationConfig] = None
    compression: Optional[str] = None    # None|"qsgd"|"topk"|"powersgd"
    compression_kwargs: Dict = field(default_factory=dict)
    seed: int = 0
    #: named communication topology (core.topology registry) — setting one
    #: switches the batched engine to the decentralized round: per-node
    #: replicas, neighborhood aggregation, gossip mixing.  None = centralized.
    topology: Optional[str] = None
    topology_kwargs: Dict = field(default_factory=dict)
    #: seed for the graph *draw* (random_regular et al.) — deliberately
    #: separate from ``seed`` so sweeping run seeds varies noise, never the
    #: graph (the same convention ``derailment.sweep`` uses for its lanes)
    topology_seed: int = 0
    #: couple the mixing matrix to the roster's join/leave schedule
    #: (topology.churn_coupled_mixing): departed or not-yet-joined nodes
    #: become isolated self-loops, so their replicas freeze instead of
    #: relaying.  False (default) keeps the graph static — every replica
    #: mixes forever, the fixed-shape contract that makes a fully-connected
    #: decentralized swarm reproduce the centralized engine even under churn.
    churn_coupled: bool = False
    #: Protocol-Model custody lane (core.unextractable.CustodyConfig):
    #: assigns the (N, S) custody matrix over this roster, traces it through
    #: the round (RoundRecord.coverage = live extraction frontier), and
    #: marks the extraction coalition for the reconstruct-attack eval.
    #: None = no custody tracking.  Never changes the training math.
    custody: Optional[CustodyConfig] = None
    #: fused hot path (kernels.masked_agg + kernels.qsgd_decode): None =
    #: auto by stack size (see make_round_fn), True = force, False = never.
    fused: Optional[bool] = None
    #: bounded-staleness async rounds (paper §3 heterogeneity): K > 0 keeps
    #: a fixed-shape ring of the last K+1 parameter snapshots in the scanned
    #: carry and lets each node gradient against a deterministically-drawn
    #: delayed snapshot (see NodeSpec.delay).  0 (default) is the
    #: bulk-synchronous round — the async machinery is not even traced, so
    #: staleness_bound=0 is bit-exact with the pre-async engine.
    staleness_bound: int = 0
    #: economy lane (core.economy.EconomyConfig): threads a device-resident
    #: economic state (stakes, balances, reward escrow, slash pool) through
    #: the scanned round — stake-gated admission, fee/reward flows, and
    #: (``adaptive=True``) the coalition's best-response inner step.  The
    #: coalition defaults to the roster's byzantine slots.  None = no
    #: economy (the round is bit-exact with the pre-economy engine).
    economy: Optional[EconomyConfig] = None


def corrupt(kind: str, grad_flat: Array, honest_mean: Array, scale: float, key) -> Array:
    """Scalar (single-node) corruption table — the reference the vectorized
    ``_corrupt_all`` table below must match branch for branch."""
    if kind == "sign_flip":
        return -scale * grad_flat
    if kind == "scale":
        return scale * grad_flat
    if kind == "noise":
        return grad_flat + scale * jax.random.normal(key, grad_flat.shape)
    if kind == "zero":
        return jnp.zeros_like(grad_flat)
    if kind == "inner_product":
        # [87]-style: oppose the honest consensus direction
        return -scale * honest_mean
    raise ValueError(kind)


def _corrupt_all(codes: Array, gf: Array, honest_mean: Array, scales: Array,
                 keys: Array) -> Array:
    """Vectorized corruption table: every behaviour evaluated on the whole
    (N, D) stack, selected per node by code — branch for branch equal to
    :func:`corrupt`.  Written as arithmetic selects rather than a vmapped
    ``lax.switch``: with per-node codes vmap evaluates every branch anyway,
    and the flat form is measurably cheaper to trace and compile inside the
    scanned campaign round (sweeps are compile-bound).

    The (N, D) normal draw is the one expensive branch input (threefry over
    the full stack, ~1s/round at N=16, D=1M on CPU), so it runs under a
    ``lax.cond`` on "any noise node in the roster": rosters without noise
    attackers skip it entirely.  Bit-exact either way — when the cond takes
    the zeros branch no select ever reads the noise values (and under vmap,
    where cond lowers to both-branches select, this is exactly the old
    unconditional draw)."""
    any_noise = jnp.any(codes == BEHAVIOUR_CODES["noise"])
    noise = jax.lax.cond(
        any_noise,
        lambda: jax.vmap(lambda k, g: jax.random.normal(k, g.shape))(keys, gf),
        lambda: jnp.zeros_like(gf))
    c, s = codes[:, None], scales[:, None]
    out = jnp.where(c == BEHAVIOUR_CODES["sign_flip"], -s * gf, gf)
    out = jnp.where(c == BEHAVIOUR_CODES["scale"], s * gf, out)
    out = jnp.where(c == BEHAVIOUR_CODES["noise"], gf + s * noise, out)
    out = jnp.where(c == BEHAVIOUR_CODES["zero"], 0.0, out)
    out = jnp.where(c == BEHAVIOUR_CODES["inner_product"],
                    -s * honest_mean[None], out)
    return out


# ============================ functional core ==================================
class LaneParams(NamedTuple):
    """Per-run traced parameters of the functional round.

    Every field is a jax array, so a *campaign* is simply a LaneParams whose
    leaves carry a leading run axis (see :func:`stack_lanes`) vmapped by
    :func:`run_campaign`.  Roster fields have shape (N,); audit fields are
    scalars (``p_check == 0`` disables auditing even when the round was built
    with ``verify=True``); ``agg_id`` selects this run's aggregator when the
    round was built with several (0 otherwise); ``agg_kwargs`` holds *traced*
    aggregator keyword arguments (e.g. a per-run krum ``f`` or centered-clip
    ``clip_tau``) — static kwargs go to :func:`make_round_fn` instead.

    ``mixing`` is the decentralized round's doubly-stochastic mixing matrix
    — (N, N), or (T, N, N) for time-varying / churn-coupled graphs (indexed
    by ``round % T``).  It is traced like every other field, so one compiled
    campaign sweeps *topologies* as a lane axis.  ``None`` (the default)
    means the round is centralized; all lanes of a campaign must agree.

    ``custody``/``coalition`` are the Protocol-Model custody lane — the
    (N, S) custody matrix and the (N,) extraction-coalition mask
    (``core.unextractable``).  Traced like ``mixing``, so one compiled
    campaign sweeps *redundancy and coalition fraction* as lane axes: the
    round records the live coverage frontier each round, and the campaign
    eval reassembles the coalition's shards next to the honest eval.
    ``None`` (the default) disables custody; all lanes must agree.

    ``delays`` is the bounded-staleness lane — (N,) int32 per-node *maximum*
    delays, only read by rounds built with ``staleness_bound > 0`` (the ring
    size is static; the delay values are traced, so one compiled campaign
    sweeps *staleness* as a lane axis).  ``None`` (the default) means the
    synchronous round; all lanes of a campaign must agree.

    ``econ`` is the economy lane — a :class:`~repro.core.economy.EconParams`
    of traced knobs (identity cost, budget, bond, fee/reward/jackpot
    schedule, adaptive flag, coalition mask).  Traced like every other
    field, so one compiled campaign sweeps the *incentive* axes; the round
    gains stake-gated admission, the per-round economy update, and (in
    adaptive lanes) the coalition's best-response inner step.  ``None``
    (the default) disables the economy; all lanes of a campaign must agree.
    """
    codes: Array          # (N,) int32 behaviour codes (BEHAVIOUR_CODES)
    scales: Array         # (N,) f32 byzantine scales
    speeds: Array         # (N,) f32 capacity -> minted shares per kept round
    joins: Array          # (N,) int32 join round (inclusive)
    leaves: Array         # (N,) int32 leave round (exclusive; _FAR = never)
    base_key: Array       # PRNG key — the per-run seed
    p_check: Array        # () f32 audit probability (0 = never audited)
    tolerance: Array      # () f32 audit relative-mismatch tolerance
    numeric_noise: Array  # () f32 simulated cross-stack nondeterminism
    agg_id: Array         # () int32 index into the round's aggregator set
    agg_kwargs: Dict[str, Array]  # traced per-run aggregator kwargs
    mixing: Optional[Array] = None  # (N, N) | (T, N, N) mixing matrix | None
    custody: Optional[Array] = None    # (N, S) bool custody matrix | None
    coalition: Optional[Array] = None  # (N,) bool extraction coalition | None
    delays: Optional[Array] = None     # (N,) int32 max staleness | None
    econ: Optional[EconParams] = None  # traced economy knobs | None


class SwarmState(NamedTuple):
    """The carry of the scanned round: everything that evolves across rounds
    lives on device, so a run never round-trips to the host."""
    params: Any           # model parameters (pytree; leading node axis when
                          # the round is decentralized — per-node replicas)
    opt_state: Any        # optimizer state (pytree; ditto)
    slashed: Array        # (N,) bool — caught by an audit in a prior round
    contrib: Array        # (N,) f32 — speed-weighted kept rounds (mint counter)
    ring: Any = None      # staleness ring: params-shaped pytree with a
                          # leading (K+1,) snapshot axis — slot r % (K+1)
                          # holds the params as of the start of round r.
                          # None in synchronous rounds (staleness_bound=0).
    econ: Any = None      # economy state (economy.EconState): stakes,
                          # balances, reward escrow, slash pool — advanced
                          # by econ_round_update each round.  None when the
                          # round has no economy lane.


class RoundRecord(NamedTuple):
    """Per-round outputs stacked by ``lax.scan`` (leading round axis)."""
    n_active: Array       # () int32
    n_byzantine: Array    # () int32
    caught: Array         # (N,) bool — slashed in *this* round
    keep: Array           # (N,) bool — active & not caught (minted this round)
    agg_norm: Array       # () f32 (decentralized: mean per-node agg norm)
    consensus_err: Array  # () f32 max *active*-replica deviation from the
                          # active-replica mean after gossip mixing
                          # (0 in centralized rounds)
    coverage: Array       # () f32 fraction of custody shards held by >= 1
                          # active node — the live extraction frontier
                          # (1.0 when the round has no custody lane)
    staleness: Array      # () f32 mean realized gradient delay (rounds) over
                          # active nodes (0 in synchronous rounds)
    coalition_stake: Optional[Array] = None  # () f32 coalition share of the
                          # kept nodes' post-round stake (economy lanes
                          # only; None otherwise — the capture trajectory)


def lane_for_nodes(nodes: Sequence[NodeSpec], cfg: SwarmConfig, *,
                   agg_kwargs: Optional[Dict] = None) -> LaneParams:
    """Build the single-run :class:`LaneParams` for a node roster + config.
    ``cfg.topology`` (if set) resolves to the named Metropolis mixing matrix
    at this roster size, drawn with ``cfg.topology_seed`` (NOT the run
    seed — reruns across seeds keep the same graph).  ``cfg.churn_coupled``
    expands it to the (T, N, N) schedule-coupled stack, T spanning the last
    membership event (the round consuming it must index with
    ``mixing_schedule="clamp"`` — the engine wires this automatically).
    ``cfg.custody`` draws the (N, S) custody matrix with ``custody.seed``
    (same convention: run seeds never reshuffle who holds what) and marks
    the coalition as the last ``ceil(coalition_fraction * N)`` roster
    slots.  ``cfg.staleness_bound > 0`` fills the ``delays`` lane with each
    node's ``NodeSpec.effective_delay`` (explicit ``delay``, else derived
    from ``speed``) clamped to the bound (0 leaves it ``None`` — the
    synchronous round).  ``cfg.economy`` fills the ``econ`` lane, with the
    roster's byzantine slots as the strategic coalition."""
    from repro.core import topology as topo  # local: keep import cycle-free
    v = cfg.verification
    custody = coalition = None
    if cfg.custody is not None:
        cc = cfg.custody
        custody = jnp.asarray(assign_matrix(
            len(nodes), cc.num_shards, cc.redundancy, cc.seed,
            cc.max_fraction))
        coalition = jnp.asarray(
            coalition_tail_mask(len(nodes), cc.coalition_fraction))
    mixing = None
    if cfg.topology is not None:
        w = topo.mixing_matrix(cfg.topology, len(nodes),
                               seed=cfg.topology_seed, **cfg.topology_kwargs)
        if cfg.churn_coupled:
            joins = np.asarray([n.join_round for n in nodes])
            leaves = np.asarray([_FAR if n.leave_round is None
                                 else n.leave_round for n in nodes])
            events = [int(t) for t in (*joins, *leaves) if 0 < t < _FAR]
            w = topo.churn_coupled_mixing(
                w, joins, leaves, rounds=(max(events) + 1) if events else 1)
        mixing = jnp.asarray(w, jnp.float32)
    delays = None
    if cfg.staleness_bound > 0:
        delays = jnp.asarray([min(n.effective_delay, cfg.staleness_bound)
                              for n in nodes], jnp.int32)
    econ = None
    if cfg.economy is not None:
        econ = cfg.economy.params_for(
            np.asarray([n.byzantine is not None for n in nodes]))
    return LaneParams(
        mixing=mixing,
        custody=custody,
        coalition=coalition,
        delays=delays,
        econ=econ,
        codes=jnp.asarray([n.behaviour_code for n in nodes], jnp.int32),
        scales=jnp.asarray([n.byzantine_scale for n in nodes], jnp.float32),
        speeds=jnp.asarray([n.speed for n in nodes], jnp.float32),
        joins=jnp.asarray([n.join_round for n in nodes], jnp.int32),
        leaves=jnp.asarray([_FAR if n.leave_round is None else n.leave_round
                            for n in nodes], jnp.int32),
        base_key=jax.random.PRNGKey(cfg.seed),
        p_check=jnp.asarray(v.p_check if v else 0.0, jnp.float32),
        tolerance=jnp.asarray(v.tolerance if v else 1.0, jnp.float32),
        numeric_noise=jnp.asarray(v.numeric_noise if v else 0.0, jnp.float32),
        agg_id=jnp.asarray(0, jnp.int32),
        agg_kwargs={k: jnp.asarray(x) for k, x in (agg_kwargs or {}).items()},
    )


def stack_lanes(lanes: Sequence[LaneParams]) -> LaneParams:
    """Stack single-run lanes into a campaign (leading run axis on every
    leaf).  All lanes must share N, the same ``agg_kwargs`` keys, and agree
    on ``mixing`` (all None = centralized, or all same-shaped matrices =
    decentralized) and on ``custody``/``coalition`` (all None = no custody
    lane, or all same-shaped matrices/masks)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *lanes)


def init_ring(params, staleness_bound: int):
    """The bounded-staleness snapshot ring: ``params`` repeated along a new
    leading (K+1,) axis (every slot starts at the initial params, which is
    exactly the round-0 snapshot any early-round delay resolves to).
    ``jnp.repeat`` (not ``broadcast_to``) so each slot owns real memory —
    the ring is donated through the scanned run and updated in place."""
    if staleness_bound <= 0:
        return None
    return jax.tree.map(
        lambda l: jnp.repeat(l[None], staleness_bound + 1, axis=0), params)


def init_state(params, optimizer, n_nodes: int, *,
               staleness_bound: int = 0, econ=None) -> SwarmState:
    return SwarmState(params=params, opt_state=optimizer.init(params),
                      slashed=jnp.zeros(n_nodes, bool),
                      contrib=jnp.zeros(n_nodes, jnp.float32),
                      ring=init_ring(params, staleness_bound),
                      econ=econ)


def init_decentralized_state(params, optimizer, n_nodes: int, *,
                             staleness_bound: int = 0) -> SwarmState:
    """Per-node replica state: every node starts from the same ``params``
    with its own (vmapped) optimizer state."""
    replicas = jax.tree.map(
        lambda l: jnp.broadcast_to(l[None], (n_nodes,) + l.shape), params)
    return SwarmState(params=replicas,
                      opt_state=jax.vmap(optimizer.init)(replicas),
                      slashed=jnp.zeros(n_nodes, bool),
                      contrib=jnp.zeros(n_nodes, jnp.float32),
                      ring=init_ring(replicas, staleness_bound))


def consensus_params(params):
    """Collapse per-node replicas to the swarm-mean (consensus) params."""
    return jax.tree.map(lambda l: jnp.mean(l.astype(jnp.float32),
                                           axis=0).astype(l.dtype), params)


def _accepted_kwargs(name: str) -> frozenset:
    """Keyword names a masked aggregator understands (for routing the shared
    traced ``lane.agg_kwargs`` dict in multi-aggregator rounds)."""
    sig = inspect.signature(aggregation.MASKED_AGGREGATORS[name])
    return frozenset(p.name for p in sig.parameters.values()
                     if p.kind is inspect.Parameter.KEYWORD_ONLY)


def make_round_fn(loss_fn: Callable, optimizer, params_template, n_nodes: int, *,
                  aggregator, agg_kwargs: Optional[Dict] = None,
                  compression_kind: Optional[str] = None,
                  compression_kwargs: Optional[Dict] = None,
                  verify: bool = False, decentralized: bool = False,
                  mixing_schedule: str = "cycle",
                  fused: Optional[bool] = None,
                  staleness_bound: int = 0) -> Callable:
    """Build the pure round: ``round_fn(lane, state, rnd, batches) ->
    (state, RoundRecord)``.

    Static structure (aggregator choice, static agg kwargs, wire codec,
    whether the audit branch exists at all) is baked here; everything
    per-run lives in ``lane`` as traced arrays, so one trace serves every
    lane of a campaign.  ``batches`` is a pytree with leading node axis N.

    ``decentralized=True`` (static — it changes the state shapes) builds
    the no-central-aggregator round: ``state.params``/``opt_state`` carry a
    leading node axis, every node gradients its own replica, aggregates its
    ``lane.mixing``-row neighborhood (neighbor ∧ keep mask through the same
    masked aggregators), applies its own optimizer update, and replicas
    gossip-mix ``W @ params``.  Activity gates *contribution* (keep) only:
    inactive/slashed replicas keep updating from their neighborhood and keep
    mixing — the decentralized twin of the centralized engine's "inactive
    nodes still occupy a lane" fixed-shape contract, and what makes a
    fully-connected graph reproduce the centralized round exactly even
    under churn.  Nodes whose rounds should truly freeze (leavers) get that
    via a churn-coupled (T, N, N) ``lane.mixing`` stack
    (``topology.churn_coupled_mixing``; ``SwarmConfig.churn_coupled`` on
    the engine).  ``mixing_schedule`` picks how a 3-D stack is indexed:
    ``"cycle"`` (``round % T`` — periodic time-varying graphs) or
    ``"clamp"`` (``min(round, T-1)`` — a membership schedule whose graph is
    constant past its last event).

    ``aggregator`` is either one name (static ``agg_kwargs`` apply to it;
    traced ``lane.agg_kwargs`` pass through verbatim) or a sequence of
    ``(name, static_kwargs)`` pairs — then every aggregator is evaluated and
    ``lane.agg_id`` selects the result per run, which lets a whole
    multi-regime phase diagram share **one** compiled program (the gradient
    / corruption / audit machinery — the bulk of the compile cost — is
    compiled once).  In that mode each aggregator receives only the
    ``lane.agg_kwargs`` entries its signature accepts.

    ``fused`` selects the fused hot path (``kernels.masked_agg`` +
    ``kernels.qsgd_decode``): aggregators run their fused twins, and a
    qsgd wire keeps the compressed payload (int8 codes + bucket norms) live
    into aggregation instead of a decoded fp32 stack.  ``None`` (default)
    auto-enables it when the round is centralized, every aggregator has a
    fused twin, the wire is uncompressed or int8-codeable qsgd, and the
    (N, D) fp32 stack crosses ``masked_agg.ops.FUSED_MIN_BYTES``.
    ``True`` forces it (raising on unsupported combinations); ``False``
    forces the reference path.  Fused == unfused bit-for-bit except krum's
    distance arithmetic (selection-equal away from exact score ties) —
    pinned by tests/test_kernel_conformance.py.  The resolved choice is
    exposed as ``round_fn.fused``.

    ``staleness_bound`` (static — it sizes the snapshot ring) builds the
    **bounded-staleness async round**: ``state.ring`` carries the last K+1
    parameter snapshots (fixed shape — no recompiles), each round writes
    the current params into slot ``round % (K+1)``, draws a per-node
    realized delay ``~ U[0, min(lane.delays[i], round, K)]`` from the
    (seed, _DELAY, round, node) key schedule, and each node gradients
    against *its own delayed snapshot* (``vmap`` over the gathered stack).
    Everything downstream — corruption, wire, aggregation masks — consumes
    the mixed-staleness gradient stack unchanged, and the §4.2 audit stays
    sound *by construction*: the validator recomputes from the same ``gf``
    row the contributor produced, i.e. against the same stale snapshot —
    the delay is part of the claim because it is part of the shared key
    schedule.  ``staleness_bound=0`` (default) takes the literal
    synchronous code path (no ring, no extra keys): bit-exact with the
    pre-async engine by construction, pinned in tests/test_async.py.
    Note a zero-*delay* lane inside a ``staleness_bound>0`` program is only
    allclose to the synchronous program — gathering per-node snapshots
    batches the gradient matmuls differently (reduction order), exactly
    like the FC-decentralized vs centralized pinning.
    """
    leaves = jax.tree.leaves(params_template)
    treedef = jax.tree.structure(params_template)
    shapes = [(l.shape, l.dtype) for l in leaves]
    if isinstance(aggregator, str):
        agg_specs = [(aggregator, dict(agg_kwargs or {}))]
        route_kwargs = False
    else:
        if agg_kwargs:
            raise ValueError("pass per-aggregator static kwargs inside the "
                             "(name, kwargs) pairs, not via agg_kwargs")
        agg_specs = [(name, dict(kw)) for name, kw in aggregator]
        route_kwargs = True
    if mixing_schedule not in ("cycle", "clamp"):
        raise ValueError(f"unknown mixing_schedule: {mixing_schedule!r} "
                         "(known: 'cycle', 'clamp')")
    # in routed mode an aggregator's *static* kwargs win over same-named
    # traced lane kwargs (call-time kwargs would silently override the
    # functools.partial baked ones otherwise — e.g. a krum regime pinned to
    # f=4 must not pick up the per-lane f meant for the auto-f krum regime)
    ckw = dict(compression_kwargs or {})

    # -- fused hot-path resolution (static) ------------------------------------
    d_total = sum(int(np.prod(shape)) if shape else 1 for shape, _ in shapes)
    stack_bytes = n_nodes * d_total * 4
    fusable_aggs = all(name in masked_agg_ops.FUSED_MASKED_AGGREGATORS
                       for name, _ in agg_specs)
    fusable_wire = (compression_kind is None
                    or (compression_kind == "qsgd"
                        and ckw.get("levels", 16) <= 127))
    fused_ok = (not decentralized) and fusable_aggs and fusable_wire
    if fused is None:
        fused = fused_ok and stack_bytes >= masked_agg_ops.FUSED_MIN_BYTES
    elif fused and not fused_ok:
        raise ValueError(
            "fused=True unsupported here: needs a centralized round, "
            f"aggregators within {sorted(masked_agg_ops.FUSED_MASKED_AGGREGATORS)} "
            f"(got {[n for n, _ in agg_specs]}), and an uncompressed or "
            f"int8-codeable qsgd wire (got {compression_kind!r}, "
            f"levels={ckw.get('levels', 16)})")
    fused_qsgd = fused and compression_kind == "qsgd"

    # kwarg routing always reads the *reference* signatures — the fused
    # twins deliberately share names and keyword surface
    getter = (masked_agg_ops.get_fused_aggregator if fused
              else aggregation.get_masked_aggregator)
    agg_fns = [(getter(name, **kw),
                _accepted_kwargs(name) - set(kw)) for name, kw in agg_specs]
    # the adaptive coalition's model of the defense (economy lanes): always
    # the *reference* masked aggregators — the attacker scores candidate
    # attacks on the raw fp32 stack even when the round itself runs fused
    # on wire payloads
    ref_agg_fns = agg_fns if not fused else [
        (aggregation.get_masked_aggregator(name, **kw),
         _accepted_kwargs(name) - set(kw)) for name, kw in agg_specs]
    grad_fn = jax.grad(loss_fn)
    idx = jnp.arange(n_nodes)

    def flatten_stack(tree) -> Array:
        """pytree with leading node axis -> (N, D) fp32 matrix.  The
        barrier keeps XLA from fusing the producers (the whole backward
        pass) into the concatenate: on TPU that fusion made the program's
        generated code and compile time grow with D (~4x compile time at
        58M parameters).  It is an identity, so no value changes."""
        tree = jax.lax.optimization_barrier(tree)
        return jnp.concatenate([l.reshape(n_nodes, -1).astype(jnp.float32)
                                for l in jax.tree.leaves(tree)], axis=1)

    def unflatten(vec: Array):
        out, off = [], 0
        for shape, dtype in shapes:
            size = int(np.prod(shape)) if shape else 1
            out.append(vec[off:off + size].reshape(shape).astype(dtype))
            off += size
        return jax.tree.unflatten(treedef, out)

    def wire(key, g):
        return compression.roundtrip(compression_kind, key, g, **ckw)

    def wire_payload(key, g):
        """Fused qsgd wire: encode only — the int8 payload stays live into
        aggregation (decode happens inside the fused aggregator / audit)."""
        return qsgd_decode_ops.wire_encode(key, g, **ckw)

    def round_fn(lane: LaneParams, state: SwarmState, rnd, batches):
        if staleness_bound > 0 and lane.delays is None:
            raise ValueError("staleness_bound > 0 needs a LaneParams.delays "
                             "lane (build it via lane_for_nodes with "
                             "SwarmConfig.staleness_bound set)")
        # Every operation of the round sits under one stage scope
        # (``jax.named_scope``, op metadata only: the compiled program is
        # the same).  ``repro.analysis.stages`` reads the stages back from
        # the compiled program, so a profiler's device time joins to them.
        # The roster mask and the key schedule open the gradient stage.
        econ = lane.econ
        with jax.named_scope("swarm.grad"):
            active = ((lane.joins <= rnd) & (rnd < lane.leaves)
                      & (~state.slashed))
            if econ is not None:
                if decentralized:
                    raise ValueError("economy lanes need a centralized round "
                                     "(stake-gated admission and the fee "
                                     "market assume one aggregate)")
                if state.econ is None:
                    raise ValueError("economy lane without SwarmState.econ "
                                     "— init the state with "
                                     "economy.init_econ_state(lane.econ, n)")
                # stake-gated admission, derived in-program from live
                # stakes: de-admitted nodes vanish from gradients, audits,
                # aggregation masks, minting, and coverage alike
                active = active & economy.admitted_mask(econ, state.econ)
            nact = jnp.sum(active.astype(jnp.float32))

            # the whole (purpose, round, node) fold_in schedule in three
            # batched call sites — same keys as _node_key per (purpose,
            # rnd, i), but the compiler sees 3 threefry kernels instead of
            # 12+ (sweeps are compile-bound, and threefry dominates the
            # round's compile cost).  Synchronous rounds don't trace the
            # _DELAY purpose at all.
            pk = jax.vmap(lambda p: jax.random.fold_in(lane.base_key, p))(
                jnp.arange(5 if staleness_bound > 0 else 4))
            rk = jax.vmap(lambda k: jax.random.fold_in(k, rnd))(pk)
            allk = jax.vmap(lambda k: jax.vmap(
                lambda i: jax.random.fold_in(k, i))(idx))(rk)     # (P, N, 2)
            ck, wk, sk, nk = allk[_CORRUPT], allk[_WIRE], \
                allk[_AUDIT_SEL], allk[_AUDIT_NOISE]

            if staleness_bound > 0:
                # async round: snapshot first (slot r % (K+1) holds the
                # params as of the start of round r — a realized delay of 0
                # reads the same params the synchronous round would), then
                # per-node realized delays, then gradients at the gathered
                # snapshots.
                ring_len = jnp.int32(staleness_bound + 1)
                ring = jax.tree.map(
                    lambda r, l: r.at[jnp.mod(rnd, ring_len)].set(l),
                    state.ring, state.params)
                cap = jnp.minimum(jnp.minimum(lane.delays, rnd),
                                  jnp.int32(staleness_bound))
                delay = jax.vmap(
                    lambda k, m: jax.random.randint(
                        k, (), 0, m + jnp.int32(1)))(allk[_DELAY], cap)
                slots = jnp.mod(rnd - delay, ring_len)            # (N,)
                if decentralized:
                    # ring leaves are (K+1, N, ...): node i reads its OWN
                    # replica as of round rnd - delay[i]
                    delayed = jax.tree.map(lambda r: r[slots, idx], ring)
                else:
                    delayed = jax.tree.map(lambda r: r[slots], ring)
                grads = jax.vmap(grad_fn, in_axes=(0, 0))(delayed, batches)
                staleness = (jnp.sum(delay.astype(jnp.float32)
                                     * active.astype(jnp.float32))
                             / jnp.maximum(nact, 1.0))
            else:
                # decentralized: every node gradients its OWN replica
                # (leading node axis on state.params); centralized: one
                # shared params
                ring = state.ring
                grad_axes = (0, 0) if decentralized else (None, 0)
                grads = jax.vmap(grad_fn, in_axes=grad_axes)(state.params,
                                                             batches)
                staleness = jnp.zeros((), jnp.float32)
        with jax.named_scope("swarm.flatten"):
            gf = flatten_stack(grads)                             # (N, D)
            maskf = active.astype(jnp.float32)[:, None]
            honest_mean = (jnp.sum(gf * maskf, axis=0)
                           / jnp.maximum(nact, 1.0))
        with jax.named_scope("swarm.corrupt"):
            corrupted = _corrupt_all(lane.codes, gf, honest_mean, lane.scales,
                                     ck)

        def route_aggs(fns, stack, mask):
            if route_kwargs:
                outs = [fn(stack, mask,
                           **{k: v for k, v in sorted(lane.agg_kwargs.items())
                              if k in acc})
                        for fn, acc in fns]
                return jnp.stack(outs)[lane.agg_id] if len(outs) > 1 else outs[0]
            return fns[0][0](stack, mask, **lane.agg_kwargs)

        if econ is not None:
            # adaptive adversary (economy lanes): the coalition scores a
            # static menu of attack scales against the KNOWN aggregator —
            # the reference twin of the round's own defense, on the
            # anticipated active mask — and overrides its fixed behaviour
            # with the best response.  One traced computation, like the
            # audit recompute; fixed (adaptive=0) lanes select it away.
            with jax.named_scope("swarm.corrupt"):
                coal_act = econ.coalition & active
                best = economy.best_response_scale(
                    lambda s, m: route_aggs(ref_agg_fns, s, m),
                    gf, honest_mean, coal_act, active)
                use_adaptive = (econ.adaptive > 0) & coal_act
                corrupted = jnp.where(use_adaptive[:, None],
                                      -best * honest_mean[None, :], corrupted)

        with jax.named_scope("swarm.wire"):
            if fused_qsgd:
                submitted = jax.vmap(wire_payload)(wk, corrupted)
            else:
                submitted = jax.vmap(wire)(wk, corrupted)

        with jax.named_scope("swarm.audit"):
            caught = jnp.zeros(n_nodes, bool)
            if verify:                       # static: baked at trace time
                # audit rate / tolerance / noise are *traced* (array-valued
                # VerificationConfig fields), so one program serves lanes
                # with different p_check — including p_check == 0 (never
                # audited).
                vcfg = VerificationConfig(p_check=lane.p_check,
                                          tolerance=lane.tolerance,
                                          numeric_noise=lane.numeric_noise)
                sel = jax.vmap(jax.random.uniform)(sk)
                audited = active & (sel < lane.p_check)
                # the validator recomputes the honest gradient and
                # re-encodes it with the submitter's wire key (see
                # SequentialSwarm.step).  In async rounds gf is the
                # *delayed* gradient stack, so the recompute runs against
                # the same stale snapshot the contributor claims — the
                # delay is reproducible from the shared key schedule, which
                # is what keeps the §4.2 audit sound under asynchrony
                # (honest-but-stale is never slashed as cheating).
                recomputed = jax.vmap(wire)(wk, gf)
                audited_view = (qsgd_decode_ops.wire_decode(submitted)
                                if fused_qsgd else submitted)
                passes, _ = audit_batch(audited_view, recomputed, nk, vcfg)
                caught = audited & (~passes)
            keep = active & (~caught)

        def run_aggs(mask):
            return route_aggs(agg_fns, submitted, mask)

        if decentralized:
            with jax.named_scope("swarm.gossip"):
                w = lane.mixing.astype(jnp.float32)
                if w.ndim == 3:          # time-varying / churn-coupled stack
                    t_max = w.shape[0]
                    w = w[jnp.minimum(rnd, t_max - 1)
                          if mixing_schedule == "clamp"
                          else jnp.mod(rnd, t_max)]
            with jax.named_scope("swarm.aggregate"):
                # node i robust-aggregates its neighborhood's kept
                # submissions (Metropolis W has self-loops, so i's own
                # update is in its set)
                per_keep = (w > 0) & keep[None, :]        # (N, N)
                agg = jax.vmap(run_aggs)(per_keep)        # (N, D)
                node_any = jnp.any(per_keep, axis=1)
                agg = jnp.where(node_any[:, None], agg, jnp.zeros_like(agg))
            with jax.named_scope("swarm.update"):
                new_params, new_opt = jax.vmap(
                    lambda ok, a, p, o: jax.lax.cond(
                        ok,
                        lambda p, o: optimizer.update(unflatten(a), o, p),
                        lambda p, o: (p, o),
                        p, o))(node_any, agg, state.params, state.opt_state)
            with jax.named_scope("swarm.gossip"):
                # gossip mix the replicas (momentum stays local — standard
                # DSGD)
                mixed = w @ flatten_stack(new_params)     # (N, P)
                new_params = jax.vmap(unflatten)(mixed)
                # consensus over *active* replicas only: under
                # churn-coupled mixing a departed node's replica freezes
                # (its row is e_i) and would otherwise dominate the max
                # forever
                mean_act = (jnp.sum(mixed * maskf, axis=0, keepdims=True)
                            / jnp.maximum(nact, 1.0))
                consensus_err = jnp.max(
                    jnp.linalg.norm((mixed - mean_act) * maskf, axis=1))
            with jax.named_scope("swarm.aggregate"):
                agg_norm = jnp.mean(jax.vmap(jnp.linalg.norm)(agg))
        else:
            with jax.named_scope("swarm.aggregate"):
                agg = run_aggs(keep)
                any_keep = jnp.any(keep)
                agg = jnp.where(any_keep, agg, jnp.zeros_like(agg))
            with jax.named_scope("swarm.update"):
                new_params, new_opt = jax.lax.cond(
                    any_keep,
                    lambda p, o: optimizer.update(unflatten(agg), o, p),
                    lambda p, o: (p, o),
                    state.params, state.opt_state)
            consensus_err = jnp.zeros((), jnp.float32)
            with jax.named_scope("swarm.aggregate"):
                agg_norm = jnp.linalg.norm(agg)

        with jax.named_scope("swarm.record"):
            # custody observability: the live extraction frontier — a
            # shard is available while >= 1 holder is active
            # (custody-coupled churn: departed/slashed holders zero their
            # shards' availability)
            if lane.custody is not None:
                coverage = jnp.mean(jnp.any(lane.custody & active[:, None],
                                            axis=0).astype(jnp.float32))
            else:
                coverage = jnp.ones((), jnp.float32)

            new_econ, coalition_stake = state.econ, None
            if econ is not None:
                new_econ = economy.econ_round_update(
                    econ, state.econ, active=active, keep=keep,
                    caught=caught, speeds=lane.speeds)
                fkeep = keep.astype(jnp.float32)
                act_stake = jnp.sum(new_econ.stake * fkeep)
                coal_stake = jnp.sum(new_econ.stake * fkeep
                                     * econ.coalition.astype(jnp.float32))
                coalition_stake = jnp.where(
                    act_stake > 0.0,
                    coal_stake / jnp.maximum(act_stake, 1e-9),
                    jnp.zeros((), jnp.float32))

            new_state = SwarmState(
                params=new_params, opt_state=new_opt,
                slashed=state.slashed | caught,
                contrib=state.contrib + lane.speeds * keep.astype(jnp.float32),
                ring=ring, econ=new_econ)
            rec = RoundRecord(
                n_active=jnp.sum(active).astype(jnp.int32),
                n_byzantine=jnp.sum(active & (lane.codes > 0)).astype(
                    jnp.int32),
                caught=caught, keep=keep, agg_norm=agg_norm,
                consensus_err=consensus_err, coverage=coverage,
                staleness=staleness, coalition_stake=coalition_stake)
        return new_state, rec

    round_fn.fused = fused                    # resolved choice, inspectable
    round_fn.stack_bytes = stack_bytes
    round_fn.staleness_bound = staleness_bound
    return round_fn


def scan_rounds(round_fn: Callable, lane: LaneParams, state: SwarmState,
                rounds: int, batch_fn: Callable,
                eval_fn: Optional[Callable] = None):
    """``lax.scan`` the pure round over ``rounds`` — one device program per
    run.  ``batch_fn(rnd)`` must be traceable and return the leading-N batch
    stack; ``eval_fn(params)``, if given, is evaluated once on the final
    params inside the program.  Returns ``(state, RoundRecord-stacked,
    final_loss)``."""
    def body(st, rnd):
        return round_fn(lane, st, rnd, batch_fn(rnd))

    state, recs = jax.lax.scan(body, state, jnp.arange(rounds))
    final = eval_fn(state.params) if eval_fn is not None else jnp.zeros(())
    return state, recs, final


def make_scan_program(round_fn: Callable, batch_fn: Callable, rounds: int,
                      eval_fn: Optional[Callable] = None) -> Callable:
    """The batched engine's scanned-run program, with donation declared:
    ``run(lane, params, opt_state, slashed, contrib, ring=None, econ=None)
    -> (SwarmState, RoundRecord-stacked, final_loss)``.

    The engine-owned carry buffers — ``opt_state``, ``slashed``,
    ``contrib``, (async rounds) the staleness ``ring``, and (economy
    rounds) the ``econ`` state — are donated:
    they are consumed by the scan and handed back as outputs, so XLA can
    run the whole campaign in place instead of holding a dead copy of the
    optimizer state for the program's lifetime (at real model sizes the
    opt state is as large as the params, and the ring is K+1 of them).
    ``params`` is deliberately NOT donated: the initial params buffer is
    caller-owned — tests and drivers seed several engines from one
    ``params0`` — and donating it would invalidate the caller's copy.
    ``analysis.jaxpr_audit`` (JX006) checks the declared donation is
    honored in the lowered program."""
    def run(lane: LaneParams, params, opt_state, slashed, contrib,
            ring=None, econ=None):
        state = SwarmState(params=params, opt_state=opt_state,
                           slashed=slashed, contrib=contrib, ring=ring,
                           econ=econ)
        return scan_rounds(round_fn, lane, state, rounds, batch_fn, eval_fn)
    return jax.jit(run, donate_argnums=(2, 3, 4, 5, 6))


def run_campaign(loss_fn: Callable, params0, optimizer, data_fn: Callable,
                 lanes: LaneParams, *, rounds: int, aggregator,
                 agg_kwargs: Optional[Dict] = None,
                 compression_kind: Optional[str] = None,
                 compression_kwargs: Optional[Dict] = None,
                 verify: bool = False, eval_fn: Optional[Callable] = None,
                 batched_data_fn: Optional[Callable] = None,
                 fast_compile: bool = False, mixing_schedule: str = "cycle",
                 fused: Optional[bool] = None,
                 plan: Optional[MeshPlan] = None):
    """Run a whole campaign — ``vmap`` over the leading run axis of ``lanes``
    of the scanned round — as **one** jit-compiled device program.

    All lanes share the aggregator set (and its static kwargs), the wire
    codec, the data stream, and the initial params; they differ in
    everything :class:`LaneParams` carries (roster behaviour/membership,
    seed, audit rate/tolerance, aggregator id, traced agg kwargs, and — in
    decentralized campaigns — the per-lane mixing matrix, which makes
    *topology* a campaign axis).  Decentralized mode is detected from
    ``lanes.mixing`` (all lanes must agree): the round switches to per-node
    replicas + neighborhood aggregation + gossip mixing, and ``eval_fn``
    is evaluated on each lane's consensus (node-mean) params.
    Per-round data is computed once and broadcast across lanes (it does not
    depend on the lane), so a campaign costs one gradient batch per (round,
    node) per *lane* but only one data generation per (round, node).

    ``data_fn(node_idx, rnd)`` (or ``batched_data_fn(rnd)``) and ``eval_fn``
    must be jax-traceable.  ``fast_compile=True`` asks XLA for backend
    optimization level 0 — measured ~3x faster compiles with bit-identical
    results on CPU; a backend that rejects the option raises.  Only use it
    for *tiny* models, where campaigns are compile-bound: on real models
    the unfused code pays far
    more in per-op memory traffic than it saves in compilation (measured
    ~4x slower end-to-end on the small-LM example).
    ``derailment.sweep`` picks this automatically by parameter count.

    Async mode is likewise detected from ``lanes.delays`` (all lanes must
    agree): the staleness ring is sized to the campaign-wide max delay
    (static), per-lane delay *values* stay traced — so staleness is one
    more sweep axis inside the single compiled program, and
    ``RoundRecord.staleness`` traces each round's mean realized delay.

    Custody mode is likewise detected from ``lanes.custody`` (all lanes
    must agree): every round traces ``RoundRecord.coverage`` (the live
    extraction frontier under churn/slashing), and the eval additionally
    runs the reconstruct-attack — each lane's final loss comes back as an
    ``[honest, extracted]`` pair (final losses are (R, 2) instead of (R,)),
    where ``extracted`` is the loss of the model reassembled from exactly
    the shards the lane's coalition holds.

    ``plan`` (a :class:`~repro.core.placement.MeshPlan`) makes device
    placement explicit: the stacked lane leaves are sharded over the plan's
    ``lanes`` mesh axis (bit-exact for centralized/fused/serving rounds —
    lanes are embarrassingly parallel; the decentralized mixing matmul is
    allclose only, see ``core/placement.py``), shared params over its
    within-lane ``data``/``model`` axes (allclose), and the one program
    runs under the plan's mesh: a plan of lanes alone runs each device's
    block of lanes under ``shard_map`` (no collective; the fused round's
    Pallas kernels cannot be partitioned by XLA), a plan with within-lane
    axes carries ``spmd_axis_name`` on the campaign vmap.

    Returns ``(final SwarmState, RoundRecord, final losses)`` with a leading
    run axis on every output leaf (RoundRecord leaves are (R, T, ...)).
    """
    if plan is not None:
        params0 = plan.place_params(params0)
        lanes = plan.place_lanes(lanes)
    fn = make_campaign_program(
        loss_fn, params0, optimizer, data_fn, lanes, rounds=rounds,
        aggregator=aggregator, agg_kwargs=agg_kwargs,
        compression_kind=compression_kind,
        compression_kwargs=compression_kwargs, verify=verify,
        eval_fn=eval_fn, batched_data_fn=batched_data_fn,
        mixing_schedule=mixing_schedule, fused=fused, plan=plan)

    def run_program():
        if fast_compile:
            return fn.lower(lanes, params0).compile(
                compiler_options={
                    "xla_backend_optimization_level": "0"})(lanes, params0)
        return fn(lanes, params0)

    if plan is None:
        return run_program()
    with plan.mesh:
        return run_program()


def make_campaign_program(loss_fn: Callable, params0, optimizer,
                          data_fn: Callable, lanes: LaneParams, *,
                          rounds: int, aggregator,
                          agg_kwargs: Optional[Dict] = None,
                          compression_kind: Optional[str] = None,
                          compression_kwargs: Optional[Dict] = None,
                          verify: bool = False,
                          eval_fn: Optional[Callable] = None,
                          batched_data_fn: Optional[Callable] = None,
                          mixing_schedule: str = "cycle",
                          fused: Optional[bool] = None,
                          plan: Optional[MeshPlan] = None) -> Callable:
    """Build (without running) THE campaign program — the jitted
    ``fn(lanes, params0)`` that :func:`run_campaign` executes.
    ``lanes`` and ``params0`` are consulted here for static structure only (N,
    decentralized/custody mode, the parameter tree); callers that place
    lanes on a mesh do so before/after as :func:`run_campaign` does.  The
    initial params are an argument of the program and every run's initial
    state (optimizer state, ring, economy) is built inside it, so the
    lowered module holds no parameter-sized constant and one compiled
    program serves any initial params of the same shapes.  Split out so
    ``analysis.jaxpr_audit`` can trace the *real* engine program — not a
    reimplementation that could drift — and enforce its invariants
    statically."""
    n = int(lanes.codes.shape[-1])
    decentralized = lanes.mixing is not None
    has_custody = lanes.custody is not None
    # economy mode is detected from the econ lane like mixing/custody: the
    # knobs stay traced (incentive axes sweep within one program); the
    # initial economy is derived per lane INSIDE the program — initial
    # stakes and the Sybil identity count depend on traced knobs
    has_econ = lanes.econ is not None
    # async mode is detected from the delays lane like mixing/custody: the
    # ring is sized to the campaign-wide max delay (static — lane *values*
    # stay traced, so staleness is a sweep axis within one program).  An
    # all-zero delays lane sizes the ring to 0 and routes through the
    # literal synchronous path.
    staleness_bound = (int(np.max(np.asarray(lanes.delays)))
                       if lanes.delays is not None else 0)
    round_fn = make_round_fn(
        loss_fn, optimizer, params0, n, aggregator=aggregator,
        agg_kwargs=agg_kwargs, compression_kind=compression_kind,
        compression_kwargs=compression_kwargs, verify=verify,
        decentralized=decentralized, mixing_schedule=mixing_schedule,
        fused=fused, staleness_bound=staleness_bound)
    if batched_data_fn is None:
        def batch_fn(rnd):
            return jax.vmap(lambda i: data_fn(i, rnd))(jnp.arange(n))
    else:
        batch_fn = batched_data_fn
    user_eval = eval_fn

    def one_run(lane, state0):
        st0 = (state0._replace(econ=economy.init_econ_state(lane.econ, n))
               if has_econ else state0)
        efn = None
        if user_eval is not None:
            def efn(p):
                # decentralized lanes evaluate the consensus (mean) replica
                pe = consensus_params(p) if decentralized else p
                honest = user_eval(pe)
                if not has_custody:
                    return honest
                # reconstruct-attack eval: reassemble exactly the shards the
                # coalition holds (missing ones zero-filled) and price what
                # the attacker actually gets, inside the same program
                covered = shards_covered(lane.custody, lane.coalition)
                extracted = user_eval(masked_reconstruct(pe, covered))
                return jnp.stack([honest, extracted])
        return scan_rounds(round_fn, lane, st0, rounds, batch_fn, efn)

    if plan is None:
        vmapped = jax.vmap(one_run, in_axes=(0, None))
    elif plan.data_devices == plan.model_devices == 1:
        # lanes only: each device runs its own block of lanes, manual over
        # the lane axis — nothing is exchanged, and the round's Pallas
        # kernels (which XLA cannot partition) see per-device shapes.  No
        # value crosses lanes, so the varying-axis typing is not needed.
        lanes_spec = jax.sharding.PartitionSpec(plan.lanes_axis)
        vmapped = jax.shard_map(
            jax.vmap(one_run, in_axes=(0, None)), mesh=plan.mesh,
            in_specs=(lanes_spec, jax.sharding.PartitionSpec()),
            out_specs=lanes_spec, check_vma=False)
    else:
        vmapped = jax.vmap(one_run, in_axes=(0, None),
                           spmd_axis_name=plan.lanes_axis)

    def program(lanes, params0):
        if decentralized:
            state0 = init_decentralized_state(
                params0, optimizer, n, staleness_bound=staleness_bound)
        else:
            state0 = init_state(params0, optimizer, n,
                                staleness_bound=staleness_bound)
        return vmapped(lanes, state0)

    return jax.jit(program)


def history_from_records(recs: RoundRecord, node_ids: Sequence[str], *,
                         start_round: int = 0) -> List[dict]:
    """Rebuild the per-round host history from one run's stacked records."""
    n_active = np.asarray(recs.n_active)
    n_byz = np.asarray(recs.n_byzantine)
    caught = np.asarray(recs.caught)
    agg = np.asarray(recs.agg_norm)
    cons = np.asarray(recs.consensus_err)
    cov = np.asarray(recs.coverage)
    stale = np.asarray(recs.staleness)
    coal_stake = (np.asarray(recs.coalition_stake)
                  if recs.coalition_stake is not None else None)
    out = [{
        "round": start_round + t,
        "n_active": int(n_active[t]),
        "n_byzantine": int(n_byz[t]),
        "caught": [node_ids[int(i)] for i in np.flatnonzero(caught[t])],
        "agg_norm": float(agg[t]),
        "consensus_error": float(cons[t]),
        "coverage": float(cov[t]),
        "staleness": float(stale[t]),
    } for t in range(agg.shape[0])]
    if coal_stake is not None:
        for t, row in enumerate(out):
            row["coalition_stake"] = float(coal_stake[t])
    return out


def ledger_from_run(state: SwarmState, node_ids: Sequence[str],
                    verification: Optional[VerificationConfig] = None,
                    validator: str = "validator") -> Ledger:
    """Reconstruct the ownership :class:`Ledger` from device counters.

    Equivalent to the per-round host bookkeeping of ``Swarm.step``: a node's
    balance is its speed-weighted kept rounds; a slashed node's pre-catch
    mints are forfeited (its counter froze at the catch round) and its stake
    burns, paying the validator jackpot.
    """
    led = Ledger()
    if verification is not None:
        for nid in node_ids:
            led.stake(nid, verification.stake)
    contrib = np.asarray(state.contrib)
    slashed = np.asarray(state.slashed)
    for nid, c in zip(node_ids, contrib):
        if c > 0:
            led.record_contribution(nid, float(c))
    for i in np.flatnonzero(slashed):
        led.slash(node_ids[int(i)])
        if verification is not None:
            led.pay_jackpot(validator, verification.jackpot)
    return led


# ================================ engines ======================================
class _SwarmBase:
    """State, ledger plumbing, and the run() loop shared by both engines."""

    def __init__(self, loss_fn: Callable, params, optimizer, nodes: List[NodeSpec],
                 cfg: SwarmConfig, data_fn: Callable[[int, int], dict]):
        """loss_fn(params, batch) -> scalar; data_fn(node_idx, round) -> batch."""
        self.loss_fn = loss_fn
        self.params = params
        self.optimizer = optimizer
        self.opt_state = optimizer.init(params)
        self.nodes = list(nodes)
        self.cfg = cfg
        self.data_fn = data_fn
        self.ledger = Ledger()
        self.slashed: Set[str] = set()
        self.history: List[dict] = []
        self._base_key = jax.random.PRNGKey(cfg.seed)
        # host copy of the custody matrix (None = no custody lane) — the
        # engines read coverage from it / from the device record, and
        # callers can inspect who holds what after a run
        self.custody_matrix: Optional[np.ndarray] = (
            assign_matrix(len(self.nodes), cfg.custody.num_shards,
                          cfg.custody.redundancy, cfg.custody.seed,
                          cfg.custody.max_fraction)
            if cfg.custody is not None else None)
        if cfg.verification:
            for n in self.nodes:
                self.ledger.stake(n.node_id, cfg.verification.stake)

    def step(self, rnd: int) -> dict:
        raise NotImplementedError

    def _coverage_of(self, active_idxs: Sequence[int]) -> float:
        """Live shard coverage of the given active node indices (1.0 when
        the run has no custody lane)."""
        if self.custody_matrix is None:
            return 1.0
        if not len(active_idxs):
            return 0.0
        return float(self.custody_matrix[list(active_idxs)].any(0).mean())

    def eval_params(self):
        """The params an ``eval_fn`` should see — the decentralized engine
        overrides this with the consensus (node-mean) replica."""
        return self.params

    def _unflatten(self, vec: Array):
        """Flat fp32 vector -> params-shaped pytree.  Only SequentialSwarm
        uses this (set up lazily from its first gradient); the batched
        engine's functional core carries its own (un)flatten pair built
        from the params template in make_round_fn."""
        out, off = [], 0
        for shape, dtype in self._flat_shapes:
            size = int(np.prod(shape)) if shape else 1
            out.append(vec[off:off + size].reshape(shape).astype(dtype))
            off += size
        return jax.tree.unflatten(self._treedef, out)

    def run(self, rounds: int, eval_fn: Optional[Callable] = None, eval_every: int = 10):
        losses = []
        for r in range(rounds):
            rec = self.step(r)
            if eval_fn and (r % eval_every == 0 or r == rounds - 1):
                rec["eval_loss"] = float(eval_fn(self.eval_params()))
                losses.append(rec["eval_loss"])
        return losses

    def _slash(self, node: NodeSpec) -> None:
        self.ledger.slash(node.node_id)
        self.ledger.pay_jackpot("validator", self.cfg.verification.jackpot)
        self.slashed.add(node.node_id)


class SequentialSwarm(_SwarmBase):
    """Per-node Python-loop engine: the readable reference oracle.

    O(N) dispatches per round; use :class:`Swarm` for anything but tests and
    equivalence checks.  Bounded staleness (``cfg.staleness_bound > 0``) is
    supported as the readable twin of the batched ring buffer: a plain dict
    of the last K+1 params snapshots, per-node delays drawn host-side from
    the identical ``(seed, _DELAY, round, node)`` key schedule (rounds must
    then be stepped consecutively from 0 — ``run`` always does).
    """

    def __init__(self, loss_fn, params, optimizer, nodes, cfg, data_fn):
        if cfg.topology is not None:
            raise ValueError("the sequential reference engine is "
                             "centralized-only; decentralized topologies "
                             "need engine='batched'")
        super().__init__(loss_fn, params, optimizer, nodes, cfg, data_fn)
        self._grad = jax.jit(jax.grad(loss_fn))
        self._flat_shapes = None
        self._snapshots: Dict[int, Any] = {}   # round -> params (async only)

    # -- helpers ----------------------------------------------------------------
    def _flatten(self, tree) -> Array:
        leaves = jax.tree.leaves(tree)
        if self._flat_shapes is None:
            self._flat_shapes = [(l.shape, l.dtype) for l in leaves]
            self._treedef = jax.tree.structure(tree)
        return jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])

    def _apply_wire(self, gf: Array, key) -> Array:
        """Round-trip a flat gradient through the configured wire codec."""
        cfg = self.cfg
        return compression.roundtrip(cfg.compression, key, gf,
                                     **cfg.compression_kwargs)

    # -- one round ----------------------------------------------------------------
    def step(self, rnd: int) -> dict:
        cfg = self.cfg
        active = [(i, n) for i, n in enumerate(self.nodes)
                  if n.active(rnd) and n.node_id not in self.slashed]
        if not active:
            raise RuntimeError(f"round {rnd}: no active nodes")

        K = cfg.staleness_bound
        if K > 0:
            # the readable ring-buffer twin: snapshot this round's params,
            # keep the last K+1 — a node drawing delay d gradients against
            # the params as of the start of round rnd - d
            self._snapshots[rnd] = self.params
            for old in [r for r in self._snapshots if r < rnd - K]:
                del self._snapshots[old]

        honest_grads, submitted, metas, delays = [], [], [], []
        for i, node in active:
            batch = self.data_fn(i, rnd)
            d, p_node = 0, self.params
            if K > 0:
                cap = min(node.effective_delay, K, rnd)
                d = int(jax.random.randint(
                    _node_key(self._base_key, _DELAY, rnd, i), (), 0,
                    cap + 1))
                p_node = self._snapshots[rnd - d]
            g = self._grad(p_node, batch)
            gf = self._flatten(g)
            honest_grads.append(gf)
            delays.append(d)
            metas.append((i, node, batch, p_node))
        honest_mean = jnp.mean(jnp.stack(honest_grads), axis=0)

        # corruption + wire compression.  The wire key is part of the shared
        # (purpose, round, node) schedule: QSGD is deterministic given
        # (key, tensor), so a validator recomputing the gradient re-encodes
        # with the submitter's key and compares like with like (otherwise
        # honest lossy compression reads as cheating).
        wire_keys = []
        for gf, (i, node, _, _) in zip(honest_grads, metas):
            if node.byzantine:
                gf = corrupt(node.byzantine, gf, honest_mean, node.byzantine_scale,
                             _node_key(self._base_key, _CORRUPT, rnd, i))
            wk = _node_key(self._base_key, _WIRE, rnd, i)
            wire_keys.append(wk)
            submitted.append(self._apply_wire(gf, wk))

        # stake/slash audits (§4.2)
        caught = []
        keep = [True] * len(active)
        if cfg.verification:
            v = cfg.verification
            for j, (i, node, batch, p_node) in enumerate(metas):
                sel = jax.random.uniform(_node_key(self._base_key, _AUDIT_SEL, rnd, i))
                if float(sel) >= v.p_check:
                    continue
                # recompute the gradient, re-encode with the submitter's wire
                # key, and compare flat — audit_flat is the same noise/compare
                # formula the batched engine vmaps, so both engines reach the
                # same pass/slash decision even at the tolerance boundary.
                # ``p_node`` is the submitter's (possibly stale) snapshot:
                # the validator replays the delay from the shared key
                # schedule and audits against the SAME params the
                # contributor claims — stale-but-honest never slashes.
                recomputed = self._apply_wire(
                    self._flatten(self._grad(p_node, batch)), wire_keys[j])
                ok, mismatch = audit_flat(
                    submitted[j], recomputed,
                    _node_key(self._base_key, _AUDIT_NOISE, rnd, i), v)
                if not ok:
                    self._slash(node)
                    caught.append(node.node_id)
                    keep[j] = False

        kept = [g for g, k in zip(submitted, keep) if k]
        if kept:
            survivors = jnp.stack(kept)
            agg = aggregation.get_aggregator(cfg.aggregator, **cfg.agg_kwargs)(survivors)
            self.params, self.opt_state = self.optimizer.update(
                self._unflatten(agg), self.opt_state, self.params)
        else:
            agg = jnp.zeros_like(honest_grads[0])  # every update audited out

        # mint shares ∝ verified work (speed-weighted) (§4)
        for (_, node, _, _), k in zip(metas, keep):
            if k:
                self.ledger.record_contribution(node.node_id, node.speed)

        rec = {
            "round": rnd,
            "n_active": len(active),
            "n_byzantine": sum(1 for _, n in active if n.byzantine),
            "caught": caught,
            "agg_norm": float(jnp.linalg.norm(agg)),
            "consensus_error": 0.0,        # centralized: one shared params
            "coverage": self._coverage_of([i for i, _ in active]),
            # f32 division so the record equals the batched engine's exactly
            "staleness": float(np.float32(sum(delays))
                               / np.float32(max(len(active), 1))),
        }
        self.history.append(rec)
        return rec


class Swarm(_SwarmBase):
    """Batched, jit-compiled protocol-learning engine (the default).

    A thin wrapper over the functional core (:func:`make_round_fn`): one
    device program per round, fixed (N, D) shapes forever:

    - gradients: ``jax.vmap(jax.grad(loss_fn))`` over stacked per-node batches;
    - corruption: the vectorized select table over per-node behaviour codes;
    - wire codec: ``vmap`` of ``compression.roundtrip`` over per-node keys;
    - audits: ``verification.audit_batch`` on the full stack, gated by a
      per-node audit-selection mask;
    - aggregation: mask-aware aggregators (``aggregation.masked_*``) driven
      by ``keep = active & ~caught``.

    Inactive nodes still occupy a lane (their gradient is computed and then
    masked) — that is the price of a churn-proof compiled round, and it is
    why this engine is O(1) dispatches per round instead of O(N).

    ``run`` with no ``eval_fn`` dispatches the **scanned** core — the whole
    run is one ``lax.scan`` device program with zero per-round host
    round-trips; the host history and ledger are rebuilt from device
    counters afterwards.  (Requires ``data_fn``/``batched_data_fn`` to be
    jax-traceable; otherwise it falls back to the per-round ``step`` loop.
    Note the scanned path cannot raise mid-run if audits slash the last
    active node — such rounds aggregate to zero instead, exactly as a
    fully-audited-out round does.)

    ``batched_data_fn(rnd) -> batch-with-leading-N-axis`` skips the per-node
    host stacking loop when the data pipeline can produce a stacked batch
    directly (see ``core.scenarios.batched_data_fn_for``).

    ``cfg.topology`` (a ``core.topology`` registry name) switches this
    engine to the **decentralized** round: ``self.params`` becomes per-node
    replicas (leading N axis), each round every node neighborhood-aggregates
    and the replicas gossip-mix, ``history`` rows gain a nonzero
    ``consensus_error``, and ``eval_params()`` returns the consensus
    (node-mean) replica for evaluation.  Everything else — step/run/scan
    dispatch, ledger, slashing — is unchanged.
    """

    def __init__(self, loss_fn, params, optimizer, nodes, cfg, data_fn, *,
                 batched_data_fn: Optional[Callable[[int], dict]] = None):
        super().__init__(loss_fn, params, optimizer, nodes, cfg, data_fn)
        self.batched_data_fn = batched_data_fn
        n = len(self.nodes)
        self._decentralized = cfg.topology is not None
        self._lane = lane_for_nodes(self.nodes, cfg)
        self._joins_np = np.asarray([s.join_round for s in self.nodes], np.int32)
        self._leaves_np = np.asarray(
            [_FAR if s.leave_round is None else s.leave_round for s in self.nodes],
            np.int32)
        self._slashed_np = np.zeros(n, bool)
        self._core = make_round_fn(
            loss_fn, optimizer, self.params, n,
            aggregator=cfg.aggregator, agg_kwargs=cfg.agg_kwargs,
            compression_kind=cfg.compression,
            compression_kwargs=cfg.compression_kwargs,
            verify=cfg.verification is not None,
            decentralized=self._decentralized,
            mixing_schedule="clamp" if cfg.churn_coupled else "cycle",
            fused=cfg.fused, staleness_bound=cfg.staleness_bound)
        if self._decentralized:
            # per-node replicas + per-node optimizer states from round 0
            init = init_decentralized_state(self.params, optimizer, n)
            self.params, self.opt_state = init.params, init.opt_state
        # the bounded-staleness snapshot ring (None when synchronous) —
        # engine state like params/opt_state, advanced by every round
        self._ring = init_ring(self.params, cfg.staleness_bound)
        # the economy state (None without an economy lane) — ditto
        self._econ_state = (economy.init_econ_state(self._lane.econ, n)
                            if self._lane.econ is not None else None)
        self._round_fn = jax.jit(functools.partial(self._core, self._lane))
        self._scan_cache: Dict[int, Callable] = {}
        self._batches_traceable: Optional[bool] = None

    # -- helpers ----------------------------------------------------------------
    def _stack_batches(self, rnd: int):
        if self.batched_data_fn is not None:
            return self.batched_data_fn(rnd)
        per_node = [self.data_fn(i, rnd) for i in range(len(self.nodes))]
        return jax.tree.map(lambda *xs: jnp.stack(xs), *per_node)

    def _traced_batch_fn(self) -> Callable:
        if self.batched_data_fn is not None:
            return self.batched_data_fn
        n = len(self.nodes)
        return lambda rnd: jax.vmap(lambda i: self.data_fn(i, rnd))(jnp.arange(n))

    def _state(self) -> SwarmState:
        return SwarmState(params=self.params, opt_state=self.opt_state,
                          slashed=jnp.asarray(self._slashed_np),
                          contrib=jnp.zeros(len(self.nodes), jnp.float32),
                          ring=self._ring, econ=self._econ_state)

    def _can_scan(self, rounds: int) -> bool:
        """Scanned run needs a traceable batch fn and a membership schedule
        that never goes empty (the step loop raises at the exact round)."""
        r = np.arange(rounds)[:, None]
        sched = ((self._joins_np[None] <= r) & (r < self._leaves_np[None])
                 & ~self._slashed_np[None])
        if not sched.any(axis=1).all():
            return False
        if self._batches_traceable is None:
            # a data_fn that needs a concrete round raises a tracer error:
            # int(rnd), numpy on it or a Python branch a JAXTypeError, a
            # boolean mask built from it a JAXIndexError, a dict keyed by it
            # Python's "unhashable type: '...Tracer'" TypeError.  Anything
            # else is a real bug and propagates.
            try:
                jax.eval_shape(self._traced_batch_fn(), jnp.asarray(0, jnp.int32))
                self._batches_traceable = True
            except (jax.errors.JAXTypeError, jax.errors.JAXIndexError):
                self._batches_traceable = False
            except TypeError as e:
                if not ("unhashable type" in str(e) and "Tracer" in str(e)):
                    raise
                self._batches_traceable = False
        return self._batches_traceable

    @property
    def fused(self) -> bool:
        """Whether the round resolved to the fused kernel path."""
        return self._core.fused

    def lower_step(self, rnd: int):
        """The program :meth:`step` runs for round ``rnd``, lowered
        (``jax.stages.Lowered``): compile it ahead of time or read its HLO.
        Its compiled executable is what ``step`` then reuses."""
        return self._round_fn.lower(self._state(), rnd,
                                    self._stack_batches(rnd))

    # -- one round ----------------------------------------------------------------
    def step(self, rnd: int) -> dict:
        """One round.  Host spans (``jax.profiler.TraceAnnotation``, free
        when no profiler runs) split it in the trace: ``feed`` builds the
        batch, ``dispatch`` enqueues the round, ``wait`` is the first read
        of its results (the host waits for the device there), ``settle``
        reads the rest, slashes, and fills the ledger and the history."""
        with jax.profiler.TraceAnnotation("repro.swarm.step"):
            active_np = ((self._joins_np <= rnd) & (rnd < self._leaves_np)
                         & ~self._slashed_np)
            if not active_np.any():
                raise RuntimeError(f"round {rnd}: no active nodes")

            with jax.profiler.TraceAnnotation("repro.swarm.feed"):
                batches = self._stack_batches(rnd)
            with jax.profiler.TraceAnnotation("repro.swarm.dispatch"):
                state, core_rec = self._round_fn(self._state(), rnd, batches)
            self.params, self.opt_state = state.params, state.opt_state
            self._ring = state.ring
            self._econ_state = state.econ

            with jax.profiler.TraceAnnotation("repro.swarm.wait"):
                caught = np.asarray(core_rec.caught)
            with jax.profiler.TraceAnnotation("repro.swarm.settle"):
                return self._settle(rnd, active_np, caught, core_rec)

    def _settle(self, rnd: int, active_np, caught, core_rec) -> dict:
        caught_ids = []
        for i in np.flatnonzero(caught):
            node = self.nodes[int(i)]
            self._slash(node)
            self._slashed_np[int(i)] = True
            caught_ids.append(node.node_id)
        for i in np.flatnonzero(np.asarray(core_rec.keep)):
            node = self.nodes[int(i)]
            self.ledger.record_contribution(node.node_id, node.speed)

        rec = {
            "round": rnd,
            # economy rounds gate admission on device (stakes) — the record
            # is the authoritative count there
            "n_active": (int(core_rec.n_active) if self._econ_state is not None
                         else int(active_np.sum())),
            "n_byzantine": (int(core_rec.n_byzantine)
                            if self._econ_state is not None
                            else int(sum(1 for i in np.flatnonzero(active_np)
                                         if self.nodes[int(i)].byzantine))),
            "caught": caught_ids,
            "agg_norm": float(core_rec.agg_norm),
            "consensus_error": float(core_rec.consensus_err),
            "coverage": float(core_rec.coverage),
            "staleness": float(core_rec.staleness),
        }
        if core_rec.coalition_stake is not None:
            rec["coalition_stake"] = float(core_rec.coalition_stake)
        self.history.append(rec)
        return rec

    def eval_params(self):
        return consensus_params(self.params) if self._decentralized \
            else self.params

    # -- the scanned run ---------------------------------------------------------
    def run(self, rounds: int, eval_fn: Optional[Callable] = None,
            eval_every: int = 10):
        if eval_fn is None and self._can_scan(rounds):
            self._run_scanned(rounds)
            return []
        return super().run(rounds, eval_fn, eval_every)

    def _run_scanned(self, rounds: int) -> None:
        if rounds not in self._scan_cache:
            self._scan_cache[rounds] = make_scan_program(
                self._core, self._traced_batch_fn(), rounds)
        was_slashed = self._slashed_np.copy()
        st = self._state()
        # opt_state/slashed/contrib/ring are donated (make_scan_program) and
        # reassigned from the outputs below — never read the old buffers
        state, recs, _ = self._scan_cache[rounds](
            self._lane, st.params, st.opt_state, st.slashed, st.contrib,
            st.ring, st.econ)
        self.params, self.opt_state = state.params, state.opt_state
        self._ring = state.ring
        self._econ_state = state.econ
        # run() numbers rounds from 0 on every call (same as the step loop)
        self.history.extend(history_from_records(
            recs, [n.node_id for n in self.nodes]))
        # Ledger from device counters — mints first, then this run's slashes,
        # so a slashed node's pre-catch mints are forfeited exactly as in the
        # per-round step path (its contrib counter froze at the catch round).
        contrib = np.asarray(state.contrib)
        for i, node in enumerate(self.nodes):
            if contrib[i] > 0:
                self.ledger.record_contribution(node.node_id, float(contrib[i]))
        for i in np.flatnonzero(np.asarray(state.slashed) & ~was_slashed):
            node = self.nodes[int(i)]
            self._slash(node)
            self._slashed_np[int(i)] = True


ENGINES: Dict[str, type] = {"batched": Swarm, "sequential": SequentialSwarm}


def make_swarm(loss_fn, params, optimizer, nodes: List[NodeSpec], cfg: SwarmConfig,
               data_fn, *, engine: str = "batched",
               batched_data_fn: Optional[Callable[[int], dict]] = None) -> _SwarmBase:
    """Build a swarm with the requested engine ("batched" | "sequential")."""
    if engine not in ENGINES:
        raise ValueError(f"unknown engine: {engine!r} (known: {sorted(ENGINES)})")
    if batched_data_fn is not None:
        if engine != "batched":
            raise ValueError("batched_data_fn requires engine='batched'")
        return Swarm(loss_fn, params, optimizer, nodes, cfg, data_fn,
                     batched_data_fn=batched_data_fn)
    return ENGINES[engine](loss_fn, params, optimizer, nodes, cfg, data_fn)
