"""The audited engine programs — traced, never run.

This module builds the *real* entry-point programs of the engine (the same
builders ``run_campaign`` / ``derailment.sweep`` / ``ServingEngine`` execute
— not reimplementations that could drift) against tiny probe problems, and
hands ``jaxpr_audit`` their :class:`jax.extend.core.ClosedJaxpr`.
Seven programs:

``round_unfused`` / ``round_fused``
    ``swarm.make_round_fn`` in both hot-path modes, plus the scanned-run
    donation unit (``make_scan_program`` lowered text for JX006).
``round_async``
    the bounded-staleness round (``staleness_bound=K``): delay-schedule
    variants share one fingerprint, the K+1-snapshot ring is donated
    through the scan, and the staleness-axis *campaign* (two
    ``build_sweep_lanes`` value-variant grids) fingerprints stably.
``campaign``
    ``swarm.make_campaign_program`` — the jit(vmap(scan)) phase-diagram
    program, with value-variants (base / churn / attack) that must share a
    retrace fingerprint, and a :class:`~repro.core.placement.MeshPlan`
    variant (its own fingerprint group: ``spmd_axis_name`` and placement
    legitimately change the jaxpr) that declares its mesh axes for JX005.
``sweep``
    ``derailment.build_sweep_lanes`` feeding ``make_campaign_program`` —
    the multi-aggregator fused phase-diagram program, with two grids
    differing only in seed/scale values (one fingerprint group).
``economy``
    the incentive phase diagram: ``build_sweep_lanes`` over economy axes
    (identity cost / fee / reward schedule / fixed-vs-adaptive) feeding
    ``make_campaign_program`` — knob-value-variant grids share one
    fingerprint, and the scanned economy run donates the ``EconState``
    carry next to opt_state.
``serve_step``
    ``ServingEngine.program`` — the custody-gated continuous-batching
    scan, vmapped over a stacked lane campaign, with load / churn lane
    variants (one fingerprint group).

Everything here is shape-tiny so tracing stays sub-second; the invariants
audited (dtypes, primitives, donation, axis names, fingerprint stability)
do not depend on problem size.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Callable, Dict, FrozenSet, List, Optional, Tuple

import numpy as np

import jax
import jax.extend
import jax.numpy as jnp

from repro.core import derailment, economy, serving, swarm
from repro.core.economy import EconomyConfig
from repro.core.placement import MeshPlan
from repro.core.scenarios import Regime, SweepGrid
from repro.core.swarm import NodeSpec, SwarmConfig
from repro.core.unextractable import assign_matrix
from repro.core.verification import VerificationConfig
from repro.optim.optimizer import SGD


@dataclass(frozen=True)
class TracedUnit:
    """One traced variant of a program: a ClosedJaxpr plus audit context.

    ``group`` names the retrace-fingerprint group: every unit sharing a
    group must produce an identical fingerprint (JX007) — they are the
    lane-value variants one compiled program is contractually required to
    serve without retracing.  ``declared_axes`` are the mesh axis names
    collectives may legally use (JX005); empty = no collectives allowed.
    """
    label: str
    closed: jax.extend.core.ClosedJaxpr
    group: Optional[str] = None
    declared_axes: FrozenSet[str] = frozenset()


@dataclass(frozen=True)
class DonationUnit:
    """A lowered program whose declared buffer donation JX006 verifies:
    ``lowered_text`` must contain at least ``min_aliases`` occurrences of
    ``tf.aliasing_output`` (one per donated input buffer)."""
    label: str
    lowered_text: str
    min_aliases: int


@dataclass
class TracedProgram:
    name: str
    units: List[TracedUnit]
    donations: List[DonationUnit] = field(default_factory=list)


# ---------------------------------------------------------------------------
# tiny probe problems
# ---------------------------------------------------------------------------
def _tiny_problem(d: int = 8):
    """A d-dim linear regression — the smallest loss with a real gradient
    path, shared by the round/campaign/sweep probes."""
    params = {"w": jnp.zeros((d,), jnp.float32)}
    w_true = jnp.arange(d, dtype=jnp.float32) / d

    def data_fn(node_idx, rnd):
        k = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(17), node_idx), rnd)
        x = jax.random.normal(k, (4, d))
        return {"x": x, "y": x @ w_true}

    def loss_fn(p, batch):
        return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2)

    def eval_fn(p):
        x = jax.random.normal(jax.random.PRNGKey(3), (16, d))
        return jnp.mean((x @ p["w"] - x @ w_true) ** 2)

    return params, loss_fn, data_fn, eval_fn


def _roster(n: int, *, churn: bool = False, attack: bool = False):
    nodes = [NodeSpec(node_id=f"n{i}") for i in range(n)]
    if churn:
        nodes[1] = NodeSpec(node_id="n1", join_round=1)
        nodes[2] = NodeSpec(node_id="n2", leave_round=2)
    if attack:
        nodes[-1] = NodeSpec(node_id=f"n{n - 1}", byzantine="sign_flip",
                             byzantine_scale=5.0)
    return nodes


def _batch_fn(data_fn, n):
    def batch_fn(rnd):
        return jax.vmap(lambda i: data_fn(i, rnd))(jnp.arange(n))
    return batch_fn


# ---------------------------------------------------------------------------
# round programs (unfused / fused) + donation units
# ---------------------------------------------------------------------------
def _round_program(name: str, *, fused: bool) -> TracedProgram:
    n, d = 4, (128 if fused else 8)   # fused wire is bucketed per lane-width
    params, loss_fn, data_fn, _ = _tiny_problem(d)
    opt = SGD(lr=0.05)
    kind, ckw = (("qsgd", {"levels": 64}) if fused else (None, None))
    round_fn = swarm.make_round_fn(
        loss_fn, opt, params, n, aggregator="centered_clip",
        compression_kind=kind, compression_kwargs=ckw, verify=True,
        fused=fused)
    batch_fn = _batch_fn(data_fn, n)
    state0 = swarm.init_state(params, opt, n)
    cfg = SwarmConfig(verification=VerificationConfig(p_check=0.5))

    units = []
    for label, roster in (("base", _roster(n)),
                          ("churn", _roster(n, churn=True)),
                          ("attack", _roster(n, attack=True))):
        lane = swarm.lane_for_nodes(roster, cfg)
        closed = jax.make_jaxpr(round_fn)(
            lane, state0, jnp.asarray(0, jnp.int32), batch_fn(0))
        units.append(TracedUnit(label, closed, group=name))

    # the scanned-run program donates opt_state + slashed + contrib — one
    # aliased output per donated leaf (SGDState: step + per-param momentum)
    lane = swarm.lane_for_nodes(_roster(n), cfg)
    scan_fn = swarm.make_scan_program(round_fn, batch_fn, rounds=3)
    lowered = scan_fn.lower(lane, state0.params, state0.opt_state,
                            state0.slashed, state0.contrib).as_text()
    min_aliases = len(jax.tree.leaves(state0.opt_state)) + 2
    return TracedProgram(name, units,
                         donations=[DonationUnit("scan", lowered, min_aliases)])


def build_round_unfused() -> TracedProgram:
    return _round_program("round_unfused", fused=False)


def build_round_fused() -> TracedProgram:
    return _round_program("round_fused", fused=True)


# ---------------------------------------------------------------------------
# campaign program (value variants + mesh variant)
# ---------------------------------------------------------------------------
def _campaign_lanes(cfg: SwarmConfig, n: int, variant: str):
    rosters = {
        "base": [_roster(n), _roster(n), _roster(n)],
        "churn": [_roster(n), _roster(n, churn=True), _roster(n, churn=True)],
        "attack": [_roster(n, attack=True), _roster(n), _roster(n, attack=True)],
    }[variant]
    return swarm.stack_lanes([swarm.lane_for_nodes(r, cfg) for r in rosters])


def build_campaign() -> TracedProgram:
    n = 4
    params, loss_fn, data_fn, eval_fn = _tiny_problem()
    opt = SGD(lr=0.05)
    cfg = SwarmConfig()
    lanes = _campaign_lanes(cfg, n, "base")
    fn = swarm.make_campaign_program(
        loss_fn, params, opt, data_fn, lanes, rounds=2,
        aggregator="centered_clip", eval_fn=eval_fn)

    units = []
    for variant in ("base", "churn", "attack"):
        closed = jax.make_jaxpr(fn)(_campaign_lanes(cfg, n, variant), params)
        units.append(TracedUnit(variant, closed, group="campaign"))

    # mesh variant: same campaign under an explicit MeshPlan — placement and
    # spmd_axis_name legitimately change the jaxpr, so it gets its OWN
    # fingerprint group, and declares the axes its collectives may use
    plan = MeshPlan.for_lanes(3)
    placed = plan.place_lanes(_campaign_lanes(cfg, n, "base"))
    mesh_fn = swarm.make_campaign_program(
        loss_fn, plan.place_params(params), opt, data_fn, placed, rounds=2,
        aggregator="centered_clip", eval_fn=eval_fn, plan=plan)
    with plan.mesh:
        closed = jax.make_jaxpr(mesh_fn)(placed, plan.place_params(params))
    units.append(TracedUnit(
        "mesh", closed, group="campaign_mesh",
        declared_axes=frozenset(
            {plan.lanes_axis, plan.data_axis, plan.model_axis})))
    return TracedProgram("campaign", units)


# ---------------------------------------------------------------------------
# sweep program (derailment phase diagram)
# ---------------------------------------------------------------------------
def _sweep_grid(seed: int, scale: float) -> SweepGrid:
    return SweepGrid(
        name=f"audit_probe_{seed}",
        description="tiny two-regime probe grid for the static audit",
        regimes=(Regime("mean", "mean"),
                 Regime("cc+audit", "centered_clip",
                        verification=VerificationConfig(p_check=0.5))),
        n_honest=3, attacker_counts=(1,), seeds=(seed,), scales=(scale,),
        rounds=2)


def build_sweep() -> TracedProgram:
    params, loss_fn, data_fn, eval_fn = _tiny_problem()
    opt = SGD(lr=0.05)
    spec0 = derailment.build_sweep_lanes(_sweep_grid(0, 10.0), rounds=2)
    fn = swarm.make_campaign_program(
        loss_fn, params, opt, data_fn, swarm.stack_lanes(spec0.lanes),
        rounds=2, aggregator=spec0.aggregator, agg_kwargs=spec0.agg_kwargs,
        verify=spec0.verify, eval_fn=eval_fn)

    units = []
    for label, (seed, scale) in (("base", (0, 10.0)), ("shifted", (1, 50.0))):
        spec = derailment.build_sweep_lanes(_sweep_grid(seed, scale), rounds=2)
        closed = jax.make_jaxpr(fn)(swarm.stack_lanes(spec.lanes), params)
        units.append(TracedUnit(label, closed, group="sweep"))
    return TracedProgram("sweep", units)


# ---------------------------------------------------------------------------
# async round program (bounded-staleness ring)
# ---------------------------------------------------------------------------
def _async_grid(seed: int, scale: float) -> SweepGrid:
    return SweepGrid(
        name=f"audit_async_{seed}",
        description="tiny staleness-axis probe grid for the static audit",
        regimes=(Regime("cc", "centered_clip"),),
        n_honest=3, attacker_counts=(1,), seeds=(seed,), scales=(scale,),
        staleness_bounds=(0, 2), rounds=2)


def build_round_async() -> TracedProgram:
    """The bounded-staleness async round (``swarm.make_round_fn`` with
    ``staleness_bound=K``): the K+1-snapshot ring must keep static shapes
    (JX001-004), be donated through the scanned run next to opt_state
    (JX006), and hold one retrace fingerprint across delay-schedule
    variants (JX007) — plus the async *campaign* (the staleness-axis sweep
    via ``derailment.build_sweep_lanes``), whose two value-variant grids
    share a fingerprint the same way the sync sweep's do."""
    n, K = 4, 2
    params, loss_fn, data_fn, eval_fn = _tiny_problem()
    opt = SGD(lr=0.05)
    round_fn = swarm.make_round_fn(
        loss_fn, opt, params, n, aggregator="centered_clip", verify=True,
        staleness_bound=K)
    batch_fn = _batch_fn(data_fn, n)
    state0 = swarm.init_state(params, opt, n, staleness_bound=K)
    cfg = SwarmConfig(verification=VerificationConfig(p_check=0.5),
                      staleness_bound=K)

    def stale(nodes, jitter: int = 0):
        return [replace(nd, delay=(i + jitter) % (K + 1))
                for i, nd in enumerate(nodes)]

    units = []
    for label, roster in (("base", stale(_roster(n))),
                          ("churn", stale(_roster(n, churn=True))),
                          ("attack", stale(_roster(n, attack=True))),
                          ("jitter", stale(_roster(n), jitter=1))):
        lane = swarm.lane_for_nodes(roster, cfg)
        closed = jax.make_jaxpr(round_fn)(
            lane, state0, jnp.asarray(0, jnp.int32), batch_fn(0))
        units.append(TracedUnit(label, closed, group="round_async"))

    # the async campaign: both probe grids carry staleness_bounds=(0, 2),
    # so the compiled ring has the same K and the jaxprs must coincide
    fn = None
    for label, (seed, scale) in (("sweep_base", (0, 10.0)),
                                 ("sweep_shifted", (1, 50.0))):
        spec = derailment.build_sweep_lanes(_async_grid(seed, scale), rounds=2)
        if fn is None:
            fn = swarm.make_campaign_program(
                loss_fn, params, opt, data_fn, swarm.stack_lanes(spec.lanes),
                rounds=2, aggregator=spec.aggregator,
                agg_kwargs=spec.agg_kwargs, verify=spec.verify,
                eval_fn=eval_fn)
        closed = jax.make_jaxpr(fn)(swarm.stack_lanes(spec.lanes), params)
        units.append(TracedUnit(label, closed, group="campaign_async"))

    # the scanned async run donates the ring buffer next to opt_state +
    # slashed + contrib — one aliased output per donated leaf
    lane = swarm.lane_for_nodes(stale(_roster(n)), cfg)
    scan_fn = swarm.make_scan_program(round_fn, batch_fn, rounds=3)
    lowered = scan_fn.lower(lane, state0.params, state0.opt_state,
                            state0.slashed, state0.contrib,
                            state0.ring).as_text()
    min_aliases = (len(jax.tree.leaves(state0.opt_state)) + 2
                   + len(jax.tree.leaves(state0.ring)))
    return TracedProgram("round_async", units,
                         donations=[DonationUnit("scan", lowered, min_aliases)])


# ---------------------------------------------------------------------------
# economy program (incentive phase diagram)
# ---------------------------------------------------------------------------
def _econ_grid(seed: int, icost: float, fee: float) -> SweepGrid:
    return SweepGrid(
        name=f"audit_econ_{seed}",
        description="tiny incentive-axis probe grid for the static audit",
        regimes=(Regime("mean+audit", "mean",
                        verification=VerificationConfig(p_check=0.5)),),
        n_honest=3, attacker_counts=(1,), seeds=(seed,), scales=(2.0,),
        rounds=2, identity_costs=(icost,), fees=(fee,),
        reward_schedules=((0.1, 5.0),), adaptive=(False, True))


def build_economy() -> TracedProgram:
    """The economy campaign (incentive axes as traced lane data): every
    knob — identity cost, fee income, reward schedule, jackpot, and the
    fixed-vs-adaptive switch — rides in ``EconParams``, so probe grids that
    differ only in knob *values* must share one retrace fingerprint
    (JX007), and the scanned economy run donates the ``EconState`` carry
    (stakes, balances, escrow, pool/income counters) next to opt_state
    through the scan (JX006)."""
    n = 4
    params, loss_fn, data_fn, eval_fn = _tiny_problem()
    opt = SGD(lr=0.05)

    units = []
    fn = None
    for label, (seed, icost, fee) in (("base", (0, 1.0, 1.0)),
                                      ("shifted", (1, 8.0, 0.25))):
        spec = derailment.build_sweep_lanes(_econ_grid(seed, icost, fee),
                                            rounds=2)
        if fn is None:
            fn = swarm.make_campaign_program(
                loss_fn, params, opt, data_fn, swarm.stack_lanes(spec.lanes),
                rounds=2, aggregator=spec.aggregator,
                agg_kwargs=spec.agg_kwargs, verify=spec.verify,
                eval_fn=eval_fn)
        closed = jax.make_jaxpr(fn)(swarm.stack_lanes(spec.lanes), params)
        units.append(TracedUnit(label, closed, group="campaign_economy"))

    # the scanned economy run donates the EconState carry next to
    # opt_state + slashed + contrib — one aliased output per donated leaf
    cfg = SwarmConfig(verification=VerificationConfig(p_check=0.5),
                      economy=EconomyConfig(adaptive=True))
    lane = swarm.lane_for_nodes(_roster(n, attack=True), cfg)
    round_fn = swarm.make_round_fn(loss_fn, opt, params, n,
                                   aggregator="mean", verify=True)
    batch_fn = _batch_fn(data_fn, n)
    state0 = swarm.init_state(params, opt, n,
                              econ=economy.init_econ_state(lane.econ, n))
    scan_fn = swarm.make_scan_program(round_fn, batch_fn, rounds=3)
    lowered = scan_fn.lower(lane, state0.params, state0.opt_state,
                            state0.slashed, state0.contrib, state0.ring,
                            state0.econ).as_text()
    min_aliases = (len(jax.tree.leaves(state0.opt_state)) + 2
                   + len(jax.tree.leaves(state0.econ)))
    return TracedProgram("economy", units,
                         donations=[DonationUnit("scan", lowered, min_aliases)])


# ---------------------------------------------------------------------------
# serving program (custody-gated continuous batching)
# ---------------------------------------------------------------------------
def _serve_lane(custody: np.ndarray, steps: int, variant: str):
    kw = {"load": 1.0} if variant == "load" else {
        "load": 2.0, "churn_rate": 0.5, "coalition_fraction": 0.25,
        "defect_step": steps // 2}
    return serving.build_lane(
        n_requests=6, prompt_lens=[6, 4, 5, 6, 3, 4], max_new=4,
        steps=steps, n_nodes=4, balances=[8.0, 8.0, 1.0], fee=1.0,
        custody=custody, **kw)


def build_serve_step() -> TracedProgram:
    from repro.configs import get_config
    from repro.models.model import build_model

    cfg = get_config("protocol-125m").reduced(
        num_layers=1, d_model=32, num_heads=2, head_dim=16, d_ff=64,
        vocab_size=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = jax.random.randint(jax.random.PRNGKey(1), (6, 6), 0,
                                 cfg.vocab_size)
    scfg = serving.ServingConfig(slots=3, max_new=4, steps=20)
    engine = serving.ServingEngine(model, scfg, prompts)
    fn = engine.program(has_custody=True, vmapped=True)
    custody = assign_matrix(4, 8, 2, 0, 0.5)

    units = []
    for variant in ("load", "churn"):
        lanes = serving.stack_serve_lanes(
            [_serve_lane(custody, scfg.steps, variant),
             _serve_lane(custody, scfg.steps, variant)])
        closed = jax.make_jaxpr(fn)(params, prompts, lanes)
        units.append(TracedUnit(variant, closed, group="serve"))
    return TracedProgram("serve_step", units)


#: name -> builder, in audit order.  ``build_all`` is what the CLI and the
#: integration test iterate; each builder is independent so golden tests
#: can trace one program without paying for the rest.
PROGRAM_BUILDERS: Dict[str, Callable[[], TracedProgram]] = {
    "round_unfused": build_round_unfused,
    "round_fused": build_round_fused,
    "round_async": build_round_async,
    "campaign": build_campaign,
    "sweep": build_sweep,
    "economy": build_economy,
    "serve_step": build_serve_step,
}


def build_all() -> List[TracedProgram]:
    return [build() for build in PROGRAM_BUILDERS.values()]
