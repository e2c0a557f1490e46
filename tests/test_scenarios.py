"""Batched engine + scenario registry coverage.

Three layers:
1. masked aggregators == dense aggregators on the kept subset (the algebra
   the batched engine's fixed-shape round rests on);
2. the batched engine is *equivalent* to the sequential reference: same seed
   -> same agg_norm history (fp32 tolerance), same slash decisions, same
   active counts — honest, byzantine, churn, compressed, and audited runs;
3. every registered scenario builds and runs on the batched engine.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregation
from repro.core.scenarios import (
    SCENARIOS,
    batched_data_fn_for,
    get_scenario,
    list_scenarios,
)
from repro.core.swarm import NodeSpec, SwarmConfig, make_swarm
from repro.core.verification import VerificationConfig
from repro.optim.optimizer import SGD

from conftest import tiny_quadratic_problem


# ------------------------- masked aggregator algebra ---------------------------
AGG_CASES = [
    ("mean", {}),
    ("median", {}),
    ("trimmed_mean", {"trim": 2}),
    ("krum", {"f": 1}),
    ("multi_krum", {"f": 1}),
    ("centered_clip", {"iters": 3}),
    ("centered_clip", {"clip_tau": 1.0, "iters": 3}),
]


@pytest.mark.parametrize("name,kwargs", AGG_CASES)
def test_masked_aggregator_matches_dense_subset(name, kwargs):
    rng = np.random.default_rng(0)
    for trial in range(4):
        x = jnp.asarray(rng.normal(size=(12, 17)).astype(np.float32))
        mask = rng.random(12) < 0.7
        mask[0] = True                               # never fully empty
        dense = aggregation.get_aggregator(name, **kwargs)(x[mask])
        masked = jax.jit(
            lambda x, m: aggregation.get_masked_aggregator(name, **kwargs)(x, m)
        )(x, jnp.asarray(mask))
        np.testing.assert_allclose(np.asarray(masked), np.asarray(dense),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"{name} trial {trial}")


def test_masked_krum_single_survivor_never_picks_masked_row():
    """Regression: with one kept node every krum score is +inf, and argmin
    must still land on the kept row, not a slashed byzantine one."""
    x = jnp.asarray([[100.0] * 3, [1.0] * 3, [2.0] * 3])
    mask = jnp.asarray([False, True, False])
    out = aggregation.masked_krum(x, mask, f=1)
    np.testing.assert_allclose(np.asarray(out), [1.0, 1.0, 1.0])


def test_masked_multi_krum_clamps_static_m_to_kept_count():
    """Regression: m larger than the kept count must not average the
    masked-out rows (real corrupted updates) into the aggregate."""
    x = jnp.asarray([[100.0] * 3, [1.0] * 3, [3.0] * 3])
    mask = jnp.asarray([False, True, True])
    out = aggregation.masked_multi_krum(x, mask, f=0, m=3)
    np.testing.assert_allclose(np.asarray(out), [2.0, 2.0, 2.0])


# ------------------------- engine equivalence ----------------------------------
def _run_both(nodes, cfg, rounds=15):
    loss_fn, params0, data_fn, _ = tiny_quadratic_problem(8)
    swarms = {}
    for engine in ("sequential", "batched"):
        s = make_swarm(loss_fn, params0, SGD(lr=0.1, momentum=0.0),
                       nodes, cfg, data_fn, engine=engine)
        s.run(rounds)
        swarms[engine] = s
    return swarms["sequential"], swarms["batched"]


def _assert_equivalent(seq, bat):
    assert [r["n_active"] for r in seq.history] == \
        [r["n_active"] for r in bat.history]
    assert [r["caught"] for r in seq.history] == \
        [r["caught"] for r in bat.history]
    assert seq.slashed == bat.slashed
    a_seq = np.array([r["agg_norm"] for r in seq.history])
    a_bat = np.array([r["agg_norm"] for r in bat.history])
    np.testing.assert_allclose(a_bat, a_seq, rtol=2e-3, atol=1e-5)
    # balances mint identically (speed-weighted verified work)
    assert seq.ledger.balances == pytest.approx(bat.ledger.balances)


def test_batched_matches_sequential_honest():
    nodes = [NodeSpec(f"h{i}") for i in range(8)]
    _assert_equivalent(*_run_both(nodes, SwarmConfig(aggregator="mean")))


@pytest.mark.parametrize("aggregator,kwargs", [
    ("centered_clip", {"clip_tau": 1.0, "iters": 3}),
    ("centered_clip", {}),
    ("median", {}),
    ("trimmed_mean", {"trim": 2}),
    ("krum", {"f": 2}),
    ("multi_krum", {"f": 2}),
])
def test_batched_matches_sequential_byzantine(aggregator, kwargs):
    nodes = [NodeSpec(f"h{i}") for i in range(6)] + [
        NodeSpec("adv0", byzantine="sign_flip", byzantine_scale=20.0),
        NodeSpec("adv1", byzantine="inner_product", byzantine_scale=10.0),
    ]
    cfg = SwarmConfig(aggregator=aggregator, agg_kwargs=kwargs)
    _assert_equivalent(*_run_both(nodes, cfg))


def test_batched_matches_sequential_noise_attack():
    """'noise' draws randomness — the shared fold_in key schedule makes the
    realization identical across engines."""
    nodes = [NodeSpec(f"h{i}") for i in range(7)] + \
        [NodeSpec("nz", byzantine="noise", byzantine_scale=5.0)]
    _assert_equivalent(*_run_both(nodes, SwarmConfig(aggregator="centered_clip")))


@pytest.mark.parametrize("compression,kwargs", [
    ("qsgd", {"levels": 64}),
    ("topk", {"k_frac": 0.25}),
])
def test_batched_matches_sequential_compressed_wire(compression, kwargs):
    nodes = [NodeSpec(f"h{i}") for i in range(6)]
    cfg = SwarmConfig(aggregator="mean", compression=compression,
                      compression_kwargs=kwargs)
    _assert_equivalent(*_run_both(nodes, cfg))


def test_batched_matches_sequential_verification():
    vcfg = VerificationConfig(p_check=0.4, stake=5.0, tolerance=1e-3)
    nodes = [NodeSpec(f"h{i}") for i in range(5)] + \
        [NodeSpec("cheat", byzantine="zero")]
    cfg = SwarmConfig(aggregator="mean", verification=vcfg)
    seq, bat = _run_both(nodes, cfg, rounds=20)
    _assert_equivalent(seq, bat)
    assert bat.slashed == {"cheat"}


# ------------------------- active-mask / churn ---------------------------------
def test_active_mask_tracks_join_leave():
    nodes = [NodeSpec("h0"), NodeSpec("h1"),
             NodeSpec("late", join_round=5),
             NodeSpec("early", leave_round=8),
             NodeSpec("window", join_round=3, leave_round=12)]
    cfg = SwarmConfig(aggregator="mean")
    seq, bat = _run_both(nodes, cfg, rounds=15)
    _assert_equivalent(seq, bat)
    expected = [sum(1 for n in nodes if n.active(r)) for r in range(15)]
    assert [r["n_active"] for r in bat.history] == expected
    # members outside their window never mint shares for those rounds
    assert bat.ledger.balances["late"] == pytest.approx(10.0)     # rounds 5..14
    assert bat.ledger.balances["early"] == pytest.approx(8.0)     # rounds 0..7


def test_batched_round_compiles_once_despite_churn():
    """The fixed-shape claim: join/leave/slash only flips mask bits — the
    jitted per-round path must not retrace."""
    loss_fn, params0, data_fn, _ = tiny_quadratic_problem(8)
    nodes = [NodeSpec(f"c{i}") for i in range(3)] + \
        [NodeSpec(f"w{i}", join_round=2 + i, leave_round=6 + 2 * i)
         for i in range(5)]
    swarm = make_swarm(loss_fn, params0, SGD(lr=0.1, momentum=0.0), nodes,
                       SwarmConfig(aggregator="centered_clip"), data_fn)
    for r in range(20):
        swarm.step(r)
    if not hasattr(swarm._round_fn, "_cache_size"):
        pytest.skip("this jax exposes no jit cache-size introspection — "
                    "the no-recompile claim is unverifiable here")
    assert swarm._round_fn._cache_size() == 1


def test_scanned_run_is_one_program_and_matches_step_loop():
    """run() with no eval_fn dispatches the scanned core: one compiled
    program for the whole run, identical history/ledger to the step loop."""
    loss_fn, params0, data_fn, _ = tiny_quadratic_problem(8)
    nodes = [NodeSpec(f"h{i}") for i in range(5)] + [
        NodeSpec("adv", byzantine="sign_flip", byzantine_scale=20.0),
        NodeSpec("late", join_round=4),
    ]
    cfg = SwarmConfig(aggregator="centered_clip")
    mk = lambda: make_swarm(loss_fn, params0, SGD(lr=0.1, momentum=0.0),
                            nodes, cfg, data_fn)
    scanned, stepped = mk(), mk()
    scanned.run(15)
    for r in range(15):
        stepped.step(r)
    assert [r["n_active"] for r in scanned.history] == \
        [r["n_active"] for r in stepped.history]
    np.testing.assert_allclose(
        [r["agg_norm"] for r in scanned.history],
        [r["agg_norm"] for r in stepped.history], rtol=1e-5, atol=1e-7)
    assert scanned.ledger.balances == pytest.approx(stepped.ledger.balances)
    if hasattr(scanned._round_fn, "_cache_size"):
        assert scanned._round_fn._cache_size() == 0     # never used per-round
        (scan_fn,) = scanned._scan_cache.values()
        assert scan_fn._cache_size() == 1


@pytest.mark.parametrize("needs", ["int", "bool_index", "dict_key", "bug"])
def test_run_steps_when_data_fn_needs_a_concrete_round(needs):
    """A data_fn that cannot be traced in the round (it needs the round as
    a Python value) makes run() fall back to the step loop, with the same
    history; any other error from it propagates."""
    loss_fn, params0, data_fn, _ = tiny_quadratic_problem(8)
    table = {r: r for r in range(6)}

    def concrete_data_fn(i, rnd):
        if needs == "int":
            rnd = int(rnd)
        elif needs == "bool_index":
            rnd = jnp.sum(jnp.arange(6)[jnp.arange(6) < rnd])
        elif needs == "dict_key":
            rnd = table[rnd]
        else:
            raise ValueError("a bug in the data_fn")
        return data_fn(i, rnd)

    nodes = [NodeSpec(f"h{i}") for i in range(4)]
    cfg = SwarmConfig(aggregator="mean")
    swarm = make_swarm(loss_fn, params0, SGD(lr=0.1, momentum=0.0), nodes,
                       cfg, concrete_data_fn)
    if needs == "bug":
        with pytest.raises(ValueError, match="a bug"):
            swarm.run(6)
        return
    swarm.run(6)
    assert not swarm._scan_cache                    # never took the scan
    stepped = make_swarm(loss_fn, params0, SGD(lr=0.1, momentum=0.0), nodes,
                         cfg, concrete_data_fn)
    for r in range(6):
        stepped.step(r)
    assert [r["agg_norm"] for r in swarm.history] == \
        [r["agg_norm"] for r in stepped.history]


def test_make_swarm_rejects_batched_data_fn_on_sequential():
    loss_fn, params0, data_fn, _ = tiny_quadratic_problem(8)
    with pytest.raises(ValueError, match="batched_data_fn"):
        make_swarm(loss_fn, params0, SGD(lr=0.1, momentum=0.0),
                   [NodeSpec("h0")], SwarmConfig(aggregator="mean"), data_fn,
                   engine="sequential",
                   batched_data_fn=batched_data_fn_for(data_fn, 1))


def test_no_active_nodes_raises():
    loss_fn, params0, data_fn, _ = tiny_quadratic_problem(8)
    nodes = [NodeSpec("late", join_round=5)]
    swarm = make_swarm(loss_fn, params0, SGD(lr=0.1, momentum=0.0), nodes,
                       SwarmConfig(aggregator="mean"), data_fn)
    with pytest.raises(RuntimeError, match="no active nodes"):
        swarm.step(0)


def test_batched_data_fn_matches_stacking():
    loss_fn, params0, data_fn, _ = tiny_quadratic_problem(8)
    nodes = [NodeSpec(f"h{i}") for i in range(6)]
    cfg = SwarmConfig(aggregator="mean")
    plain = make_swarm(loss_fn, params0, SGD(lr=0.1, momentum=0.0),
                       nodes, cfg, data_fn)
    fused = make_swarm(loss_fn, params0, SGD(lr=0.1, momentum=0.0),
                       nodes, cfg, data_fn,
                       batched_data_fn=batched_data_fn_for(data_fn, len(nodes)))
    plain.run(10)
    fused.run(10)
    np.testing.assert_allclose(
        [r["agg_norm"] for r in fused.history],
        [r["agg_norm"] for r in plain.history], rtol=1e-6)


# ------------------------- scenario registry -----------------------------------
def test_registry_has_the_documented_scenarios():
    assert set(list_scenarios()) == {
        "honest_baseline", "sign_flip_minority", "inner_product_collusion",
        "high_churn_elastic", "heterogeneous_speed", "compressed_wire",
        "audit_heavy", "derailment_stress",
        "gossip_ring_honest", "byzantine_neighborhood", "partitioned_swarm",
        "straggler_majority", "stale_poisoning", "async_churn",
        "custody_leech", "custody_churn_collapse",
        "economy_rational", "economy_sybil_adaptive",
    }


def test_get_scenario_unknown_name():
    with pytest.raises(KeyError, match="registered"):
        get_scenario("nope")


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_scenario_builds_and_runs(name):
    loss_fn, params0, data_fn, _ = tiny_quadratic_problem(8)
    scn = get_scenario(name)
    nodes, cfg = scn.build(n_nodes=8, seed=0)
    assert len(nodes) == 8
    assert len({n.node_id for n in nodes}) == 8          # ids unique
    assert any(n.active(0) and not n.byzantine for n in nodes)
    swarm = scn.build_swarm(loss_fn, params0, SGD(lr=0.1, momentum=0.0),
                            data_fn, n_nodes=8)
    swarm.run(12)
    assert len(swarm.history) == 12
    assert all(np.isfinite(r["agg_norm"]) for r in swarm.history)
    assert all(r["n_active"] >= 1 for r in swarm.history)


def test_honest_baseline_converges():
    loss_fn, params0, data_fn, _ = tiny_quadratic_problem(8)
    swarm = get_scenario("honest_baseline").build_swarm(
        loss_fn, params0, SGD(lr=0.1, momentum=0.0), data_fn, n_nodes=8)
    eval_fn = lambda p: loss_fn(p, data_fn(0, 10_000))
    losses = swarm.run(40, eval_fn=eval_fn)
    assert losses[-1] < 0.05 * losses[0]


def test_audit_heavy_slashes_freeloaders():
    loss_fn, params0, data_fn, _ = tiny_quadratic_problem(8)
    swarm = get_scenario("audit_heavy").build_swarm(
        loss_fn, params0, SGD(lr=0.1, momentum=0.0), data_fn, n_nodes=8)
    swarm.run(25)
    byz = {n.node_id for n in swarm.nodes if n.byzantine}
    assert swarm.slashed == byz                       # all freeloaders caught
    assert swarm.ledger.burned_stake > 0


def test_scenarios_scale_and_reproduce():
    scn = get_scenario("sign_flip_minority")
    for n in (4, 16, 33):
        nodes, _ = scn.build(n_nodes=n)
        assert len(nodes) == n
        assert sum(1 for x in nodes if x.byzantine) == max(1, n // 4)
    a, _ = scn.build(n_nodes=9, seed=3)
    b, _ = scn.build(n_nodes=9, seed=3)
    assert a == b


def test_scenario_config_is_immutable():
    cfg = get_scenario("audit_heavy").build(n_nodes=8)[1]
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg.aggregator = "mean"
