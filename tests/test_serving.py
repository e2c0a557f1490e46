"""Serving engine tests: scanned-decoder equivalence, continuous-batching
equivalence with per-request greedy decoding, custody-gated halting,
on-device credential admission, and the serving sweep."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import serving
from repro.core.scenarios import get_serving_grid, list_serving_grids
from repro.core.unextractable import ShardCustody, assign_matrix
from repro.models.model import build_model

_FAR = np.iinfo(np.int32).max


@pytest.fixture(scope="module")
def serve_model():
    cfg = get_config("protocol-125m").reduced(
        num_layers=1, d_model=32, num_heads=2, head_dim=16, d_ff=64,
        vocab_size=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


@pytest.fixture(scope="module")
def workload(serve_model):
    cfg, model, params = serve_model
    r, p = 6, 6
    prompts = jax.random.randint(jax.random.PRNGKey(1), (r, p), 0,
                                 cfg.vocab_size)
    plens = np.array([6, 4, 5, 6, 3, 4], np.int32)
    return prompts, plens


@pytest.fixture(scope="module")
def greedy_reference(serve_model, workload):
    """Per-request python-loop greedy outputs — the oracle."""
    _, model, params = serve_model
    prompts, plens = workload
    max_new = 5
    refs = []
    for r in range(prompts.shape[0]):
        gen, _ = serving.greedy_decode_loop(
            model, params, prompts[r:r + 1, :int(plens[r])], max_new)
        refs.append(np.asarray(gen[0]))
    return np.stack(refs), max_new


@pytest.fixture(scope="module")
def engine(serve_model, workload):
    _, model, _ = serve_model
    prompts, _ = workload
    cfg = serving.ServingConfig(slots=3, max_new=5, steps=44)
    return serving.ServingEngine(model, cfg, prompts)


# ---------------------- scanned greedy decoder ---------------------------------
def test_scanned_greedy_matches_loop(serve_model, workload):
    _, model, params = serve_model
    prompts, _ = workload
    g_scan, stats = serving.greedy_decode(model, params, prompts, 6)
    g_loop, _ = serving.greedy_decode_loop(model, params, prompts, 6)
    assert np.array_equal(np.asarray(g_scan), np.asarray(g_loop))
    assert g_scan.shape == (prompts.shape[0], 6)
    assert stats.tokens_out == 6


# ---------------------- continuous-batching equivalence ------------------------
@pytest.mark.parametrize("order", [
    [0, 1, 2, 3, 4, 5],          # arrival = request order
    [5, 3, 1, 0, 2, 4],          # shuffled admission order
    [2, 2, 2, 9, 9, 9],          # bursts (ties admitted in request order)
])
def test_engine_reproduces_per_request_greedy(serve_model, workload,
                                              greedy_reference, engine, order):
    """The engine's continuous batching — queueing on 3 slots, mixed
    prefill/decode slot states, slot recycling — must deliver exactly the
    tokens per-request greedy decoding delivers, whatever the admission
    order."""
    _, model, params = serve_model
    prompts, plens = workload
    refs, max_new = greedy_reference
    lane = serving.build_lane(
        n_requests=prompts.shape[0], prompt_lens=plens, max_new=5,
        steps=engine.cfg.steps, n_nodes=4, balances=[100.0, 100.0],
        fee=1.0, arrivals=np.asarray(order, np.int32))
    res = engine.run(params, lane)
    assert res.done.all(), "all requests must complete within the horizon"
    assert np.array_equal(res.tokens, refs)


def test_engine_recycles_slots_without_leaking_cache(serve_model, workload,
                                                     greedy_reference, engine):
    """6 requests through 3 slots forces every slot to serve two requests;
    outputs staying bit-exact proves the masked cache reset (pristine KV
    state per admission) works."""
    _, model, params = serve_model
    prompts, plens = workload
    refs, _ = greedy_reference
    lane = serving.build_lane(
        n_requests=prompts.shape[0], prompt_lens=plens, max_new=5,
        steps=engine.cfg.steps, n_nodes=4, balances=[100.0], fee=1.0,
        load=10.0)                          # everything arrives at step 0
    res = engine.run(params, lane)
    assert res.done.all()
    assert int(res.n_active.max()) == 3     # the pool really was saturated
    assert np.array_equal(res.tokens, refs)


def test_engine_honours_per_request_decode_budgets(serve_model, workload,
                                                   engine):
    """Per-request max_new: a slot retires the moment ITS request is done
    (no head-of-line padding to the batch max), and each request's tokens
    equal its own greedy decode of exactly that length."""
    _, model, params = serve_model
    prompts, plens = workload
    budgets = np.array([5, 2, 4, 1, 3, 5], np.int32)
    lane = serving.build_lane(
        n_requests=prompts.shape[0], prompt_lens=plens, max_new=budgets,
        steps=engine.cfg.steps, n_nodes=4, balances=[100.0], fee=1.0,
        load=10.0)
    res = engine.run(params, lane)
    assert res.done.all()
    for r in range(prompts.shape[0]):
        ref, _ = serving.greedy_decode(
            model, params, prompts[r:r + 1, :int(plens[r])], int(budgets[r]))
        np.testing.assert_array_equal(res.tokens[r, :budgets[r]],
                                      np.asarray(ref[0]))
        assert (res.tokens[r, budgets[r]:] == 0).all()   # untouched buffer


# ---------------------- custody coupling ---------------------------------------
def test_serving_halts_exactly_when_coverage_below_one(serve_model, workload,
                                                       engine):
    """Tokens are served on a step iff every shard has a live holder —
    serving halts exactly when coverage < 1, and resumes when the outage
    heals."""
    _, model, params = serve_model
    prompts, plens = workload
    custody = assign_matrix(4, 8, redundancy=1, seed=0, max_fraction=0.5)
    lane = serving.build_lane(
        n_requests=prompts.shape[0], prompt_lens=plens, max_new=5,
        steps=engine.cfg.steps, n_nodes=4, balances=[100.0], fee=1.0,
        load=0.5, custody=custody)
    # node 0 suffers an outage mid-horizon, then returns
    down_from = np.full(4, _FAR, np.int32)
    down_until = np.full(4, _FAR, np.int32)
    down_from[0], down_until[0] = 8, 20
    lane = lane._replace(node_down_from=jnp.asarray(down_from),
                         node_down_until=jnp.asarray(down_until))
    res = engine.run(params, lane)
    assert (res.live == (res.coverage >= 1.0)).all()
    assert not res.live[8:20].any()          # redundancy 1: outage kills it
    assert (res.new_tokens[~res.live] == 0).all()
    assert res.new_tokens[20:].sum() > 0     # serving resumed after the heal
    assert res.done.all()                    # and finished the backlog
    assert res.availability < 1.0


@pytest.mark.parametrize("departed", [[], ["n0"], ["n1", "n2"], ["n3"]])
def test_availability_agrees_with_tolerates_departures(serve_model, workload,
                                                       engine, departed):
    """A static departed set halts serving iff the custody engine says the
    swarm does not tolerate those departures."""
    _, model, params = serve_model
    prompts, plens = workload
    holds = assign_matrix(4, 8, redundancy=2, seed=0, max_fraction=0.5)
    custody = ShardCustody(8, 2, tuple(f"n{i}" for i in range(4)),
                           jnp.asarray(holds))
    lane = serving.build_lane(
        n_requests=prompts.shape[0], prompt_lens=plens, max_new=5,
        steps=engine.cfg.steps, n_nodes=4, balances=[100.0], fee=1.0,
        load=0.5, custody=holds)
    down_from = np.full(4, _FAR, np.int32)
    for d in departed:
        down_from[int(d[1:])] = 0
    lane = lane._replace(node_down_from=jnp.asarray(down_from))
    res = engine.run(params, lane)
    assert bool(res.live.all()) == custody.tolerates_departures(departed)


# ---------------------- credential admission -----------------------------------
def test_admission_gated_by_credentials_on_device(serve_model, workload,
                                                  engine):
    """Requests whose holder cannot afford the fee (strict
    balance - fee > min_shares, the Ledger.can_infer boundary) are never
    admitted; funded holders' requests all complete and pay their fees."""
    _, model, params = serve_model
    prompts, plens = workload
    # holder 0 funds requests 0/2/4; holder 1 (requests 1/3/5) holds exactly
    # one fee — strictly > 0 is required AFTER the spend, so only nothing
    # can be afforded: balance - fee == 0 is refused at the boundary
    lane = serving.build_lane(
        n_requests=prompts.shape[0], prompt_lens=plens, max_new=5,
        steps=engine.cfg.steps, n_nodes=4, balances=[100.0, 1.0], fee=1.0,
        load=10.0)
    res = engine.run(params, lane)
    assert res.admitted[0::2].all() and res.done[0::2].all()
    assert not res.admitted[1::2].any() and not res.done[1::2].any()
    np.testing.assert_allclose(res.balances, [97.0, 1.0])


def test_same_step_burst_cannot_overdraw_credentials(serve_model, workload,
                                                     engine):
    """Regression: funding used to be checked against step-start balances
    for every candidate independently, so a same-holder burst admitted in
    one step could drive the balance negative.  The k-th same-step sibling
    must afford k+1 fees — with balance 2.5 and fee 1 only two of three
    burst requests are ever served (0.5 left cannot strictly exceed 0
    after another fee)."""
    _, model, params = serve_model
    prompts, plens = workload
    lane = serving.build_lane(
        n_requests=prompts.shape[0], prompt_lens=plens, max_new=5,
        steps=engine.cfg.steps, n_nodes=4, balances=[2.5, 100.0], fee=1.0,
        holders=np.array([0, 1, 0, 1, 0, 1], np.int32),
        load=10.0)                          # everything arrives at step 0
    res = engine.run(params, lane)
    assert res.done[1::2].all()             # holder 1: all served
    assert int(res.admitted[0::2].sum()) == 2   # holder 0: exactly two
    assert not res.done[4]                  # the third sibling never runs
    np.testing.assert_allclose(res.balances, [0.5, 97.0])
    assert res.balances.min() >= 0.0
    # refused waiters are not demand: the lane still reads fully available
    assert res.availability == 1.0


def test_admission_is_fifo_by_arrival_not_request_index(serve_model):
    """Regression: admission used to rank waiting requests by request
    index, so a later-arriving low-index request preempted an
    earlier-arriving high-index one.  On a 1-slot engine with a horizon
    that only fits two requests, the long-waiting request must win the
    contested slot."""
    cfg, model, params = serve_model
    prompts = jax.random.randint(jax.random.PRNGKey(3), (3, 3), 0,
                                 cfg.vocab_size)
    scfg = serving.ServingConfig(slots=1, max_new=2, steps=10)
    engine = serving.ServingEngine(model, scfg, prompts)
    lane = serving.build_lane(
        n_requests=3, prompt_lens=np.full(3, 3, np.int32), max_new=2,
        steps=scfg.steps, n_nodes=2, balances=[100.0], fee=1.0,
        arrivals=np.array([5, 0, 0], np.int32))
    res = engine.run(params, lane)
    # r1 serves first (steps 0-4); at step 5 both r0 (arrived 5) and r2
    # (arrived 0, waited 5 steps) contend — FIFO admits r2
    assert res.done.tolist() == [False, True, True]


def test_engine_validates_lane_shapes(serve_model, workload, engine):
    """Prompts longer than the buffer (or a mis-shaped prompts override)
    would silently re-feed the last buffered token — refuse them."""
    _, model, params = serve_model
    prompts, plens = workload
    bad = plens.copy()
    bad[0] = prompts.shape[1] + 3
    lane = serving.build_lane(
        n_requests=prompts.shape[0], prompt_lens=bad, max_new=5,
        steps=engine.cfg.steps, n_nodes=4, balances=[100.0], fee=1.0,
        load=1.0)
    with pytest.raises(ValueError, match="prompt buffer width"):
        engine.run(params, lane)
    good = serving.build_lane(
        n_requests=prompts.shape[0], prompt_lens=plens, max_new=5,
        steps=engine.cfg.steps, n_nodes=4, balances=[100.0], fee=1.0,
        load=1.0)
    with pytest.raises(ValueError, match="max_new"):
        engine.run(params, good._replace(
            max_new=jnp.full((prompts.shape[0],), 99, jnp.int32)))
    # a zero decode budget would never satisfy the retirement condition
    # and wedge its slot for the whole horizon
    with pytest.raises(ValueError, match="wedge"):
        engine.run(params, good._replace(
            max_new=jnp.zeros((prompts.shape[0],), jnp.int32)))
    with pytest.raises(ValueError, match="compiled shape"):
        engine.run(params, good, prompts=jnp.zeros((2, 2), jnp.int32))


# ---------------------- the serving campaign -----------------------------------
def test_serving_sweep_one_program_and_table(serve_model):
    _, model, params = serve_model
    grid = get_serving_grid("serving_smoke")
    res = serving.sweep(model, params, grid)
    assert res.n_programs == 1
    assert res.n_runs == grid.n_points == len(res.cells)
    table = res.availability_table()
    assert "load=" in table and "S=served" in table
    # zero churn at any redundancy serves everything with full availability
    for c in res.cells:
        if c.churn_rate == 0 and c.coalition_fraction == 0:
            assert c.regime == "served" and c.availability == 1.0
    # the sweep exercises all three grid axes
    assert {c.redundancy for c in res.cells} == set(grid.redundancies)
    assert {c.load for c in res.cells} == set(grid.loads)
    assert {c.churn_rate for c in res.cells} == set(grid.churn_rates)


def test_sweep_lane_matches_single_run(serve_model):
    """Lane k of the vmapped campaign reproduces the single-lane run —
    the serving twin of the campaign-vs-Swarm equivalence tests."""
    _, model, params = serve_model
    grid = get_serving_grid("serving_smoke")
    prompts = jax.random.randint(jax.random.PRNGKey(0),
                                 (grid.n_requests, grid.prompt_len), 0,
                                 model.cfg.vocab_size)
    res = serving.sweep(model, params, grid, prompts=prompts)
    # rebuild lane 2 (load, churn, red ordering as in sweep) by hand
    cell = res.cells[2]
    cfg = serving.ServingConfig(slots=grid.slots, max_new=grid.max_new,
                                steps=grid.steps)
    plens = (grid.prompt_len // 2 + np.arange(grid.n_requests)
             % (grid.prompt_len - grid.prompt_len // 2 + 1)).astype(np.int32)
    lane = serving.build_lane(
        n_requests=grid.n_requests, prompt_lens=plens,
        max_new=grid.max_new, steps=grid.steps,
        n_nodes=grid.n_nodes,
        balances=np.full(grid.n_holders,
                         grid.fee * grid.n_requests + 1.0, np.float32),
        fee=grid.fee, load=cell.load,
        custody=assign_matrix(grid.n_nodes, grid.num_shards,
                              cell.redundancy, seed=0,
                              max_fraction=grid.max_fraction),
        churn_rate=cell.churn_rate, coalition_fraction=cell.coalition_fraction,
        defect_step=grid.defect_step, seed=cell.seed)
    single = serving.ServingEngine(model, cfg, prompts).run(params, lane)
    assert int(single.done.sum()) == cell.completed
    assert single.tokens_served == cell.tokens_served
    assert single.availability == pytest.approx(cell.availability)


def test_serving_grids_registered():
    names = list_serving_grids()
    assert {"serving_frontier", "serving_coalition",
            "serving_smoke"} <= set(names)
    with pytest.raises(KeyError, match="serving_smoke"):
        get_serving_grid("nope")


def test_compile_ahead_serves_like_the_jitted_program(serve_model, workload):
    """``ServingEngine.compile`` compiles ``run``'s program before the first
    episode: ``run`` then calls that executable, compiles nothing, and
    serves exactly what the jitted program serves."""
    _, model, params = serve_model
    prompts, plens = workload
    cfg = serving.ServingConfig(slots=3, max_new=4, steps=30)
    lanes = [serving.build_lane(n_requests=6, prompt_lens=plens,
                                max_new=[4, 2, 3, 1, 4, 2], steps=30,
                                n_nodes=2, balances=[50.0], load=load)
             for load in (0.5, 2.0)]
    want = [serving.ServingEngine(model, cfg, prompts).run(params, lane)
            for lane in lanes]
    engine = serving.ServingEngine(model, cfg, prompts)
    engine.compile(params, lanes[0])
    got = [engine.run(params, lane) for lane in lanes]
    # the jitted program was lowered, never called: nothing traced or
    # compiled in ``run``
    assert engine.program(has_custody=False, vmapped=False)._cache_size() == 0
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.tokens, w.tokens)
        np.testing.assert_array_equal(g.n_active, w.n_active)


def test_compile_ahead_serves_other_shapes_with_the_jitted_program(
        serve_model, workload):
    """An executable compiled ahead serves only its own argument shapes: a
    lane of another signature (here one with custody) takes the jitted
    program and serves what a fresh engine serves.  A dense model's
    episode carries no per-step counters."""
    _, model, params = serve_model
    prompts, plens = workload
    cfg = serving.ServingConfig(slots=3, max_new=4, steps=30)
    kw = dict(n_requests=6, prompt_lens=plens, max_new=[4, 2, 3, 1, 4, 2],
              steps=30, n_nodes=2, balances=[50.0], load=0.5)
    plain = serving.build_lane(**kw)
    held = serving.build_lane(**kw, custody=np.ones((2, 2), bool))
    engine = serving.ServingEngine(model, cfg, prompts)
    engine.compile(params, plain)
    got = engine.run(params, held)
    want = serving.ServingEngine(model, cfg, prompts).run(params, held)
    np.testing.assert_array_equal(got.tokens, want.tokens)
    np.testing.assert_array_equal(got.coverage, want.coverage)
    assert engine.program(has_custody=True, vmapped=False)._cache_size() == 1
    assert got.stats == {} and engine.run(params, plain).stats == {}

