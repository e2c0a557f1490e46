"""Time to first token from the engine's per-step admission counts, against
a stepped reference: the engine's own step function driven one step at a
time, watching each request's slot."""
import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic
from bench.drivers import program_model
from bench.drivers.serve_engine import first_token_steps
from bench.tests import tiny


def test_first_token_steps_match_a_stepped_engine():
    from repro.core.serving import (ServingConfig, ServingEngine, build_lane,
                                    make_serve_step)
    t = dict(tiny.CHAT, slots=3, horizon=80)
    config = {"name": "tiny", "source": "test", "family": "dense",
              "model": tiny.TINY_LM, "param_count": tiny.param_count(tiny.TINY_LM)}
    model = program_model(config)
    params = model.init(jax.random.PRNGKey(0))
    ep = traffic.serve_episode(t, 256, 5, 0)
    n = len(ep["arrivals"])
    cfg = ServingConfig(slots=t["slots"], max_new=t["max_new"]["max"],
                        steps=t["horizon"], cache_len=t["cache_len"])
    lane = build_lane(n_requests=n, prompt_lens=ep["prompt_lens"],
                      max_new=ep["max_new"], steps=cfg.steps, n_nodes=8,
                      arrivals=ep["arrivals"], balances=[n + 1.0] * 4)
    prompts = jnp.asarray(ep["prompts"])
    res = ServingEngine(model, cfg, prompts).run(params, lane)
    assert res.done.all()
    derived = first_token_steps(ep["arrivals"], ep["prompt_lens"],
                                res.n_admitted)

    step, init = make_serve_step(model, cfg, prompts.shape, has_custody=False)
    step = jax.jit(step)
    state, first = init(lane), np.full(n, -1)
    plens = ep["prompt_lens"]
    for s in range(cfg.steps):
        state, _ = step(params, prompts, lane, state, s)
        slot_req, slot_t = np.asarray(state.slot_req), np.asarray(state.slot_t)
        done = np.asarray(state.done)
        for r in np.flatnonzero(first < 0):
            in_slot = (slot_req == r) & (slot_t >= plens[r])
            if in_slot.any() or done[r]:
                first[r] = s
    np.testing.assert_array_equal(derived, first - ep["arrivals"] + 1)
    assert derived.min() >= plens.min()
