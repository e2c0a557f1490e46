"""The engine's own admission step (``ServeResult.admit_step``) against the
harness's FIFO derivation of each request's first-token step."""
import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic
from bench.drivers import program_model
from bench.drivers.serve_engine import first_token_steps
from bench.tests import tiny


def _tiny_model():
    config = {"name": "tiny", "source": "test", "family": "dense",
              "model": tiny.TINY_LM,
              "param_count": tiny.param_count(tiny.TINY_LM)}
    model = program_model(config)
    return model, model.init(jax.random.PRNGKey(0))


def test_admit_step_is_what_first_token_steps_derives():
    """On the chat mix in miniature, where every request is funded, the
    engine's own admission step gives each request's first token where the
    FIFO derivation puts it."""
    from repro.core.serving import ServingConfig, ServingEngine, build_lane
    t = dict(tiny.CHAT, slots=3, horizon=80)
    model, params = _tiny_model()
    ep = traffic.serve_episode(t, 256, 7, 0)
    n = len(ep["arrivals"])
    cfg = ServingConfig(slots=t["slots"], max_new=t["max_new"]["max"],
                        steps=t["horizon"], cache_len=t["cache_len"])
    lane = build_lane(n_requests=n, prompt_lens=ep["prompt_lens"],
                      max_new=ep["max_new"], steps=cfg.steps, n_nodes=8,
                      arrivals=ep["arrivals"], balances=[n + 1.0] * 4)
    res = ServingEngine(model, cfg, jnp.asarray(ep["prompts"])).run(params,
                                                                   lane)
    assert res.done.all() and (res.admit_step >= ep["arrivals"]).all()
    np.testing.assert_array_equal(
        res.admit_step + ep["prompt_lens"] - 1 - ep["arrivals"] + 1,
        first_token_steps(ep["arrivals"], ep["prompt_lens"], res.n_admitted))


def test_admit_step_where_a_holder_cannot_fund_a_request():
    """Holder 0 can pay one fee, so its second request is never admitted;
    the FIFO derivation hands that request the next admission and shifts
    every later one, the engine's counter does not."""
    from repro.core.serving import ServingConfig, ServingEngine, build_lane
    model, params = _tiny_model()
    plens = np.array([3, 3, 3])
    prompts = jnp.asarray(np.arange(3 * 12).reshape(3, 12) % 256, jnp.int32)
    cfg = ServingConfig(slots=2, max_new=2, steps=12, cache_len=20)
    arrivals = np.array([0, 0, 1])
    lane = build_lane(n_requests=3, prompt_lens=plens, max_new=2,
                      steps=cfg.steps, n_nodes=8, arrivals=arrivals,
                      holders=[0, 0, 1], balances=[1.5, 10.0], fee=1.0)
    res = ServingEngine(model, cfg, prompts).run(params, lane)
    np.testing.assert_array_equal(res.admitted, [True, False, True])
    np.testing.assert_array_equal(res.admit_step, [0, -1, 1])
    np.testing.assert_array_equal(res.n_admitted[:3], [1, 1, 0])
    assert res.tokens_served == 4
    derived = first_token_steps(arrivals, plens, res.n_admitted)
    counted = res.admit_step + plens - 1 - arrivals + 1
    assert derived[0] == counted[0]
    # the refused request takes step 1's admission, request 2's, and the
    # served request 2 is pushed past the horizon
    assert derived[1] == (1 + plens[1] - 1) - arrivals[1] + 1
    assert derived[2] > cfg.steps > counted[2]
