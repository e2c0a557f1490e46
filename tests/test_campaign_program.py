"""The campaign program takes its initial state's params as an argument:
the lowered module holds no parameter-sized constant, one compiled program
serves other params, and a lane-only plan on four devices runs each
device's lanes with no collective and the records of the unplaced run."""
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.swarm import (NodeSpec, SwarmConfig, lane_for_nodes,
                              make_campaign_program, run_campaign,
                              stack_lanes)
from repro.core.verification import VerificationConfig
from repro.optim.optimizer import AdamW

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N = 4


def _problem(seed=0):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    params = {"w1": jax.random.normal(k1, (32, 48)),
              "w2": jax.random.normal(k2, (48, 16))}

    def loss_fn(p, batch):
        return jnp.mean(jnp.square(jnp.tanh(batch["x"] @ p["w1"]) @ p["w2"]))

    def data_fn(node, rnd):
        k = jax.random.fold_in(jax.random.fold_in(k3, rnd), node)
        return {"x": jax.random.normal(k, (8, 32))}

    return params, loss_fn, data_fn


def _lanes(seeds=(0, 1, 2, 3)):
    nodes = [NodeSpec(f"h{i}") for i in range(N - 1)] + [
        NodeSpec("adv", byzantine="inner_product", byzantine_scale=20.0)]
    cfg = lambda s: SwarmConfig(verification=VerificationConfig(p_check=0.5),
                                seed=s)
    return stack_lanes([lane_for_nodes(nodes, cfg(s)) for s in seeds])


def _constant_sizes(text):
    """Element counts of the lowered module's dense constants."""
    sizes = []
    for shape in re.findall(r"dense<.*?>\s*:\s*tensor<([0-9x]*)x?[a-z]", text):
        dims = [int(d) for d in shape.split("x") if d]
        sizes.append(int(np.prod(dims)) if dims else 1)
    return sizes


def test_lowered_campaign_holds_no_parameter_sized_constant():
    params, loss_fn, data_fn = _problem()
    lanes = _lanes()
    fn = make_campaign_program(loss_fn, params, AdamW(lr=0.01), data_fn,
                               lanes, rounds=2, aggregator="centered_clip",
                               verify=True)
    text = fn.lower(lanes, params).as_text()
    smallest_leaf = min(leaf.size for leaf in jax.tree.leaves(params))
    sizes = _constant_sizes(text)
    assert sizes and max(sizes) < smallest_leaf, max(sizes)


def test_one_program_serves_other_initial_params():
    params_a, loss_fn, data_fn = _problem(0)
    params_b = jax.tree.map(lambda a: a * 0.5 + 0.1, params_a)
    lanes = _lanes()
    opt = AdamW(lr=0.01)
    kw = dict(rounds=2, aggregator="centered_clip", verify=True)
    compiled = make_campaign_program(loss_fn, params_a, opt, data_fn, lanes,
                                     **kw).lower(lanes, params_a).compile()
    for params in (params_a, params_b):
        state, recs, _ = compiled(lanes, params)
        want_state, want_recs, _ = run_campaign(loss_fn, params, opt, data_fn,
                                                lanes, **kw)
        for got, want in zip(jax.tree.leaves((state.params, recs)),
                             jax.tree.leaves((want_state.params, want_recs))):
            np.testing.assert_array_equal(got, want)


FOUR_DEVICES_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import re, sys
import jax, numpy as np
sys.path.insert(0, os.path.join(os.environ["REPO"], "tests"))
from test_campaign_program import _lanes, _problem
from repro.core.placement import MeshPlan
from repro.core.swarm import make_campaign_program
from repro.optim.optimizer import AdamW

assert len(jax.devices()) == 4
params, loss_fn, data_fn = _problem()
lanes = _lanes()
opt = AdamW(lr=0.01)
kw = dict(rounds=3, aggregator="centered_clip", verify=True)
whole = make_campaign_program(loss_fn, params, opt, data_fn, lanes, **kw)(
    lanes, params)
plan = MeshPlan.for_lanes(4)
assert plan.lane_devices == 4
placed = plan.place_lanes(lanes)
fn = make_campaign_program(loss_fn, params, opt, data_fn, placed, plan=plan,
                           **kw)
with plan.mesh:
    compiled = fn.lower(placed, params).compile()
    state, recs, _ = compiled(placed, params)
text = compiled.as_text()
for op in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
           "collective-permute"):
    assert not re.search(r"\b" + op + r"(-start)?\(", text), op
assert len(state.params["w1"].sharding.device_set) == 4
# each device's lane is bit-equal to that lane run alone, unplaced
for k, seed in enumerate((0, 1, 2, 3)):
    one = _lanes((seed,))
    s1, r1, _ = make_campaign_program(loss_fn, params, opt, data_fn, one,
                                      **kw)(one, params)
    for a, b in zip(jax.tree.leaves((state.params, recs)),
                    jax.tree.leaves((s1.params, r1))):
        np.testing.assert_array_equal(np.asarray(a)[k], np.asarray(b)[0])
# and has the records of the one unplaced four-lane program
np.testing.assert_array_equal(recs.caught, whole[1].caught)
np.testing.assert_allclose(recs.agg_norm, whole[1].agg_norm, rtol=1e-5)
print("FOUR_DEVICE_LANES_OK")
"""


def test_four_device_lane_plan_runs_each_lane_apart():
    """Four lanes over four (fake CPU) devices, one each: each lane's records
    and final state equal that lane's run alone, its caught nodes those of
    the unplaced four-lane program, and the compiled program holds no
    collective."""
    out = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICES_SCRIPT], capture_output=True,
        text=True, timeout=600, cwd=REPO,
        env={**os.environ, "REPO": REPO,
             "PYTHONPATH": os.path.join(REPO, "src")})
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FOUR_DEVICE_LANES_OK" in out.stdout
