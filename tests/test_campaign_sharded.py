"""Mesh-sharded campaign engine (core/placement.py): sharded-vs-unsharded
conformance on a fake-device host mesh, the MeshPlan/mesh-factory
validation rules, and the batch/cache pspec dedupe regression.

The conformance suite runs in a subprocess with its own XLA_FLAGS
(``--xla_force_host_platform_device_count=8``, the test_launch.py pattern)
because the flag must be set before jax imports.  Inside it:

- a lane-sharded ``run_campaign`` (two lanes per device) is **bit-equal**
  both to the unsharded engine run on each device's block of lanes and to
  one unsharded program over all lanes — final params, the whole
  SwarmState, and every ``RoundRecord`` field (lanes are embarrassingly
  parallel, so sharding the run axis must not change a single training
  bit); the one exception is the final *eval* scalar, where XLA may fuse
  the eval matmul differently under a mesh — pinned 1-ULP allclose
  instead;
- a lane-sharded ``derailment.sweep`` matches the single-device sweep;
- a param-sharded (model-axis) plan is **allclose** (resharding reorders
  float reductions);
- the campaign program does **not recompile** under a mesh (second call,
  same shardings -> jit cache hit);
- a lane-sharded serving campaign returns bit-equal tokens;
- an indivisible plan raises the MeshPlan validation error.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.core.placement import MeshPlan, lane_axis_size
from repro.launch import mesh as mesh_lib
from repro.models.sharding import batch_pspecs, cache_pspecs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------- sharded conformance (subprocess) ---------------------
CAMPAIGN_SHARDED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import derailment, serving
from repro.core.placement import MeshPlan
from repro.core.scenarios import Regime, ServingGrid, SweepGrid
from repro.core.swarm import (NodeSpec, SwarmConfig, init_state,
                              lane_for_nodes, make_round_fn, run_campaign,
                              scan_rounds, stack_lanes)
from repro.optim.optimizer import SGD

assert len(jax.devices()) == 8

n_params = 64
key = jax.random.PRNGKey(42)
k1, k2 = jax.random.split(key)
target = jax.random.normal(k1, (n_params,))

def loss_fn(params, batch):
    return jnp.mean(jnp.square((batch["x"] @ (params["w"] - target))))

def data_fn(node_idx, rnd):
    k = jax.random.fold_in(jax.random.fold_in(k2, rnd), node_idx)
    return {"x": jax.random.normal(k, (16, n_params))}

def eval_fn(params):
    k = jax.random.fold_in(k2, 999)
    return loss_fn(params, {"x": jax.random.normal(k, (64, n_params))})

params0 = {"w": jnp.zeros((n_params,))}
opt = SGD(lr=0.1, momentum=0.0)

def assert_tree_bitequal(a, b, what):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(np.asarray(la), np.asarray(lb)), what

# -- 1) run_campaign: every output leaf bit-equal under lane sharding -----------
nodes = [NodeSpec("h%d" % i) for i in range(4)] + [
    NodeSpec("adv", byzantine="sign_flip", byzantine_scale=20.0)]
lanes = stack_lanes([lane_for_nodes(nodes, SwarmConfig(seed=s))
                     for s in range(16)])
def campaign(lanes, plan=None):
    return run_campaign(loss_fn, params0, opt, data_fn, lanes, rounds=4,
                        aggregator="centered_clip", eval_fn=eval_fn,
                        plan=plan)
def check_bitequal(out, ref, what):
    st_o, rec_o, fin_o = out
    st_r, rec_r, fin_r = ref
    for f in rec_r._fields:
        assert_tree_bitequal(getattr(rec_o, f), getattr(rec_r, f),
                             what + ": RoundRecord." + f)
    assert_tree_bitequal(st_o.params, st_r.params, what + ": state.params")
    assert_tree_bitequal(st_o, st_r, what + ": SwarmState")
    # the final eval matmul is the one op XLA may fuse differently under
    # a mesh: the training state is bit-exact, the eval is 1-ULP close
    assert np.allclose(np.asarray(fin_o), np.asarray(fin_r), rtol=1e-6), \
        (what, fin_o, fin_r)
plan = MeshPlan.for_lanes(16)
assert plan.lane_devices == 8, plan.mesh
out = campaign(lanes, plan)                 # two lanes per device
# against the unsharded engine on each device's block of lanes — the
# program every device runs — and against one program over all 16 lanes.
# (A block of ONE lane is not that program on the CPU: XLA drops the
# size-1 lane dim and the forward dot becomes matrix-vector, whose sums
# round differently from the matrix-matrix dot of a wider lane batch.)
blocks = [campaign(jax.tree.map(lambda x: x[i:i + 2], lanes))
          for i in range(0, 16, 2)]
check_bitequal(out, jax.tree.map(
    lambda *xs: np.concatenate([np.asarray(x) for x in xs]), *blocks),
    "per-device blocks")
check_bitequal(out, campaign(lanes), "one 16-lane program")
print("RUN_CAMPAIGN_BITEXACT_OK")

# -- 2) derailment.sweep: lane-sharded phase diagram bit-equal -------------------
grid = SweepGrid(name="t", description="",
                 regimes=(Regime("mean", "mean"),
                          Regime("cc", "centered_clip")),
                 n_honest=4, attacker_counts=(1, 2), seeds=(0, 1),
                 scales=(20.0,), rounds=4)
sref = derailment.sweep(loss_fn, params0, opt, data_fn, eval_fn, grid)
splan = MeshPlan.from_grid(grid)
sshd = derailment.sweep(loss_fn, params0, opt, data_fn, eval_fn, grid,
                        plan=splan)
assert sshd.n_devices == splan.n_devices > 1
for a, b in zip(sref.results, sshd.results):
    assert np.isclose(a.final_loss, b.final_loss, rtol=1e-6), (a, b)
    assert np.isclose(a.baseline_loss, b.baseline_loss, rtol=1e-6)
    assert a.attackers_slashed == b.attackers_slashed
print("SWEEP_BITEXACT_OK")

# -- 3) within-lane model-axis sharding: allclose --------------------------------
mplan = MeshPlan.from_grid(grid, model=2)
assert mplan.model_devices == 2, mplan.mesh
mshd = derailment.sweep(loss_fn, params0, opt, data_fn, eval_fn, grid,
                        plan=mplan)
for a, b in zip(sref.results, mshd.results):
    assert np.isclose(a.final_loss, b.final_loss, rtol=1e-5), (a, b)
    assert a.attackers_slashed == b.attackers_slashed
print("MODEL_SHARDED_ALLCLOSE_OK")

# -- 4) no recompile under the mesh ----------------------------------------------
round_fn = make_round_fn(loss_fn, opt, params0, 5,
                         aggregator="centered_clip")
state0 = init_state(params0, opt, 5)
def batch_fn(rnd):
    return jax.vmap(lambda i: data_fn(i, rnd))(jnp.arange(5))
def one_run(lane):
    return scan_rounds(round_fn, lane, state0, 4, batch_fn)
fn = jax.jit(jax.vmap(one_run, spmd_axis_name=plan.lanes_axis))
lanes_s = plan.place_lanes(lanes)
with plan.mesh:
    jax.block_until_ready(fn(lanes_s))
    jax.block_until_ready(fn(lanes_s))
if hasattr(fn, "_cache_size"):
    assert fn._cache_size() == 1, fn._cache_size()
print("NO_RECOMPILE_OK")

# -- 5) serving campaign: lane-sharded tokens bit-equal --------------------------
from repro.configs import get_config
from repro.models.model import build_model
cfg = get_config("protocol-125m").reduced()
model = build_model(cfg)
mparams = model.init(jax.random.PRNGKey(0))
sgrid = ServingGrid(name="t", description="", loads=(0.5, 1.0),
                    churn_rates=(0.0, 0.5), redundancies=(1, 2), seeds=(0,),
                    n_nodes=6, num_shards=8, n_requests=8, n_holders=3,
                    slots=3, prompt_len=6, max_new=4, steps=24)
vref = serving.sweep(model, mparams, sgrid)
vshd = serving.sweep(model, mparams, sgrid, plan=MeshPlan.from_grid(sgrid))
for a, b in zip(vref.cells, vshd.cells):
    assert (a.completed, a.tokens_served, a.availability) == \
           (b.completed, b.tokens_served, b.availability), (a, b)
print("SERVING_BITEXACT_OK")

# -- 6) indivisible lane counts raise the MeshPlan validation error --------------
from repro.launch.mesh import make_campaign_mesh
bad = MeshPlan(mesh=make_campaign_mesh(lanes=8))
lanes12 = stack_lanes([lane_for_nodes(nodes, SwarmConfig(seed=s))
                       for s in range(12)])
try:
    bad.place_lanes(lanes12)
except ValueError as e:
    assert "shard evenly" in str(e), e
else:
    raise AssertionError("indivisible lane count did not raise")
print("CAMPAIGN_SHARDED_OK")
"""


@pytest.mark.slow
def test_campaign_sharded_conformance_subprocess():
    """Lane sharding bit-exact, model sharding allclose, no recompiles,
    serving bit-exact, and the divisibility error — on 8 fake devices."""
    out = subprocess.run(
        [sys.executable, "-c", CAMPAIGN_SHARDED_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
        cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    for sentinel in ("RUN_CAMPAIGN_BITEXACT_OK", "SWEEP_BITEXACT_OK",
                     "MODEL_SHARDED_ALLCLOSE_OK", "NO_RECOMPILE_OK",
                     "SERVING_BITEXACT_OK", "CAMPAIGN_SHARDED_OK"):
        assert sentinel in out.stdout, (sentinel, out.stdout)


# ------------------------------ placement math ---------------------------------
def test_lane_axis_size_picks_largest_divisor():
    assert lane_axis_size(30, 8) == 6
    assert lane_axis_size(16, 8) == 8
    assert lane_axis_size(7, 8) == 7
    assert lane_axis_size(13, 8) == 1     # prime > devices: single device
    assert lane_axis_size(1, 8) == 1
    assert lane_axis_size(8, 1) == 1


def test_meshplan_for_lanes_single_device():
    plan = MeshPlan.for_lanes(10)         # host: however many devices exist
    assert plan.lane_devices >= 1
    assert 10 % plan.lane_devices == 0
    plan.validate_lanes(10)               # must accept its own lane count
    assert plan.n_devices == plan.lane_devices * plan.data_devices \
        * plan.model_devices


def test_meshplan_rejects_oversized_within_lane_factors():
    n = len(jax.devices())
    with pytest.raises(ValueError, match="devices"):
        MeshPlan.for_lanes(8, model=n + 1)


# ------------------------------ mesh factories ----------------------------------
def test_make_host_mesh_default_unchanged():
    mesh = mesh_lib.make_host_mesh()
    n = len(jax.devices())
    assert mesh.devices.shape == (n, 1)
    assert mesh.axis_names == mesh_lib.SINGLE_POD_AXES


def test_make_host_mesh_model_factor_validation():
    n = len(jax.devices())
    with pytest.raises(ValueError, match="divide"):
        mesh_lib.make_host_mesh(model=n + 1)
    with pytest.raises(ValueError):
        mesh_lib.make_host_mesh(model=0)
    mesh = mesh_lib.make_host_mesh(model=n)   # n always divides n
    assert mesh.devices.shape == (1, n)


def test_make_campaign_mesh_shapes_and_validation():
    n = len(jax.devices())
    mesh = mesh_lib.make_campaign_mesh()
    assert mesh.axis_names == mesh_lib.CAMPAIGN_AXES
    assert mesh.devices.shape == (n, 1, 1)
    sub = mesh_lib.make_campaign_mesh(lanes=1)   # subset mesh is legal
    assert sub.devices.shape == (1, 1, 1)
    with pytest.raises(ValueError, match="needs"):
        mesh_lib.make_campaign_mesh(lanes=n + 1)
    with pytest.raises(ValueError):
        mesh_lib.make_campaign_mesh(lanes=1, data=0)


# --------------------- pspec dedupe regression (satellite) ----------------------
def test_batch_pspecs_dedupes_data_axis():
    """Passing data_axis inside extra_batch_axes used to produce a
    PartitionSpec naming the axis twice — invalid under any mesh."""
    batch = {"x": jax.ShapeDtypeStruct((4, 8), jnp.float32),
             "positions": jax.ShapeDtypeStruct((3, 4, 8), jnp.int32)}
    specs = batch_pspecs(batch, {"data": 2, "pod": 2}, data_axis="data",
                         extra_batch_axes=("pod", "data"))
    assert specs["x"][0] == ("pod", "data")
    assert specs["positions"][1] == ("pod", "data")
    for spec in jax.tree.leaves(specs):
        flat = [a for part in spec if part is not None
                for a in (part if isinstance(part, tuple) else (part,))]
        assert len(flat) == len(set(flat)), spec


def test_cache_pspecs_dedupes_data_axis():
    cache = {"k": jax.ShapeDtypeStruct((2, 4, 8, 2, 4), jnp.float32),
             "v": jax.ShapeDtypeStruct((2, 4, 8, 2, 4), jnp.float32)}
    specs = cache_pspecs(cache, None, {"data": 2, "model": 1, "pod": 2},
                         data_axis="data", extra_batch_axes=("pod", "data"))
    for spec in jax.tree.leaves(specs):
        flat = [a for part in spec if part is not None
                for a in (part if isinstance(part, tuple) else (part,))]
        assert len(flat) == len(set(flat)), spec
    assert specs["k"][1] == ("pod", "data")
