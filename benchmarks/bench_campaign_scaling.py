"""Weak scaling of the mesh-sharded campaign engine (``core/placement.py``).

A §5.5 phase diagram is ONE compiled program — so the cost that matters is
the end-to-end campaign wall (compile + execute, the same clock
``derailment.sweep`` reports as runs/s).  This bench holds the per-device
lane count fixed and grows the device count: the single-device engine runs
L lanes, the 8-fake-device mesh (``--xla_force_host_platform_device_count``,
the ``launch/dryrun.py`` pattern) runs 8·L lanes under a
``MeshPlan`` — same program, lane axis sharded, bit-exact (pinned in
``tests/test_campaign_sharded.py``).  **Weak scaling** = total lanes/s vs
the single-device engine; the acceptance floor is ≥ 4x at 8 devices.

Every measurement runs in a fresh CPU subprocess: XLA_FLAGS must be set
before jax imports, timings must include compile (a sweep is a one-shot
program), and the fake devices are the host platform's — never a chip.
These are CPU figures, not a chip measurement.

CLI:  ``python benchmarks/bench_campaign_scaling.py [--tiny] [--json F]``
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from benchmarks.common import Row

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(os.path.dirname(_HERE), "src")

#: filled by run() for the --json artifact
LAST_SCALING_META: dict = {}

_WORKER = r"""
import json, os, sys, time
cfg = json.loads(sys.argv[1])
flags = "--xla_force_host_platform_device_count=%d" % cfg["devices"]
inherited = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
os.environ["XLA_FLAGS"] = " ".join([flags] + inherited)
import jax
import jax.numpy as jnp
from repro.core.placement import MeshPlan
from repro.core.swarm import (NodeSpec, SwarmConfig, lane_for_nodes,
                              run_campaign, stack_lanes)
from repro.optim.optimizer import SGD

n_params = cfg["n_params"]
key = jax.random.PRNGKey(42)
k1, k2 = jax.random.split(key)
target = jax.random.normal(k1, (n_params,))

def loss_fn(params, batch):
    return jnp.mean(jnp.square((batch["x"] @ (params["w"] - target))))

def data_fn(node_idx, rnd):
    k = jax.random.fold_in(jax.random.fold_in(k2, rnd), node_idx)
    return {"x": jax.random.normal(k, (16, n_params))}

params0 = {"w": jnp.zeros((n_params,))}
opt = SGD(lr=0.1, momentum=0.0)
nodes = [NodeSpec("h%d" % i) for i in range(cfg["nodes"])]
lanes = stack_lanes([lane_for_nodes(nodes, SwarmConfig(seed=s))
                     for s in range(cfg["lanes"])])
plan = (MeshPlan.for_lanes(cfg["lanes"], model=cfg["model"])
        if cfg["devices"] > 1 else None)

def campaign():
    out = run_campaign(loss_fn, params0, opt, data_fn, lanes,
                       rounds=cfg["rounds"], aggregator="centered_clip",
                       plan=plan)
    jax.block_until_ready(out)

t0 = time.perf_counter()
campaign()
cold_s = time.perf_counter() - t0          # compile + run: the sweep cost
t0 = time.perf_counter()
campaign()
warm_s = time.perf_counter() - t0          # program-cache hit: trace + run
print(json.dumps({"cold_s": cold_s, "warm_s": warm_s,
                  "devices": len(jax.devices()),
                  "mesh": str(plan.mesh) if plan else "none"}))
"""


def _measure(devices: int, lanes: int, *, rounds: int, n_params: int,
             nodes: int, model: int = 1) -> dict:
    cfg = {"devices": devices, "lanes": lanes, "rounds": rounds,
           "n_params": n_params, "nodes": nodes, "model": model}
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"             # fake host devices, never a chip
    proc = subprocess.run([sys.executable, "-c", _WORKER, json.dumps(cfg)],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    if proc.returncode != 0:
        raise RuntimeError(f"scaling worker failed for {cfg}:\n{proc.stderr}")
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    out.update(cfg)
    return out


def run(tiny: bool = False) -> list:
    per_dev = 4 if tiny else 8              # lanes per device (weak scaling)
    rounds = 6 if tiny else 10
    n_params = 64 if tiny else 256
    nodes = 6
    n_dev = 8

    single = _measure(1, per_dev, rounds=rounds, n_params=n_params,
                      nodes=nodes)
    meshed = _measure(n_dev, n_dev * per_dev, rounds=rounds,
                      n_params=n_params, nodes=nodes)
    # within-lane model axis: (4, 1, 2) mesh — lowers + runs on old jax
    model2 = _measure(n_dev, (n_dev // 2) * per_dev, rounds=rounds,
                      n_params=n_params, nodes=nodes, model=2)

    def lanes_per_s(m, clock="cold_s"):
        return m["lanes"] / max(m[clock], 1e-9)

    ratio = lanes_per_s(meshed) / max(lanes_per_s(single), 1e-9)
    warm_ratio = lanes_per_s(meshed, "warm_s") / max(
        lanes_per_s(single, "warm_s"), 1e-9)

    global LAST_SCALING_META
    LAST_SCALING_META = {"single": single, "meshed": meshed, "model2": model2,
                         "weak_scaling": ratio, "warm_scaling": warm_ratio,
                         "per_device_lanes": per_dev, "rounds": rounds}

    rows: list[Row] = [
        (f"campaign_scaling.1dev.L{single['lanes']}",
         single["cold_s"] * 1e6,
         f"{lanes_per_s(single):.1f} lanes/s end-to-end "
         f"(warm {lanes_per_s(single, 'warm_s'):.1f})"),
        (f"campaign_scaling.{n_dev}dev.L{meshed['lanes']}",
         meshed["cold_s"] * 1e6,
         f"{lanes_per_s(meshed):.1f} lanes/s end-to-end "
         f"(warm {lanes_per_s(meshed, 'warm_s'):.1f}) mesh={meshed['mesh']}"),
        (f"campaign_scaling.{n_dev}dev.model2.L{model2['lanes']}",
         model2["cold_s"] * 1e6,
         f"{lanes_per_s(model2):.1f} lanes/s end-to-end "
         f"mesh={model2['mesh']}"),
        ("campaign_scaling.weak_scaling", 0.0,
         f"x{ratio:.2f} total lanes/s vs 1dev at {per_dev} lanes/device "
         f"(>=4x target; warm-program x{warm_ratio:.2f})"),
    ]
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: 4 lanes/device, 6 rounds")
    ap.add_argument("--json", default=None, metavar="FILE",
                    help="dump rows + scaling metadata as JSON")
    args = ap.parse_args()

    rows = run(tiny=args.tiny)
    emit(rows)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": [{"name": n, "us_per_call": us, "derived": d}
                                for n, us, d in rows],
                       "scaling": LAST_SCALING_META}, f, indent=2)
