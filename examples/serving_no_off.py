"""The no-off problem at inference time (§4.1 × §5): who can refuse or
halt *serving* when custody holders churn or defect?

One ``serving.sweep`` call compiles the whole serving phase diagram —
(load × churn rate × custody redundancy × coalition fraction × seed),
every lane a full continuous-batching run with admission queues, per-slot
KV caches, on-device credential fees, and coverage-gated availability —
into a single device program: the custody matrix, the outage windows, and
the arrival schedule all ride as traced lanes, exactly like the training
campaign's mixing/custody lanes.

    PYTHONPATH=src python examples/serving_no_off.py            # both grids
    PYTHONPATH=src python examples/serving_no_off.py --smoke    # tiny
"""
import argparse

import jax

from repro.configs import get_config
from repro.core import serving
from repro.core.scenarios import get_serving_grid
from repro.models.model import build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="the 8-lane serving_smoke grid only")
    args = ap.parse_args()

    cfg = get_config("protocol-125m").reduced(
        num_layers=1, d_model=32, num_heads=2, head_dim=16, d_ff=64,
        vocab_size=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    grids = (["serving_smoke"] if args.smoke
             else ["serving_frontier", "serving_coalition"])
    for name in grids:
        grid = get_serving_grid(name)
        print(f"\n== {name}: {grid.n_points} serving lanes as one compiled "
              f"program ==")
        print(f"   ({grid.slots} slots, {grid.n_requests} requests/lane, "
              f"{grid.num_shards} shards over {grid.n_nodes} nodes, "
              f"horizon {grid.steps} steps)")
        res = serving.sweep(model, params, grid)
        print(f"   {res.n_runs} lanes in {res.n_programs} program, "
              f"{res.wall_s:.1f}s -> {res.runs_per_s:.1f} lanes/s, "
              f"{res.tok_per_s:.0f} tok/s aggregate")
        print(res.availability_table())

    print(
        "\nReading: a Protocol Model's inference inherits an off-switch "
        "nobody designed.  Serving halts exactly when custody coverage "
        "drops below 1 — with a shard missing there is no model to run, "
        "so whoever holds a shard's LAST live copy holds a serving veto.  "
        "At redundancy 1 every holder is such a veto (churn alone halts "
        "serving); redundancy buys availability under churn (gaps heal -> "
        "'degraded', not 'halted') but widens the coalition needed to "
        "refuse serving — the same redundancy dial that §4.1 trades "
        "against extractability.  Load, by contrast, only backlogs: "
        "overload delays requests, it cannot halt the swarm.  The no-off "
        "property cuts both ways at inference: nobody can switch the "
        "model off unilaterally at high redundancy, and nobody can *keep "
        "it on* against a shard-covering coalition's exit.")


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
