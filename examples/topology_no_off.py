"""The No-Off Problem without the center (§3.2 × §5.5): when aggregation
itself is decentralized — per-node replicas, neighborhood robust
aggregation over a gossip graph, no global aggregate — at what spectral
gap does local robust aggregation stop resisting derailment?

One ``derailment.sweep`` call compiles the whole decentralized phase
diagram — (topology × attacker fraction × seed) for every aggregation
regime, honest baselines trained per topology — into a single device
program: the mixing matrix rides as a traced lane of the campaign.

    PYTHONPATH=src python examples/topology_no_off.py           # small LM
    PYTHONPATH=src python examples/topology_no_off.py --tiny    # quadratic
"""
import argparse

from repro.core import topology
from repro.core.derailment import no_off_report, sweep
from repro.core.scenarios import Regime, SweepGrid

TOPOLOGIES = ("ring", "clustered", "random_regular", "fully_connected")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=25)
    ap.add_argument("--seeds", type=int, default=2,
                    help="seeds per phase-diagram cell")
    ap.add_argument("--tiny", action="store_true",
                    help="convex toy problem instead of the small LM")
    args = ap.parse_args()

    from common import small_lm_problem, tiny_quadratic_problem
    loss_fn, params, data_fn, eval_fn, opt = (
        tiny_quadratic_problem() if args.tiny else small_lm_problem())
    n_honest = 8
    grid = SweepGrid(
        name="no_off_decentralized",
        description="§5.5 without the center",
        regimes=(Regime("mean", "mean"),
                 Regime("centered_clip", "centered_clip")),
        topologies=TOPOLOGIES,
        n_honest=n_honest,
        attacker_counts=(1, 4, 8),
        seeds=tuple(range(args.seeds)),
        scales=(20.0,),
        rounds=args.rounds,
    )

    n_total = n_honest + max(grid.attacker_counts)
    print("spectral gaps at swarm size", n_total, "(higher = faster mixing):")
    for t in TOPOLOGIES:
        gap = topology.spectral_gap(topology.mixing_matrix(t, n_total))
        print(f"  {t:16s} gap={gap:.4f}")

    print(f"\nrunning the {grid.n_points}-point decentralized phase diagram "
          f"as one compiled program ({grid.n_points + len(TOPOLOGIES) * len(grid.seeds)}"
          " decentralized runs incl per-topology baselines)...")
    res = sweep(loss_fn, params, opt, data_fn, eval_fn, grid)
    print(f"  {res.n_runs} runs in {res.n_programs} program, "
          f"{res.wall_s:.1f}s -> {res.runs_per_s:.2f} runs/s")

    print("\n== decentralized §5.5 phase diagram "
          "(derailed seeds / total, s = attackers slashed) ==")
    print(res.phase_table())

    print("\n== per-cell detail ==")
    print(no_off_report(sorted(
        res.results, key=lambda r: (r.regime, r.topology, r.attacker_fraction))))

    print("\nReading: the centralized breakdown point is a *global* "
          "fraction, but a sparse graph is attacked neighborhood by "
          "neighborhood — the same coalition that CenteredClip shrugs off "
          "on the complete graph can exceed the local breakdown point of a "
          "low-gap ring or near-partitioned swarm and let the poison "
          "gossip outward.  Robust aggregation's resistance to derailment "
          "degrades with the spectral gap: decentralization widens the "
          "no-off gap the paper warns about.")


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
