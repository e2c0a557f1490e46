"""The No-Off Problem (§5.5), measured: can a derailment attack — the one
*digital* emergency brake — actually halt a protocol-learning run?

One ``derailment.sweep`` call compiles the whole phase diagram — attacker
fraction × seed for every (aggregator, verification) regime, honest
baselines included — into a single device program (``lax.scan`` over
rounds, ``vmap`` over runs) on a real (small) LM, then prints the paper's
qualitative table with numbers attached, plus the attack's price tag.

    PYTHONPATH=src python examples/derailment_no_off.py
"""
import argparse

from common import small_lm_problem

from repro.core.derailment import attack_cost, no_off_report, sweep
from repro.core.scenarios import Regime, SweepGrid
from repro.core.verification import VerificationConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--seeds", type=int, default=1,
                    help="seeds per phase-diagram cell")
    args = ap.parse_args()

    # small enough that the whole phase diagram (counts x regimes lanes,
    # each lane an 18-node swarm) sweeps in minutes on a 2-core CPU box
    loss_fn, params, data_fn, eval_fn, opt = small_lm_problem()
    n_honest = 8

    vcfg = VerificationConfig(p_check=0.5, stake=10.0, tolerance=1e-3)
    grid = SweepGrid(
        name="no_off_lm",
        description="§5.5 table on a real (small) LM",
        regimes=(Regime("mean", "mean"),
                 Regime("centered_clip", "centered_clip"),
                 Regime("mean+verified", "mean", verification=vcfg)),
        n_honest=n_honest,
        attacker_counts=(1, 4, 10),
        seeds=tuple(range(args.seeds)),
        scales=(20.0,),
        rounds=args.rounds,
    )

    print(f"running the {grid.n_points}-point derailment phase diagram as "
          "one compiled program (this trains a small LM "
          f"{grid.n_points + len(grid.seeds)} times on device)...")
    res = sweep(loss_fn, params, opt, data_fn, eval_fn, grid)
    print(f"  {res.n_runs} runs (incl {len(grid.seeds)} shared honest "
          f"baselines) in {res.n_programs} program, {res.wall_s:.1f}s "
          f"-> {res.runs_per_s:.2f} runs/s")

    print("\n== §5.5 phase diagram (derailed seeds / total, s = attackers "
          "slashed) ==")
    print(res.phase_table())

    print("\n== per-cell detail ==")
    print(no_off_report(sorted(res.results,
                               key=lambda r: (r.regime, r.attacker_fraction))))

    print("\n== attack economics ==")
    for n_attack in (4, 10):
        c_unv = attack_cost(n_attack, args.rounds, compute_cost_per_round=1.0,
                            verification=None)
        c_ver = attack_cost(n_attack, args.rounds, compute_cost_per_round=1.0,
                            verification=vcfg)
        print(f"  {n_attack:2d} attackers x {args.rounds} rounds: "
              f"unverified={c_unv:.0f} units, verified={c_ver:.0f} units "
              f"(stakes burned)")

    print("\nReading: under mean aggregation the off-switch works (and so "
          "does any vandal); robust aggregation raises the bar to the "
          "breakdown point; near-perfect verification neutralizes it — "
          "the paper's conclusion that only physical intervention remains.")


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
