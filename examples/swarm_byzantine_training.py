"""END-TO-END DRIVER: train the paper's demonstrator LM across a simulated
incentivized swarm on the batched vmap/jit engine.

The default "showcase" roster exercises all five §3 properties + §4
incentives at once:

  - 10 heterogeneous nodes (speeds 0.5-3x), elastic (2 join late, 1 leaves),
  - 2 byzantine nodes (inner-product attack [87]),
  - QSGD-compressed wire (§3.1), CenteredClip aggregation (§3.3, [27, 40]),
  - stake/slash verification audits (§4.2),
  - fractional-ownership ledger + custody-sharded checkpoint (§4.1).

Any scenario from the registry (docs/scenarios.md) runs the same driver:

    PYTHONPATH=src python examples/swarm_byzantine_training.py             # showcase, ~2 min
    PYTHONPATH=src python examples/swarm_byzantine_training.py --scenario audit_heavy --nodes 16
    PYTHONPATH=src python examples/swarm_byzantine_training.py --full      # true 125M
"""
import argparse
import time

import jax

from repro.checkpoint import checkpoint as ckpt
from repro.configs import get_config
from repro.core.scenarios import batched_data_fn_for, get_scenario, list_scenarios
from repro.core.swarm import NodeSpec, SwarmConfig, make_swarm
from repro.core.unextractable import ShardCustody
from repro.core.verification import VerificationConfig
from repro.data.pipeline import DataConfig, data_fn_for_swarm, model_batch
from repro.models.model import build_model
from repro.optim.optimizer import AdamW


def showcase_roster(rounds: int):
    """The all-properties-at-once roster (not a registry scenario: it mixes
    every regime deliberately; the registry keeps regimes isolated)."""
    nodes = [
        NodeSpec("h0", speed=3.0),
        NodeSpec("h1", speed=1.0),
        NodeSpec("h2", speed=1.0),
        NodeSpec("h3", speed=0.5),
        NodeSpec("h4", speed=1.0, leave_round=rounds // 2),
        NodeSpec("h5", speed=1.0),
        NodeSpec("late0", speed=2.0, join_round=rounds // 4),
        NodeSpec("late1", speed=1.0, join_round=rounds // 4),
        NodeSpec("adv0", byzantine="inner_product", byzantine_scale=20.0),
        NodeSpec("adv1", byzantine="sign_flip", byzantine_scale=10.0),
    ]
    cfg = SwarmConfig(
        aggregator="centered_clip",
        agg_kwargs={"clip_tau": 2.0, "iters": 3},
        verification=VerificationConfig(p_check=0.25, stake=10.0,
                                        tolerance=1e-3, jackpot=5.0),
        compression="qsgd",
        compression_kwargs={"levels": 127, "bucket_size": 512},
    )
    return nodes, cfg


def build_lm(full: bool):
    """(cfg, model): protocol-125m at its published width, or the reduced
    4-layer variant the CPU default trains."""
    cfg = get_config("protocol-125m")
    if not full:
        cfg = cfg.reduced(num_layers=4, d_model=256, num_heads=4,
                          head_dim=64, d_ff=1024, vocab_size=2048)
    return cfg, build_model(cfg)


def build_roster(scenario: str, rounds: int, n_nodes: int):
    """(nodes, SwarmConfig) for the showcase roster or a registry scenario
    at ``n_nodes`` (the showcase roster has a fixed size)."""
    if scenario == "showcase":
        return showcase_roster(rounds)
    return get_scenario(scenario).build(n_nodes=n_nodes)


def build_swarm(cfg, model, nodes, swarm_cfg, *, engine: str = "batched"):
    """(swarm, eval_fn): the LM swarm over the synthetic data pipeline —
    two sequences of 128 tokens per node per round, AdamW, seed 0."""
    n_nodes = len(nodes)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=128,
                      global_batch=n_nodes * 2)
    params = model.init(jax.random.PRNGKey(0))
    opt = AdamW(lr=5e-3)
    loss_fn = lambda p, b: model.loss(p, b)[0]
    data_fn = data_fn_for_swarm(cfg, dcfg, n_nodes)
    # the synthetic pipeline is jax-pure in the node index, so the batched
    # engine can build all N node batches in a single vmapped dispatch
    bdf = (batched_data_fn_for(data_fn, n_nodes)
           if engine == "batched" else None)
    swarm = make_swarm(loss_fn, params, opt, nodes, swarm_cfg, data_fn,
                       engine=engine, batched_data_fn=bdf)
    eval_fn = lambda p: loss_fn(p, model_batch(cfg, dcfg, 10**6))
    return swarm, eval_fn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=200)
    ap.add_argument("--scenario", default="showcase",
                    choices=["showcase"] + list_scenarios())
    ap.add_argument("--nodes", type=int, default=10,
                    help="swarm size (registry scenarios only)")
    ap.add_argument("--engine", default="batched",
                    choices=["batched", "sequential"])
    ap.add_argument("--full", action="store_true",
                    help="true 125M params (slow on CPU)")
    ap.add_argument("--ckpt", default="/tmp/repro_swarm_custody_ckpt")
    args = ap.parse_args()

    cfg, model = build_lm(args.full)
    print(f"model: {cfg.name} N={model.cfg.param_count():,} "
          f"({'full' if args.full else 'reduced'})")

    nodes, swarm_cfg = build_roster(args.scenario, args.rounds, args.nodes)
    print(f"scenario: {args.scenario} ({len(nodes)} nodes, "
          f"engine={args.engine})")
    swarm, eval_fn = build_swarm(cfg, model, nodes, swarm_cfg,
                                 engine=args.engine)

    t0 = time.time()
    print(f"{'round':>6} {'active':>6} {'byz':>4} {'loss':>8}  slashed")
    for r in range(args.rounds):
        rec = swarm.step(r)
        if r % 20 == 0 or r == args.rounds - 1:
            loss = float(eval_fn(swarm.eval_params()))
            print(f"{r:6d} {rec['n_active']:6d} {rec['n_byzantine']:4d} "
                  f"{loss:8.4f}  {sorted(swarm.slashed)}")

    dt = time.time() - t0
    print(f"\ntrained {args.rounds} rounds in {dt:.0f}s "
          f"({args.rounds / max(dt, 1e-9):.1f} rounds/s)")

    # §4: ownership proportional to verified (speed-weighted) work
    print("\nfractional ownership (ledger):")
    for node, bal in sorted(swarm.ledger.balances.items(),
                            key=lambda kv: -kv[1]):
        print(f"  {node:10s} {bal:8.1f} shares "
              f"({swarm.ledger.ownership_fraction(node) * 100:5.1f}%)")
    print(f"  burned stake: {swarm.ledger.burned_stake:g} "
          f"(slashed: {sorted(swarm.slashed)})")
    assert swarm.ledger.check_conservation()

    # §4.1: the checkpoint itself is custody-sharded — no node holds it all
    holders = [n.node_id for n in nodes if n.node_id not in swarm.slashed]
    custody = ShardCustody.assign(holders, num_shards=16, redundancy=2,
                                  max_fraction=0.4)
    # decentralized scenarios checkpoint the consensus replica
    ckpt.save_custody(args.ckpt, swarm.eval_params(), custody)
    print(f"\ncustody checkpoint -> {args.ckpt}")
    print(f"  min extraction coalition: {custody.min_extraction_coalition()} "
          f"of {len(holders)} nodes")
    try:
        ckpt.restore_custody(args.ckpt, swarm.eval_params(),
                             holders=holders[:2])
        raise RuntimeError("partial coalition restored — bug!")
    except PermissionError as e:
        print(f"  partial-coalition restore correctly refused: {e}")


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
