"""Protocol Model serving (§4.1): credential-gated, custody-sharded
batched inference — weights never leave the protocol.

Demonstrates: (1) credential gating + transferable credentials, (2) serving
requires the live swarm, (3) a partial coalition reassembles only garbage,
(4) the extraction-vs-retrain economics that define a Protocol Model.

    PYTHONPATH=src python examples/protocol_inference.py
"""
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.ledger import Ledger
from repro.core.protocol import (
    CredentialError,
    ExtractionError,
    ProtocolModelServer,
)
from repro.core.unextractable import (
    extraction_cost_flops,
    is_protocol_model,
    retrain_cost_flops,
)
from repro.models.model import build_model


def main():
    cfg = get_config("protocol-125m").reduced(
        num_layers=4, d_model=256, num_heads=4, head_dim=64, d_ff=1024,
        vocab_size=2048)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    nodes = [f"node{i}" for i in range(8)]
    ledger = Ledger()
    for i, n in enumerate(nodes):
        ledger.record_contribution(n, float(1 + i % 3))    # training shares

    srv = ProtocolModelServer.create(model, params, nodes, ledger,
                                     num_shards=16, redundancy=2,
                                     max_fraction=0.35)
    print(f"model sharded into {srv.custody.num_shards} custody shards over "
          f"{len(nodes)} nodes (redundancy {srv.custody.redundancy}, "
          f"max fraction 0.35)")

    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 16),
                                          0, cfg.vocab_size)}

    # 1. credential gating + transfer
    try:
        srv.serve("customer", batch)
    except CredentialError as e:
        print(f"no credentials -> refused: {e}")
    ledger.transfer("node0", "customer", 0.5)
    logits = srv.serve("customer", batch)
    print(f"after credential transfer: served batch of 4, "
          f"logits {logits.shape}, top tok {int(jnp.argmax(logits[0]))}")

    # 2. elasticity: serving survives departures (redundancy 2) ...
    online = [n for n in nodes if n != "node3"]
    srv.serve("customer", batch, online_nodes=online)
    print(f"node3 offline: still served ({srv.custody.tolerates_departures(['node3'])})")
    # ... but not a collapsed swarm — and the failure names the shard ids
    # the survivors are missing, so the outage is diagnosable
    try:
        srv.serve("customer", batch, online_nodes=nodes[:2])
    except ExtractionError as e:
        print(f"swarm collapsed to 2 nodes -> {e}")
        print(f"  (missing shard ids: {srv.custody.missing_shards(nodes[:2])})")

    # 3. a coalition below full coverage extracts garbage
    coalition = nodes[:3]
    cov = srv.custody.coverage(coalition)
    broken = srv.attempt_extraction(coalition)
    ref = model.prefill(params, batch)
    got = model.prefill(broken, batch)
    print(f"coalition of 3 covers {cov * 100:.0f}% of shards; "
          f"extracted-model logit error: "
          f"{float(jnp.max(jnp.abs(got - ref))):.2f} (unusable)")

    # 4. the defining inequality: acquire-missing-shards vs retrain
    n_params = cfg.param_count()
    tokens = 20 * n_params                                 # chinchilla-ish
    cost_per_shard = retrain_cost_flops(n_params, tokens) / 4
    extract = extraction_cost_flops(srv.custody, coalition, cost_per_shard)
    retrain = retrain_cost_flops(n_params, tokens)
    print(f"extraction cost {extract:.2e} FLOPs vs retrain {retrain:.2e} "
          f"-> protocol model: "
          f"{is_protocol_model(srv.custody, coalition, n_params, tokens, cost_per_shard)}")
    print(f"min coalition for full coverage: "
          f"{srv.custody.min_extraction_coalition()} of {len(nodes)} nodes")


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
