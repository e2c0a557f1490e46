"""Serving driver over the unified Model API.

The token loops live in ``core.serving`` now: :func:`greedy_decode` is the
jitted *scanned* decoder (prefill = ``Model.decode_scan``, decode =
``lax.scan`` over ``decode_step``), and :func:`greedy_decode_loop` is the
replaced per-token python loop, kept as the reference oracle and benchmark
baseline.  This module is the CLI:

- ``--driver scan``   : the scanned greedy decoder (default);
- ``--driver loop``   : the old python loop (reference / baseline);
- ``--driver engine`` : the continuous-batching engine
  (``core.serving.ServingEngine``) — fixed decode slots, arrival-ordered
  admission, per-slot KV caches, custody-gated availability — serving a
  queue of requests in one compiled scan.
"""
from __future__ import annotations

import jax

from repro.configs import get_config
from repro.core.serving import (  # noqa: F401  (re-exported API)
    ServeStats,
    ServingConfig,
    ServingEngine,
    build_lane,
    greedy_decode,
    greedy_decode_loop,
)
from repro.models.model import build_model


def engine_for(model, prompts, *, slots: int, max_new: int):
    """(ServingEngine, ServeLane) serving every prompt row once: all
    requests arrive at step 0, each decodes ``max_new`` tokens, and the
    scan horizon fits the queue through ``slots`` decode slots."""
    import numpy as np

    n, prompt_len = prompts.shape
    per_request = prompt_len + max_new
    scfg = ServingConfig(
        slots=slots, max_new=max_new,
        steps=per_request + per_request * ((n + slots - 1) // slots))
    lane = build_lane(
        n_requests=n, prompt_lens=np.full(n, prompt_len, np.int32),
        max_new=max_new, steps=scfg.steps, n_nodes=8,
        balances=[float(n)] * 4, fee=1.0, load=1.0)
    return ServingEngine(model, scfg, prompts), lane


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description="serving driver")
    ap.add_argument("--arch", default="protocol-125m")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the tiny same-family variant (default); "
                         "--no-reduced serves the published width")
    ap.add_argument("--driver", default="scan",
                    choices=("scan", "loop", "engine"))
    ap.add_argument("--batch", type=int, default=4,
                    help="batch (scan/loop) or request count (engine)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4,
                    help="engine: decode slot-pool size")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab_size)

    if args.driver == "engine":
        engine, lane = engine_for(model, prompts, slots=args.slots,
                                  max_new=args.max_new)
        engine.run(params, lane)                     # warm the program
        res = engine.run(params, lane)
        print(f"arch={cfg.name} engine slots={args.slots} "
              f"requests={args.batch} served={int(res.done.sum())} "
              f"tokens={res.tokens_served} ({res.tok_per_s:.1f} tok/s, "
              f"availability {res.availability:.2f})")
        print("sample:", res.tokens[0, :16].tolist())
        return

    decode = greedy_decode if args.driver == "scan" else greedy_decode_loop
    gen, stats = decode(model, params, prompts, args.max_new)
    print(f"arch={cfg.name} driver={args.driver} batch={stats.batch} "
          f"prefill={stats.prefill_s:.2f}s decode={stats.decode_s:.2f}s "
          f"({stats.tok_per_s:.1f} tok/s)")
    print("sample:", gen[0, :16].tolist())


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
