"""Quickstart: build any assigned architecture, train it on the synthetic
LM pipeline, checkpoint it, and serve a few greedy tokens.

    PYTHONPATH=src python examples/quickstart.py --arch tinyllama-1.1b
"""
import argparse

import jax

from repro.checkpoint import checkpoint as ckpt
from repro.configs import ASSIGNED_ARCHS, get_config
from repro.data.pipeline import DataConfig, model_batch
from repro.launch.mesh import make_host_mesh
from repro.launch.serve import greedy_decode
from repro.launch.train import TrainOptions, TrainState, make_train_step
from repro.models.model import build_model
from repro.optim.optimizer import AdamW, cosine_schedule


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b",
                    choices=ASSIGNED_ARCHS + ["protocol-125m"])
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--ckpt", default="/tmp/repro_quickstart_ckpt")
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()          # CPU-sized, same family
    model = build_model(cfg)
    print(f"arch={cfg.name} family={cfg.family} "
          f"(reduced: {cfg.num_layers}L d={cfg.d_model}); "
          f"full-size N={get_config(args.arch).param_count():,}")

    opt = AdamW(lr=cosine_schedule(3e-3, 10, args.steps))
    params = model.init(jax.random.PRNGKey(0))
    state = TrainState(params, opt.init(params))
    step_fn = jax.jit(make_train_step(model, opt, make_host_mesh(),
                                      TrainOptions()))
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=128, global_batch=8)

    for step in range(args.steps):
        state, metrics = step_fn(state, model_batch(cfg, dcfg, step))
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {float(metrics['loss']):.4f}")

    ckpt.save(args.ckpt, state.params, step=args.steps)
    print(f"checkpoint -> {args.ckpt} (step {ckpt.load_step(args.ckpt)})")

    restored = ckpt.restore(args.ckpt, jax.eval_shape(lambda: state.params))
    prompts = model_batch(cfg, dcfg, 0)["tokens"][:2, :8]
    gen, stats = greedy_decode(model, restored, prompts, max_new=16)
    print(f"served {stats.batch}x{stats.tokens_out} tokens "
          f"({stats.tok_per_s:.1f} tok/s): {gen[0].tolist()}")


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.enable()
    main()
