"""Multi-pod dry-run: prove the distribution config lowers + compiles for
every (architecture × input shape × mesh) — no hardware, no allocation.

MUST be run as a module entry point:  PYTHONPATH=src python -m repro.launch.dryrun
The first lines create 512 placeholder host devices BEFORE any jax import
(jax locks the device count at first init) and pin the CPU platform: this
is a fake-device tool and never takes a chip.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from typing import Optional  # noqa: E402

import jax           # noqa: E402

from repro.configs import (     # noqa: E402
    ASSIGNED_ARCHS,
    applicable_shapes,
    get_config,
    get_shape,
)
from repro.launch import mesh as mesh_lib                     # noqa: E402
from repro.launch import roofline as rl                      # noqa: E402
from repro.launch.train import (                              # noqa: E402
    TrainOptions,
    TrainState,
    batch_shardings,
    make_serve_step,
    make_train_step,
    serve_shardings,
    train_state_shardings,
)
from repro.models.model import build_model                   # noqa: E402
from repro.optim.optimizer import AdamW                      # noqa: E402


def _state_sds(model, optimizer):
    """ShapeDtypeStructs for TrainState without allocating."""
    return jax.eval_shape(
        lambda k: TrainState(*_init_state(model, optimizer, k)),
        jax.random.PRNGKey(0))


def _init_state(model, optimizer, key):
    params = model.init(key)
    return params, optimizer.init(params)


def lower_one(arch: str, shape_name: str, *, multi_pod: bool,
              pod_sync: str = "dense", microbatches: int = 1,
              param_gather: str = "fsdp", verbose: bool = True,
              keep_hlo: str = "") -> dict:
    """Lower + compile one (arch × shape × mesh) combination; return record."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    model = build_model(cfg)
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    num_chips = mesh.devices.size
    mesh_name = "multi_pod" if multi_pod else "single_pod"

    from repro.launch.mesh import axis_sizes
    from repro.models.sharding import activation_sharding
    batch_axes = ("pod", "data") if multi_pod else ("data",)

    t0 = time.time()
    with mesh, activation_sharding(batch_axes,
                                   model_axis_size=axis_sizes(mesh)["model"]):
        if shape.kind == "train":
            opt = AdamW(lr=1e-4)
            opts = TrainOptions(pod_sync=pod_sync, microbatches=microbatches,
                                param_gather=param_gather)
            step = make_train_step(model, opt, mesh, opts)
            state_ns = train_state_shardings(model, opt, mesh)
            batch_ns = batch_shardings(model, shape, mesh)
            state_sds = _state_sds(model, opt)
            batch_sds = model.batch_specs(shape)
            jitted = jax.jit(step, in_shardings=(state_ns, batch_ns),
                             out_shardings=(state_ns, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_sds, batch_sds)
        elif shape.kind == "prefill":
            from repro.launch.train import make_param_gather
            params_ns = train_state_shardings(model, AdamW(), mesh).params
            batch_ns = batch_shardings(model, shape, mesh)
            batch_ns = {k: v for k, v in batch_ns.items() if k != "labels"}
            batch_sds = model.batch_specs(shape, with_labels=False)
            gather = make_param_gather(model, mesh, param_gather)

            def prefill(params, batch):
                return model.prefill(gather(params), batch)

            jitted = jax.jit(prefill, in_shardings=(params_ns, batch_ns))
            lowered = jitted.lower(
                jax.eval_shape(model.init, jax.random.PRNGKey(0)), batch_sds)
        else:  # decode
            from repro.launch.train import serve_param_shardings
            params_ns = serve_param_shardings(model, mesh)
            tok_ns, cache_ns = serve_shardings(model, shape, mesh)
            tok_sds, cache_sds = model.decode_specs(shape)
            serve = make_serve_step(model)
            jitted = jax.jit(serve,
                             in_shardings=(params_ns, tok_ns, cache_ns),
                             out_shardings=(None, cache_ns),
                             donate_argnums=(2,))
            lowered = jitted.lower(
                jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                tok_sds, cache_sds)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    hlo = compiled.as_text()
    if keep_hlo:
        with open(keep_hlo, "w") as f:
            f.write(hlo)
    mem = compiled.memory_analysis()
    mem_rec = {
        k: int(getattr(mem, k))
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "peak_memory_in_bytes", "generated_code_size_in_bytes")
        if hasattr(mem, k)
    }
    roof = rl.analyze(compiled, hlo, num_chips=num_chips,
                      model_flops_global=rl.model_flops(cfg, shape))

    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_name,
        "num_chips": num_chips,
        "pod_sync": pod_sync,
        "microbatches": microbatches,
        "param_gather": param_gather,
        "kind": shape.kind,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem_rec,
        "roofline": roof.to_dict(),
        "status": "ok",
    }
    if verbose:
        _print_record(rec)
    return rec


def _print_record(rec: dict) -> None:
    r = rec["roofline"]
    mem = rec["memory"]
    live = mem.get("argument_size_in_bytes", 0) + mem.get("temp_size_in_bytes", 0)
    print(f"[{rec['mesh']}/{rec['pod_sync']}] {rec['arch']:22s} {rec['shape']:12s} "
          f"compute={rl.fmt_seconds(r['compute_s'])} "
          f"memory={rl.fmt_seconds(r['memory_s'])} "
          f"coll={rl.fmt_seconds(r['collective_s'])} "
          f"dom={r['dominant']:10s} "
          f"useful={r['useful_flops_ratio']:6.3f} "
          f"mem/dev={live / 1e9:7.2f}GB "
          f"(lower {rec['lower_s']}s compile {rec['compile_s']}s)",
          flush=True)


def run_all(archs, *, multi_pod: bool, pod_sync: str, out_dir: str,
            microbatches: int = 1, shapes: Optional[list] = None,
            param_gather: str = "fsdp") -> list:
    os.makedirs(out_dir, exist_ok=True)
    records = []
    for arch in archs:
        cfg = get_config(arch)
        for shape_name in (shapes or applicable_shapes(cfg)):
            if shape_name not in applicable_shapes(cfg):
                print(f"SKIP {arch} {shape_name} (DESIGN.md §3: "
                      f"quadratic attention at 500k)", flush=True)
                continue
            tag = f"{arch}__{shape_name}__" \
                  f"{'multi' if multi_pod else 'single'}__{pod_sync}" \
                  + (f"__mb{microbatches}" if microbatches != 1 else "") \
                  + (f"__{param_gather}" if param_gather != "fsdp" else "")
            path = os.path.join(out_dir, tag + ".json")
            try:
                rec = lower_one(arch, shape_name, multi_pod=multi_pod,
                                pod_sync=pod_sync, microbatches=microbatches,
                                param_gather=param_gather,
                                keep_hlo=os.path.join(out_dir, tag + ".hlo.txt"))
            except Exception as e:
                rec = {"arch": arch, "shape": shape_name,
                       "mesh": "multi_pod" if multi_pod else "single_pod",
                       "pod_sync": pod_sync, "status": "fail",
                       "error": f"{type(e).__name__}: {e}"}
                print(f"FAIL {arch} {shape_name}: {type(e).__name__}: {e}",
                      flush=True)
                traceback.print_exc()
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            records.append(rec)
    return records


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--pod-sync", default="dense",
                    choices=["dense", "qsgd", "gossip", "centered_clip", "median"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--param-gather", default="fsdp", choices=["fsdp", "none"])
    ap.add_argument("--out-dir", default="experiments/dryrun")
    args = ap.parse_args(argv)

    if args.all:
        recs = run_all(ASSIGNED_ARCHS, multi_pod=args.multi_pod,
                       pod_sync=args.pod_sync, out_dir=args.out_dir,
                       microbatches=args.microbatches,
                       param_gather=args.param_gather,
                       shapes=[args.shape] if args.shape else None)
        bad = [r for r in recs if r.get("status") != "ok"]
        print(f"\n{len(recs) - len(bad)}/{len(recs)} combinations compiled")
        return 1 if bad else 0

    if not args.arch:
        ap.error("--arch or --all required")
    archs = args.arch.split(",")
    shapes = args.shape.split(",") if args.shape else None
    recs = run_all(archs, multi_pod=args.multi_pod, pod_sync=args.pod_sync,
                   out_dir=args.out_dir, microbatches=args.microbatches,
                   param_gather=args.param_gather, shapes=shapes)
    return 1 if any(r.get("status") != "ok" for r in recs) else 0


if __name__ == "__main__":
    raise SystemExit(main())
