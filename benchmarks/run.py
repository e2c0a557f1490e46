"""Benchmark driver — one module per paper table/claim (DESIGN.md §0).
Prints ``name,us_per_call,derived`` CSV rows.

  §2   capacity          centralized vs volunteer vs incentivized watts/FLOPS
  §3.1 compression       wire ratios + loss impact + qsgd kernel
  §3.2 gossip            convergence vs spectral gap, traffic vs all-reduce
  §3.2 pipeline_scaling  SWARM square-cube: comm/compute shrinks with d_model
  §3.3 byzantine         attacks x aggregators (+ centered_clip kernel)
  §4.2 verification      stake/slash EV grid + measured catch rate
  §4.1 custody           coalition reductions + the extractability frontier
  §4.1 serving           scanned decode + continuous batching vs the loop
                         driver + the (load x churn x redundancy) sweep
  §5.5 derailment        no-off frontier + attack economics
  §4   economy           incentive phase diagram + the adaptivity gap
  §3   async             bounded-staleness rounds/s vs sync + straggler util
  §3.3 round_fused       fused Pallas round path vs per-op jnp, rounds/s
  (g)  roofline          per arch x shape terms from the dry-run artifacts
  (g)  campaign_scaling  mesh-sharded campaign weak scaling (lanes/s vs
                         the single-device engine, fake-device host mesh)
"""
from __future__ import annotations

import subprocess
import sys
import time

MODULES = [
    "bench_capacity",
    "bench_compression",
    "bench_gossip",
    "bench_pipeline_scaling",
    "bench_byzantine",
    "bench_verification",
    "bench_custody",
    "bench_serving",
    "bench_derailment",
    "bench_economy",
    "bench_async",
    "bench_round_fused",
    "bench_roofline",
    "bench_campaign_scaling",
]


# Each module runs in a process of its own: a device belongs to one process
# at a time, so this runner never imports jax, and no module inherits a
# device another one holds.
_CHILD = (
    "import sys\n"
    "from repro.launch import compile_cache\n"
    "from benchmarks.common import emit\n"
    "compile_cache.enable()\n"
    "mod = __import__('benchmarks.' + sys.argv[1], fromlist=['run'])\n"
    "emit(mod.run())\n")


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    selected = argv or MODULES
    print("name,us_per_call,derived", flush=True)
    failures = 0
    for name in selected:
        mod_name = name if name.startswith("bench_") else f"bench_{name}"
        t0 = time.time()
        rc = subprocess.run([sys.executable, "-c", _CHILD, mod_name]).returncode
        if rc:
            failures += 1
            print(f"# {mod_name} FAILED (exit {rc})", file=sys.stderr)
        else:
            print(f"# {mod_name} done in {time.time() - t0:.1f}s",
                  file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
