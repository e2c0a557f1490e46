"""Readings that set a cell's limits, on the chip, many seeds in one process:

    python3 bench/control.py --workload <name> --seeds 1,2,3 [--control 1]

For each seed it builds the cell's program as a run does, drives it through
its checked steps (a serving cell: one episode at the cell's own load),
and prints one JSON line with the program's compared numbers against the
reference, and with ``--control 1`` those of the control (the reference in
fp8, put in the program's place) and of the cell's planted faults.  The
benchmark's own runs never run this.
"""
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = harness.load_spec(args.workload)
    harness.enable_compile_cache()
    try:
        devices = harness.chip_devices(spec.chips, harness.load_peaks())
    except harness.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    entry = harness.load_driver(spec.traffic["entry"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        driver = entry.Driver(spec, seed, devices)
        driver.setup()
        if spec.traffic["entry"] == "serve_engine":
            driver.window(0.0)
        driver.release()
        row = {"seed": seed, "program": {k: v for k, (v, _) in
                                         driver.check().items()}}
        if args.control:
            row.update(driver.control())
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
        del driver
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
