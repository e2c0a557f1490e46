"""The benchmark harness: finds a cell's configuration, traffic and metrics by
the names in ``BENCHMARK.json``, runs it once on the chip, and prints one
JSON result line.

A cell's traffic file names its entry (``bench/drivers/<entry>.py``), the
driver that builds the system under test from the configuration and the
traffic, warms it up, runs the measured window and checks what the window
produced against the plain reference.  A per-layer metric is a reader
``bench/metrics/<name>.py`` with ``read(ctx) -> float | None``.  Adding a
cell, configuration, traffic mix or metric adds files only.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_trace"


class BenchError(RuntimeError):
    """The cell cannot be measured here: exit non-zero, print no result."""


@dataclasses.dataclass
class Spec:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def load_spec(workload: str, root: Path = ROOT) -> Spec:
    """The cell ``workload`` as ``BENCHMARK.json`` under ``root`` names it."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in e2e_names)]
    return Spec(workload, cell["chips"], config, traffic, e2e, layer)


def load_peaks(root: Path = ROOT) -> Dict[str, Dict]:
    return json.loads((root / "bench" / "peaks.json").read_text())["devices"]


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` if set, else at
    the fixed ``<checkout>/.jax_cache``; every program is cached."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def chip_devices(chips: int, peaks: Dict, *, platform: str = "tpu"):
    """The first ``chips`` devices; refuses another platform, too few
    devices, or a device kind the peaks table does not hold."""
    import jax
    devices = jax.devices()
    if devices[0].platform != platform:
        raise BenchError(f"no {platform.upper()} here: JAX platform is "
                         f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, found {len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return devices[:chips]


def load_driver(entry: str):
    return importlib.import_module(f"bench.drivers.{entry}")


def load_metric(name: str, root: Path = ROOT) -> Callable:
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def annotate(name: str):
    """A host span in the profiler's trace (free when no trace is taken)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric reader may read."""
    chips: int
    peak: Dict                 # the device's row of bench/peaks.json
    facts: Dict                # the driver's counts: flops, bytes, steps
    trace: Optional[Any]       # tracereduce.TraceSummary of the window


def compiled_bytes(compiled) -> int:
    """What a compiled program holds at once by the compiler's account: its
    peak where the backend reports one (the TPU does), else its arguments,
    outputs and temporaries, less the outputs that alias an argument."""
    m = compiled.memory_analysis()
    if m is None:
        return 0
    return int(getattr(m, "peak_memory_in_bytes", 0)
               or m.argument_size_in_bytes + m.output_size_in_bytes
               - m.alias_size_in_bytes + m.temp_size_in_bytes)


def memory_peak(devices, program_bytes: int = 0) -> int:
    """The peak on the fullest chip: the allocator's peak of live buffers
    or, where that is larger, what the cell's largest timed program holds
    at once (``compiled_bytes``).  The allocator's peak leaves out the
    temporaries of a running program."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(max(peaks), program_bytes)


def run_cell(spec: Spec, seed: int, seconds: float, trace: bool, *,
             t_start: float, devices, root: Path = ROOT) -> Dict:
    """Set up, measure, check on ``devices``.  Returns the result object."""
    import jax
    from bench import tracereduce

    peak = load_peaks(root)[devices[0].device_kind]
    driver = load_driver(spec.traffic["entry"]).Driver(spec, seed, devices)
    driver.setup()
    setup_s = time.perf_counter() - t_start

    summary = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    with annotate("bench.window"):
        window = driver.window(seconds)
    if trace:
        jax.profiler.stop_trace()
        summary = tracereduce.summarize(tracereduce.load(TRACE_DIR),
                                        window_span="bench.window")
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        print(f"trace: {summary.window_s:.3f} s of the {summary.span_s:.3f} s "
              "window traced", file=sys.stderr)
    mem = memory_peak(devices, driver.program_bytes())

    driver.release()
    checks = driver.check()
    correct = all(v <= lim for v, lim in checks.values())

    if trace:
        ctx = MetricContext(spec.chips, peak, driver.facts(window), summary)
        metrics = {}
        for m in spec.per_layer:
            value = load_metric(m["name"], root)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(driver.end_to_end(window), setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec.end_to_end}

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": bool(correct), "attempted": window.attempted,
              "failed": window.failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None, *, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = load_spec(args.workload)
        devices = chip_devices(spec.chips, load_peaks())
        enable_compile_cache()
        result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                          t_start=t_start, devices=devices)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
