"""Device time per program stage, and the program's own host spans, from
the profiler's trace of the measured window: the join of
``tracereduce``'s device operations to the stage scopes the program puts
around its round and its serving step.

A device event names its instruction only (``%fusion.12 = ...``), so the
join goes through the compiled programs: ``scopes`` maps each program's
module name to {instruction name: stage} (``repro.analysis.stages.
stage_map`` of ``compiled.as_text()``).  An "XLA Ops" event belongs to the
program whose "XLA Modules" event encloses it in time (a trace names a
module ``jit_round_fn(<program id>)``), and to the stage its instruction
maps to there; every other op is ``unscoped``.  A stage's time is device
*self* time: an enclosing ``while``, ``conditional`` or ``call`` counts
only the part of its interval that the ops recorded inside it leave
uncovered.  Times are clipped to the window and averaged over the chips.

Host spans are the program's ``jax.profiler.TraceAnnotation``s named
``repro.*`` (``Swarm.step``'s feed, dispatch, wait, settle;
``ServingEngine.run``'s wait and readback): each one's count and total
seconds over the spans that start in the window.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

from bench import tracereduce as tr

HOST_PREFIX = "repro."
UNSCOPED = "unscoped"

Scopes = Dict[str, Dict[str, Optional[str]]]


def module_name(event_name: str) -> str:
    """``jit_round_fn`` from a module event named ``jit_round_fn(123)``."""
    head, _, tail = event_name.rpartition("(")
    return head if head and tail.rstrip(")").isdigit() else event_name


def _window(profile, window_span: str) -> Tuple[int, int, Dict[int, int]]:
    """The window span's (start, end) on the host clock, and the host's
    enqueue time of each program run."""
    window = None
    enqueued: Dict[int, int] = {}
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if window is None and ev.name == window_span:
                    s = int(ev.start_ns)
                    window = (s, s + int(ev.duration_ns))
            enqueued.update(tr._run_ids(line.events, "DoEnqueueProgram"))
    if window is None:
        raise ValueError(f"no host span {window_span!r} in the trace")
    return window[0], window[1], enqueued


def _covered(intervals: List[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in tr._merge(intervals))


def stage_seconds(profile, scopes: Scopes, *,
                  window_span: str = "bench.window") -> Dict[str, float]:
    """Device self seconds per stage in the window, mean over the chips."""
    w0, w1, enqueued = _window(profile, window_span)
    devices = [p for p in profile.planes if p.name.startswith("/device:TPU")]
    totals: Dict[str, float] = {}
    for plane in devices:
        started: Dict[int, int] = {}
        modules: List[Tuple[int, int, str]] = []
        ops: List[Tuple[int, int, str]] = []
        for line in plane.lines:
            if line.name == tr.MODULES_LINE:
                started.update(tr._run_ids(line.events))
                modules += [(int(ev.start_ns), int(ev.start_ns)
                             + int(ev.duration_ns), module_name(ev.name))
                            for ev in line.events]
            elif line.name == tr.OPS_LINE:
                ops += [(int(ev.start_ns), int(ev.start_ns)
                         + int(ev.duration_ns), tr.op_name(ev.name))
                        for ev in line.events]
        offset = tr.clock_offset(enqueued, started)
        modules.sort()
        ops.sort(key=lambda o: (o[0], -o[1]))     # an enclosing op first
        module_starts = [m[0] for m in modules]
        op_starts = [o[0] for o in ops]
        for i, (s, e, name) in enumerate(ops):
            lo, hi = max(s + offset, w0), min(e + offset, w1)
            if hi <= lo:
                continue
            k = bisect.bisect_right(module_starts, s) - 1
            module = modules[k][2] if k >= 0 and s < modules[k][1] else ""
            stage = scopes.get(module, {}).get(name) or UNSCOPED
            seconds = hi - lo
            if name.split(".")[0] in tr.ENCLOSING:
                inner = [(max(a + offset, lo), min(b + offset, hi))
                         for a, b, _ in ops[i + 1:bisect.bisect_left(
                             op_starts, e, lo=i + 1)]
                         if b <= e]
                seconds -= _covered([iv for iv in inner if iv[1] > iv[0]])
            totals[stage] = totals.get(stage, 0.0) + seconds * 1e-9
    n = max(len(devices), 1)
    return {k: v / n for k, v in totals.items()}


def host_spans(profile, *, window_span: str = "bench.window"
               ) -> Dict[str, Tuple[int, float]]:
    """{span name: (count, total seconds)} of the ``repro.*`` host spans
    that start in the window."""
    w0, w1, _ = _window(profile, window_span)
    out: Dict[str, Tuple[int, float]] = {}
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s = int(ev.start_ns)
                if ev.name.startswith(HOST_PREFIX) and w0 <= s < w1:
                    n, t = out.get(ev.name, (0, 0.0))
                    out[ev.name] = (n + 1, t + int(ev.duration_ns) * 1e-9)
    return out
