"""From the profiler's trace of the measured window to the numbers the
per-layer metrics read: device busy time, time per device operation, and
the idle gaps labelled by the harness's host span that was open during each.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
Device operations are the events of each TPU plane's "XLA Ops" line; the
window is the host span ``bench.window``; the harness opens ``bench.*``
spans around each call into a layer.  Busy is the union of the operations'
intervals inside the window, averaged over the chips used.  The
profiler keeps a bounded number of device events (about five million on a
v5e: ten seconds of the serving cell).  Where it stopped early, which shows
as a device idle for more than ``TRUNCATED_TAIL_NS`` at the window's end
(every driver's window ends waiting on its last device result), the traced
window ends at the device's last recorded operation.  A device plane
keeps its own clock: it is put on the host's by the programs both sides
record (the host's ``DoEnqueueProgram`` and the device's "XLA Modules"
event of the same ``run_id``).
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
#: control-flow ops enclose the ops of their bodies.  Their intervals count
#: in busy time, which is a union, so a body's ops are not counted twice
#: and a program's own control time between them counts as busy; in the
#: table of operations they are left out, their time being their body's
ENCLOSING = ("while", "conditional", "cond", "call")
#: a device idle this long at the window's end means the profiler stopped
#: recording: every window ends waiting on its last device result
TRUNCATED_TAIL_NS = 500_000_000


def op_name(event_name: str) -> str:
    """``fusion.12`` from a TPU op event named by its HLO text
    (``%fusion.12 = bf16[...] fusion(...)``); other names as they are."""
    return event_name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class OpStat:
    seconds: float = 0.0
    count: int = 0
    detail: str = ""          # the op's HLO text, where the trace gives it


@dataclasses.dataclass
class TraceSummary:
    window_s: float                     # traced, mean over the chips
    busy_s: float                       # mean over the chips traced
    ops: Dict[str, OpStat]              # summed over chips
    gaps: List[Tuple[str, float]]       # (host span, seconds), all chips
    n_devices: int
    span_s: float = 0.0                 # the whole window span, host clock

    def breakdown(self, top: int = 10) -> Dict:
        """The ``top`` operations by device time, each named with the head
        of its HLO text (result type and operation), and the ``top``
        longest idle gaps by host span."""
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1].seconds)[:top]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:top]
        return {"device_ops": [[_describe(n, s.detail), s.seconds]
                               for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def _describe(name: str, detail: str, width: int = 160) -> str:
    """``name`` and, from its HLO text, what it computes: the custom call's
    target, or the operation with its result type, cut to ``width``."""
    head = detail.split(" = ", 1)[1] if " = " in detail else ""
    target = re.search(r'custom_call_target="([^"]+)"', detail)
    text = f"{name} {target.group(1)} {head}" if target else f"{name} {head}"
    return text.strip()[:width]


def load(directory):
    from jax.profiler import ProfileData
    files = sorted(Path(directory).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return ProfileData.from_file(str(files[-1]))


def _stats(event) -> Dict:
    try:
        return dict(event.stats)
    except Exception:            # a stat the reader cannot decode
        return {}


def _merge(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _label(spans: List[Tuple[str, int, int]], t: int, default: str) -> str:
    """The innermost (shortest) open span at ``t``, else ``default``."""
    best: Optional[Tuple[int, str]] = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else default


def _run_ids(events, name: Optional[str] = None) -> Dict[int, int]:
    """run_id -> start (ns) of the events (named ``name``) that carry one."""
    out = {}
    for ev in events:
        if name is None or ev.name == name:
            run = _stats(ev).get("run_id")
            if run is not None:
                out.setdefault(int(run), int(ev.start_ns))
    return out


def clock_offset(enqueued: Dict[int, int], started: Dict[int, int]) -> int:
    """Nanoseconds to add to a device plane's times to put them on the host's
    clock: a program cannot start before the host enqueued it, so the device
    clock is behind by at least the largest (enqueue - start) over the
    programs both sides saw (0 when none are matched)."""
    gaps = [enqueued[r] - started[r] for r in enqueued.keys() & started.keys()]
    return max(max(gaps), 0) if gaps else 0


def summarize(profile, *, window_span: str = "bench.window",
              min_gap_ns: int = 1000,
              truncated_tail_ns: int = TRUNCATED_TAIL_NS) -> TraceSummary:
    spans: List[Tuple[str, int, int]] = []
    enqueued: Dict[int, int] = {}
    devices = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
                enqueued.update(_run_ids(line.events, "DoEnqueueProgram"))
        elif plane.name.startswith("/device:TPU"):
            devices.append(plane)
    windows = [sp for sp in spans if sp[0] == window_span]
    if not windows:
        raise ValueError(f"no host span {window_span!r} in the trace")
    _, w0, w1 = windows[0]

    raw: Dict[str, OpStat] = {}
    gaps: List[Tuple[str, float]] = []
    inner = [sp for sp in spans if sp[0] != window_span]
    busy_total = covered_total = 0
    for plane in devices:
        started: Dict[int, int] = {}
        for line in plane.lines:
            if line.name == MODULES_LINE:
                started.update(_run_ids(line.events))
        offset = clock_offset(enqueued, started)
        intervals = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = int(ev.start_ns) + offset
                e = s + int(ev.duration_ns)
                if e <= w0 or s >= w1:
                    continue
                s, e = max(s, w0), min(e, w1)
                intervals.append((s, e))
                st = raw.get(ev.name)
                if st is None:
                    st = raw[ev.name] = OpStat()
                st.seconds += (e - s) * 1e-9
                st.count += 1
        merged = _merge(intervals)
        end = w1
        if merged and w1 - merged[-1][1] > truncated_tail_ns:
            end = merged[-1][1]               # the profiler stopped early
        busy_total += sum(e - s for s, e in merged)
        covered_total += end - w0
        edges = [w0] + [t for iv in merged for t in iv] + [end]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a >= min_gap_ns:
                gaps.append((_label(inner, (a + b) // 2, window_span),
                             (b - a) * 1e-9))
    ops: Dict[str, OpStat] = {}
    for text, st in raw.items():
        name = op_name(text)
        if name.split(".")[0] in ENCLOSING:
            continue
        agg = ops.setdefault(name, OpStat(detail=text if text != name else ""))
        agg.seconds += st.seconds
        agg.count += st.count
    n = max(len(devices), 1)
    return TraceSummary(window_s=(covered_total / n if devices else w1 - w0) * 1e-9,
                        busy_s=busy_total * 1e-9 / n, ops=ops, gaps=gaps,
                        n_devices=len(devices), span_s=(w1 - w0) * 1e-9)
