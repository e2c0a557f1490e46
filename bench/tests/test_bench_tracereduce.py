"""The reduction from a profiler trace to busy time, operation times and
labelled idle gaps: on a synthetic trace with hand-counted answers, and on
a small trace recorded on a TPU v5e (a toy program under the harness's
span names), kept as a fixture."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import tracereduce

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "toy_v5e.xplane.pb"


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=stats.items())


def synthetic():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 1000, 9000),
        ev("bench.x.step", 1000, 4000),
        ev("bench.x.feed", 6000, 3000),
        ev("unrelated", 0, 20000)])])
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_f", 0, 20000)]),
        NS(name="XLA Ops", events=[
            ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 500, 1500),
            ev("%while.2 = (s32[]) while((s32[]) %t)", 2500, 2000),
            ev("fusion.3", 2600, 900),
            ev('%custom-call.4 = f32[8]{0} custom-call(f32[8]{0} %p), '
               'custom_call_target="tpu_custom_call"', 3800, 400),
            ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 7000, 1000),
            ev("fusion.9", 12000, 100)])])                    # outside
    return NS(planes=[host, device])


def test_synthetic_trace_by_hand():
    s = tracereduce.summarize(synthetic(), min_gap_ns=100)
    # the whole window [1000, 10000]: its 2000 ns idle tail is too short
    # to mean the profiler stopped
    assert s.window_s == s.span_s == pytest.approx(9000e-9)
    # busy: [1000,2000] + [2500,4500] (the while and its body) + [7000,8000]
    assert s.busy_s == pytest.approx(4000e-9)
    assert s.ops["fusion.1"].count == 2
    assert s.ops["fusion.1"].seconds == pytest.approx(2000e-9)
    assert s.ops["fusion.1"].detail.startswith("%fusion.1 = f32[8]")
    assert "while.2" not in s.ops                   # encloses its body
    # gaps: [2000,2500] in step, [4500,7000] mid 5750 -> window only,
    # [8000,10000] mid 9000 -> feed
    gaps = dict(s.gaps)
    assert len(gaps) == len(s.gaps) == 3
    assert gaps == pytest.approx({"bench.x.step": 500e-9,
                                  "bench.window": 2500e-9,
                                  "bench.x.feed": 2000e-9})
    b = s.breakdown(top=2)
    assert b["device_ops"][0][0] == "fusion.1 f32[8]{0} fusion(f32[8]{0} %p)"
    assert s.breakdown()["device_ops"][2][0].startswith(
        "custom-call.4 tpu_custom_call f32[8]")
    assert b["idle_gaps"][0][0] == "bench.window"


def test_truncated_trace_ends_at_the_last_operation():
    s = tracereduce.summarize(synthetic(), min_gap_ns=100,
                              truncated_tail_ns=1000)
    # an idle tail over the limit: the window ends at the last op, 8000
    assert s.window_s == pytest.approx(7000e-9)
    assert s.span_s == pytest.approx(9000e-9)
    assert s.busy_s == pytest.approx(4000e-9)
    assert dict(s.gaps) == pytest.approx({"bench.x.step": 500e-9,
                                          "bench.window": 2500e-9})


def test_no_window_span_is_an_error():
    bad = synthetic()
    bad.planes[0].lines[0].events.pop(0)
    with pytest.raises(ValueError, match="bench.window"):
        tracereduce.summarize(bad)


def test_recorded_v5e_trace():
    s = tracereduce.summarize(tracereduce.load(FIXTURE.parent))
    assert s.n_devices == 1
    assert 0 < s.busy_s < s.window_s
    assert s.ops and all(o.seconds > 0 and o.count > 0 for o in s.ops.values())
    labels = {name for name, _ in s.gaps}
    assert labels <= {"bench.window", "bench.toy.step", "bench.toy.loop"}
    assert "bench.window" in labels
