"""The join of device operations to the program's stages, and the program's
host spans: on a synthetic trace with hand-counted answers (a ``while``
whose body covers part of it, two programs that both hold a ``fusion.1``),
and on the recorded v5e trace, whose reduction by ``tracereduce`` stays
what it was."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import stages, tracereduce

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "toy_v5e.xplane.pb"


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=())


def synthetic():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.window", 1000, 9000),
        ev("repro.swarm.step", 1000, 4000),
        ev("repro.swarm.wait", 2000, 2500),
        ev("bench.swarm.step", 1000, 4000),            # not the program's
        ev("repro.swarm.step", 6000, 3500),
        ev("repro.swarm.wait", 7000, 2000),
        ev("repro.swarm.step", 12000, 500)])])         # after the window
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_a(11)", 0, 5000),
                                       ev("jit_b(22)", 5000, 15000)]),
        NS(name="XLA Ops", events=[
            ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", 500, 1500),
            ev("%while.2 = (s32[]) while((s32[]) %t)", 2500, 2000),
            ev("fusion.3", 2600, 900),
            ev("custom-call.4", 3800, 400),
            ev("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %q)", 7000, 1000),
            ev("fusion.5", 9000, 500),
            ev("fusion.9", 12000, 100)])])                # outside
    return NS(planes=[host, device])


SCOPES = {"jit_a": {"fusion.1": "swarm.grad", "while.2": "swarm.aggregate",
                    "fusion.3": "swarm.aggregate",
                    "custom-call.4": "swarm.update"},
          "jit_b": {"fusion.1": "swarm.record", "fusion.5": None}}


def test_stage_self_time_by_hand():
    got = stages.stage_seconds(synthetic(), SCOPES)
    # fusion.1 of jit_a clipped to the window [1000, 2000]; the while's
    # 2000 ns less its body's 900 + 400; fusion.1 of jit_b is another
    # instruction; fusion.5 maps to no stage; fusion.9 lies outside
    assert got == pytest.approx({"swarm.grad": 1000e-9,
                                 "swarm.aggregate": 700e-9 + 900e-9,
                                 "swarm.update": 400e-9,
                                 "swarm.record": 1000e-9,
                                 "unscoped": 500e-9})
    # self times partition the busy time
    busy = tracereduce.summarize(synthetic(), min_gap_ns=100).busy_s
    assert sum(got.values()) == pytest.approx(busy)


def test_a_program_missing_from_the_map_is_unscoped():
    got = stages.stage_seconds(synthetic(), {"jit_b": SCOPES["jit_b"]})
    assert got == pytest.approx({"unscoped": 3000e-9 + 500e-9,
                                 "swarm.record": 1000e-9})


def test_host_spans_in_the_window():
    got = stages.host_spans(synthetic())
    assert got.keys() == {"repro.swarm.step", "repro.swarm.wait"}
    assert got["repro.swarm.step"][0] == 2
    assert got["repro.swarm.step"][1] == pytest.approx(7500e-9)
    assert got["repro.swarm.wait"][0] == 2
    assert got["repro.swarm.wait"][1] == pytest.approx(4500e-9)


@pytest.mark.parametrize("event, module", [
    ("jit_round_fn(8485634492780914798)", "jit_round_fn"),
    ("jit__lambda(12)", "jit__lambda"),
    ("jit_f", "jit_f"),
    ("jit_g(x)", "jit_g(x)"),
])
def test_module_name(event, module):
    assert stages.module_name(event) == module


def test_recorded_v5e_trace_reduces_as_before():
    profile = tracereduce.load(FIXTURE.parent)
    s = tracereduce.summarize(profile)
    assert (s.window_s, s.busy_s, s.span_s, s.n_devices) == pytest.approx(
        (0.01284655, 8.7015e-05, 0.01284655, 1))
    assert {k: v.count for k, v in s.ops.items()} == {
        "copy-done": 6, "copy-done.1": 3, "copy-start": 6, "copy-start.1": 3,
        "fusion": 3, "sine_multiply_fusion.2": 9}
    assert {k: v.seconds for k, v in s.ops.items()} == pytest.approx({
        "copy-done": 1.0326e-05, "copy-done.1": 5.654e-06,
        "copy-start": 5.9e-08, "copy-start.1": 1.6e-08, "fusion": 1.176e-05,
        "sine_multiply_fusion.2": 5.905e-05})
    assert len(s.gaps) == 7
    assert {n for n, _ in s.gaps} == {"bench.window", "bench.toy.step"}
    # a program the map does not hold: all its time is unscoped, and the
    # stages' self times add up to the busy time
    assert stages.stage_seconds(profile, {}) == pytest.approx(
        {"unscoped": s.busy_s})
    assert stages.host_spans(profile) == {}
