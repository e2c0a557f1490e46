"""Whole runs of miniature cells on the CPU, past the harness's look for a
chip: a sound program comes out correct, the control (the reference in
fp8) fails the limits, and each fault the cells can have, planted in the
timed path, makes ``correct`` false."""
import time

import jax
import numpy as np
import pytest

from bench import harness
from bench.tests import tiny

SEED = 2**31 + 77


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"))


def run(root, cell, trace=False):
    spec = harness.load_spec(cell, root)
    devices = harness.chip_devices(1, harness.load_peaks(root), platform="cpu")
    return harness.run_cell(spec, SEED, 0.2, trace, t_start=time.perf_counter(),
                            devices=devices, root=root)


@pytest.mark.parametrize("cell", ["tiny.swarm", "tiny.chat"])
def test_sound_program_is_correct(root, cell):
    res = run(root, cell)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert all(m["value"] > 0 for m in res["metrics"].values())
    # no allocator stats on the CPU: the compiled timed program's own bytes
    assert res["device"]["memory_peak_bytes"] > 0


@pytest.mark.parametrize("cell", ["tiny.swarm", "tiny.chat"])
def test_control_fails_the_limits(root, cell):
    spec = harness.load_spec(cell, root)
    devices = harness.chip_devices(1, harness.load_peaks(root), platform="cpu")
    driver = harness.load_driver(spec.traffic["entry"]).Driver(spec, SEED, devices)
    driver.setup()
    driver.window(0.0)
    driver.release()
    checks = driver.check()
    assert all(v <= lim for v, lim in checks.values()), checks
    control = driver.control()
    limits = spec.traffic["limits"]
    for fault, readings in control.items():
        assert any(v > limits[k] for k, v in readings.items()), (fault, readings)


def test_state_left_unchanged_is_caught(root, monkeypatch):
    from repro.optim import optimizer
    monkeypatch.setattr(optimizer.AdamW, "update",
                        lambda self, grads, state, params: (params, state))
    res = run(root, "tiny.swarm")
    assert not res["correct"]
    assert res["checks"]["grad_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out_is_caught(root, monkeypatch):
    from repro.models import transformer
    loss = transformer.loss_fn
    monkeypatch.setattr(transformer, "loss_fn", lambda params, cfg, batch: loss(
        params, cfg, jax.tree.map(lambda x: x[: x.shape[0] // 2], batch)))
    res = run(root, "tiny.swarm")
    assert not res["correct"], res["checks"]


def test_token_altered_where_produced_is_caught(root, monkeypatch):
    from repro.core import serving
    result = serving._result_from_device

    def altered(state, recs, wall_s=0.0):
        res = result(state, recs, wall_s)
        res.tokens = (res.tokens + 1) % tiny.TINY_LM["vocab_size"]
        return res

    monkeypatch.setattr(serving, "_result_from_device", altered)
    res = run(root, "tiny.chat")
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > tiny.CHAT["limits"]["logit_gap"]


def test_engine_that_never_finishes_is_caught(root, monkeypatch):
    from repro.core import serving
    result = serving._result_from_device

    def stuck(state, recs, wall_s=0.0):
        res = result(state, recs, wall_s)
        res.done = np.zeros_like(res.done)
        return res

    monkeypatch.setattr(serving, "_result_from_device", stuck)
    res = run(root, "tiny.chat")
    assert not res["correct"] and res["failed"] == res["attempted"]
