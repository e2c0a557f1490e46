"""BENCHMARK.json and the files it names: every cell loads against its
configuration, every name resolves to a file, and a new cell is found by
its name alone."""
import json
import re
import shutil
from pathlib import Path

import pytest

from bench import harness
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_contract_keys_and_names():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert BENCHMARK["paths"] == ["bench"]
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    metrics = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]
    names = [m["name"] for m in metrics] + CELLS + [
        c["name"] for c in BENCHMARK["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCHMARK["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["better"] in ("lower", "higher")
    assert {m["name"] for m in BENCHMARK["end_to_end"]} >= {"setup_s"}
    for m in BENCHMARK["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCHMARK["end_to_end"]}
        assert set(m["workloads"]) <= set(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_against_its_configuration(cell):
    from bench.drivers import program_model
    spec = harness.load_spec(cell)
    model = program_model(spec.config)            # param count as stated
    assert model.cfg.param_count() == spec.config["param_count"]
    assert harness.load_driver(spec.traffic["entry"]).Driver
    e2e = {m["name"] for m in spec.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert spec.per_layer, "every cell reports a per-layer metric"
    for m in spec.per_layer:
        assert m["moves"] in e2e
        assert callable(harness.load_metric(m["name"]))
    for key in spec.config["reduced"]:
        assert key in spec.config["model"]


def test_new_cell_is_found_by_name(tmp_path):
    root = tiny.make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    shutil.copy(root / "bench" / "traffic" / "swarm.json",
                root / "bench" / "traffic" / "dummy.json")
    bench["workloads"].append({"name": "dummy.cell", "config": "tiny-lm",
                               "traffic": "dummy", "chips": 1, "why": "x"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny.swarm" in m.get("workloads", []):
            m["workloads"].append("dummy.cell")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = harness.load_spec("dummy.cell", root)
    assert spec.traffic["entry"] == "swarm_step"
    assert spec.config["name"] == "tiny-lm"
    assert {m["name"] for m in spec.per_layer} == {
        "idle_share.swarm", "swarm_mfu", "masked_agg_roofline"}
    with pytest.raises(harness.BenchError):
        harness.load_spec("no.such.cell", root)


def test_refuses_a_missing_tpu(capsys):
    with pytest.raises(harness.BenchError, match="no TPU"):
        harness.chip_devices(1, harness.load_peaks())
    assert harness.main(["--workload", CELLS[0], "--seed", "1",
                         "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""


def test_refuses_a_device_kind_not_in_the_peaks_table():
    with pytest.raises(harness.BenchError, match="not in bench/peaks.json"):
        harness.chip_devices(1, {"TPU v5 lite": {}}, platform="cpu")
    with pytest.raises(harness.BenchError, match="needs 4 chips"):
        harness.chip_devices(4, {"cpu": {}}, platform="cpu")
    assert harness.chip_devices(1, {"cpu": {}}, platform="cpu")
