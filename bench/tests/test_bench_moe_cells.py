"""The DeepSeek-V2-Lite serving cell loads by name with its metrics, the
reasoning mix gives its stated rate and cache, the FLOP counts hold by
hand, and a miniature whole run of its driver on the CPU reads correct
while its control and planted faults fail the limits."""
import dataclasses
import json
import time

import jax
import pytest

from bench import flops_moe, harness, traffic
from bench.tests import tiny

SEED = 2**31 + 91
BENCH = tiny.BENCH
CONFIG = json.loads((BENCH / "configs" / "deepseek-v2-lite-ep8.json").read_text())
REASON = json.loads((BENCH / "traffic" / "reason.json").read_text())

TINY_MLA = {
    "num_layers": 3, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
    "d_ff": 24, "vocab_size": 256, "tie_embeddings": False,
    "sliding_window": None, "rope_theta": 10000.0,
    "yarn": {"factor": 40.0, "original_max_position_embeddings": 64,
             "beta_fast": 32.0, "beta_slow": 1.0, "mscale": 0.707,
             "mscale_all_dim": 0.707},
    "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "num_experts": 16, "experts_per_token": 3,
    "n_routed_experts": 4, "num_shared_experts": 2, "first_k_dense": 1,
    "dense_d_ff": 96, "norm_topk_prob": False, "routed_scaling_factor": 1.0,
    "norm_eps": 1e-06, "dtype": "bfloat16", "max_seq_len": 64}

REASON_TINY = {
    "entry": "serve_moe", "slots": 4, "requests_per_episode": 10,
    "prompt": {"median": 4, "sigma": 0.5, "min": 2, "max": 8},
    "max_new": {"median": 8, "sigma": 0.6, "min": 2, "max": 16},
    "load_factor": 0.8, "cache_len": 24, "horizon": 80, "n_nodes": 8,
    "holders": 4, "fee": 1.0, "check": {"requests": 10},
    # set from this size's own readings on the CPU (5 seeds): the program
    # reads at most 0.023, the fp8 control at least 0.34
    "limits": {"unfinished": 0.0, "logit_gap": 0.15}}

def make_root(tmp):
    """tiny.make_root's checkout with a tiny latent-attention expert model
    served under a miniature reasoning mix."""
    from repro.configs.base import ModelConfig
    root = tiny.make_root(tmp)
    config = {"name": "tiny-mla", "source": "test", "family": "moe",
              "model": TINY_MLA, "reduced": ["n_routed_experts"],
              "param_count": ModelConfig(name="t", family="moe",
                                         **TINY_MLA).param_count()}
    (root / "bench" / "configs" / "tiny-mla.json").write_text(json.dumps(config))
    (root / "bench" / "traffic" / "reason.json").write_text(
        json.dumps(REASON_TINY))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-mla", "source": "test",
                             "file": "bench/configs/tiny-mla.json",
                             "reduced": ["n_routed_experts"], "why": "test"})
    bench["workloads"].append(
        {"name": "tiny.reason", "config": "tiny-mla", "traffic": "reason",
         "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        ws = m.get("workloads")
        if ws is None:
            continue
        if "tiny.chat" in ws:
            ws.append("tiny.reason")
        if m["name"] == "serve_expert_load":
            m["workloads"] = ["tiny.reason"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("checkout"))


def run(root, cell, trace=False):
    spec = harness.load_spec(cell, root)
    devices = harness.chip_devices(1, harness.load_peaks(root), platform="cpu")
    return harness.run_cell(spec, SEED, 0.2, trace, t_start=time.perf_counter(),
                            devices=devices, root=root)


# -- the cell as BENCHMARK.json has it -----------------------------------------
def test_new_cell_loads_with_its_metrics():
    spec = harness.load_spec("dsv2lite.serve.reason")
    assert {m["name"] for m in spec.per_layer} == {
        "idle_share.serve", "serve_mfu", "serve_step_ms", "serve_occupancy",
        "serve_expert_load"}
    assert {m["name"] for m in spec.end_to_end} == {
        "setup_s", "serve_tok_s", "serve_ttft_p95_ms"}
    assert harness.load_driver(spec.traffic["entry"]).Driver


def test_expert_share_config_is_the_published_model_but_its_experts():
    from bench.drivers import program_model
    from repro.configs import get_config
    model = program_model(CONFIG)
    published = get_config("deepseek-v2-lite")
    share = dataclasses.replace(published, n_routed_experts=8,
                                name=CONFIG["name"], source=CONFIG["source"])
    assert model.cfg == share
    assert CONFIG["param_count"] == 3_110_989_312
    assert CONFIG["published"]["param_count"] == published.param_count()
    assert CONFIG["reduced"] == ["n_routed_experts"]
    assert (CONFIG["n_routed_experts"], CONFIG["model"]["n_routed_experts"]) == (8, 8)
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = {c["name"]: c for c in spec["configs"]}["deepseek-v2-lite-ep8"]
    assert entry["reduced"] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]


def test_reason_mix_gives_its_rate_and_cache():
    n = REASON["requests_per_episode"]
    mean = (traffic.lognormal_sizes(REASON["prompt"], n).mean()
            + traffic.lognormal_sizes(REASON["max_new"], n).mean())
    assert traffic.arrival_rate(REASON) == pytest.approx(0.8 * 64 / mean)
    assert 0.14 < traffic.arrival_rate(REASON) < 0.15
    assert REASON["cache_len"] == REASON["prompt"]["max"] + REASON["max_new"]["max"] == 832
    assert REASON["slots"] == 64 and 96 <= n <= 160


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 98765432101])
def test_reason_horizon_finishes_every_request(seed):
    t = REASON
    for episode in range(8):
        ep = traffic.serve_episode(t, 102400, seed, episode)
        _, last = traffic.fifo_schedule(ep["arrivals"], ep["prompt_lens"],
                                        ep["max_new"], t["slots"])
        assert last.max() < t["horizon"]


def test_flop_counts_by_hand():
    m = CONFIG["model"]
    # MLA: 2048*16*192 + 2048*576 + 512*16*256 + 16*128*2048
    attn = 6_291_456 + 1_179_648 + 2_097_152 + 4_194_304
    assert flops_moe.mla_attention_params(m) == attn
    # 27 attention layers, the dense SwiGLU, 26 x (router + shared), unembed
    shared = 27 * attn + 67_239_936 + 26 * (131_072 + 17_301_504) + 209_715_200
    assert flops_moe.shared_matmul_params(m) == shared
    assert flops_moe.routed_expert_params(m) == 8_650_752
    assert flops_moe.decode_flops(m, 3, 5) == 2 * (3 * shared + 5 * 8_650_752)


# -- miniature whole runs -----------------------------------------------------------
@pytest.mark.parametrize("trace", [False, True])
def test_sound_program_is_correct(root, trace):
    res = run(root, "tiny.reason", trace=trace)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    if trace:
        load = res["metrics"]["serve_expert_load"]["value"]
        # 4 of 16 experts held, top-3 of 4 slots: at most 4 * 3 / 4 per step
        assert 0 < load <= 3
    assert res["device"]["memory_peak_bytes"] > 0


def test_control_and_faults_fail_the_limits(root):
    spec = harness.load_spec("tiny.reason", root)
    devices = harness.chip_devices(1, harness.load_peaks(root), platform="cpu")
    driver = harness.load_driver(spec.traffic["entry"]).Driver(spec, SEED, devices)
    driver.setup()
    driver.window(0.0)
    driver.release()
    checks = driver.check()
    assert all(v <= lim for v, lim in checks.values()), checks
    limits = spec.traffic["limits"]
    for fault, readings in driver.control().items():
        assert any(v > limits[k] for k, v in readings.items()
                   if k in limits), (fault, readings)


def test_expert_outputs_not_weighted_by_their_gates_are_caught(root, monkeypatch):
    """A planted fault in the held-expert layer: each routed expert's output
    added whole, its gate left out."""
    from repro.models import moe
    gates = moe.held_gates

    def ungated(*args, **kw):
        g, experts = gates(*args, **kw)
        return jax.numpy.ones_like(g), experts

    monkeypatch.setattr(moe, "held_gates", ungated)
    res = run(root, "tiny.reason")
    assert not res["correct"], res["checks"]


def test_expert_load_reads_nothing_without_the_counter():
    """A serving cell whose model holds no experts (or a program without the
    counter) gives the metric nothing to read: no value, no error."""
    read = harness.load_metric("serve_expert_load")
    ctx = harness.MetricContext(1, {}, {"steps": 10, "elapsed": 1.0,
                                        "occupancy": 0.5}, None)
    assert read(ctx) is None
    ctx.facts.update(expert_slots=260, held_expert_layers=26 * 8)
    assert read(ctx) == pytest.approx(0.125)
