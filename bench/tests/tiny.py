"""A checkout in miniature for the CPU tests: ``BENCHMARK.json`` with tiny
cells of both entries, their configuration and traffic files, the real
metric readers, and a peaks row for the CPU device."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

TINY_LM = {
    "num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
    "head_dim": 16, "d_ff": 96, "vocab_size": 256, "tie_embeddings": False,
    "sliding_window": None, "rope_theta": 10000.0, "norm_eps": 1e-05,
    "dtype": "bfloat16", "max_seq_len": 64}

SWARM = {
    "entry": "swarm_step",
    "roster": [{"node_id": "h0"}, {"node_id": "h1"}, {"node_id": "h2"},
               {"node_id": "adv0", "byzantine": "inner_product",
                "byzantine_scale": 50.0}],
    "aggregator": "centered_clip", "agg_kwargs": {"iters": 3},
    "verification": {"p_check": 0.5, "stake": 10.0, "tolerance": 0.001,
                     "jackpot": 5.0, "numeric_noise": 1e-05},
    "compression": None, "swarm_seed": 0, "fused": False,
    "optimizer": {"lr": 0.005, "b1": 0.9, "b2": 0.95, "eps": 1e-08,
                  "weight_decay": 0.1, "clip_norm": 1.0},
    "seqs_per_node": 2, "seq_len": 16,
    "data": {"num_states": 8, "branch": 4},
    "check_steps": 3,
    # set from this size's own readings on the CPU: the program reads
    # grad_gap 1.4e-3, the fp8 control 2.0e-2
    "limits": {"caught_mismatch": 0.0, "agg_norm_gap": 0.05,
               "grad_gap": 0.005, "change_gap": 0.05}}

CHAT = {
    "entry": "serve_engine", "slots": 4, "requests_per_episode": 12,
    "prompt": {"median": 6, "sigma": 0.8, "min": 2, "max": 12},
    "max_new": {"median": 4, "sigma": 0.8, "min": 1, "max": 8},
    "load_factor": 0.8, "cache_len": 20, "horizon": 64, "n_nodes": 8,
    "holders": 4, "fee": 1.0, "check": {"requests": 12},
    "limits": {"unfinished": 0.0, "logit_gap": 0.13}}     # the chat cell's

PEAKS = {"source": "test values", "devices": {"cpu": {
    "bf16_flops_per_s": 1e12, "int8_ops_per_s": 2e12,
    "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10, "ici_bits_per_s": 0.0}}}


def param_count(model):
    from repro.configs.base import ModelConfig
    return ModelConfig(name="tiny", family="dense", **model).param_count()


def make_root(tmp: Path, *, swarm=None, chat=None, window=16) -> Path:
    """Write the miniature checkout under ``tmp``; returns its root."""
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir()
    shutil.copytree(BENCH / "metrics", tmp / "bench" / "metrics")
    (tmp / "bench" / "peaks.json").write_text(json.dumps(PEAKS))
    configs = []
    for name, window_ in (("tiny-lm", None), ("tiny-swa", window)):
        model = dict(TINY_LM, sliding_window=window_)
        cfg = {"name": name, "source": "test", "family": "dense",
               "model": model, "param_count": param_count(model)}
        (tmp / "bench" / "configs" / f"{name}.json").write_text(json.dumps(cfg))
        configs.append({"name": name, "source": "test",
                        "file": f"bench/configs/{name}.json", "reduced": [],
                        "why": "test"})
    (tmp / "bench" / "traffic" / "swarm.json").write_text(
        json.dumps(swarm or SWARM))
    (tmp / "bench" / "traffic" / "chat.json").write_text(
        json.dumps(chat or CHAT))
    bench = {
        "configs": configs,
        "workloads": [
            {"name": "tiny.swarm", "config": "tiny-lm", "traffic": "swarm",
             "chips": 1, "why": "test"},
            {"name": "tiny.chat", "config": "tiny-swa", "traffic": "chat",
             "chips": 1, "why": "test"}],
        "end_to_end": json.loads((BENCH.parent / "BENCHMARK.json").read_text()
                                 )["end_to_end"],
        "per_layer": json.loads((BENCH.parent / "BENCHMARK.json").read_text()
                                )["per_layer"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = (["tiny.swarm"] if "swarm" in m["name"]
                              or m["name"] == "masked_agg_roofline"
                              else ["tiny.chat"])
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp
