"""The FLOP and byte counters against hand counts at the cells' shapes, and
the traffic generator's promises."""
import json
from pathlib import Path

import numpy as np
import pytest

from bench import flops, traffic

BENCH = Path(__file__).resolve().parents[1]
P125M = json.loads((BENCH / "configs" / "protocol-125m.json").read_text())["model"]
DANUBE = json.loads((BENCH / "configs" / "h2o-danube-1.8b.json").read_text())["model"]
TRAFFIC = {n: json.loads((BENCH / "traffic" / f"{n}.json").read_text())
           for n in ("derail8", "honest_long", "chat")}


def test_matmul_params_by_hand():
    # 12 x (768*64*(12+12+12+12) + 3*768*3072) + 768*32000
    assert flops.matmul_params(P125M) == 12 * (2_359_296 + 7_077_888) + 24_576_000
    # 24 x (2560*80*(32+32+8+8) + 3*2560*6912) + 2560*32000
    assert flops.matmul_params(DANUBE) == 24 * (16_384_000 + 53_084_160) + 81_920_000
    assert flops.decode_flops_per_token(DANUBE) == 3_498_311_680


@pytest.mark.parametrize("name,rows,seq", [("derail8", 16, 128),
                                            ("honest_long", 32, 512)])
def test_swarm_round_flops_by_hand(name, rows, seq):
    per_token = 6 * 137_822_208 + 12 * 12 * 12 * 64 * seq
    assert flops.swarm_round_flops(P125M, TRAFFIC[name]) == rows * seq * per_token
    assert traffic.tokens_per_round(TRAFFIC[name]) == rows * seq


def test_centered_clip_kernel_counts_by_hand():
    d, n = 162_417_408, 8
    stack, row = 4 * n * d, 4 * d                # 5,197,357,056 / 649,669,632
    got = flops.centered_clip_kernel_bytes(n, d, 3)
    assert got["median"] == 5_847_026_720 == stack + 32 + row
    assert got["iteration"] == 12_343_723_008 == 2 * (stack + row) + row
    assert got["round"] == got["median"] + 3 * got["iteration"]
    assert [flops.batcher_pairs(k) for k in (2, 4, 8, 16)] == [1, 5, 19, 63]
    assert flops.centered_clip_kernel_flops(n, d, 3) == (38 + 3 * 56) * d


def test_serving_sizes_are_one_multiset_in_seed_order():
    # one multiset in every episode, in the episode's own order whatever
    # the seed; the seed draws the prompt tokens
    t = TRAFFIC["chat"]
    a = traffic.serve_episode(t, 32000, 7, 1)
    b = traffic.serve_episode(t, 32000, 2**31 + 12345, 3)
    c = traffic.serve_episode(t, 32000, 2**31 + 12345, 1)
    for key in ("prompt_lens", "max_new"):
        assert sorted(a[key]) == sorted(b[key])
        assert not np.array_equal(a[key], b[key])
    for key in ("prompt_lens", "max_new", "arrivals"):
        np.testing.assert_array_equal(a[key], c[key])
    assert not np.array_equal(a["prompts"], c["prompts"])
    assert np.all(np.diff(a["arrivals"]) >= 0) and a["arrivals"][0] == 0
    assert a["prompts"].shape == (t["requests_per_episode"], t["prompt"]["max"])
    assert a["prompts"].max() < 32000
    np.testing.assert_array_equal(
        a["prompts"], traffic.serve_episode(t, 32000, 7, 1)["prompts"])


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 5, 98765432101])
def test_chat_horizon_finishes_every_request(seed):
    t = TRAFFIC["chat"]
    for episode in range(4):
        ep = traffic.serve_episode(t, 32000, seed, episode)
        _, last = traffic.fifo_schedule(ep["arrivals"], ep["prompt_lens"],
                                        ep["max_new"], t["slots"])
        assert last.max() < t["horizon"]


def test_arrival_rate_is_four_fifths_of_slot_capacity():
    t = TRAFFIC["chat"]
    n = t["requests_per_episode"]
    mean = (traffic.lognormal_sizes(t["prompt"], n).mean()
            + traffic.lognormal_sizes(t["max_new"], n).mean())
    assert traffic.arrival_rate(t) == pytest.approx(0.8 * t["slots"] / mean)


def test_chat_drain_is_a_small_share_of_the_episode():
    # arrivals span at least 84% of the horizon: the load holds over all
    # but the drain, and the slots are at least 65% occupied on average
    t = TRAFFIC["chat"]
    ep = traffic.serve_episode(t, 32000, 2**31 + 1, 1)
    assert ep["arrivals"].max() >= 0.84 * t["horizon"]
    work = np.sum(ep["prompt_lens"] + ep["max_new"] - 1)
    assert work >= 0.65 * t["slots"] * t["horizon"]
    assert t["prompt"]["max"] + t["max_new"]["max"] <= t["cache_len"]


def test_swarm_batches_depend_on_seed_round_and_node_only():
    t = dict(TRAFFIC["derail8"], seqs_per_node=2, seq_len=16)
    _, batched = traffic.swarm_batches(t, 32000, 2**31 + 9)
    _, again = traffic.swarm_batches(t, 32000, 2**31 + 9)
    x, y = batched(3), again(3)
    np.testing.assert_array_equal(x["tokens"], y["tokens"])
    assert x["tokens"].shape == (8, 2, 16)
    rows = np.asarray(x["tokens"]).reshape(16, 16)
    assert len({r.tobytes() for r in rows}) == 16          # rows all differ
    assert not np.array_equal(batched(4)["tokens"], x["tokens"])
