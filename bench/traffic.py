"""The one traffic generator: every cell's inputs come from its traffic file
and ``--seed``, through the functions here.

- Swarm cells: per node and round, ``seqs_per_node`` rows of ``seq_len``
  tokens from a mixture of Markov chains (a copy of the repo's synthetic
  pipeline, ``data/pipeline.py``, kept here so that a change to the program
  cannot move the yardstick).  The sizes are fixed by the traffic file; the
  seed changes only the tokens.
- Serving cells: episodes of ``requests_per_episode`` requests.  Prompt
  lengths, decode budgets and Poisson inter-arrival gaps are stratified
  quantiles of their distributions, the same multiset for every episode,
  in an order drawn from the episode's number alone; the seed draws the
  prompt tokens.  So every seed offers the same schedule: the queue, and
  with it the tail of the time to first token, does not move with the seed.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict

import numpy as np


def seed_words(seed: int, n: int) -> np.ndarray:
    """``n`` independent 31-bit words from any non-negative integer seed
    (the driver's seeds exceed 32 signed bits)."""
    return (np.random.SeedSequence(int(seed)).generate_state(n)
            & 0x7FFFFFFF).astype(np.int64)


# -- swarm traffic ---------------------------------------------------------------
def transition_table(table_seed: int, vocab_size: int, num_states: int,
                     branch: int) -> np.ndarray:
    rng = np.random.default_rng(int(table_seed))
    return rng.integers(0, vocab_size, size=(num_states, branch)).astype(
        np.int32)


def markov_rows(key, table, n_rows: int, length: int, num_states: int,
                branch: int):
    """(n_rows, length + 1) tokens of the Markov mixture, jax-traceable in
    ``key`` (the pipeline's ``sample_tokens``)."""
    import jax
    import jax.numpy as jnp
    k1, k2 = jax.random.split(key)
    state0 = jax.random.randint(k1, (n_rows,), 0, num_states)
    choices = jax.random.randint(k2, (n_rows, length + 1), 0, branch)

    def step(state, choice):
        tok = table[state, choice]
        return tok % num_states, tok

    _, toks = jax.lax.scan(step, state0, choices.T)
    return toks.T


def swarm_batches(traffic: Dict, vocab_size: int, seed: int):
    """``(node_fn, batched_fn)``: ``node_fn(node, rnd)`` is one node's batch
    and ``batched_fn(rnd)`` the (N, ...) stack of all of them, both pure in
    (seed, round, node)."""
    import jax
    import jax.numpy as jnp
    n = len(traffic["roster"])
    rows, length = traffic["seqs_per_node"], traffic["seq_len"]
    data = traffic["data"]
    data_seed, table_seed = seed_words(seed, 3)[1:]
    table = jnp.asarray(transition_table(table_seed, vocab_size,
                                         data["num_states"], data["branch"]))
    base = jax.random.PRNGKey(int(data_seed))

    def batch(table, base, node, rnd):
        key = jax.random.fold_in(jax.random.fold_in(base, rnd), node)
        toks = markov_rows(key, table, rows, length, data["num_states"],
                           data["branch"])
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    # the seed's table and key are arguments, not constants of the program,
    # so one compiled feed serves every seed from the compile cache
    stacked = jax.jit(lambda table, base, rnd: jax.vmap(
        lambda i: batch(table, base, i, rnd))(jnp.arange(n)))

    def node_fn(node, rnd):
        return batch(table, base, node, rnd)

    def batched_fn(rnd):
        return stacked(table, base, rnd)

    return node_fn, batched_fn


def tokens_per_round(traffic: Dict) -> int:
    return len(traffic["roster"]) * traffic["seqs_per_node"] * traffic["seq_len"]


# -- serving traffic -------------------------------------------------------------
def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_sizes(spec: Dict, n: int) -> np.ndarray:
    """Stratified quantiles of a log-normal (median, sigma), clipped to
    [min, max] and rounded: the same ``n`` sizes for every seed."""
    z = np.asarray([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    sizes = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(sizes, spec["min"], spec["max"]).astype(np.int32)


def arrival_rate(traffic: Dict) -> float:
    """Requests per engine step: ``load_factor`` of the slots' capacity,
    ``slots / E[prompt + max_new]`` with E over the drawn sizes."""
    n = traffic["requests_per_episode"]
    mean_work = float(np.mean(lognormal_sizes(traffic["prompt"], n))
                      + np.mean(lognormal_sizes(traffic["max_new"], n)))
    return traffic["load_factor"] * traffic["slots"] / mean_work


def interarrival_gaps(traffic: Dict) -> np.ndarray:
    """Stratified quantiles of the exponential gap of a Poisson stream."""
    n = traffic["requests_per_episode"]
    rate = arrival_rate(traffic)
    return np.asarray([-math.log1p(-q) / rate for q in _quantiles(n)])


def serve_episode(traffic: Dict, vocab_size: int, seed: int,
                  episode: int) -> Dict[str, np.ndarray]:
    """One episode's requests: ``arrivals`` (sorted engine steps),
    ``prompt_lens``, ``max_new`` and ``prompts`` (R, prompt max) int32."""
    n = traffic["requests_per_episode"]
    order = np.random.default_rng(int(episode))
    plens = order.permutation(lognormal_sizes(traffic["prompt"], n))
    budgets = order.permutation(lognormal_sizes(traffic["max_new"], n))
    gaps = order.permutation(interarrival_gaps(traffic))
    arrivals = np.floor(np.concatenate([[0.0], np.cumsum(gaps[:-1])]))
    word = seed_words(seed, 2)[1]
    rng = np.random.default_rng([int(word), int(episode)])
    prompts = rng.integers(0, vocab_size,
                           size=(n, traffic["prompt"]["max"])).astype(np.int32)
    return {"arrivals": arrivals.astype(np.int32),
            "prompt_lens": plens.astype(np.int32),
            "max_new": budgets.astype(np.int32),
            "prompts": prompts}


def fifo_schedule(arrivals, prompt_lens, max_new, slots: int):
    """Host simulation of the engine's admission: fixed slots, FIFO by
    (arrival, index), a slot freed in step t admits from step t + 1.
    Returns (admission step, last step) per request."""
    n = len(arrivals)
    admit = np.full(n, -1, np.int64)
    last = np.full(n, -1, np.int64)
    free_at = []                       # steps at which busy slots free
    nxt, t = 0, 0
    while nxt < n:
        free_at = [f for f in free_at if f > t]
        while nxt < n and arrivals[nxt] <= t and len(free_at) < slots:
            admit[nxt] = t
            last[nxt] = t + prompt_lens[nxt] + max_new[nxt] - 2
            free_at.append(last[nxt] + 1)
            nxt += 1
        t += 1
    return admit, last
