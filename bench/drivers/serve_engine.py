"""Serving cells: ``ServingEngine.run``, one scanned episode per call.

Set-up makes the weights from the seed, builds the engine at the traffic's
slot count, budget, horizon and cache length, and serves episode 0 (which
compiles).  The window serves episodes 1, 2, ... until it has lasted
``--seconds``.  Each request's time to first token is

    (admission step + prompt length - 1 - arrival step + 1) x wall / steps

of its episode: every request is funded and no custody halts the engine,
so it admits in FIFO order and ``ServeRecord.n_admitted`` gives each
request's admission step; the engine's steps are fixed-shape, so each costs
the same.  After the window, a sample of the finished requests drawn from
the seed, the longest among them, is run through the plain reference
(``reference/dense_lm.py``) over prompt and served tokens; the check is
the widest gap by which a served token's logit lies below the reference's
best at its position.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import numpy as np

from bench import compare, flops, traffic as traffic_mod
from bench.drivers import Window, check_layout, program_model
from bench.harness import annotate
from bench.reference import dense_lm
from bench.weights import make_params

#: the logit gap reported when no request finished: no served token could
#: be compared, which fails any limit
NOTHING_SERVED = 1e9


def first_token_steps(arrivals, prompt_lens, n_admitted) -> np.ndarray:
    """Each request's engine steps from arrival to its first token,
    inclusive, from the per-step admission counts (FIFO by arrival step,
    then request index)."""
    arrivals = np.asarray(arrivals)
    order = np.lexsort((np.arange(len(arrivals)), arrivals))
    rank_step = np.searchsorted(np.cumsum(n_admitted), np.arange(len(order)),
                                side="right")
    admit = np.empty(len(order), np.int64)
    admit[order] = rank_step
    return admit + np.asarray(prompt_lens) - 1 - arrivals + 1


class Driver:
    def __init__(self, spec, seed: int, devices):
        self.seed = seed
        self.config, self.traffic = spec.config, spec.traffic
        self.served: List = []
        self._sample = None

    # -- program ------------------------------------------------------------------
    def setup(self) -> None:
        import jax.numpy as jnp
        from repro.core.serving import ServingConfig, ServingEngine

        t = self.traffic
        model = program_model(self.config)
        self.params = make_params(self.config, self.seed)
        check_layout(self.params, model)
        self.vocab = self.config["model"]["vocab_size"]
        self.horizon = t["horizon"]
        cfg = ServingConfig(slots=t["slots"], max_new=t["max_new"]["max"],
                            steps=self.horizon, cache_len=t["cache_len"])
        ep = traffic_mod.serve_episode(t, self.vocab, self.seed, 0)
        self.engine = ServingEngine(model, cfg, jnp.asarray(ep["prompts"]))
        self._serve(ep)                        # compiles; not measured
        self.served = []
        self.episode = 1

    def _serve(self, ep):
        import jax.numpy as jnp
        from repro.core.serving import build_lane

        t = self.traffic
        n = t["requests_per_episode"]
        with annotate("bench.serve.lane_build"):
            lane = build_lane(
                n_requests=n, prompt_lens=ep["prompt_lens"],
                max_new=ep["max_new"], steps=self.horizon,
                n_nodes=t["n_nodes"], arrivals=ep["arrivals"],
                balances=[t["fee"] * n + 1.0] * t["holders"], fee=t["fee"])
            prompts = jnp.asarray(ep["prompts"])
        with annotate("bench.serve.run"):
            res = self.engine.run(self.params, lane, prompts)
        self.served.append((ep, res))
        self._args = (lane, prompts)

    def window(self, seconds: float) -> Window:
        t0 = time.perf_counter()
        while not self.served or time.perf_counter() - t0 < seconds:
            self._serve(traffic_mod.serve_episode(
                self.traffic, self.vocab, self.seed, self.episode))
            self.episode += 1
        elapsed = time.perf_counter() - t0
        attempted = sum(len(ep["arrivals"]) for ep, _ in self.served)
        failed = sum(int(np.sum(~res.done)) for _, res in self.served)
        return Window(elapsed, attempted, failed,
                      self.horizon * len(self.served))

    def program_bytes(self) -> int:
        """What the window's episode program holds at once, by the compiler."""
        from bench.harness import compiled_bytes
        program = self.engine.program(has_custody=False, vmapped=False)
        return compiled_bytes(program.lower(self.params, self._args[1],
                                            self._args[0]).compile())

    def release(self) -> None:
        del self.engine, self._args
        gc.collect()

    # -- metrics ------------------------------------------------------------------
    def ttft_ms(self) -> np.ndarray:
        out = []
        for ep, res in self.served:
            steps = first_token_steps(ep["arrivals"], ep["prompt_lens"],
                                      res.n_admitted)
            out.append(steps * res.wall_s / self.horizon * 1e3)
        return np.concatenate(out)

    def end_to_end(self, w: Window) -> Dict[str, float]:
        tokens = sum(res.tokens_served for _, res in self.served)
        return {"serve_tok_s": tokens / w.elapsed,
                "serve_ttft_p95_ms": float(np.percentile(self.ttft_ms(), 95))}

    def facts(self, w: Window) -> Dict:
        occupied = sum(int(np.sum(res.n_active)) for _, res in self.served)
        return {"steps": w.steps, "elapsed": w.elapsed,
                "occupancy": occupied / (self.traffic["slots"] * w.steps),
                "engine_wall_s": sum(res.wall_s for _, res in self.served),
                "flops": occupied * flops.decode_flops_per_token(
                    self.config["model"])}

    # -- correctness --------------------------------------------------------------
    def sample(self):
        """(tokens (K, T), target positions mask, served tokens) of the
        sampled finished requests: the longest of the window, and others
        drawn from the seed; each row is prompt + served tokens but the
        last, padded to the cache length."""
        if self._sample is not None:
            return self._sample
        t = self.traffic
        done = [(i, j) for i, (_, res) in enumerate(self.served)
                for j in np.flatnonzero(res.done)]
        if not done:
            return None
        size = lambda ij: (self.served[ij[0]][0]["prompt_lens"][ij[1]]
                           + self.served[ij[0]][0]["max_new"][ij[1]])
        longest = max(done, key=size)
        rng = np.random.default_rng(int(traffic_mod.seed_words(self.seed, 5)[4]))
        rest = [d for d in done if d != longest]
        k = min(t["check"]["requests"] - 1, len(rest))
        picked = [longest] + [rest[i] for i in rng.choice(len(rest), k,
                                                          replace=False)]
        width = t["cache_len"]
        rows = np.zeros((len(picked), width), np.int32)
        target = np.zeros((len(picked), width), np.int32)
        where = np.zeros((len(picked), width), bool)
        for r, (i, j) in enumerate(picked):
            ep, res = self.served[i]
            plen, budget = ep["prompt_lens"][j], ep["max_new"][j]
            toks = res.tokens[j, :budget]
            seq = np.concatenate([ep["prompts"][j, :plen], toks[:-1]])
            rows[r, :len(seq)] = seq
            target[r, plen - 1: plen - 1 + budget] = toks
            where[r, plen - 1: plen - 1 + budget] = True
        self._sample = (rows, target, where)
        return self._sample

    def reference_logits(self, rows, *, fp8: bool = False) -> np.ndarray:
        import jax
        fwd = jax.jit(lambda p, x: dense_lm.forward(
            p, x, self.config["model"], fp8=fp8))
        return np.asarray(fwd(self.params, rows))

    def check(self) -> Dict[str, tuple]:
        limits = self.traffic["limits"]
        unfinished = float(sum(int(np.sum(~res.done))
                               for _, res in self.served))
        if self.sample() is None:               # nothing served to compare
            return {"unfinished": (unfinished, limits["unfinished"]),
                    "logit_gap": (NOTHING_SERVED, limits["logit_gap"])}
        rows, target, where = self.sample()
        self._logits = self.reference_logits(rows)
        gap = float(compare.logit_gaps(self._logits, target)[where].max())
        return {"unfinished": (unfinished, limits["unfinished"]),
                "logit_gap": (gap, limits["logit_gap"])}

    def control(self) -> Dict[str, Dict[str, float]]:
        """The control (the reference in fp8 at the same positions, its own
        best token read against the reference) and a planted fault (each
        sampled served token replaced by the next id)."""
        rows, target, where = self.sample()
        fp8 = self.reference_logits(rows, fp8=True)
        control = compare.logit_gaps(self._logits, fp8.argmax(-1))[where].max()
        altered = compare.logit_gaps(self._logits,
                                     (target + 1) % self.vocab)[where].max()
        return {"fp8": {"logit_gap": float(control)},
                "altered_token": {"logit_gap": float(altered)}}
