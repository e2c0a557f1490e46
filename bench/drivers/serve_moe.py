"""Serving cells of the latent-attention expert model: ``ServingEngine.run``
as ``serve_engine`` drives it (its episode, time to first token and
logit-gap check are imported), with this configuration's weights
(``weights_mla.py``) and plain reference (``reference/mla_moe_lm.py``,
run in blocks of requests so that it fits beside the weights).  Set-up
compiles the episode program ahead of time (``ServingEngine.compile``)
instead of serving a warm-up episode: the window's episodes call that
executable, and nothing compiles inside the window.

``facts`` add the engine's per-step count of token-slots routed to held
experts (``ServeResult.stats["expert_slots"]``) and count the step's
FLOPs from the weights every token passes through and the routed
token-slots.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from bench import flops_moe, traffic as traffic_mod
from bench.drivers import Window, check_layout, program_model
from bench.drivers import serve_engine
from bench.reference import mla_moe_lm
from bench.weights_mla import make_params

#: requests the reference forwards at once
REFERENCE_BLOCK = 4


class Driver(serve_engine.Driver):
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
        self.engine.compile(self.params, self._lane(ep))
        self.served = []
        self.episode = 1                       # the window's episodes: 1, 2, ...

    def _lane(self, ep):
        from repro.core.serving import build_lane
        t = self.traffic
        n = t["requests_per_episode"]
        return build_lane(
            n_requests=n, prompt_lens=ep["prompt_lens"], max_new=ep["max_new"],
            steps=self.horizon, n_nodes=t["n_nodes"], arrivals=ep["arrivals"],
            balances=[t["fee"] * n + 1.0] * t["holders"], fee=t["fee"])

    def facts(self, w: Window) -> Dict:
        m = self.config["model"]
        occupied = sum(int(np.sum(res.n_active)) for _, res in self.served)
        routed = sum(int(np.sum(res.stats["expert_slots"]))
                     for _, res in self.served)
        return {"steps": w.steps, "elapsed": w.elapsed,
                "occupancy": occupied / (self.traffic["slots"] * w.steps),
                "engine_wall_s": sum(res.wall_s for _, res in self.served),
                "flops": flops_moe.decode_flops(m, occupied, routed),
                "expert_slots": routed,
                "held_expert_layers": (m["num_layers"] - m["first_k_dense"])
                * m["n_routed_experts"]}

    def reference_logits(self, rows, *, fp8: bool = False) -> np.ndarray:
        import jax
        fwd = jax.jit(lambda p, x: mla_moe_lm.forward(
            p, x, self.config["model"], fp8=fp8))
        return np.concatenate([
            np.asarray(fwd(self.params, rows[i:i + REFERENCE_BLOCK]))
            for i in range(0, len(rows), REFERENCE_BLOCK)])
