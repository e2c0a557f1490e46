"""Swarm cells: the batched engine's ``Swarm.step``, one round per call.

Set-up builds the one swarm object from the seed (weights, roster, audit
game, optimizer, data feed), and drives it through its first
``check_steps`` rounds: the first compiles, and they are the rounds the
reference follows.  The window goes on with the same object.  After the
window the plain reference (``reference/swarm_round.py``) replays those
first rounds, and the check compares each round's aggregate norm and caught
nodes, the first gradient as the optimizer got it (from Adam's first moment
after one step), and the parameters' change after the last of them, leaf by
leaf.
"""
from __future__ import annotations

import gc
import time
from typing import Dict

import numpy as np

from bench import compare, flops, traffic as traffic_mod
from bench.drivers import Window, check_layout, program_model
from bench.harness import BenchError, annotate
from bench.reference import swarm_round
from bench.weights import make_params


class Driver:
    def __init__(self, spec, seed: int, devices):
        self.seed = seed
        self.config, self.traffic = spec.config, spec.traffic
        self.steps = self.traffic["check_steps"]
        self._reference = None

    # -- program ------------------------------------------------------------------
    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.core.swarm import NodeSpec, SwarmConfig, make_swarm
        from repro.core.verification import VerificationConfig
        from repro.optim.optimizer import AdamW

        t = self.traffic
        model = program_model(self.config)
        params = make_params(self.config, self.seed)
        check_layout(params, model)
        audit = t.get("verification")
        cfg = SwarmConfig(
            aggregator=t["aggregator"], agg_kwargs=dict(t["agg_kwargs"]),
            verification=VerificationConfig(**audit) if audit else None,
            compression=t.get("compression"), seed=t["swarm_seed"])
        node_fn, batched = traffic_mod.swarm_batches(
            t, self.config["model"]["vocab_size"], self.seed)

        def feed(rnd):
            with annotate("bench.swarm.batch_build"):
                return batched(rnd)

        self.swarm = make_swarm(
            lambda p, b: model.loss(p, b)[0], params, AdamW(**t["optimizer"]),
            [NodeSpec(**r) for r in t["roster"]], cfg, node_fn,
            engine="batched", batched_data_fn=feed)
        if self.swarm.fused != t["fused"]:
            raise BenchError(f"the round resolved fused={self.swarm.fused}, "
                             f"the traffic file expects {t['fused']}")
        b1 = t["optimizer"]["b1"]
        for rnd in range(self.steps):
            with annotate("bench.swarm.step"):
                self.swarm.step(rnd)
            if rnd == 0:
                self.grad = swarm_round.leaf_norms(jax.tree.map(
                    lambda m: m / (1.0 - b1), self.swarm.opt_state.m))
        p0 = make_params(self.config, self.seed)
        self.change = swarm_round.leaf_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            self.swarm.params, p0))
        del p0, params
        ids = [r["node_id"] for r in t["roster"]]
        self.agg_norm = [h["agg_norm"] for h in self.swarm.history]
        self.caught = [np.isin(ids, h["caught"]) for h in self.swarm.history]
        self.round = self.steps

    def window(self, seconds: float) -> Window:
        import jax
        t0 = time.perf_counter()
        rounds = 0
        while rounds == 0 or time.perf_counter() - t0 < seconds:
            with annotate("bench.swarm.step"):
                self.swarm.step(self.round)
            self.round += 1
            rounds += 1
        jax.block_until_ready(self.swarm.params)
        return Window(time.perf_counter() - t0, rounds, 0, rounds)

    def program_bytes(self) -> int:
        """What the window's round program holds at once, by the compiler."""
        from bench.harness import compiled_bytes
        return compiled_bytes(self.swarm.lower_step(self.round).compile())

    def release(self) -> None:
        del self.swarm
        gc.collect()

    # -- metrics ------------------------------------------------------------------
    def end_to_end(self, w: Window) -> Dict[str, float]:
        return {"swarm_tok_s": w.steps * traffic_mod.tokens_per_round(
            self.traffic) / w.elapsed}

    def facts(self, w: Window) -> Dict:
        t = self.traffic
        d = self.config["param_count"]
        n = len(t["roster"])
        iters = t["agg_kwargs"].get("iters", 3)
        out = {"steps": w.steps, "elapsed": w.elapsed,
               "flops_per_step": flops.swarm_round_flops(
                   self.config["model"], t)}
        if t["fused"] and t["aggregator"] == "centered_clip":
            out["kernel_bytes_per_step"] = flops.centered_clip_kernel_bytes(
                n, d, iters)["round"]
            out["kernel_flops_per_step"] = flops.centered_clip_kernel_flops(
                n, d, iters)
        return out

    # -- correctness --------------------------------------------------------------
    def reference(self, **fault) -> Dict:
        return swarm_round.run(self.config, self.traffic, self.seed,
                               self.steps, **fault)

    def readings(self, prog: Dict, ref: Dict) -> Dict[str, float]:
        """The compared numbers of ``prog`` (the program, or the reference
        put in its place) against the reference ``ref``."""
        caught = sum(int(np.sum(np.asarray(a) != np.asarray(b)))
                     for a, b in zip(prog["caught"], ref["caught"]))
        return {
            "caught_mismatch": float(caught),
            "agg_norm_gap": compare.relative_gap(prog["agg_norm"],
                                                 ref["agg_norm"]),
            "grad_gap": compare.worst_leaf_gap(prog["grad"], ref["grad"]),
            "change_gap": compare.worst_leaf_gap(
                prog["change"], ref["change"],
                keep=compare.moved_leaves(ref["grad"])),
        }

    def check(self) -> Dict[str, tuple]:
        self._reference = self.reference()
        prog = {"caught": self.caught, "agg_norm": self.agg_norm,
                "grad": self.grad, "change": self.change}
        limits = self.traffic["limits"]
        return {k: (v, limits[k])
                for k, v in self.readings(prog, self._reference).items()
                if k in limits}

    def control(self) -> Dict[str, Dict[str, float]]:
        """Readings of the control (the reference in fp8) and of a planted
        fault (half of each node's batch left out) against the reference."""
        return {name: self.readings(self.reference(**fault), self._reference)
                for name, fault in (("fp8", {"fp8": True}),
                                    ("half_batch", {"half_batch": True}))}
