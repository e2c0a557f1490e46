"""Compute verification (paper §4.2).

The paper rejects proof-of-learning for frontier workloads (numerical
nondeterminism [20, 73]) and lands on *game-theoretic* verification:
contributors stake capital; validators recompute a random subset of claimed
gradients and slash on mismatch beyond a tolerance; jackpots incentivize
validation [41, 66].

This module implements that mechanism over real gradients, with the
real-world numerical spread *simulated* as configurable noise (this
container's XLA/CPU is deterministic — DESIGN.md §2).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


@dataclass(frozen=True)
class VerificationConfig:
    """Audit-game parameters.

    ``p_check`` / ``tolerance`` / ``numeric_noise`` may be **array-valued**
    (including jax tracers): the swarm campaign engine sweeps them as traced
    per-run lanes, so one compiled program serves every audit regime —
    ``p_check == 0`` disables auditing.  ``stake`` / ``jackpot`` /
    ``reward_per_step`` are host-side economics consumed by the ledger and
    stay Python floats.  Jackpots are funded from the slashed-stake pool,
    never minted (``Ledger.pay_jackpot`` caps the payout by the pool;
    ``economy.econ_round_update`` applies the same cap on device), so a
    validator can never be paid more than cheaters actually forfeited —
    keep ``jackpot <= stake`` unless under-funded jackpots are the point.
    """
    p_check: "float | Array" = 0.1   # probability a given update is audited
    stake: float = 10.0              # capital locked per contributor
    reward_per_step: float = 1.0     # shares minted per verified step
    tolerance: "float | Array" = 1e-3   # relative mismatch tolerated
    jackpot: float = 5.0             # validator reward for a catch
                                     # (pool-capped — see class docstring)
    numeric_noise: "float | Array" = 1e-5  # simulated cross-stack nondeterminism


def relative_mismatch(claimed, recomputed) -> Array:
    """‖claimed − recomputed‖ / ‖recomputed‖ over the full update pytree."""
    c = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in jax.tree.leaves(claimed)])
    r = jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in jax.tree.leaves(recomputed)])
    return jnp.linalg.norm(c - r) / jnp.maximum(jnp.linalg.norm(r), 1e-30)


def _perturbed(recomputed, key: Array, cfg: VerificationConfig):
    """Add the simulated cross-stack numeric spread to a recomputed pytree.

    The key is ``fold_in``-ed per leaf — one shared key would draw the *same*
    noise pattern on every same-shaped leaf (correlated "nondeterminism",
    unlike the independent per-node keys ``audit_flat`` receives), which
    systematically under-disperses the mismatch statistic on multi-leaf
    trees.  Leaf i of a flattened (single-leaf) tree sees exactly the noise
    ``audit_flat`` would draw from ``fold_in(key, 0)``.
    """
    leaves, treedef = jax.tree.flatten(recomputed)
    noisy = [
        x + cfg.numeric_noise
        * jax.random.normal(jax.random.fold_in(key, i), x.shape, jnp.float32)
        * jnp.linalg.norm(x.astype(jnp.float32)) / np.sqrt(max(1, x.size))
        for i, x in enumerate(leaves)
    ]
    return jax.tree.unflatten(treedef, noisy)


def audit(claimed, recompute_fn: Callable[[], object], cfg: VerificationConfig,
          key: Array) -> tuple[bool, Array]:
    """Recompute the work and compare.  Returns (passes, mismatch).

    ``recompute_fn`` re-runs the gradient; simulated nondeterminism is added
    (one independent draw per leaf — see :func:`_perturbed`) so honest work
    shows a small nonzero mismatch — the tolerance must absorb it (paper:
    proofs fail precisely because this spread exists).
    """
    noisy = _perturbed(recompute_fn(), key, cfg)
    mm = relative_mismatch(claimed, noisy)
    return bool(mm <= cfg.tolerance), mm


def audit_flat(claimed: Array, recomputed: Array, key: Array,
               cfg: VerificationConfig) -> tuple[Array, Array]:
    """§4.2 audit over flat fp32 update vectors — the ONE noise-and-compare
    formula both swarm engines use, so that with a shared key they reach the
    same pass/slash decision even at the tolerance boundary.  Returns
    ``(passes, mismatch)`` (0-d bool/float arrays; jit-safe)."""
    d = claimed.shape[-1]
    noisy = recomputed + (cfg.numeric_noise
                          * jax.random.normal(key, recomputed.shape, jnp.float32)
                          * jnp.linalg.norm(recomputed) / np.sqrt(max(1, d)))
    mm = jnp.linalg.norm(claimed - noisy) / jnp.maximum(
        jnp.linalg.norm(noisy), 1e-30)
    return mm <= cfg.tolerance, mm


def audit_batch(claimed: Array, recomputed: Array, keys: Array,
                cfg: VerificationConfig) -> tuple[Array, Array]:
    """Vectorized :func:`audit_flat` over fixed (N, D) stacks — per-node
    claimed vs validator-recomputed updates, one noise key per node.
    jit/vmap-safe — the batched engine evaluates every node each round and
    selects the audited subset with a boolean mask."""
    return jax.vmap(lambda c, r, k: audit_flat(c, r, k, cfg))(
        claimed, recomputed, keys)


# -- economics (paper §4.2 / §5.5) ---------------------------------------------
def expected_cheat_value(gain_per_step: float, cfg: VerificationConfig) -> float:
    """E[value of submitting fake work for one step]."""
    return gain_per_step - cfg.p_check * cfg.stake


def honest_value(cost_per_step: float, cfg: VerificationConfig) -> float:
    return cfg.reward_per_step - cost_per_step


def cheating_irrational(gain_per_step: float, cfg: VerificationConfig) -> bool:
    """The protocol is incentive-secure when cheating has non-positive EV.

    The boundary (EV exactly 0) counts as irrational: faking work has
    strictly positive effort cost the EV formula doesn't price, so zero
    expected gain already loses to honesty.  This is also what makes
    :func:`min_p_check`'s "smallest sufficient audit rate" actually
    sufficient at the boundary instead of one ulp short."""
    return expected_cheat_value(gain_per_step, cfg) <= 0


def min_p_check(gain_per_step: float, stake: float) -> float:
    """Smallest audit rate making cheating irrational for a given stake.

    Guaranteed sufficient *in floating point*: the quotient
    ``gain / stake`` is nudged up by ulps until ``p * stake >= gain``
    (division and multiplication each round, so the raw quotient can land
    a hair below break-even), hence
    ``cheating_irrational(gain, VerificationConfig(p_check=p, stake=s))``
    holds for the returned ``p`` whenever any rate <= 1 suffices —
    property-tested over random (gain, stake) in tests/test_properties.py.
    Non-positive gain needs no auditing at all (rate 0)."""
    if gain_per_step <= 0.0:
        return 0.0
    p = gain_per_step / max(stake, 1e-12)   # may underflow to 0.0
    while p < 1.0 and p * stake < gain_per_step:
        p = math.nextafter(p, 1.0)
    return min(1.0, p)


def validator_ev(cost_of_audit: float, p_cheater: float, cfg: VerificationConfig) -> float:
    """Validators audit iff jackpot × catch-rate exceeds audit cost."""
    return p_cheater * cfg.jackpot - cost_of_audit
