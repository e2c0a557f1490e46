"""Unified model API: ``build_model(cfg)`` returns a ``Model`` with the same
functional surface for every family — init / loss / prefill / decode_step /
init_cache / input-spec builders for the dry-run.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import (
    AUDIO,
    DENSE,
    HYBRID,
    MOE,
    SSM,
    VLM,
    ModelConfig,
    ShapeConfig,
)
from repro.models import encdec, hybrid, rwkv6, transformer

Array = jax.Array


def _family_module(cfg: ModelConfig):
    return {
        DENSE: transformer,
        MOE: transformer,
        VLM: transformer,
        HYBRID: hybrid,
        SSM: rwkv6,
        AUDIO: encdec,
    }[cfg.family]


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # -- construction ---------------------------------------------------------
    def init(self, rng: Array):
        return _family_module(self.cfg).init_params(rng, self.cfg)

    def param_shapes(self):
        return jax.eval_shape(lambda k: self.init(k), jax.random.PRNGKey(0))

    # -- training -------------------------------------------------------------
    def loss(self, params, batch) -> tuple[Array, Dict[str, Array]]:
        return _family_module(self.cfg).loss_fn(params, self.cfg, batch)

    # -- serving --------------------------------------------------------------
    def prefill(self, params, batch) -> Array:
        return _family_module(self.cfg).prefill(params, self.cfg, batch)

    def decode_step(self, params, tokens, cache):
        return _family_module(self.cfg).decode_step(params, self.cfg, tokens, cache)

    def decode_step_stats(self, params, tokens, cache):
        """``decode_step`` with the step's counters beside the cache:
        ``(logits, cache, stats)``, ``stats`` a dict of per-step counts
        (a model with held-expert layers counts the token-slots routed to
        them, ``expert_slots``; empty for the others)."""
        family = _family_module(self.cfg)
        if hasattr(family, "decode_step_stats"):
            return family.decode_step_stats(params, self.cfg, tokens, cache)
        logits, cache = family.decode_step(params, self.cfg, tokens, cache)
        return logits, cache, {}

    def init_cache(self, batch: int, seq_len: int):
        return _family_module(self.cfg).init_cache(self.cfg, batch, seq_len)

    def decode_scan(self, params, tokens: Array, cache):
        """Scanned multi-token decode (the serving engine's prefill hook):
        feed ``tokens`` (B, T) one position at a time through
        ``decode_step`` inside a single ``lax.scan``, returning the stacked
        per-position logits (B, T, V) and the advanced cache.  Exact for
        every family (recurrent ones included) — it is the same math as the
        per-token python loop, compiled into one program."""
        def body(c, tok):
            logits, c = self.decode_step(params, tok[:, None], c)
            return c, logits[:, 0]

        cache, logits = jax.lax.scan(body, cache, jnp.moveaxis(tokens, 1, 0))
        return jnp.moveaxis(logits, 0, 1), cache

    # -- dry-run input specs (no allocation) -----------------------------------
    def batch_specs(self, shape: ShapeConfig, *, with_labels: bool = True) -> Dict[str, Any]:
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        sds = jax.ShapeDtypeStruct
        if cfg.family == VLM:
            m = cfg.num_media_tokens
            out = {
                "tokens": sds((b, s - m), i32),
                "media": sds((b, m, cfg.d_model), jnp.dtype(cfg.dtype)),
                "positions": sds((3, b, s), i32),
            }
        elif cfg.family == AUDIO:
            out = {
                "frames": sds((b, s, cfg.d_model), jnp.dtype(cfg.dtype)),
                "tokens": sds((b, s), i32),
            }
        else:
            out = {"tokens": sds((b, s), i32)}
        if with_labels:
            out["labels"] = sds((b, s), i32)
        return out

    def decode_specs(self, shape: ShapeConfig):
        b, s = shape.global_batch, shape.seq_len
        tokens = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        cache = jax.eval_shape(lambda: self.init_cache(b, s))
        return tokens, cache

    def concrete_batch(self, rng: Array, batch: int, seq: int) -> Dict[str, Array]:
        """Small concrete batch for smoke tests / examples."""
        cfg = self.cfg
        ks = jax.random.split(rng, 4)
        tok_len = seq - cfg.num_media_tokens if cfg.family == VLM else seq
        out: Dict[str, Array] = {
            "tokens": jax.random.randint(ks[0], (batch, tok_len), 0, cfg.vocab_size),
            "labels": jax.random.randint(ks[1], (batch, seq), 0, cfg.vocab_size),
        }
        if cfg.family == VLM:
            m = cfg.num_media_tokens
            out["media"] = jax.random.normal(ks[2], (batch, m, cfg.d_model), jnp.dtype(cfg.dtype))
            pos_t = jnp.broadcast_to(jnp.arange(seq)[None], (batch, seq))
            out["positions"] = jnp.stack([pos_t, pos_t // 4, pos_t % 4])
        if cfg.family == AUDIO:
            out["frames"] = jax.random.normal(ks[3], (batch, seq, cfg.d_model), jnp.dtype(cfg.dtype))
        return out


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
