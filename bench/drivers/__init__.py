"""Entry points a traffic file may name (``"entry"``).  Each module defines
``Driver(spec, seed, devices)`` with ``setup``, ``window(seconds)``,
``program_bytes``, ``release``, ``check``, ``end_to_end(window)``,
``facts(window)`` and ``control``.  ``devices`` are the chips the cell
asked for: the one-chip drivers run on the default device, a driver of a
four-chip cell places its mesh on them.  ``program_model`` builds the
program's model from a configuration file."""
from __future__ import annotations

import dataclasses
from typing import Dict

from bench.harness import BenchError


@dataclasses.dataclass
class Window:
    elapsed: float        # seconds, host clock, whole calls only
    attempted: int
    failed: int
    steps: int            # rounds or engine steps in the window


def program_model(config: Dict):
    """The program's model for a configuration file, checked against its
    stated parameter count."""
    from repro.configs.base import ModelConfig
    from repro.models.model import build_model
    cfg = ModelConfig(name=config["name"], family=config["family"],
                      source=config["source"], **config["model"])
    if cfg.param_count() != config["param_count"]:
        raise BenchError(f"{config['name']}: the program counts "
                         f"{cfg.param_count()} parameters, the file "
                         f"{config['param_count']}")
    return build_model(cfg)


def check_layout(params, model) -> None:
    """The benchmark's weights must have the program's tree, shapes and
    types."""
    import jax
    want = model.param_shapes()
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise BenchError("the benchmark's weights do not match the "
                         "program's parameter layout")
