"""Production meshes (DESIGN.md §4).

Functions, not module constants — importing this module never touches jax
device state.  The dry-run creates 512 host-platform placeholder devices
(XLA_FLAGS set in dryrun.py before any jax import); everything else sees
the container's single real device.

Target hardware: TPU v5e.  Mesh axes:
  single pod : (16, 16)        ``(data, model)``     = 256 chips
  multi-pod  : (2, 16, 16)     ``(pod, data, model)`` = 512 chips

``pod`` is the Protocol Learning axis — the slow, inter-pod "internet"
boundary where the paper's techniques (compression / gossip / robust
aggregation, core/hierarchical.py) apply.  ``data``/``model`` are the
fast intra-pod ICI axes driven by ordinary pjit.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


SINGLE_POD_SHAPE = (16, 16)
SINGLE_POD_AXES = ("data", "model")
MULTI_POD_SHAPE = (2, 16, 16)
MULTI_POD_AXES = ("pod", "data", "model")
# the campaign mesh (core/placement.MeshPlan): lanes = the embarrassingly
# parallel run axis of a sweep; data/model = the within-lane axes the
# models/sharding.py rules partition over
CAMPAIGN_AXES = ("lanes", "data", "model")


def _auto_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with Auto axes: the launch layer places arrays
    with sharding constraints that the compiler propagates, which an
    Explicit-typed axis (``jax.make_mesh``'s default) refuses."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = MULTI_POD_SHAPE if multi_pod else SINGLE_POD_SHAPE
    axes = MULTI_POD_AXES if multi_pod else SINGLE_POD_AXES
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1) -> Mesh:
    """Mesh over the container's real device(s) — smoke tests/examples.

    Zero-arg: ``(n, 1)`` over ``("data", "model")``, as before.  ``model``
    splits a model axis off the host devices — ``(n // model, model)`` —
    so fake-device tests (``--xla_force_host_platform_device_count=8``)
    can build ``(4, 2)``-style meshes; it must divide the device count."""
    n = len(jax.devices())
    if model < 1 or n % model:
        raise ValueError(
            f"model-axis factor {model} must be >= 1 and divide the "
            f"{n} available device(s)")
    return _auto_mesh((n // model, model), SINGLE_POD_AXES)


def make_campaign_mesh(lanes: Optional[int] = None, *, data: int = 1,
                       model: int = 1) -> Mesh:
    """The ``("lanes", "data", "model")`` mesh for a MeshPlan, over the
    first ``lanes * data * model`` devices (a campaign may deliberately use
    a divisor of the host's devices so its lane count shards evenly —
    ``jax.make_mesh`` would insist on all of them).  Zero-arg: every
    device on the lane axis."""
    devs = jax.devices()
    if data < 1 or model < 1:
        raise ValueError(f"data/model factors must be >= 1, got "
                         f"data={data} model={model}")
    if lanes is None:
        if len(devs) % (data * model):
            raise ValueError(
                f"data={data} x model={model} must divide the "
                f"{len(devs)} available device(s) when lanes is unset")
        lanes = len(devs) // (data * model)
    if lanes < 1:
        raise ValueError(f"lane-axis extent must be >= 1, got {lanes}")
    need = lanes * data * model
    if need > len(devs):
        raise ValueError(
            f"campaign mesh ({lanes}, {data}, {model}) needs {need} "
            f"devices, have {len(devs)}")
    arr = np.asarray(devs[:need]).reshape(lanes, data, model)
    return Mesh(arr, CAMPAIGN_AXES,
                axis_types=(AxisType.Auto,) * len(CAMPAIGN_AXES))


def axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def batch_axes(mesh: Mesh) -> tuple:
    """Axes the global batch shards over (pod first when present)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def has_pod_axis(mesh: Mesh) -> bool:
    return "pod" in mesh.axis_names
