"""Device placement as a first-class campaign property (DESIGN.md §4).

Every §5.5 phase diagram used to compile to one ``jit(vmap(scan))`` program
on whatever device jax picked — lanes × model size capped by a single HBM.
:class:`MeshPlan` makes placement explicit: it maps a campaign's lane count
onto a ``("lanes", "data", "model")`` mesh and the engines
(``swarm.run_campaign``, ``derailment.sweep``, ``serving.ServingEngine``)
accept it as an optional argument.

Two sharding levels, with different exactness contracts:

- **lane axis** — the stacked :class:`~repro.core.swarm.LaneParams` /
  :class:`~repro.core.serving.ServeLane` leaves shard their leading run
  axis over ``lanes`` (``place_lanes``), and the engine's ``vmap`` carries
  ``spmd_axis_name`` so internal sharding constraints stay lane-local (a
  campaign whose plan has lanes alone runs each device's block under
  ``shard_map`` instead: its Pallas kernels cannot be partitioned).
  Lanes are embarrassingly parallel, so this is **bit-exact** against the
  unsharded engine run on each device's block of lanes, for the
  centralized, fused-kernel, and serving rounds (pinned in
  ``tests/test_campaign_sharded.py``): every params/opt-state leaf and
  every per-round record.  Two ULP-level exceptions, both from XLA making
  different fusion decisions under a mesh (which reorders float
  reductions): the final *eval* matmul, and the decentralized round's
  gossip mixing matmul — those are allclose, not bit-equal.  Against one
  unsharded program over *all* lanes the counters are equal and every
  float within one bf16 ULP once each device holds ≥8 lanes; with fewer,
  the TPU tiles a (lanes, D) array by the lane count and per-lane sums
  over D round differently (``docs/scaling.md``).
- **within-lane axes** — ``place_params`` shards a lane's *shared* params
  over ``model`` (and ``data``): via the symbolic rules in
  ``models.sharding.param_pspecs`` when the plan carries a
  :class:`~repro.configs.base.ModelConfig`, else a generic
  largest-divisible-dim rule for toy pytrees.  Resharding changes
  reduction order, so this level is **allclose-pinned** only.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LANES_AXIS = "lanes"


def lane_axis_size(n_lanes: int, max_devices: int) -> int:
    """Largest divisor of ``n_lanes`` that fits in ``max_devices`` — the
    lane-axis extent :meth:`MeshPlan.for_lanes` picks so the stacked run
    axis always shards evenly (30 lanes on 8 devices -> 6)."""
    if n_lanes < 1 or max_devices < 1:
        return 1
    for d in range(min(n_lanes, max_devices), 0, -1):
        if n_lanes % d == 0:
            return d
    return 1


@dataclass(frozen=True)
class MeshPlan:
    """A placement: the mesh plus which of its axes mean what.

    ``cfg`` (optional) is the :class:`~repro.configs.base.ModelConfig` of
    the params being swept — it switches ``param_specs`` from the generic
    toy rule to the real ``models.sharding.param_pspecs`` rules."""
    mesh: Mesh
    lanes_axis: str = LANES_AXIS
    data_axis: str = "data"
    model_axis: str = "model"
    cfg: Optional[object] = None

    # -- axis sizes ---------------------------------------------------------
    def axis_size(self, name: str) -> int:
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        return int(sizes.get(name, 1))

    @property
    def lane_devices(self) -> int:
        return self.axis_size(self.lanes_axis)

    @property
    def data_devices(self) -> int:
        return self.axis_size(self.data_axis)

    @property
    def model_devices(self) -> int:
        return self.axis_size(self.model_axis)

    @property
    def n_devices(self) -> int:
        return int(self.mesh.devices.size)

    # -- construction -------------------------------------------------------
    @classmethod
    def for_lanes(cls, n_lanes: int, *, data: int = 1, model: int = 1,
                  max_devices: Optional[int] = None,
                  cfg: Optional[object] = None) -> "MeshPlan":
        """Plan for a campaign of ``n_lanes`` runs: the lane axis takes the
        largest divisor of ``n_lanes`` that fits in the available devices
        after the within-lane ``data``/``model`` factors."""
        from repro.launch.mesh import make_campaign_mesh  # avoid cycle
        avail = len(jax.devices()) if max_devices is None else max_devices
        if data < 1 or model < 1:
            raise ValueError(f"data/model factors must be >= 1, got "
                             f"data={data} model={model}")
        if avail < data * model:
            raise ValueError(
                f"within-lane factors data={data} x model={model} need "
                f"{data * model} devices, have {avail}")
        lanes = lane_axis_size(n_lanes, avail // (data * model))
        mesh = make_campaign_mesh(lanes=lanes, data=data, model=model)
        return cls(mesh=mesh, cfg=cfg)

    @classmethod
    def from_grid(cls, grid, **kwargs) -> "MeshPlan":
        """Plan for a ``scenarios.SweepGrid`` / ``ServingGrid`` — the lane
        count is the grid's total lane count (baseline lanes included)."""
        return cls.for_lanes(grid.n_lanes, **kwargs)

    # -- lane-axis placement (bit-exact level) -------------------------------
    def validate_lanes(self, n_lanes: int) -> None:
        d = self.lane_devices
        if n_lanes % d:
            raise ValueError(
                f"{n_lanes} lanes do not shard evenly over the "
                f"{d}-device '{self.lanes_axis}' axis of {self.mesh}; pad "
                f"the grid or build the plan with MeshPlan.for_lanes "
                f"({n_lanes} lanes -> lane axis "
                f"{lane_axis_size(n_lanes, self.n_devices)})")

    def lane_sharding(self, leaf) -> NamedSharding:
        spec = P(*((self.lanes_axis,) + (None,) * (leaf.ndim - 1)))
        return NamedSharding(self.mesh, spec)

    def place_lanes(self, stacked):
        """device_put every stacked-lane leaf with its leading run axis
        sharded over ``lanes`` (None leaves — e.g. an absent custody or
        mixing field — pass through)."""
        leaves = [l for l in jax.tree.leaves(stacked) if l is not None]
        if leaves:
            self.validate_lanes(int(leaves[0].shape[0]))
        return jax.tree.map(
            lambda x: jax.device_put(x, self.lane_sharding(x)), stacked)

    # -- within-lane placement (allclose level) -------------------------------
    def param_specs(self, params):
        """PartitionSpecs for a lane's shared params: the real
        ``models.sharding`` rules when ``cfg`` is given, else a generic
        rule sharding each leaf's largest ``model``-divisible dim."""
        m = self.model_devices
        if self.cfg is not None:
            from repro.models.sharding import param_pspecs
            sizes = {self.data_axis: self.data_devices, self.model_axis: m}
            return param_pspecs(params, self.cfg, sizes,
                                data_axis=self.data_axis,
                                model_axis=self.model_axis)

        def generic(leaf):
            if m <= 1 or leaf.ndim == 0:
                return P()
            dims = [(size, i) for i, size in enumerate(leaf.shape)
                    if size % m == 0]
            if not dims:
                return P()
            _, best = max(dims)
            spec = [None] * leaf.ndim
            spec[best] = self.model_axis
            return P(*spec)

        return jax.tree.map(generic, params)

    def place_params(self, params):
        """device_put a lane's shared params per :meth:`param_specs` —
        replicated leaves stay replicated; the identity when the plan has
        no within-lane axes (nothing to reshard, nothing to pay)."""
        if self.model_devices <= 1 and self.data_devices <= 1:
            return params
        specs = self.param_specs(params)
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            params, specs)
