"""PK-rules: symbolic evaluation of every kernel's BlockSpec index maps.

A Pallas kernel's correctness story starts before its body runs: the
BlockSpec index maps decide which tile each grid point touches, and a map
that skips a tile, runs past the padded bounds, or asks for more VMEM than
a core has fails only on real hardware — CPU ``interpret=True`` tests
cannot see it.  This analyzer makes those properties static: it intercepts
``pl.pallas_call`` (recording grid, specs, arg shapes — the kernel body
never executes), drives each kernel's public ``*_fwd`` wrapper at
representative shapes, and evaluates every index map over the *full* grid
with python ints.

- **PK001** every output tile must be visited: the union of visited block
  indices must cover ``ceil(dim/block)`` per dimension (inputs may
  legitimately be read partially; outputs may legitimately be revisited —
  accumulator kernels do).
- **PK002** no tile may extend past the (padded) array bounds in any
  dimension, for inputs and outputs both.
- **PK003** the per-grid-point VMEM tile footprint — every in/out block
  double-buffered, plus scratch — must fit the per-kernel budget,
  default :data:`repro.launch.roofline.VMEM_BYTES` (the same constant the
  roofline model uses, so the two can never drift apart).
- **PK004** Mosaic's tiling rule on the last two block dims: the trailing
  extent must be a multiple of 128 (the lane width) or equal the array's
  trailing dim, and the second-to-last a multiple of 8 (the sublane
  count) or equal the array's — the padding contract
  ``masked_agg._pad_lanes`` exists to guarantee.  Sub-lane arrays are in
  scope: a per-bucket norms block of 8 over a trailing dim of D/512 is
  refused by the TPU compiler, and by this rule.
- **PK005** every call passes ``name=``: the name is what the profiler's
  trace shows for the kernel (an unnamed call reads ``_unknown_.N``).
"""
from __future__ import annotations

import contextlib
import itertools
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.report import Violation
from repro.launch.roofline import VMEM_BYTES

LANE = 128
SUBLANE = 8
_GRID_POINT_CAP = 65536          # probes are tiny; a blowup is a probe bug


@dataclass
class CapturedCall:
    """One intercepted ``pl.pallas_call``: everything the checks need."""
    kernel: str                    # registry name
    index: int                     # nth pallas_call of this probe
    grid: Tuple[int, ...]
    in_specs: List[object]
    out_specs: List[object]
    in_shapes: List[Tuple[Tuple[int, ...], int]]    # (shape, itemsize)
    out_shapes: List[Tuple[Tuple[int, ...], int]]
    scratch_bytes: int
    num_scalar_prefetch: int
    name: Optional[str] = None     # the call's ``name=``

    def label(self, kind: str, i: int) -> str:
        return f"{self.kernel}[{self.index}].{kind}{i}"


def _as_tuple(x) -> tuple:
    if x is None:
        return ()
    return tuple(x) if isinstance(x, (tuple, list)) else (x,)


def _scratch_bytes(shapes) -> int:
    total = 0
    for s in _as_tuple(shapes):
        shape = getattr(s, "shape", None)
        dtype = getattr(s, "dtype", None)
        if shape is not None and dtype is not None:
            total += int(np.prod(shape)) * jnp.dtype(dtype).itemsize
    return total


@contextlib.contextmanager
def capture_pallas_calls(records: List[CapturedCall], kernel: str):
    """Swap ``pl.pallas_call`` for a recorder that returns zeros of
    ``out_shape`` — kernel wrappers run their real pre/post reshapes while
    the device call itself is captured, not executed."""
    from jax.experimental import pallas as pl

    real = pl.pallas_call
    counter = itertools.count()

    def fake(kern, *pargs, out_shape=None, grid_spec=None, grid=None,
             in_specs=None, out_specs=None, scratch_shapes=(), name=None,
             **kw):
        if out_shape is None and pargs:
            out_shape, pargs = pargs[0], pargs[1:]
        nsp = 0
        if grid_spec is not None:
            grid = grid_spec.grid
            in_specs = grid_spec.in_specs
            out_specs = grid_spec.out_specs
            nsp = int(getattr(grid_spec, "num_scalar_prefetch", 0))
            scratch_shapes = getattr(grid_spec, "scratch_shapes",
                                     scratch_shapes)
        outs = _as_tuple(out_shape)
        idx = next(counter)

        def runner(*args):
            blocks = args[nsp:]           # scalar-prefetch args have no spec
            records.append(CapturedCall(
                kernel=kernel, index=idx,
                grid=tuple(int(g) for g in _as_tuple(grid)),
                in_specs=list(_as_tuple(in_specs)),
                out_specs=list(_as_tuple(out_specs)),
                in_shapes=[(tuple(a.shape), jnp.dtype(a.dtype).itemsize)
                           for a in blocks],
                out_shapes=[(tuple(o.shape), jnp.dtype(o.dtype).itemsize)
                            for o in outs],
                scratch_bytes=_scratch_bytes(scratch_shapes),
                num_scalar_prefetch=nsp, name=name))
            zeros = [jnp.zeros(o.shape, o.dtype) for o in outs]
            if isinstance(out_shape, (tuple, list)):
                return type(out_shape)(zeros)
            return zeros[0]

        return runner

    pl.pallas_call = fake
    try:
        yield
    finally:
        pl.pallas_call = real


# ------------------------------- the checks -----------------------------------
def _eval_map(spec, point: Sequence[int], nsp: int) -> Optional[Tuple[int, ...]]:
    """Index map at one grid point, python ints in — ints out.  Scalar
    prefetch refs get inert placeholders (this repo's maps never read
    them for indexing)."""
    args = tuple(point) + (object(),) * nsp
    try:
        idx = spec.index_map(*args)
    except TypeError:
        idx = spec.index_map(*point)
    return tuple(int(i) for i in _as_tuple(idx))


def _check_call(call: CapturedCall,
                budget: int = VMEM_BYTES) -> List[Violation]:
    out: List[Violation] = []
    if not call.name:
        out.append(Violation(
            "PK005", f"{call.kernel}[{call.index}]",
            "pallas_call without name=: the trace shows it as _unknown_"))
    vmem = _vmem_bytes(call)
    if vmem > budget:
        out.append(Violation(
            "PK003", f"{call.kernel}[{call.index}]",
            f"tile set needs {vmem} B of VMEM (double-buffered blocks "
            f"+ scratch) > budget {budget} B"))
    if not call.grid:
        return out
    npoints = int(np.prod(call.grid))
    if npoints > _GRID_POINT_CAP:
        out.append(Violation("PK001", call.label("grid", 0),
                             f"probe grid {call.grid} too large to "
                             "enumerate — shrink the probe"))
        return out
    points = list(itertools.product(*(range(g) for g in call.grid)))

    units = (
        [("in", i, spec, shp) for i, (spec, shp)
         in enumerate(zip(call.in_specs, call.in_shapes))]
        + [("out", i, spec, shp) for i, (spec, shp)
           in enumerate(zip(call.out_specs, call.out_shapes))])

    for kind, i, spec, (shape, itemsize) in units:
        where = call.label(kind, i)
        block = tuple(int(b) for b in spec.block_shape)
        if len(block) != len(shape):
            out.append(Violation(
                "PK002", where,
                f"block rank {len(block)} != array rank {len(shape)} "
                f"(block {block}, array {shape})"))
            continue
        visited = set()
        oob = None
        for p in points:
            idx = _eval_map(spec, p, call.num_scalar_prefetch)
            visited.add(idx)
            for d, (bi, bd, ad) in enumerate(zip(idx, block, shape)):
                if bi < 0 or (bi * bd + bd) > ad:
                    oob = (p, idx, d)
            if oob:
                break
        if oob:
            p, idx, d = oob
            out.append(Violation(
                "PK002", where,
                f"grid point {p} maps block index {idx}: dim {d} spans "
                f"[{idx[d] * block[d]}, {idx[d] * block[d] + block[d]}) "
                f"outside array extent {shape[d]} (block {block}, "
                f"array {shape})"))
            continue
        if kind == "out":
            required = set(itertools.product(
                *(range(-(-ad // bd)) for ad, bd in zip(shape, block))))
            missing = required - visited
            if missing:
                out.append(Violation(
                    "PK001", where,
                    f"{len(missing)}/{len(required)} output tiles never "
                    f"visited, e.g. {sorted(missing)[0]} (grid "
                    f"{call.grid}, block {block}, array {shape})"))
        # PK004 — Mosaic's (8, 128) tiling rule on the last two dims
        bt, at = block[-1], shape[-1]
        if bt != at and bt % LANE:
            out.append(Violation(
                "PK004", where,
                f"trailing dim tiled {bt}/{at}: tile is neither a "
                f"multiple of the {LANE}-wide lane nor the full dim (pad "
                "the array — see masked_agg._pad_lanes)"))
        if len(block) >= 2:
            bs, as_ = block[-2], shape[-2]
            if bs != as_ and bs % SUBLANE:
                out.append(Violation(
                    "PK004", where,
                    f"second-to-last dim tiled {bs}/{as_}: tile is "
                    f"neither a multiple of {SUBLANE} sublanes nor the "
                    "full dim"))
    return out


def _vmem_bytes(call: CapturedCall) -> int:
    total = call.scratch_bytes
    for spec, (_, itemsize) in (
            list(zip(call.in_specs, call.in_shapes))
            + list(zip(call.out_specs, call.out_shapes))):
        total += 2 * int(np.prod(spec.block_shape)) * itemsize   # dbl-buffered
    return total


# ------------------------------- kernel probes --------------------------------
def _probe_qsgd():
    from repro.kernels.qsgd.kernel import qsgd_encode_fwd
    x = jnp.ones((512, 128), jnp.float32)
    qsgd_encode_fwd(x, x, jnp.float32(1.0), levels=64, block_rows=256)


def _probe_qsgd_decode():
    from repro.kernels.qsgd_decode.kernel import qsgd_decode_accumulate_fwd
    n, l, bucket = 8, 8192, 128
    codes = jnp.zeros((n, l), jnp.int8)
    norms = jnp.ones((n, l // bucket), jnp.float32)
    qsgd_decode_accumulate_fwd(codes, norms, jnp.ones((n,), jnp.float32),
                               levels=64, bucket_size=bucket, block_d=4096)


def _probe_masked_agg():
    from repro.kernels.masked_agg import kernel as k
    upd = jnp.ones((8, 4000), jnp.float32)        # exercises _pad_lanes
    mask = jnp.ones((8,), jnp.float32)
    k.masked_median_fwd(upd, mask, block_d=2048)
    k.masked_cc_iter_fwd(upd, jnp.zeros((4000,), jnp.float32), mask,
                         block_d=2048)
    k.masked_krum_d2_fwd(upd, block_d=2048)
    # no cap: the byte rule's tile, as the chip runs it — two of the
    # 56,064-column tiles protocol-125m's (8, D) stack gets
    upd = jnp.ones((8, 2 * 56064), jnp.float32)
    k.masked_median_fwd(upd, mask)
    k.masked_cc_iter_fwd(upd, jnp.zeros((2 * 56064,), jnp.float32), mask)
    k.masked_krum_d2_fwd(upd)


def _probe_centered_clip():
    from repro.kernels.centered_clip.kernel import centered_clip_iter_fwd
    centered_clip_iter_fwd(jnp.ones((8, 4096), jnp.float32),
                           jnp.zeros((4096,), jnp.float32), block_d=2048)


def _probe_swa_attention():
    from repro.kernels.swa_attention.kernel import swa_attention_fwd
    q = jnp.ones((1, 2, 512, 128), jnp.float32)
    swa_attention_fwd(q, q, q, window=256, block_q=128)


def _probe_mamba2_scan():
    from repro.kernels.mamba2_scan.kernel import ssd_scan_fwd
    b, s, h, p, n = 1, 512, 2, 64, 128
    ssd_scan_fwd(jnp.ones((b, s, h, p), jnp.float32),
                 jnp.zeros((b, s, h), jnp.float32),
                 jnp.ones((b, s, n), jnp.float32),
                 jnp.ones((b, s, n), jnp.float32),
                 jnp.zeros((b, h, p, n), jnp.float32), chunk=128)


def _probe_rwkv6_wkv():
    from repro.kernels.rwkv6_wkv.kernel import wkv_scan_fwd
    b, s, h, dk = 1, 256, 2, 64
    r = jnp.ones((b, s, h, dk), jnp.float32)
    wkv_scan_fwd(r, r, r, r, jnp.ones((h, dk), jnp.float32),
                 jnp.zeros((b, h, dk, dk), jnp.float32), chunk=64)


#: name -> (probe, VMEM budget in bytes).  Budgets are the full-core
#: default; a kernel wanting a tighter promise overrides here.
KERNEL_PROBES: Dict[str, Tuple[Callable[[], None], int]] = {
    "qsgd": (_probe_qsgd, VMEM_BYTES),
    "qsgd_decode": (_probe_qsgd_decode, VMEM_BYTES),
    "masked_agg": (_probe_masked_agg, VMEM_BYTES),
    "centered_clip": (_probe_centered_clip, VMEM_BYTES),
    "swa_attention": (_probe_swa_attention, VMEM_BYTES),
    "mamba2_scan": (_probe_mamba2_scan, VMEM_BYTES),
    "rwkv6_wkv": (_probe_rwkv6_wkv, VMEM_BYTES),
}


def check_kernel(name: str) -> Tuple[List[Violation], List[CapturedCall]]:
    probe, budget = KERNEL_PROBES[name]
    records: List[CapturedCall] = []
    with capture_pallas_calls(records, name):
        probe()
    out: List[Violation] = []
    for call in records:
        out.extend(_check_call(call, budget))
    return out, records


def check_all() -> Tuple[List[Violation], Dict[str, int]]:
    """All registered kernels.  Returns (violations, {kernel: #pallas_calls})."""
    violations: List[Violation] = []
    counts: Dict[str, int] = {}
    for name in sorted(KERNEL_PROBES):
        v, records = check_kernel(name)
        violations.extend(v)
        counts[name] = len(records)
    return violations, counts
