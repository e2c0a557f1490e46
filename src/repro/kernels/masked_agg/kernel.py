"""Fused masked robust aggregation — Pallas TPU kernels (paper §3.3 hot path).

The swarm round's aggregation phase consumes the (N, D) submitted-update
stack with the active-mask folded in (``keep = active & ~caught``).  The
unfused path materializes several stack-sized intermediates per round —
most expensively the coordinate-median warm start (a full sort of the
stack) and CenteredClip's per-iteration ``diff``/``scale`` arrays.  These
kernels stream D in VMEM tiles so nothing of size D beyond the stack
itself round-trips through HBM:

- ``masked_median_fwd`` — the masked coordinate-median warm start.  Columns
  are independent, so each (N, block_d) tile is sorted **in VMEM** by a
  Batcher odd-even merge network over the node rows (N is small; the
  network is generated statically in Python and unrolled as vectorized
  min/max pairs).  Masked rows are +inf-padded; the two middle ranks of
  the *kept* count k (a traced scalar — churn never retraces) are selected
  arithmetically and averaged, which reproduces ``nanmedian``'s
  interpolation bit-for-bit.
- ``masked_cc_iter_fwd`` — one CenteredClip iteration, flash-style
  two-phase grid (phase 0 accumulates per-node squared norms into a
  persistent (N, 1) VMEM scratch; phase 1 re-streams the tiles and applies
  the masked clipped mean).  Extends the centralized ``centered_clip``
  kernel with the keep-mask and the engine's default **adaptive τ** (the
  masked median of the per-node distances, computed in-kernel from the
  norm scratch by the same sorting network).
- ``masked_krum_d2_fwd`` — krum's pairwise-distance phase.  Streams D
  tiles and accumulates the (N, N) squared-distance matrix via the gram
  form ``|x_i|² + |x_j|² − 2·x_iᵀx_j`` (one MXU matmul per tile) into a
  revisited output block.  The O(N²) selection phase is left to plain jnp
  in ops.py — it touches nothing of size D.

Grids: median/krum (n_d_blocks,); CC (2, n_d_blocks) phase-outermost, its
output block parked on tile 0 through phase 0 (``(0, j * ph)``), so each
iteration writes the centre once.  All kernels carry an ``interpret=True``
path so tier-1 pins them on CPU.

The tile rule (``_fit_block``) sizes the stack tile by bytes: the widest
lane multiple that divides the padded D with the (N, block_d) f32 tile, as
VMEM holds it (N rounded up to whole 8-row sublane groups), within
``TILE_BYTES``.  A grid step costs a fixed ~0.3 µs on a v5e whatever it
moves, so the tile has to carry enough bytes to hide it, and a cap by
column count does not see what D allows: under a 2048-column cap
protocol-125m's D = 128 · 2 · 3 · 73 · 2897 gets 768-column tiles (24 KB,
211,481 steps a pass), under the byte target 56,064 columns (1.8 MB, 2,897
steps).  A D whose lane count has no divisor near the limit still gets a
narrow tile: a prime D / 128 gets 128 columns.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def oddeven_merge_pairs(n: int) -> List[Tuple[int, int]]:
    """Compare-exchange pairs of Batcher's odd-even merge sort for ``n`` a
    power of two.  Sorting is pure min/max — no arithmetic — so a network
    sort equals ``jnp.sort`` exactly, while vectorizing over the lane
    dimension instead of paying XLA's generic sort."""
    if n & (n - 1):
        raise ValueError(f"network size must be a power of two, got {n}")
    pairs: List[Tuple[int, int]] = []
    p = 1
    while p < n:
        k = p
        while k >= 1:
            for j in range(k % p, n - k, 2 * k):
                for i in range(min(k, n - j - k)):
                    if (i + j) // (p * 2) == (i + j + k) // (p * 2):
                        pairs.append((i + j, i + j + k))
            k //= 2
        p *= 2
    return pairs


def _next_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


LANE = 128


def _pad_lanes(x, *, mult: int = LANE):
    """Zero-pad the trailing (feature) dim to a lane multiple.  Zero columns
    are exact no-ops for every kernel here — they contribute 0 to squared
    norms and pairwise distances, and median/CC outputs are sliced back —
    whereas letting block_d degenerate toward 1 both wastes the VPU and
    (observed in interpret mode) reorders accumulation enough to break
    d2's symmetry at the last ulp."""
    d = x.shape[-1]
    pad = (-d) % mult
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x, d


SUBLANE = 8
#: Bytes of one (N, block_d) f32 stack tile in VMEM: the tile rule's target.
TILE_BYTES = 2 << 20


def _fit_block(n: int, d: int, block_d: Optional[int] = None) -> int:
    """Widest lane-multiple tile that divides d (d is already a lane
    multiple, so this bottoms out at LANE) and keeps the (n, tile) f32
    block, rows padded to whole sublane groups, within TILE_BYTES;
    ``block_d``, when given, caps the tile further."""
    rows = -(-n // SUBLANE) * SUBLANE
    limit = TILE_BYTES // (4 * rows)
    if block_d is not None:
        limit = min(limit, block_d)
    width = max(LANE, min(limit, d) // LANE * LANE)
    while d % width:
        width -= LANE
    return width


def _sorted_rows(rows: List[jax.Array]) -> List[jax.Array]:
    """Apply the odd-even network to a list of equal-shaped rows (+inf rows
    pad to the next power of two); returns the rows in ascending order."""
    n = len(rows)
    npad = _next_pow2(n)
    rows = rows + [jnp.full_like(rows[0], jnp.inf)] * (npad - n)
    for i, j in oddeven_merge_pairs(npad):
        a, b = rows[i], rows[j]
        rows[i], rows[j] = jnp.minimum(a, b), jnp.maximum(a, b)
    return rows[:n]


def _masked_rank_interp(rows: List[jax.Array], k: jax.Array) -> jax.Array:
    """(lo + hi) / 2 of the two middle ranks of the first k sorted rows —
    nanmedian's even/odd interpolation with a *traced* kept-count k."""
    lo_idx = (k - 1) // 2
    hi_idx = k // 2
    lo = rows[0] * 0.0
    hi = rows[0] * 0.0
    for r, row in enumerate(rows):
        lo = lo + jnp.where(r == lo_idx, row, 0.0)
        hi = hi + jnp.where(r == hi_idx, row, 0.0)
    return (lo + hi) * 0.5


# ---------------------------- masked median ------------------------------------
def _median_kernel(x_ref, m_ref, o_ref, *, n: int):
    m = m_ref[...].astype(jnp.float32)                     # (N, 1)
    k = jnp.sum(m).astype(jnp.int32)
    rows = [jnp.where(m[i, 0] > 0,
                      x_ref[i:i + 1, :].astype(jnp.float32),
                      jnp.inf)
            for i in range(n)]
    o_ref[...] = _masked_rank_interp(_sorted_rows(rows), k)


def masked_median_fwd(updates, mask, *, block_d: Optional[int] = None,
                      interpret: bool = False):
    """Masked coordinate median.  updates (N, D) f32, mask (N,) -> (D,).
    Bit-equal to ``aggregation._masked_median`` for k >= 1 (all-masked
    columns are meaningless — callers guard k == 0).  ``block_d`` caps the
    tile the byte rule picks (None: no cap)."""
    n, d0 = updates.shape
    updates, _ = _pad_lanes(updates)
    d = updates.shape[1]
    block_d = _fit_block(n, d, block_d)
    kern = functools.partial(_median_kernel, n=n)
    out = pl.pallas_call(
        kern,
        grid=(d // block_d,),
        in_specs=[
            pl.BlockSpec((n, block_d), lambda j: (0, j)),
            pl.BlockSpec((n, 1), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        interpret=interpret,
        name="masked_median",
    )(updates, mask.reshape(n, 1).astype(jnp.float32))
    return out.reshape(d)[:d0]


# --------------------------- masked CenteredClip -------------------------------
def _cc_kernel(x_ref, v_ref, m_ref, o_ref, sq_ref, *, n: int, tau):
    """tau: static float for fixed-τ, or None for the adaptive masked-median
    τ recomputed per phase-1 tile from the completed norm scratch."""
    ph = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((ph == 0) & (j == 0))
    def _init():
        sq_ref[...] = jnp.zeros_like(sq_ref)

    diff = x_ref[...].astype(jnp.float32) - v_ref[...].astype(jnp.float32)

    @pl.when(ph == 0)
    def _accumulate():
        sq_ref[...] += jnp.sum(diff * diff, axis=1, keepdims=True)

    @pl.when(ph == 1)
    def _apply():
        m = m_ref[...].astype(jnp.float32)             # (N, 1)
        k = jnp.maximum(jnp.sum(m), 1.0)
        norm = jnp.sqrt(sq_ref[...])                   # (N, 1)
        if tau is None:
            kept = jnp.sum(m).astype(jnp.int32)
            rows = [jnp.where(m[i, 0] > 0, norm[i:i + 1, :], jnp.inf)
                    for i in range(n)]
            t = _masked_rank_interp(_sorted_rows(rows), kept)[0, 0]
        else:
            t = tau
        scale = jnp.minimum(1.0, t / jnp.maximum(norm, 1e-12))
        o_ref[...] = v_ref[...] + jnp.sum(
            diff * scale * m, axis=0, keepdims=True) / k


def masked_cc_iter_fwd(updates, v, mask, *, clip_tau=None,
                       block_d: Optional[int] = None,
                       interpret: bool = False):
    """One masked CenteredClip iteration: v ← v + Σᵢ mᵢ·clip(xᵢ − v, τ)/k.
    updates (N, D) f32, v (D,), mask (N,) -> (D,).  ``clip_tau=None``
    selects the adaptive τ (masked median of ‖xᵢ − v‖).  ``block_d`` caps
    the tile the byte rule picks (None: no cap)."""
    n, d0 = updates.shape
    updates, _ = _pad_lanes(updates)
    v, _ = _pad_lanes(v)
    d = updates.shape[1]
    block_d = _fit_block(n, d, block_d)
    kern = functools.partial(_cc_kernel, n=n,
                             tau=None if clip_tau is None else float(clip_tau))
    out = pl.pallas_call(
        kern,
        grid=(2, d // block_d),
        in_specs=[
            pl.BlockSpec((n, block_d), lambda ph, j: (0, j)),
            pl.BlockSpec((1, block_d), lambda ph, j: (0, j)),
            pl.BlockSpec((n, 1), lambda ph, j: (0, 0)),
        ],
        # phase 0 writes nothing: its output block stays on tile 0, which
        # phase 1 writes first, so each tile goes back to HBM once
        out_specs=pl.BlockSpec((1, block_d), lambda ph, j: (0, j * ph)),
        out_shape=jax.ShapeDtypeStruct((1, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, 1), jnp.float32)],
        interpret=interpret,
        name="masked_cc_iter",
    )(updates, v.reshape(1, d), mask.reshape(n, 1).astype(jnp.float32))
    return out.reshape(d)[:d0]


# --------------------------- krum distance phase -------------------------------
def _krum_d2_kernel(x_ref, o_ref):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)                 # (N, bd)
    sq = jnp.sum(x * x, axis=1)                        # (N,)
    gram = jnp.dot(x, x.T, preferred_element_type=jnp.float32)
    o_ref[...] += sq[:, None] + sq[None, :] - 2.0 * gram


def masked_krum_d2_fwd(updates, *, block_d: Optional[int] = None,
                       interpret: bool = False):
    """Pairwise squared distances (N, N) of the update stack, accumulated
    tile-by-tile in the gram form (one MXU matmul per tile).  The mask and
    +inf/selection semantics are applied by the caller — they are O(N²)
    and touch nothing of size D."""
    n, _ = updates.shape
    updates, _ = _pad_lanes(updates)
    d = updates.shape[1]
    block_d = _fit_block(n, d, block_d)
    return pl.pallas_call(
        _krum_d2_kernel,
        grid=(d // block_d,),
        in_specs=[pl.BlockSpec((n, block_d), lambda j: (0, j))],
        out_specs=pl.BlockSpec((n, n), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.float32),
        interpret=interpret,
        name="masked_krum_d2",
    )(updates)
