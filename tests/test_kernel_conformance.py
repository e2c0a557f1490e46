"""Kernel conformance suite: every Pallas kernel pinned against its pure-jnp
reference (`pytest -m kernels` runs it standalone; it is part of tier-1).

The contract this suite enforces (docs/kernels.md):

- **masked_agg / qsgd_decode jnp twins** (the CPU fused round path): equal
  to the engine's masked aggregators / wire codec **bit-for-bit** — the
  twins restructure the algorithm (sorting-network median, gram-form krum
  distances, payload-fed decode) but keep every floating-point op of the
  reference.  Krum is the one asterisk: gram d2 != broadcast d2 at the
  last ulp, but krum *selects* a row, so outputs are equal away from exact
  score ties.
- **Pallas kernels** (interpret mode here; compiled jnp twins stand in for
  the compiled axis on CPU — the TPU-compiled path shares this exact
  code): tiled reductions reorder float sums, so decoded/aggregated
  values carry small documented tolerances (~3e-5 like the centralized
  centered_clip kernel); int8 qsgd codes remain bit-exact.
- **fused round == reference round** end to end, including stochastic
  wires: both paths draw identical threefry bits, so params, RoundRecord
  counters, and slashing agree bitwise (hypothesis property test below).

Axes covered: dtypes (fp32 / bf16 inputs), mask patterns (all-live,
churned, single-survivor, all-masked), padding-forcing shapes (D not a
multiple of the block/LANE/bucket), compiled + interpret modes.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregation as agg
from repro.core import compression
from repro.core.swarm import (_FAR, LaneParams, init_state, make_round_fn,
                              scan_rounds)
from repro.kernels.masked_agg import kernel as magg_kernel
from repro.kernels.masked_agg import ops as magg
from repro.kernels.qsgd_decode import ops as qdec
from repro.kernels.qsgd_decode import ref as qdec_ref

pytestmark = pytest.mark.kernels


def _mask(name: str, n: int):
    return {
        "all_live": jnp.ones(n, bool),
        "churned": jnp.arange(n) % 3 != 0,
        "single_survivor": jnp.arange(n) == min(2, n - 1),
        "all_masked": jnp.zeros(n, bool),
    }[name]


MASKS = ["all_live", "churned", "single_survivor", "all_masked"]
LIVE_MASKS = MASKS[:-1]
# (5, 257): N not a power of two (network pads to 8) and D prime — forces
# LANE/bucket padding, and under a 256 cap a 128-column tile (the widest
# lane multiple dividing the padded 384)
SHAPES = [(8, 512), (16, 1000), (5, 257)]


def _stack(n, d, dtype=jnp.float32, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (n, d)) * 2 + 0.5
    return x.astype(dtype)


# ===================== masked_agg: median warm start ==========================
@pytest.mark.parametrize("n,d", SHAPES)
@pytest.mark.parametrize("mask_name", LIVE_MASKS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_masked_median_network_bit_equal(n, d, mask_name, dtype):
    """The Batcher-network median == nanmedian bit-for-bit (pure min/max +
    the same even/odd rank interpolation)."""
    x = _stack(n, d, dtype).astype(jnp.float32)
    m = _mask(mask_name, n)
    ref = agg._masked_median(x, m)
    net = magg.masked_median_net(x, m)
    np.testing.assert_array_equal(np.asarray(net), np.asarray(ref))
    jitted = jax.jit(magg.masked_median_net)(x, m)      # compiled mode
    np.testing.assert_array_equal(np.asarray(jitted), np.asarray(ref))


@pytest.mark.parametrize("n,d", SHAPES)
@pytest.mark.parametrize("mask_name", LIVE_MASKS)
def test_masked_median_pallas_kernel(n, d, mask_name):
    """The Pallas median kernel sorts each tile with the same network —
    bit-equal to nanmedian (no arithmetic reordering to tolerate)."""
    x = _stack(n, d)
    m = _mask(mask_name, n)
    ref = agg._masked_median(x, m)
    out = magg_kernel.masked_median_fwd(x, m, block_d=256, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ===================== masked_agg: centered_clip ==============================
@pytest.mark.parametrize("n,d", SHAPES)
@pytest.mark.parametrize("mask_name", MASKS)
@pytest.mark.parametrize("clip_tau,iters", [(None, 3), (0.7, 2)])
def test_masked_cc_fused_twin_bit_equal(n, d, mask_name, clip_tau, iters):
    """The fused jnp twin == reference masked_centered_clip bitwise — both
    adaptive and fixed τ, interpreted and jit-compiled, incl. the
    all-masked → zeros guard."""
    x = _stack(n, d)
    m = _mask(mask_name, n)
    ref = agg.masked_centered_clip(x, m, clip_tau=clip_tau, iters=iters)
    fused = magg.masked_centered_clip_fused(
        x, m, clip_tau=clip_tau, iters=iters, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(ref))
    jitted = jax.jit(functools.partial(
        magg.masked_centered_clip_fused, clip_tau=clip_tau, iters=iters,
        use_kernel=False))(x, m)
    np.testing.assert_array_equal(np.asarray(jitted), np.asarray(ref))


@pytest.mark.parametrize("n,d", SHAPES)
@pytest.mark.parametrize("mask_name", MASKS)
@pytest.mark.parametrize("clip_tau", [None, 0.7])
def test_masked_cc_pallas_kernel_bounded(n, d, mask_name, clip_tau):
    """The Pallas CC kernel accumulates per-node norms tile-by-tile —
    reduction order differs from the reference's single jnp.linalg.norm, so
    the aggregate carries the same ~3e-5 tolerance as the centralized
    centered_clip kernel (adaptive τ inherits the perturbed norms)."""
    x = _stack(n, d)
    m = _mask(mask_name, n)
    ref = agg.masked_centered_clip(x, m, clip_tau=clip_tau, iters=3)
    out = magg.masked_centered_clip_fused(
        x, m, clip_tau=clip_tau, iters=3, use_kernel=True, block_d=256,
        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_masked_cc_fused_dtype_coercion(dtype):
    """Fused twins compute in fp32 like the engine's flatten_stack — a
    bf16 stack must agree with the reference fed the fp32-cast stack."""
    x = _stack(8, 300, dtype)
    m = _mask("churned", 8)
    ref = agg.masked_centered_clip(x.astype(jnp.float32), m)
    fused = magg.masked_centered_clip_fused(x, m, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(ref))


# ===================== masked_agg: krum =======================================
@pytest.mark.parametrize("n,d", SHAPES)
@pytest.mark.parametrize("mask_name", MASKS)
@pytest.mark.parametrize("f", [1, 2])
def test_masked_krum_fused_selection_equal(n, d, mask_name, f):
    """Gram-form d2 reorders float arithmetic (documented divergence ~1e-6
    relative on scores), but krum RETURNS a selected row — outputs are
    equal away from exact score ties (none at random data)."""
    x = _stack(n, d)
    m = _mask(mask_name, n)
    ref = agg.masked_krum(x, m, f=f)
    for kw in ({"use_kernel": False},
               {"use_kernel": True, "block_d": 256, "interpret": True}):
        out = magg.masked_krum_fused(x, m, f=f, **kw)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref),
                                      err_msg=str(kw))


def test_krum_d2_kernel_matches_broadcast_reference():
    from repro.kernels.masked_agg.ref import masked_krum_d2_ref
    x = _stack(8, 1000)
    ref = masked_krum_d2_ref(x)
    out = magg_kernel.masked_krum_d2_fwd(x, block_d=256, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-3)


# ===================== masked_agg: the byte-sized default tile ================
def _kernel_vs_reference(kind, x, m, block_d=None):
    """(kernel output, reference) of one masked-aggregation kernel run with
    the tile the byte rule picks (``block_d`` None) or under a cap."""
    kw = {"block_d": block_d, "interpret": True}
    if kind == "median":
        return (magg_kernel.masked_median_fwd(x, m, **kw),
                agg._masked_median(x, m))
    if kind == "krum":
        return (magg.masked_krum_fused(x, m, f=1, use_kernel=True, **kw),
                agg.masked_krum(x, m, f=1))
    tau = {"cc_adaptive": None, "cc_fixed": 0.7}[kind]
    return (magg.masked_centered_clip_fused(x, m, clip_tau=tau, iters=3,
                                            use_kernel=True, **kw),
            agg.masked_centered_clip(x, m, clip_tau=tau, iters=3))


def _assert_conforms(kind, out, ref):
    if kind in ("median", "krum"):       # min/max network; a selected row
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    else:
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("n,d", SHAPES)
@pytest.mark.parametrize("mask_name", LIVE_MASKS)
@pytest.mark.parametrize("kind", ["median", "cc_adaptive", "cc_fixed",
                                  "krum"])
def test_masked_agg_kernels_default_tile(n, d, mask_name, kind):
    """With no cap the byte rule gives these widths one whole-row tile:
    the kernels must conform there as they do on capped multi-tile grids."""
    x = _stack(n, d)
    _assert_conforms(kind, *_kernel_vs_reference(kind, x, _mask(mask_name, n)))


@pytest.mark.parametrize("kind", ["median", "cc_adaptive", "cc_fixed",
                                  "krum"])
def test_masked_agg_kernels_odd_multi_tile(kind):
    """(8, 128·73·2) under a 128·73 cap: two tiles of 9344 columns, a
    lane multiple that is no power of two."""
    assert magg_kernel._fit_block(8, 128 * 73 * 2, 128 * 73) == 128 * 73
    x = _stack(8, 128 * 73 * 2)
    out, ref = _kernel_vs_reference(kind, x, _mask("churned", 8),
                                    block_d=128 * 73)
    _assert_conforms(kind, out, ref)


# ===================== all-masked guards (total churn) ========================
@pytest.mark.parametrize("fn", [
    agg.masked_centered_clip, agg.masked_krum, agg.masked_multi_krum,
    functools.partial(magg.masked_centered_clip_fused, use_kernel=False),
    functools.partial(magg.masked_krum_fused, use_kernel=False),
    functools.partial(magg.masked_centered_clip_fused, use_kernel=True,
                      block_d=256, interpret=True),
    functools.partial(magg.masked_krum_fused, use_kernel=True,
                      block_d=256, interpret=True),
])
def test_all_masked_returns_zeros(fn):
    """Total churn: mask.sum() == 0 is defined to aggregate to zeros (a
    no-op step) — reference and fused twins alike, never NaN or an
    arbitrary surviving row."""
    x = _stack(6, 64)
    out = np.asarray(fn(x, jnp.zeros(6, bool)))
    assert np.array_equal(out, np.zeros_like(out)), out[:8]


# ===================== qsgd_decode ============================================
@pytest.mark.parametrize("size,levels,bucket_size", [
    (100, 16, 1024), (5000, 16, 1024), (3000, 127, 256), (128, 15, 128),
])
def test_wire_encode_bit_compatible_with_compression(size, levels,
                                                     bucket_size):
    """decode(wire_encode(k, x)) == compression.roundtrip("qsgd", k, x):
    same bucketing, same norms, same stochastic draws — the int8 payload
    is a lossless re-encoding of the reference's int32+bool codes."""
    x = jax.random.normal(jax.random.PRNGKey(size), (size,)) * 2
    key = jax.random.PRNGKey(size + 1)
    ref = compression.roundtrip("qsgd", key, x, levels=levels,
                                bucket_size=bucket_size)
    got = qdec.wire_roundtrip(key, x, levels=levels, bucket_size=bucket_size)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_wire_encode_rejects_wide_levels():
    with pytest.raises(ValueError, match="int8"):
        qdec.wire_encode(jax.random.PRNGKey(0), jnp.ones(8), levels=200)


def _payload_stack(n, size, levels, bucket_size, seed=7):
    xs = jax.random.normal(jax.random.PRNGKey(seed), (n, size))
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), n)
    enc = functools.partial(qdec.wire_encode, levels=levels,
                            bucket_size=bucket_size)
    pay = jax.vmap(enc)(keys, xs)
    dec = jax.vmap(functools.partial(compression.roundtrip, "qsgd",
                                     levels=levels,
                                     bucket_size=bucket_size))(keys, xs)
    return pay, dec


@pytest.mark.parametrize("mask_name", MASKS)
@pytest.mark.parametrize("size,bucket_size", [(5000, 1024), (257, 128)])
def test_decode_accumulate_twin_bit_equal(mask_name, size, bucket_size):
    """Payload-fed masked mean == decode-then-masked_mean bitwise (the jnp
    twin keeps the reference op order; all-masked accumulates to zeros)."""
    n = 8
    pay, dec = _payload_stack(n, size, 16, bucket_size)
    m = _mask(mask_name, n)
    ref = agg.masked_mean(dec, m)
    out = magg.masked_mean_fused(pay, m, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # oracle path (ref.py decodes with explicit sign/magnitude like the
    # wire codec, signed zeros and all)
    k = max(float(jnp.sum(m)), 1.0)
    oracle = qdec_ref.decode_accumulate_ref(pay, m.astype(jnp.float32)) / k
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("mask_name", LIVE_MASKS)
def test_decode_accumulate_pallas_kernel_bounded(mask_name):
    """The Pallas decode-accumulate tile kernel: per-column sums keep the
    node order, so divergence vs the twin is at most reassociation of the
    bucket-scale multiply (~1e-6 relative)."""
    n, size, bucket = 8, 5000, 1024
    pay, dec = _payload_stack(n, size, 16, bucket)
    m = _mask(mask_name, n)
    ref = agg.masked_mean(dec, m)
    out = magg.masked_mean_fused(pay, m, use_kernel=True, block_d=2048,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("agg_name", ["centered_clip", "krum"])
def test_payload_fed_robust_aggregators_bit_equal(agg_name):
    """CC/krum fused twins consume the int8 payload directly and still
    equal the reference fed the decoded fp32 stack."""
    n, size = 8, 1000
    pay, dec = _payload_stack(n, size, 16, 256)
    m = _mask("churned", n)
    if agg_name == "centered_clip":
        ref = agg.masked_centered_clip(dec, m)
        out = magg.masked_centered_clip_fused(pay, m, use_kernel=False)
    else:
        ref = agg.masked_krum(dec, m, f=1)
        out = magg.masked_krum_fused(pay, m, f=1, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# ===================== existing kernels: differential table ===================
# The five pre-existing kernels, re-pinned here in one compact table so the
# conformance suite is the single `-m kernels` entry point.  Deeper sweeps
# live in tests/test_kernels.py.
def _case_swa(dtype):
    from repro.kernels.swa_attention.ops import swa_attention
    from repro.models.attention import reference_attention
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 32), dtype)
    k = jax.random.normal(ks[1], (1, 256, 2, 32), dtype)
    v = jax.random.normal(ks[2], (1, 256, 2, 32), dtype)
    out = swa_attention(q, k, v, window=96, block_q=64, interpret=True)
    ref = reference_attention(q, k, v, causal=True, window=96)
    return out, ref, (2e-2 if dtype == jnp.bfloat16 else 2e-4)


def _case_qsgd(dtype):
    from repro.kernels.qsgd.ops import qsgd_roundtrip
    from repro.kernels.qsgd.ref import qsgd_roundtrip_ref
    key = jax.random.PRNGKey(2)
    x = (jax.random.normal(jax.random.PRNGKey(1), (1000,)) * 3).astype(dtype)
    out = qsgd_roundtrip(key, x, levels=64, interpret=True)
    ref = qsgd_roundtrip_ref(key, x, levels=64)
    return out, ref, 1e-6


def _case_centered_clip(dtype):
    from repro.kernels.centered_clip.ops import centered_clip as cc_kernel
    from repro.core.aggregation import centered_clip as cc_ref
    x = (jax.random.normal(jax.random.PRNGKey(0), (8, 257)) * 2 + 1).astype(dtype)
    out = cc_kernel(x, clip_tau=1.0, iters=3, interpret=True)
    ref = cc_ref(x.astype(jnp.float32), clip_tau=1.0, iters=3)
    return out, ref, (2e-2 if dtype == jnp.bfloat16 else 3e-5)


def _case_mamba2(dtype):
    from repro.kernels.mamba2_scan.ops import ssd_chunked_pallas
    from repro.models.mamba2 import ssd_reference
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (1, 60, 1, 8), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 60, 1))).astype(dtype)
    a = -jnp.exp(jax.random.normal(ks[2], (1,)) * 0.5)
    b = (jax.random.normal(ks[3], (1, 60, 4)) * 0.5).astype(dtype)
    c = (jax.random.normal(ks[4], (1, 60, 4)) * 0.5).astype(dtype)
    d = jnp.ones((1,)) * 0.5
    y_ref, _ = ssd_reference(x.astype(jnp.float32), dt.astype(jnp.float32),
                             a, b.astype(jnp.float32), c.astype(jnp.float32), d)
    y, _ = ssd_chunked_pallas(x.astype(jnp.float32), dt.astype(jnp.float32),
                              a, b.astype(jnp.float32), c.astype(jnp.float32),
                              d, chunk=16, interpret=True)
    return y, y_ref, 3e-4


def _case_rwkv6(dtype):
    from repro.kernels.rwkv6_wkv.ops import wkv_chunked_pallas
    from repro.models.rwkv6 import wkv_reference
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    r = jax.random.normal(ks[0], (1, 40, 1, 8)) * 0.5
    k = jax.random.normal(ks[1], (1, 40, 1, 8)) * 0.5
    v = jax.random.normal(ks[2], (1, 40, 1, 8))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (1, 40, 1, 8)) - 1) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (1, 8)) * 0.1
    y_ref, _ = wkv_reference(r, k, v, w, u)
    y, _ = wkv_chunked_pallas(r, k, v, w, u, chunk=16, interpret=True)
    return y, y_ref, 3e-4


EXISTING = {"swa_attention": _case_swa, "qsgd": _case_qsgd,
            "centered_clip": _case_centered_clip, "mamba2_scan": _case_mamba2,
            "rwkv6_wkv": _case_rwkv6}


@pytest.mark.parametrize("name", sorted(EXISTING))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_existing_kernel_conformance(name, dtype):
    if dtype == jnp.bfloat16 and name in ("mamba2_scan", "rwkv6_wkv"):
        pytest.skip("recurrent scans are pinned in fp32 (model casts)")
    out, ref, tol = EXISTING[name](dtype)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


# ===================== fused round == reference round =========================
def _round_problem(n=6, d=96):
    key = jax.random.PRNGKey(3)
    target = jax.random.normal(key, (d,))

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"]
        return jnp.mean(jnp.square(pred - batch["x"] @ target))

    def batch_fn(rnd):
        k = jax.random.fold_in(jax.random.PRNGKey(9), rnd)
        return {"x": jax.random.normal(k, (n, 4, d))}

    return loss_fn, {"w": jnp.zeros((d,))}, batch_fn


def _lane(n, codes, leaves=None, seed=11, p_check=0.0):
    return LaneParams(
        codes=jnp.asarray(codes, jnp.int32),
        scales=jnp.full((n,), 2.0), speeds=jnp.ones((n,)),
        joins=jnp.zeros((n,), jnp.int32),
        leaves=(jnp.full((n,), _FAR, jnp.int32) if leaves is None
                else jnp.asarray(leaves, jnp.int32)),
        base_key=jax.random.PRNGKey(seed), p_check=jnp.asarray(p_check),
        tolerance=jnp.asarray(1e-3), numeric_noise=jnp.asarray(0.0),
        agg_id=jnp.asarray(0, jnp.int32), agg_kwargs={})


def _run_both(aggregator, compression_kind, ckw, lane, *, verify=False,
              rounds=4, n=6, d=96):
    import optax
    loss_fn, params0, batch_fn = _round_problem(n, d)
    opt = optax.sgd(0.05)
    outs = []
    for fused in (False, True):
        rf = make_round_fn(loss_fn, opt, params0, n, aggregator=aggregator,
                           compression_kind=compression_kind,
                           compression_kwargs=ckw, verify=verify,
                           fused=fused)
        st, recs, _ = jax.jit(lambda l, rf=rf: scan_rounds(
            rf, l, init_state(params0, opt, n), rounds, batch_fn))(lane)
        outs.append((st, recs))
    return outs


@pytest.mark.parametrize("aggregator,kind,ckw,verify", [
    ("centered_clip", "qsgd", {"levels": 16, "bucket_size": 64}, True),
    ("centered_clip", None, {}, False),
    ("krum", "qsgd", {"levels": 16, "bucket_size": 64}, False),
    ("mean", "qsgd", {"levels": 16, "bucket_size": 64}, True),
])
def test_fused_round_bit_equal(aggregator, kind, ckw, verify):
    """make_round_fn(fused=True) == fused=False bitwise: final params and
    every RoundRecord counter, through corruption, the stochastic qsgd
    wire, audits/slashing, and churn."""
    n = 6
    lane = _lane(n, [0, 0, 1, 0, 3, 2], leaves=[_FAR] * 5 + [2],
                 p_check=0.5 if verify else 0.0)
    (st_u, rec_u), (st_f, rec_f) = _run_both(aggregator, kind, ckw, lane,
                                             verify=verify)
    np.testing.assert_array_equal(np.asarray(st_u.params["w"]),
                                  np.asarray(st_f.params["w"]))
    np.testing.assert_array_equal(np.asarray(st_u.slashed),
                                  np.asarray(st_f.slashed))
    for fld in ("keep", "caught", "agg_norm", "n_active"):
        np.testing.assert_array_equal(
            np.asarray(getattr(rec_u, fld)), np.asarray(getattr(rec_f, fld)),
            err_msg=fld)


def test_fused_auto_threshold_and_exposure():
    """fused=None resolves by stack bytes; the choice is inspectable on the
    returned round_fn; unsupported combinations raise for fused=True."""
    import optax
    loss_fn, params0, _ = _round_problem()
    opt = optax.sgd(0.1)
    mk = functools.partial(make_round_fn, loss_fn, opt)
    small = mk(params0, 6, aggregator="centered_clip")
    assert small.fused is False and small.stack_bytes < magg.FUSED_MIN_BYTES
    big_params = {"w": jnp.zeros((magg.FUSED_MIN_BYTES // 4 // 6 + 1,))}
    big = mk(big_params, 6, aggregator="centered_clip")
    assert big.fused is True
    assert mk(big_params, 6, aggregator="trimmed_mean").fused is False
    assert mk(big_params, 6, aggregator="centered_clip",
              compression_kind="topk").fused is False
    with pytest.raises(ValueError, match="fused=True unsupported"):
        mk(params0, 6, aggregator="median", fused=True)
    with pytest.raises(ValueError, match="levels"):
        mk(params0, 6, aggregator="mean", compression_kind="qsgd",
           compression_kwargs={"levels": 200}, fused=True)


# ===================== fused-round property ===================================
# The property: for ANY roster behaviour mix, seed, churn point, and wire
# choice, the fused centered_clip round reproduces the reference round
# bit-exactly (stochastic rounding included — both paths consume the same
# threefry draws).  A fixed grid always runs; hypothesis fuzzes the same
# property when installed (tier-1 containers without it keep the grid).
def _check_fused_round_property(codes, seed, leave, compressed):
    n = 6
    leaves = [_FAR] * (n - 1) + [leave]
    lane = _lane(n, codes, leaves=leaves, seed=seed)
    kind = "qsgd" if compressed else None
    ckw = {"levels": 16, "bucket_size": 64} if compressed else {}
    (st_u, rec_u), (st_f, rec_f) = _run_both("centered_clip", kind, ckw,
                                             lane, rounds=3)
    np.testing.assert_array_equal(np.asarray(st_u.params["w"]),
                                  np.asarray(st_f.params["w"]))
    np.testing.assert_array_equal(np.asarray(rec_u.agg_norm),
                                  np.asarray(rec_f.agg_norm))


@pytest.mark.parametrize("codes,seed,leave,compressed", [
    ([0, 0, 0, 0, 0, 0], 0, 5, True),          # all honest
    ([1, 2, 3, 4, 5, 0], 7, 2, True),          # every behaviour at once
    ([3, 3, 3, 0, 0, 0], 123, 1, False),       # noise-heavy, early churn
    ([0, 5, 0, 5, 0, 5], 2**31 - 1, 4, True),  # alternating inner_product
])
def test_fused_round_property_grid(codes, seed, leave, compressed):
    _check_fused_round_property(codes, seed, leave, compressed)


try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=15, deadline=None)
    @given(
        codes=st.lists(st.integers(0, 5), min_size=6, max_size=6),
        seed=st.integers(0, 2**31 - 1),
        leave=st.integers(1, 5),
        compressed=st.booleans(),
    )
    def test_fused_round_property_fuzzed(codes, seed, leave, compressed):
        _check_fused_round_property(codes, seed, leave, compressed)
except ImportError:                              # pragma: no cover
    pass
