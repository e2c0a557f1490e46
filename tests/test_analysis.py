"""Golden tests for the protolint static-analysis suite (repro.analysis).

Every rule code gets a minimal *firing* snippet and a minimal *quiet*
snippet, so the rule catalog can neither rot (a rule that stops firing
fails here first) nor creep (a rule that starts over-firing fails the
quiet twin).  The integration test at the bottom is the gate itself: the
six engine programs and seven kernels must audit clean at HEAD.

Run standalone with ``pytest -m analysis``; included in tier-1.
"""
import textwrap

import pytest

import jax
import jax.numpy as jnp

from repro.analysis import jaxpr_audit, pallas_check, tracer_lint
from repro.analysis.jaxpr_audit import fingerprint
from repro.analysis.programs import DonationUnit, TracedProgram, TracedUnit
from repro.analysis.report import RULES, Report, Violation, load_baseline

pytestmark = pytest.mark.analysis


def _codes(violations):
    return {v.code for v in violations}


def _audit(fn, *args, declared_axes=frozenset(), **make_jaxpr_kwargs):
    closed = jax.make_jaxpr(fn, **make_jaxpr_kwargs)(*args)
    unit = TracedUnit("t", closed, declared_axes=declared_axes)
    return jaxpr_audit._audit_unit("prog", unit)


# =============================== JX rules =====================================
def test_jx001_fires_on_f64():
    with jax.enable_x64(True):
        vs = _audit(lambda x: x.astype("float64") * 2.0,
                    jnp.zeros(4, jnp.float32))
    assert "JX001" in _codes(vs)


def test_jx001_quiet_on_f32():
    vs = _audit(lambda x: x * 2.0, jnp.zeros(4, jnp.float32))
    assert "JX001" not in _codes(vs)


def test_jx002_fires_on_weak_constant_buffer():
    # the exact bug class fixed in aggregation.py: nanmedian's internal
    # weak 0.5 quantile materializes a weak-typed buffer
    vs = _audit(lambda x: jnp.nanmedian(x, axis=0), jnp.zeros((4, 4)))
    assert "JX002" in _codes(vs)


def test_jx002_quiet_on_dtype_matched_quantile():
    vs = _audit(lambda x: jnp.nanquantile(x, jnp.asarray(0.5, x.dtype),
                                          axis=0, method="midpoint"),
                jnp.zeros((4, 4)))
    assert "JX002" not in _codes(vs)


def test_jx003_fires_on_debug_print():
    def f(x):
        jax.debug.print("x={}", x)
        return x + 1
    vs = _audit(f, jnp.zeros(3))
    assert "JX003" in _codes(vs)


def test_jx003_quiet_without_callbacks():
    vs = _audit(lambda x: x + 1, jnp.zeros(3))
    assert "JX003" not in _codes(vs)


def test_jx004_fires_on_dynamic_shape():
    # a symbolic dimension (shape polymorphism) is the non-static shape
    # the installed jax can still put into a jaxpr
    n, = jax.export.symbolic_shape("n")
    closed = jax.make_jaxpr(lambda x: x + x)(
        jax.ShapeDtypeStruct((n,), jnp.float32))
    vs = jaxpr_audit._audit_unit("prog", TracedUnit("t", closed))
    assert "JX004" in _codes(vs)


def test_jx004_quiet_on_static_shapes():
    vs = _audit(lambda x: x + x, jnp.arange(4.0))
    assert "JX004" not in _codes(vs)


def test_jx005_fires_on_undeclared_axis():
    vs = _audit(lambda x: jax.lax.psum(x, "lanes"), jnp.zeros(3),
                axis_env=[("lanes", 4)])
    assert "JX005" in _codes(vs)


def test_jx005_quiet_on_declared_axis():
    vs = _audit(lambda x: jax.lax.psum(x, "lanes"), jnp.zeros(3),
                declared_axes=frozenset({"lanes"}),
                axis_env=[("lanes", 4)])
    assert "JX005" not in _codes(vs)


def test_jx006_fires_when_donation_missing():
    text = jax.jit(lambda x: x + 1).lower(jnp.zeros(128)).as_text()
    vs = jaxpr_audit._audit_donation("prog", DonationUnit("t", text, 1))
    assert _codes(vs) == {"JX006"}


def test_jx006_quiet_when_donation_honored():
    text = jax.jit(lambda x: x + 1,
                   donate_argnums=0).lower(jnp.zeros(128)).as_text()
    vs = jaxpr_audit._audit_donation("prog", DonationUnit("t", text, 1))
    assert vs == []


def test_jx007_fires_on_structural_drift():
    closed_a = jax.make_jaxpr(lambda x: x + 1)(jnp.zeros(4))
    closed_b = jax.make_jaxpr(lambda x: x + 1)(jnp.zeros(8))
    prog = TracedProgram("prog", [TracedUnit("a", closed_a, group="g"),
                                  TracedUnit("b", closed_b, group="g")])
    vs = jaxpr_audit._audit_fingerprints(prog)
    assert _codes(vs) == {"JX007"}


def test_jx007_quiet_on_value_variants():
    # same shapes, different values -> same trace -> same fingerprint
    closed_a = jax.make_jaxpr(lambda x: x * 2)(jnp.zeros(4))
    closed_b = jax.make_jaxpr(lambda x: x * 2)(jnp.ones(4))
    assert fingerprint(closed_a) == fingerprint(closed_b)
    prog = TracedProgram("prog", [TracedUnit("a", closed_a, group="g"),
                                  TracedUnit("b", closed_b, group="g")])
    assert jaxpr_audit._audit_fingerprints(prog) == []


# =============================== PK rules =====================================
class _Spec:
    """Duck-typed BlockSpec: exactly the two attrs the checker reads."""

    def __init__(self, block_shape, index_map):
        self.block_shape = block_shape
        self.index_map = index_map


def _call(grid, in_specs, in_shapes, out_specs, out_shapes, scratch=0):
    return pallas_check.CapturedCall(
        kernel="golden", index=0, grid=grid,
        in_specs=in_specs, out_specs=out_specs,
        in_shapes=in_shapes, out_shapes=out_shapes,
        scratch_bytes=scratch, num_scalar_prefetch=0, name="golden")


def test_pk001_fires_on_uncovered_output_tile():
    call = _call((1,), [], [], [_Spec((128,), lambda i: (i,))], [((256,), 4)])
    assert "PK001" in _codes(pallas_check._check_call(call))


def test_pk001_quiet_on_full_coverage():
    call = _call((2,), [], [], [_Spec((128,), lambda i: (i,))], [((256,), 4)])
    assert pallas_check._check_call(call) == []


def test_pk002_fires_on_out_of_bounds_tile():
    call = _call((2,), [], [], [_Spec((128,), lambda i: (i,))], [((128,), 4)])
    assert "PK002" in _codes(pallas_check._check_call(call))


def test_pk002_quiet_within_bounds():
    call = _call((1,), [], [], [_Spec((128,), lambda i: (i,))], [((128,), 4)])
    assert pallas_check._check_call(call) == []


def test_pk003_fires_over_vmem_budget():
    call = _call((4,), [_Spec((128,), lambda i: (i,))], [((512,), 4)],
                 [_Spec((128,), lambda i: (i,))], [((512,), 4)])
    vs = pallas_check._check_call(call, budget=1024)
    assert "PK003" in _codes(vs)


def test_pk003_quiet_under_budget():
    call = _call((4,), [_Spec((128,), lambda i: (i,))], [((512,), 4)],
                 [_Spec((128,), lambda i: (i,))], [((512,), 4)])
    assert pallas_check._check_call(call) == []


def test_pk004_fires_on_sub_lane_tiling():
    call = _call((8,), [_Spec((64,), lambda i: (i,))], [((512,), 4)],
                 [_Spec((64,), lambda i: (i,))], [((512,), 4)])
    assert "PK004" in _codes(pallas_check._check_call(call))


def test_pk004_quiet_on_lane_multiple_tiling():
    call = _call((4,), [_Spec((128,), lambda i: (i,))], [((512,), 4)],
                 [_Spec((128,), lambda i: (i,))], [((512,), 4)])
    assert pallas_check._check_call(call) == []


# the qsgd decode-accumulate norms at N=10, D=2^20, 512-element buckets
_N, _D, _BUCKET, _BLOCK = 10, 1 << 20, 512, 4096


def test_pk004_fires_on_old_sub_lane_norms_block():
    """The norms BlockSpec the TPU compiler refused: 8 bucket norms per
    step over a trailing dim of D/512 — neither lane-aligned nor full."""
    call = _call((_D // _BLOCK,),
                 [_Spec((_N, _BLOCK // _BUCKET), lambda j: (0, j))],
                 [((_N, _D // _BUCKET), 4)],
                 [_Spec((1, _BLOCK), lambda j: (0, j))], [((1, _D), 4)])
    assert "PK004" in _codes(pallas_check._check_call(call))


def test_pk004_quiet_on_grouped_norms_block():
    """The repaired layout: norms regrouped to (nb/8, N, 8), so both
    trailing block dims equal the array's."""
    groups = _D // _BUCKET // 8
    call = _call((_D // _BLOCK,),
                 [_Spec((_BLOCK // _BUCKET // 8, _N, 8), lambda j: (j, 0, 0))],
                 [((groups, _N, 8), 4)],
                 [_Spec((1, _BLOCK), lambda j: (0, j))], [((1, _D), 4)])
    assert pallas_check._check_call(call) == []


def test_pk004_fires_on_sub_sublane_second_minor():
    call = _call((4,), [_Spec((4, 128), lambda i: (i, 0))], [((16, 128), 4)],
                 [_Spec((4, 128), lambda i: (i, 0))], [((16, 128), 4)])
    assert "PK004" in _codes(pallas_check._check_call(call))


def test_pk005_fires_on_unnamed_call():
    call = _call((2,), [], [], [_Spec((128,), lambda i: (i,))], [((256,), 4)])
    call.name = None
    assert _codes(pallas_check._check_call(call)) == {"PK005"}


def test_pk005_quiet_on_every_registered_kernel():
    """Every kernel probe's calls carry a name: the trace shows each
    Mosaic call under it, never as ``_unknown_``."""
    for name in pallas_check.KERNEL_PROBES:
        violations, records = pallas_check.check_kernel(name)
        assert records and all(r.name for r in records), name
        assert "PK005" not in _codes(violations)


# =============================== PL rules =====================================
def _lint(src):
    return tracer_lint.lint_source(textwrap.dedent(src))


def test_pl000_fires_on_stale_baseline_entry():
    report = Report()
    report.apply_baseline({"JX001::gone::unit": "historical debt"})
    assert _codes(report.violations) == {"PL000"}
    assert not report.ok


def test_pl000_quiet_on_live_baseline_entry():
    report = Report(violations=[Violation("JX001", "prog::unit", "m")])
    report.apply_baseline({"JX001::prog::unit": "known"})
    assert report.ok and len(report.baselined) == 1


def test_pl001_fires_on_python_if_over_tracer():
    vs = _lint("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            if jnp.sum(x) > 0:
                return x
            return -x
    """)
    assert "PL001" in _codes(vs)


def test_pl001_quiet_on_jnp_where():
    vs = _lint("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return jnp.where(jnp.sum(x) > 0, x, -x)
    """)
    assert "PL001" not in _codes(vs)


def test_pl002_fires_on_host_escape():
    vs = _lint("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return float(jnp.sum(x)) * x
    """)
    assert "PL002" in _codes(vs)


def test_pl002_quiet_on_traced_arithmetic():
    vs = _lint("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x):
            return jnp.sum(x) * x
    """)
    assert "PL002" not in _codes(vs)


def test_pl003_fires_on_numpy_in_traced_fn():
    vs = _lint("""
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return np.sum(x)
    """)
    assert "PL003" in _codes(vs)


def test_pl003_quiet_on_static_shape_math():
    vs = _lint("""
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return x.reshape(int(np.prod(x.shape)))
    """)
    assert "PL003" not in _codes(vs)


def test_pl004_fires_on_unordered_dict_iteration():
    vs = _lint("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x, kwargs):
            for k, v in kwargs.items():
                x = x + v
            return x
    """)
    assert "PL004" in _codes(vs)


def test_pl004_quiet_on_sorted_iteration():
    vs = _lint("""
        import jax
        import jax.numpy as jnp

        @jax.jit
        def f(x, kwargs):
            for k, v in sorted(kwargs.items()):
                x = x + v
            return x
    """)
    assert "PL004" not in _codes(vs)


def test_pl005_fires_on_array_taking_lru_cache():
    vs = _lint("""
        import functools
        import jax.numpy as jnp

        @functools.lru_cache(maxsize=None)
        def f(x):
            return jnp.sum(x)
    """)
    assert "PL005" in _codes(vs)


def test_pl005_quiet_on_static_arg_cache():
    vs = _lint("""
        import functools

        @functools.lru_cache(maxsize=None)
        def f(n):
            return n * 2
    """)
    assert "PL005" not in _codes(vs)


def test_noqa_suppresses_a_rule():
    vs = _lint("""
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return np.sum(x)  # noqa: PL003
    """)
    assert "PL003" not in _codes(vs)


def test_rule_catalog_is_complete():
    assert set(RULES) == {
        "JX001", "JX002", "JX003", "JX004", "JX005", "JX006", "JX007",
        "PK001", "PK002", "PK003", "PK004", "PK005",
        "PL000", "PL001", "PL002", "PL003", "PL004", "PL005"}


# ============================ the gate itself =================================
def test_engine_programs_and_kernels_violation_free():
    """The integration gate: the seven engine programs (22 traced
    variants), all seven kernels, and the whole source tree audit clean at
    HEAD (modulo the checked-in baseline, empty at HEAD)."""
    from repro.analysis.__main__ import build_report
    report = build_report()
    report.apply_baseline(load_baseline())
    assert set(report.summary["programs"]) == {
        "round_unfused", "round_fused", "round_async", "campaign", "sweep",
        "economy", "serve_step"}
    assert len(report.summary["kernels"]) == 7
    assert sum(report.summary["kernels"].values()) >= 7
    assert report.ok, "\n".join(
        f"{v.key}: {v.message}" for v in report.violations)
