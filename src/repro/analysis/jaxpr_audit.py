"""Static jaxpr audit of the engine programs (rules JX001-JX007).

Works on the :class:`~repro.analysis.programs.TracedProgram` registry —
the engine's real entry-point programs traced (never run) to ClosedJaxprs
— and walks every equation recursively (pjit / scan / cond / while /
pallas_call sub-jaxprs included) enforcing:

JX001  no 64-bit value anywhere on the hot path (an f64 sneaking in
       doubles wire and memory cost silently and breaks kernel tiling).
JX002  no weak-type hazard: a weak python constant materialized into a
       rank>=1 buffer (``jnp.maximum(x, 1e-30)`` and friends — the classic
       source of avoidable retraces and silent upcasts), or a weak program
       output escaping to callers.
JX003  no host callback / debug print compiled into a program (a stray
       ``jax.debug.print`` serializes the scan on every round).
JX004  no dynamic or data-dependent shapes (every dim a python int).
JX005  collectives only on mesh axes the program declares (a collective
       on an undeclared axis means a program silently depends on being
       run under some *other* transform's axis).
JX006  declared buffer donation honored: the lowered scan program aliases
       at least the declared number of inputs to outputs
       (``tf.aliasing_output`` in the StableHLO text).
JX007  retrace fingerprint stable across lane-value variants: variants of
       one program that differ only in traced values must produce
       bit-identical program structure — the no-recompile contract the
       whole campaign design rests on.

Violation messages carry ``file:line`` from the equation's source info, so
a firing names the offending engine line, not just the program.
"""
from __future__ import annotations

import hashlib
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.extend

from repro.analysis import programs as programs_mod
from repro.analysis.programs import DonationUnit, TracedProgram, TracedUnit
from repro.analysis.report import Violation

#: dtypes JX001 bans from every traced program (x64 should never be on).
_WIDE_DTYPES = frozenset({"float64", "int64", "uint64", "complex128"})

#: primitive names that are host escapes (JX003).  Matched exactly plus a
#: ``callback`` substring net — jax has renamed these across versions.
_CALLBACK_PRIMS = frozenset({"debug_print", "infeed", "outfeed",
                             "outside_call"})

#: the marker XLA puts on a donated-and-honored input in StableHLO.
_ALIAS_MARKER = "tf.aliasing_output"


def iter_eqns(jaxpr) -> Iterator:
    """Every equation of ``jaxpr``, recursing into sub-jaxprs carried in
    equation params (pjit/scan/while/cond/custom_*/pallas_call)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def _sub_jaxprs(params: dict) -> Iterator:
    for v in params.values():
        for sub in _as_jaxprs(v):
            yield sub


def _as_jaxprs(v) -> Iterator:
    if isinstance(v, jax.extend.core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jax.extend.core.Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _as_jaxprs(x)


def _src(eqn) -> str:
    """``file:line`` of the user frame that produced this equation."""
    try:
        from jax._src import source_info_util
        return source_info_util.summarize(eqn.source_info)
    except Exception:
        return "<unknown>"


def _aval_dtype(aval) -> str:
    try:
        return str(aval.dtype)
    except Exception:        # abstract tokens / key arrays without .dtype
        return ""


# ---------------------------------------------------------------------------
# fingerprint (JX007)
# ---------------------------------------------------------------------------
def fingerprint(closed: jax.extend.core.ClosedJaxpr) -> str:
    """Structural digest of a traced program: input/output avals, const
    avals, and the recursive (primitive, output-aval) sequence.  Equation
    *params* are deliberately excluded — they embed device-dependent
    objects (shardings, compiler options) that vary without retracing —
    but every sub-jaxpr's shapes and primitives are in, which is what a
    retrace would actually change."""
    h = hashlib.sha256()
    for aval in closed.in_avals:
        h.update(str(aval).encode())
    for aval in closed.out_avals:
        h.update(str(aval).encode())
    for c in closed.consts:
        h.update(f"{getattr(c, 'shape', ())}/{getattr(c, 'dtype', '?')}"
                 .encode())
    for eqn in iter_eqns(closed.jaxpr):
        h.update(eqn.primitive.name.encode())
        for v in eqn.outvars:
            h.update(str(v.aval).encode())
    return h.hexdigest()[:16]


# ---------------------------------------------------------------------------
# per-unit rules
# ---------------------------------------------------------------------------
def _audit_unit(prog: str, unit: TracedUnit) -> List[Violation]:
    where = f"{prog}::{unit.label}"
    out: List[Violation] = []
    closed = unit.closed

    # JX002b: weak program outputs escape to callers, poisoning downstream
    # dtype promotion with context-dependent types
    weak_out = [str(a) for a in closed.out_avals
                if getattr(a, "weak_type", False)]
    if weak_out:
        out.append(Violation(
            "JX002", where,
            f"{len(weak_out)} weak-typed program output(s): "
            f"{', '.join(weak_out[:4])}"))

    for eqn in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        # JX001 — 64-bit values
        for v in eqn.outvars:
            if _aval_dtype(v.aval) in _WIDE_DTYPES:
                out.append(Violation(
                    "JX001", where,
                    f"{name} produces {v.aval} at {_src(eqn)}"))
        # JX002a — weak constant materialized into a buffer: a python
        # scalar broadcast to rank>=1 keeps its weak type on the buffer
        if name == "broadcast_in_dim":
            for v in eqn.outvars:
                if (getattr(v.aval, "weak_type", False)
                        and getattr(v.aval, "ndim", 0) >= 1):
                    out.append(Violation(
                        "JX002", where,
                        f"weak python constant broadcast into {v.aval} "
                        f"at {_src(eqn)} — wrap the literal in "
                        f"jnp.<dtype>(...) so the buffer dtype is explicit"))
        # JX003 — host callbacks / debug prints
        if name in _CALLBACK_PRIMS or "callback" in name:
            out.append(Violation(
                "JX003", where,
                f"host-callback primitive '{name}' compiled into the "
                f"program at {_src(eqn)}"))
        # JX004 — dynamic shapes (every dim must be a concrete python int)
        for v in eqn.outvars:
            dims = getattr(v.aval, "shape", ())
            if not all(isinstance(d, int) for d in dims):
                out.append(Violation(
                    "JX004", where,
                    f"{name} output has non-static shape {dims} "
                    f"at {_src(eqn)}"))
        # JX005 — collectives only on declared mesh axes.  Axis names bound
        # by vmap are fresh non-str objects; only str names survive to the
        # compiled program and must come from the declared mesh.
        for key in ("axes", "axis_name"):
            if key not in eqn.params:
                continue
            names = eqn.params[key]
            if not isinstance(names, (tuple, list)):
                names = (names,)
            for ax in names:
                if isinstance(ax, str) and ax not in unit.declared_axes:
                    out.append(Violation(
                        "JX005", where,
                        f"collective '{name}' on undeclared axis "
                        f"{ax!r} at {_src(eqn)} (declared: "
                        f"{sorted(unit.declared_axes) or 'none'})"))
    return out


def _audit_donation(prog: str, don: DonationUnit) -> List[Violation]:
    n = don.lowered_text.count(_ALIAS_MARKER)
    if n >= don.min_aliases:
        return []
    return [Violation(
        "JX006", f"{prog}::{don.label}",
        f"lowered program aliases {n} buffer(s), expected >= "
        f"{don.min_aliases} (opt-state + slashed + contrib must be "
        f"donated — a dead copy of the optimizer state would live for "
        f"the whole campaign)")]


def _audit_fingerprints(prog: TracedProgram) -> List[Violation]:
    groups: Dict[str, List[Tuple[str, str]]] = {}
    for unit in prog.units:
        if unit.group is not None:
            groups.setdefault(unit.group, []).append(
                (unit.label, fingerprint(unit.closed)))
    out = []
    for group, pairs in groups.items():
        digests = {d for _, d in pairs}
        if len(digests) > 1:
            detail = ", ".join(f"{label}={d}" for label, d in pairs)
            out.append(Violation(
                "JX007", f"{prog.name}::{group}",
                f"variants that must share one compiled program trace to "
                f"different structures: {detail}"))
    return out


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------
def audit_program(prog: TracedProgram) -> List[Violation]:
    out: List[Violation] = []
    for unit in prog.units:
        out.extend(_audit_unit(prog.name, unit))
    for don in prog.donations:
        out.extend(_audit_donation(prog.name, don))
    out.extend(_audit_fingerprints(prog))
    return out


def audit_all(progs: Optional[List[TracedProgram]] = None,
              ) -> Tuple[List[Violation], Dict[str, int]]:
    """Audit every registered engine program.  Returns ``(violations,
    {program name: unit count})``."""
    if progs is None:
        progs = programs_mod.build_all()
    violations: List[Violation] = []
    summary: Dict[str, int] = {}
    for prog in progs:
        violations.extend(audit_program(prog))
        summary[prog.name] = len(prog.units)
    return violations, summary
