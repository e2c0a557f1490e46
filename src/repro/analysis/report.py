"""Violation/report plumbing shared by the three analyzers.

A :class:`Violation` is one rule firing at one stable location.  Its
``key`` (``CODE::where``) deliberately excludes line numbers — ``where`` is
a ``file::qualname`` or ``program::variant`` anchor — so a checked-in
baseline survives unrelated edits to the same file.  The human-facing
``message`` carries the precise line.

Baseline policy (docs/analysis.md): the baseline file maps keys to a
one-line justification.  A baselined violation is reported but does not
fail the gate; an *unused* baseline entry does — stale debt records are
themselves a violation (PL000), so the file can only shrink honestly.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List

#: code -> one-line rule description.  Single registry so the CLI, docs
#: test, and golden tests agree on the catalog.
RULES: Dict[str, str] = {
    # -- jaxpr_audit ----------------------------------------------------------
    "JX001": "64-bit (f64/i64) value on the hot path",
    "JX002": "weak-type hazard: weak constant materialized into a buffer, "
             "weak program output/scan carry, or mixed-dtype promotion",
    "JX003": "host callback / debug print inside a traced program",
    "JX004": "dynamic or data-dependent shape in a traced program",
    "JX005": "collective on an axis the program's mesh does not declare",
    "JX006": "declared donation not honored: params/opt-state buffers "
             "not aliased in the lowered program",
    "JX007": "retrace fingerprint unstable across lane-value variants "
             "(the no-recompile contract would break)",
    # -- pallas_check ---------------------------------------------------------
    "PK001": "kernel output tiles do not cover the output array",
    "PK002": "kernel tile reads/writes past the padded array bounds",
    "PK003": "kernel VMEM tile footprint exceeds its budget",
    "PK004": "block violates Mosaic's (8, 128) tiling rule on its last two dims",
    "PK005": "pallas_call passes no name= (the trace shows _unknown_)",
    # -- tracer_lint ----------------------------------------------------------
    "PL000": "stale baseline entry (key no longer fires)",
    "PL001": "python if/while on a traced expression inside a traced fn",
    "PL002": "host escape (.item()/float()/int()/bool()) inside a traced fn",
    "PL003": "numpy call inside a traced fn (silent constant-fold or crash)",
    "PL004": "unordered dict iteration in pytree-order-sensitive code",
    "PL005": "lru_cache on an array-taking function (pins live buffers, "
             "retraces per concrete array identity)",
}


@dataclass(frozen=True)
class Violation:
    code: str      # rule code from RULES
    where: str     # stable anchor: "file::qualname" or "program::variant"
    message: str   # human detail (line numbers, shapes, values)

    @property
    def key(self) -> str:
        return f"{self.code}::{self.where}"

    def to_dict(self) -> dict:
        return {"code": self.code, "where": self.where,
                "message": self.message, "rule": RULES.get(self.code, "?")}


@dataclass
class Report:
    """Merged result of one ``python -m repro.analysis`` run."""
    violations: List[Violation] = field(default_factory=list)
    suppressed: List[Violation] = field(default_factory=list)  # noqa'd
    baselined: List[Violation] = field(default_factory=list)
    summary: Dict[str, object] = field(default_factory=dict)

    def extend(self, violations: List[Violation]) -> None:
        self.violations.extend(violations)

    def apply_baseline(self, baseline: Dict[str, str]) -> None:
        """Move baselined violations aside; turn stale entries into PL000."""
        live, shelved = [], []
        hit_keys = set()
        for v in self.violations:
            if v.key in baseline:
                hit_keys.add(v.key)
                shelved.append(v)
            else:
                live.append(v)
        for key, why in sorted(baseline.items()):
            if key not in hit_keys:
                live.append(Violation(
                    "PL000", key,
                    f"baseline entry no longer fires (was: {why}) — "
                    "delete it from the baseline file"))
        self.violations, self.baselined = live, shelved

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_dict(self) -> dict:
        return {
            "ok": self.ok,
            "rules": dict(RULES),
            "violations": [v.to_dict() for v in self.violations],
            "suppressed": [v.to_dict() for v in self.suppressed],
            "baselined": [v.to_dict() for v in self.baselined],
            "summary": self.summary,
        }

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=True)


def default_baseline_path() -> Path:
    return Path(__file__).resolve().parent / "baseline.json"


def load_baseline(path=None) -> Dict[str, str]:
    """``{violation key: one-line justification}`` from the checked-in
    baseline file (empty at HEAD — kept so debt, if ever taken on, is
    visible in review rather than silent)."""
    p = Path(path) if path is not None else default_baseline_path()
    if not p.exists():
        return {}
    data = json.loads(p.read_text())
    return dict(data.get("keys", {}))
