"""protolint CLI — ``python -m repro.analysis [--json [PATH]] [--baseline P]``.

Runs all three analyzers:

1. ``jaxpr_audit`` over the five engine programs (round fused/unfused,
   campaign, sweep, serve scan) — rules JX001-JX007,
2. ``pallas_check`` over every registered kernel probe — rules PK001-PK005,
3. ``tracer_lint`` over ``src/`` — rules PL001-PL005,

applies the checked-in baseline (``baseline.json`` next to this package;
stale entries fire PL000), prints a human summary, and exits non-zero if
any non-baselined violation remains.  ``--json`` writes the full machine
report (violations, suppressions, baseline hits, per-analyzer summary) to
stdout or to the given path — the artifact the CI gate uploads.
"""
from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

from repro.analysis import jaxpr_audit, pallas_check, tracer_lint
from repro.analysis.report import RULES, Report, load_baseline


def build_report(src_root=None) -> Report:
    report = Report()
    t0 = time.time()

    violations, programs = jaxpr_audit.audit_all()
    report.extend(violations)
    report.summary["programs"] = programs
    t1 = time.time()

    violations, kernels = pallas_check.check_all()
    report.extend(violations)
    report.summary["kernels"] = kernels
    t2 = time.time()

    root = (Path(src_root) if src_root is not None
            else Path(__file__).resolve().parents[1])
    violations, suppressed, n_files = tracer_lint.lint_tree(root)
    report.extend(violations)
    report.suppressed.extend(suppressed)
    report.summary["linted_files"] = n_files
    report.summary["seconds"] = {
        "jaxpr_audit": round(t1 - t0, 2),
        "pallas_check": round(t2 - t1, 2),
        "tracer_lint": round(time.time() - t2, 2),
    }
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="static analysis gate: jaxpr audit + Pallas kernel "
                    "check + tracer lint")
    ap.add_argument("--json", nargs="?", const="-", default=None,
                    metavar="PATH",
                    help="write the JSON report to PATH ('-' or no value "
                         "= stdout)")
    ap.add_argument("--baseline", default=None, metavar="PATH",
                    help="baseline file (default: the checked-in "
                         "baseline.json)")
    ap.add_argument("--src", default=None, metavar="DIR",
                    help="source root for tracer_lint (default: the "
                         "installed repro package)")
    args = ap.parse_args(argv)

    report = build_report(src_root=args.src)
    report.apply_baseline(load_baseline(args.baseline))

    if args.json is not None:
        text = report.to_json()
        if args.json == "-":
            print(text)
        else:
            Path(args.json).write_text(text + "\n")

    out = sys.stderr if args.json == "-" else sys.stdout
    s = report.summary
    print(f"protolint: audited {len(s.get('programs', {}))} engine "
          f"programs ({sum(s.get('programs', {}).values())} traced "
          f"variants), {len(s.get('kernels', {}))} kernels "
          f"({sum(s.get('kernels', {}).values())} pallas_call sites), "
          f"{s.get('linted_files', 0)} source files", file=out)
    for v in report.violations:
        print(f"  FAIL {v.key}: {v.message}", file=out)
        print(f"       rule: {RULES.get(v.code, '?')}", file=out)
    for v in report.baselined:
        print(f"  baselined {v.key}", file=out)
    if report.suppressed:
        print(f"  ({len(report.suppressed)} noqa-suppressed lint "
              f"findings)", file=out)
    print(("OK — no violations" if report.ok
           else f"{len(report.violations)} violation(s)"), file=out)
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
