"""Runner + CLI: `python -m tools.analysis [--json] [--baseline PATH]`.

Exit status is the OR of the failing rules' bits (hotloop=1 clock=2
ownership=4 lockorder=8 surface=16 oneengine=32), 0 when every finding is
either pragma-suppressed or baselined. The tier-1 gate (tests/test_analysis.py)
calls :func:`run` in-process and asserts exit 0 over the real tree.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

from . import baseline as baseline_mod
from .core import Project
from .findings import Finding, finalize
from .passes import BITS, PASSES, RULES

REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", ".."))


@dataclass
class Report:
    findings: List[Finding] = field(default_factory=list)   # all, sorted
    stale_baseline: List[str] = field(default_factory=list)
    rules: Sequence[str] = RULES

    @property
    def failing(self) -> List[Finding]:
        return [f for f in self.findings
                if f.suppressed is None and f.baselined is None]

    @property
    def exit_code(self) -> int:
        code = 0
        for f in self.failing:
            code |= BITS.get(f.rule, 0)
        return code

    def to_dict(self) -> Dict[str, object]:
        by_rule = {rule: 0 for rule in self.rules}
        for f in self.failing:
            by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
        return {
            "findings": [f.to_dict() for f in self.findings],
            "summary": {
                "total": len(self.findings),
                "failing": len(self.failing),
                "suppressed": sum(1 for f in self.findings
                                  if f.suppressed is not None),
                "baselined": sum(1 for f in self.findings
                                 if f.baselined is not None),
                "failing_by_rule": by_rule,
                "stale_baseline": self.stale_baseline,
                "exit_code": self.exit_code,
            },
        }


def run(root: str = REPO_ROOT, rules: Optional[Sequence[str]] = None,
        baseline_path: Optional[str] = baseline_mod.DEFAULT_PATH,
        project: Optional[Project] = None) -> Report:
    """Run the selected passes (default: all) over `root`. Pass
    ``baseline_path=None`` to see the tree raw. A pre-built Project can
    be supplied to amortize parsing across calls (tests)."""
    if project is None:
        project = Project(root)
    selected = [p for p in PASSES if rules is None or p[0] in rules]
    findings: List[Finding] = []
    for _rule, _bit, pass_run in selected:
        findings.extend(pass_run(project))
    finalize(findings)

    for f in findings:
        reason = project.pragma_reason(f.file, f.rule, f.line)
        if reason is not None:
            f.suppressed = reason

    entries = baseline_mod.load(baseline_path) if baseline_path else {}
    seen_ids = set()
    for f in findings:
        seen_ids.add(f.id)
        if f.suppressed is None and f.id in entries:
            f.baselined = entries[f.id]
    stale = sorted(fid for fid in entries if fid not in seen_ids)
    return Report(findings=findings, stale_baseline=stale,
                  rules=[p[0] for p in selected])


def _format_text(report: Report, verbose: bool) -> str:
    lines: List[str] = []
    for f in report.findings:
        if f.suppressed is not None:
            if verbose:
                lines.append(f"  ok {f.file}:{f.line} [{f.rule}] "
                             f"suppressed: {f.suppressed}")
            continue
        if f.baselined is not None:
            if verbose:
                lines.append(f"  ok {f.file}:{f.line} [{f.rule}] "
                             f"baselined: {f.baselined}")
            continue
        lines.append(f"FAIL {f.file}:{f.line} [{f.rule}] {f.message}")
        lines.append(f"     id: {f.id}")
    summary = report.to_dict()["summary"]
    for fid in report.stale_baseline:
        lines.append(f"WARN stale baseline entry (finding no longer "
                     f"produced): {fid}")
    lines.append(
        "graftlint: %d finding(s), %d failing, %d suppressed, "
        "%d baselined -> exit %d"
        % (summary["total"], summary["failing"], summary["suppressed"],
           summary["baselined"], summary["exit_code"]))
    return "\n".join(lines)


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tools.analysis",
        description="graftlint: repo-invariant static analysis "
                    "(hot-loop sync, clock discipline, thread ownership, "
                    "lock order, surface inventory, one engine)")
    parser.add_argument("--root", default=REPO_ROOT,
                        help="tree to analyze (default: this repo)")
    parser.add_argument("--json", action="store_true",
                        help="machine-readable report on stdout")
    parser.add_argument("--rule", action="append", choices=RULES,
                        help="run only this rule (repeatable)")
    parser.add_argument("--baseline", default=baseline_mod.DEFAULT_PATH,
                        help="baseline JSON path")
    parser.add_argument("--no-baseline", action="store_true",
                        help="ignore the baseline (show the tree raw)")
    parser.add_argument("--write-baseline", action="store_true",
                        help="regenerate the baseline from the current "
                             "failing set (keeps existing reasons, new "
                             "entries get 'TODO: justify')")
    parser.add_argument("-v", "--verbose", action="store_true",
                        help="also list suppressed/baselined findings")
    args = parser.parse_args(argv)

    baseline_path = None if args.no_baseline else args.baseline
    report = run(root=args.root, rules=args.rule,
                 baseline_path=baseline_path)

    if args.write_baseline:
        existing = baseline_mod.load(args.baseline) \
            if os.path.exists(args.baseline) else {}
        entries = {f.id: existing.get(f.id, "TODO: justify")
                   for f in report.failing}
        # keep already-baselined live findings too
        for f in report.findings:
            if f.baselined is not None:
                entries[f.id] = f.baselined
        baseline_mod.save(entries, args.baseline)
        print("wrote %d entries to %s" % (len(entries), args.baseline))
        return 0

    if args.json:
        print(json.dumps(report.to_dict(), indent=2, sort_keys=True))
    else:
        print(_format_text(report, args.verbose))
    return report.exit_code


if __name__ == "__main__":
    sys.exit(main())
