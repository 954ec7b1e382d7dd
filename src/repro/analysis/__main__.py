"""CLI: run the jaxpr auditor + AST contract linter over the repository.

``python -m repro.analysis`` audits every registered scenario on both
backends (trace-only — no XLA compile), lints the source contracts, and
prints every finding.  Allowlisted findings (``rules.ALLOWLIST``) are
reported with their justification but do not fail the run; any
unallowlisted finding exits nonzero, which is the CI gate.  The
paper-scale tick's scatter and gather counts are gated separately, by
``tests/test_gates.py``.

Usage:
  PYTHONPATH=src python -m repro.analysis [--audit-only | --lint-only]
      [--scenarios a,b,...] [--backends jnp,pallas] [--quick]
      [--list-rules] [--show-allowlisted]
"""

from __future__ import annotations

import argparse
import sys
import time

from repro.analysis import audit, lint, rules

QUICK_SCENARIOS = ("tiny_3t", "tiny_perm4", "tiny_incast3")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="jaxpr auditor + AST contract linter (DESIGN.md "
                    "Sec. 10)")
    p.add_argument("--scenarios", default=None, metavar="A,B",
                   help="comma-separated scenario names (default: the "
                        "whole registry, aliases deduped)")
    p.add_argument("--backends", default="jnp,pallas", metavar="B,B")
    p.add_argument("--quick", action="store_true",
                   help=f"audit only {', '.join(QUICK_SCENARIOS)}")
    p.add_argument("--audit-only", action="store_true")
    p.add_argument("--lint-only", action="store_true")
    p.add_argument("--list-rules", action="store_true")
    p.add_argument("--show-allowlisted", action="store_true",
                   help="print allowlisted findings too (always counted)")
    args = p.parse_args(argv)

    if args.list_rules:
        for rid, desc in sorted(rules.RULES.items()):
            print(f"{rid}  {desc}")
        return 0

    t0 = time.time()
    findings = []

    if not args.audit_only:
        findings.extend(lint.lint_repo())

    if not args.lint_only:
        names = (args.scenarios.split(",") if args.scenarios
                 else QUICK_SCENARIOS if args.quick else None)
        backends = tuple(b for b in args.backends.split(",") if b)
        f, _ = audit.audit_catalogue(
            names=names, backends=backends,
            progress=lambda n: print(f"# auditing {n}", flush=True))
        findings.extend(f)

    bad = [f for f in findings if not f.allowlisted]
    allowed = [f for f in findings if f.allowlisted]
    for f in bad:
        print(f"FAIL {f}")
    for f in allowed:
        if args.show_allowlisted:
            print(f"ok   {f}")

    print(f"# {len(findings)} finding(s): {len(bad)} unallowlisted, "
          f"{len(allowed)} allowlisted intentional "
          f"({time.time() - t0:.1f}s)")
    if bad:
        print("# FAILED: fix the findings above or allowlist them in "
              "src/repro/analysis/rules.py with a justification")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
