#!/usr/bin/env python
"""Fusion lint: the promotion-safety static analyzer CLI.

Proves the fusion-stack promotion contracts hold at CI time — before any
op ever runs — in the same REASON_CODES vocabulary the fusion doctor
speaks at runtime (paddle_tpu/analysis/):

  R1 unkeyable-closure       op fn captures a Tensor/array off the
                             dispatch-input list    [unkeyable_closure]
  R2 stateful-rng            op body bypasses rng_key_input()
                             stream hoisting        [rng_rekey]
  R3 host-sync-in-hot-path   .numpy()/.item()/float() force before
                             dispatch               [mid_step_peek]
  R4 unkeyed-collective      pg call without dispatch.mark_collective
                                                    [collective_unkeyed]
  R5 contract-coverage       REASON_CODES/HINTS, METRIC_NAMES/MERGE,
                             CATEGORIES, FLAGS registry drift
                                                    [contract_drift]
  R6 lock-discipline         blocking I/O / callbacks / inversions
                             under registry locks   [lock_discipline]

Usage:

    # the repo gate (tier-1 wires exactly this; exit 1 on any
    # unsuppressed finding, exit 0 clean)
    python tools/fusion_lint.py --baseline

    # a subset of paths / rules, with actionable fix hints
    python tools/fusion_lint.py paddle_tpu/ops --rules R1,R2 --fix-hints

    # machine-readable (schema frozen by tests/test_fusion_lint.py)
    python tools/fusion_lint.py --json

    # regenerate the baseline after triaging (every entry then needs a
    # human note — edit the JSON)
    python tools/fusion_lint.py --baseline --write-baseline
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="fusion_lint",
        description="static analyzer proving the fusion promotion "
                    "contracts (R1-R6) before anything runs")
    ap.add_argument("paths", nargs="*",
                    help="files/directories to scan (default: the "
                         "package + tools)")
    ap.add_argument("--root", default=None,
                    help="repo root for relative paths/reporting "
                         "(default: the checkout containing this tool)")
    ap.add_argument("--rules", default=None,
                    help="comma-separated subset, e.g. R1,R5")
    ap.add_argument("--json", action="store_true",
                    help="print the JSON report instead of text")
    ap.add_argument("--baseline", nargs="?", const="", default=None,
                    metavar="FILE",
                    help="apply the suppression baseline (default file: "
                         "tools/fusion_lint_baseline.json)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="with --baseline: rewrite the file from the "
                         "current findings (then fill in the notes)")
    ap.add_argument("--fix-hints", action="store_true",
                    help="print the actionable fix hint under each "
                         "finding")
    args = ap.parse_args(argv)

    from paddle_tpu.analysis import (Baseline, load_project, run_rules,
                                     validate_findings)
    from paddle_tpu.analysis.baseline import DEFAULT_BASELINE
    from paddle_tpu.analysis.report import render_json, render_text

    rules = None
    if args.rules:
        rules = [r.strip().upper() for r in args.rules.split(",") if r]

    try:
        project = load_project(root=args.root, paths=args.paths or None)
        findings = run_rules(project, rules=rules)
    except (FileNotFoundError, ValueError) as e:
        print(f"fusion_lint: {e}", file=sys.stderr)
        return 2
    bad_parse = project.parse_errors()
    if bad_parse:
        for rel, err in bad_parse:
            print(f"fusion_lint: cannot parse {rel}: {err}",
                  file=sys.stderr)
        print(f"fusion_lint: {len(bad_parse)} unparsable file(s) — "
              "these files are NOT covered by any rule", file=sys.stderr)
        return 2

    bad = validate_findings(findings)
    if bad:
        print(f"fusion_lint: INTERNAL ERROR — rule emitted reason "
              f"code(s) off the REASON_CODES/REASON_HINTS contract: "
              f"{bad}", file=sys.stderr)
        return 2

    suppressed, stale = [], []
    if args.baseline is not None:
        path = args.baseline or DEFAULT_BASELINE
        bl = Baseline.load(path)
        if args.write_baseline:
            bl.expire(findings)
            for f in findings:
                bl.add(f)
            bl.save(path)
            print(f"fusion_lint: wrote {len(bl.entries)} suppression(s) "
                  f"to {path} — add a human note to each new entry")
            return 0
        findings, suppressed = bl.split(findings)
        stale = bl.stale(findings + suppressed)

    if args.json:
        print(render_json(findings, suppressed, stale))
    else:
        print(render_text(findings, suppressed, stale,
                          fix_hints=args.fix_hints))
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
