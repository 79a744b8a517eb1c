#!/usr/bin/env python
"""Perf baseline: the regression sentinel's checked-in band manager.

The runtime twin of tools/fusion_lint.py — same add/match/expire/
`--write-baseline` hygiene, applied to per-leg performance records
instead of static findings. A record is the JSON shape
`paddle_tpu.profiler.sentinel.capture_record` emits (bench.py embeds one
per leg under extra.sentinel_record; perf_smoke leg (q) writes its own);
the baseline (tools/perf_baselines.json) holds one tolerance-band entry
per leg.

Usage:

    # the CI gate (tier-1 wires exactly this through tests/
    # test_sentinel.py; exit 1 on any band violation OR unbaselined
    # record, exit 0 clean)
    python tools/perf_baseline.py --check records.json

    # seed/refresh entries from a fresh run's records (wide CPU-smoke
    # bands by default: --slack 25; tighten on the first real-TPU pass)
    python tools/perf_baseline.py --write-baseline records.json \
        --note "seeded from CPU smoke, band-tightening pass pending"

    # hygiene: list entries, report/drop legs no record exercises
    python tools/perf_baseline.py --list
    python tools/perf_baseline.py --check --expire records.json

Record files may be a single record object, a list, a JSON-lines stream
(bench.py output), or any nested document — every dict carrying the
record shape is extracted, so `--check <bench output>.json` just works.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))


def _parse_docs(path):
    """Whole-file JSON, falling back to JSON-lines (bench output)."""
    with open(path, "r", encoding="utf-8") as f:
        text = f.read()
    try:
        return [json.loads(text)]
    except ValueError:
        docs = []
        for ln in text.splitlines():
            ln = ln.strip()
            if not ln:
                continue
            try:
                docs.append(json.loads(ln))
            except ValueError:
                continue
        if not docs:
            raise ValueError(f"{path}: neither JSON nor JSON-lines")
        return docs


def _extract_records(doc, out):
    """Recursively collect every dict that looks like a sentinel record
    (the capture_record shape)."""
    if isinstance(doc, dict):
        if {"leg", "kind", "compiles", "reasons"} <= set(doc):
            out.append(doc)
        else:
            for v in doc.values():
                _extract_records(v, out)
    elif isinstance(doc, list):
        for v in doc:
            _extract_records(v, out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="perf_baseline",
        description="per-leg performance baseline bands for the "
                    "regression sentinel (profiler/sentinel.py)")
    ap.add_argument("records", nargs="*",
                    help="record files (sentinel records, bench JSON-"
                         "lines, or any document embedding records)")
    ap.add_argument("--baseline", default=None, metavar="FILE",
                    help="baseline file (default: "
                         "tools/perf_baselines.json)")
    ap.add_argument("--check", action="store_true",
                    help="gate the records against their leg bands "
                         "(exit 1 on violation or unbaselined record)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="(re)seed a band entry per record leg")
    ap.add_argument("--note", default="",
                    help="with --write-baseline: the human note new "
                         "entries carry (required for new legs)")
    ap.add_argument("--slack", type=float, default=25.0,
                    help="with --write-baseline: latency/throughput "
                         "tolerance factor (default 25 — wide CPU-smoke "
                         "bands; drop toward 1.25 on real TPU passes)")
    ap.add_argument("--policy", default="",
                    help="with --write-baseline: the file-level band-"
                         "tightening policy line (kept if empty)")
    ap.add_argument("--expire", action="store_true",
                    help="drop baseline legs no provided record "
                         "exercises (otherwise stale legs only WARN)")
    ap.add_argument("--list", action="store_true", dest="list_legs",
                    help="print the baseline entries and exit")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report")
    args = ap.parse_args(argv)

    from paddle_tpu.profiler.sentinel import (DEFAULT_PERF_BASELINE,
                                              PerfBaseline)
    path = args.baseline or DEFAULT_PERF_BASELINE

    try:
        bl = PerfBaseline.load(path)
    except (ValueError, OSError) as e:
        print(f"perf_baseline: {e}", file=sys.stderr)
        return 2

    if args.list_legs:
        doc = {leg: {"kind": e.get("kind"), "note": e.get("note"),
                     "slack": e.get("slack"),
                     "bands": e.get("bands")}
               for leg, e in sorted(bl.legs.items())}
        if args.json:
            print(json.dumps({"version": 1, "path": path, "legs": doc},
                             indent=2))
        else:
            print(f"perf_baseline: {len(doc)} leg(s) in {path}")
            for leg, e in doc.items():
                print(f"  {leg:<16} [{e['kind']}] slack x{e['slack']} — "
                      f"{e['note']}")
        return 0

    records = []
    try:
        for p in args.records:
            if not os.path.exists(p):
                raise FileNotFoundError(f"record file {p!r} does not exist")
            for doc in _parse_docs(p):
                _extract_records(doc, records)
    except (OSError, ValueError) as e:
        print(f"perf_baseline: {e}", file=sys.stderr)
        return 2
    if not records:
        print("perf_baseline: no sentinel records found in the inputs "
              "(need dicts with leg/kind/compiles/reasons)",
              file=sys.stderr)
        return 2

    if args.write_baseline:
        try:
            if args.policy:
                bl.policy = args.policy
            for rec in records:
                bl.add(rec, note=args.note, slack=args.slack)
        except ValueError as e:
            print(f"perf_baseline: {e}", file=sys.stderr)
            return 2
        if args.expire:
            for leg in bl.expire(records):
                print(f"perf_baseline: expired retired leg {leg!r}")
        bl.save(path)
        print(f"perf_baseline: wrote {len(records)} leg entr"
              f"{'y' if len(records) == 1 else 'ies'} to {path} "
              f"(slack x{args.slack:g})")
        return 0

    # --check (also the default action when records are given)
    violations, passed, unbaselined = bl.split(records)
    stale = bl.stale(records)
    if args.expire and stale:
        bl.expire(records)
        bl.save(path)
    if args.json:
        print(json.dumps({
            "version": 1, "baseline": path,
            "checked": len(records),
            "passed": [r["leg"] for r in passed],
            "unbaselined": [r["leg"] for r in unbaselined],
            "stale_legs": stale,
            "violations": [{"leg": r["leg"], "findings": fs}
                           for r, fs in violations],
        }, indent=2))
    else:
        for rec, fs in violations:
            for f in fs:
                print(f"{rec['leg']}: {f['reason']} — {f['message']}")
        for rec in unbaselined:
            print(f"{rec['leg']}: no baseline entry (seed it with "
                  "--write-baseline)")
        for leg in stale:
            act = "expired" if args.expire else \
                "stale (no record exercises it; --expire to drop)"
            print(f"{leg}: {act}")
        print(f"perf_baseline: {len(violations)} violating, "
              f"{len(unbaselined)} unbaselined, {len(passed)} clean "
              f"record(s) against {path}")
    return 1 if (violations or unbaselined) else 0


if __name__ == "__main__":
    sys.exit(main())
