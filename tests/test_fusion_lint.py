"""Promotion-safety static analyzer (paddle_tpu/analysis) — the fusion
linter.

Covers the PR 15 contract end to end:

  * one golden known-bad fixture per rule (tests/fixtures/lint/),
    asserting the EXACT {rule, reason_code, line} findings — the rules
    must keep firing on the seeded violations;
  * the clean-tree gate: `tools/fusion_lint.py --baseline` exits 0 on
    the repo (this IS the tier-1 CI wiring) and finishes inside the
    10 s budget;
  * per-fixture CLI runs exit non-zero (all six rules demonstrated);
  * baseline add/expire round-trip + stale-suppression reporting;
  * the --json schema (version/findings/summary keys, every finding
    carrying a valid REASON_CODES entry that has a REASON_HINTS hint);
  * the R5 contract freeze on the LIVE tree (extends
    tests/test_fusion_events.py's REASON_CODES/HINTS freeze to the
    whole observability surface);
  * `fusion_doctor --demo ... --lint` smoke (the lint section rides the
    doctor report).
"""
import json
import os
import subprocess
import sys

import pytest

from paddle_tpu.analysis import (Baseline, analyze, findings_to_dicts,
                                 validate_findings)
from paddle_tpu.analysis.baseline import DEFAULT_BASELINE
from paddle_tpu.profiler.events import REASON_CODES
from paddle_tpu.profiler.explain import REASON_HINTS

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "lint")

_FIXTURE_PATHS = {
    "R1": ["r1_unkeyable.py"],
    "R2": ["r2_stateful_rng.py"],
    "R3": ["r3_host_sync.py"],
    "R4": ["distributed/r4_unkeyed.py",
           "incubate/distributed/r4_lax_unkeyed.py"],
    "R5": ["r5_project"],
    "R6": ["serving/r6_locks.py", "serving/r6_tenancy.py",
           "distributed/fabric.py"],
    "R7": ["r7_perf_contract.py"],
}


def _fixture_findings(rule):
    return analyze(root=FIXTURES, paths=_FIXTURE_PATHS[rule])


def _triples(findings):
    return sorted((f.rule, f.reason_code, f.line) for f in findings)


class TestRuleFixtures:
    """Exact {rule, reason_code, line} findings per golden fixture. A
    changed line number here means the fixture drifted — keep them in
    sync deliberately."""

    def test_r1_unkeyable_closure(self):
        fs = _fixture_findings("R1")
        assert _triples(fs) == [
            ("R1", "unkeyable_closure", 19),   # captured array idx
            ("R1", "unkeyable_closure", 28),   # captured Tensor m
            ("R1", "unkeyable_closure", 36),   # mutable module global
        ]
        # the fixed form (index threaded as input) stays clean
        assert not any(f.symbol == "good_threaded" for f in fs)

    def test_r2_stateful_rng(self):
        fs = _fixture_findings("R2")
        assert _triples(fs) == [
            ("R2", "rng_rekey", 14),           # get_rng_key()
            ("R2", "rng_rekey", 19),           # split_key()
            ("R2", "rng_rekey", 25),           # default_generator.next_key()
        ]
        assert not any(f.symbol == "good_hoisted" for f in fs)

    def test_r3_host_sync(self):
        fs = _fixture_findings("R3")
        assert _triples(fs) == [
            ("R3", "mid_step_peek", 11),       # .numpy()
            ("R3", "mid_step_peek", 12),       # float()
            ("R3", "mid_step_peek", 24),       # .item()
        ]
        assert not any(f.symbol == "good_aval_op" for f in fs)

    def test_r4_unkeyed_collective(self):
        fs = _fixture_findings("R4")
        assert _triples(fs) == [
            ("R4", "collective_unkeyed", 8),   # pg call outside the funnel
            ("R4", "collective_unkeyed", 13),  # unstamped lax.ppermute
            ("R4", "collective_unkeyed", 14),  # funnel without the stamp
            ("R4", "collective_unkeyed", 20),  # unstamped lax.all_to_all
        ]
        assert not any(f.symbol == "good_marked_collective" for f in fs)
        # the stamped and shard_map-only lax forms stay clean
        assert not any(f.symbol.startswith("good_") for f in fs)

    def test_r5_contract_coverage(self):
        fs = _fixture_findings("R5")
        got = {(f.rule, f.reason_code, f.file, f.line) for f in fs}
        assert got == {
            ("R5", "contract_drift", "r5_project/events.py", 7),
            ("R5", "contract_drift", "r5_project/events.py", 24),
            ("R5", "contract_drift", "r5_project/events.py", 25),
            ("R5", "contract_drift", "r5_project/explain.py", 3),
            ("R5", "contract_drift", "r5_project/metrics.py", 4),
            ("R5", "contract_drift", "r5_project/metrics.py", 21),
            ("R5", "contract_drift", "r5_project/consumer.py", 8),
        }

    def test_r6_lock_discipline(self):
        fs = _fixture_findings("R6")
        got = {(f.rule, f.reason_code, f.file, f.line) for f in fs}
        assert got == {
            # serving/r6_locks.py + r6_tenancy.py
            ("R6", "lock_discipline", "serving/r6_locks.py", 16),
            ("R6", "lock_discipline", "serving/r6_tenancy.py", 18),
            ("R6", "lock_discipline", "serving/r6_locks.py", 22),
            ("R6", "lock_discipline", "serving/r6_locks.py", 23),
            ("R6", "lock_discipline", "serving/r6_tenancy.py", 24),
            ("R6", "lock_discipline", "serving/r6_tenancy.py", 25),
            ("R6", "lock_discipline", "serving/r6_locks.py", 35),
            ("R6", "lock_discipline", "serving/r6_tenancy.py", 38),
            # distributed/fabric.py (the elastic-fabric control plane)
            ("R6", "lock_discipline", "distributed/fabric.py", 18),
            ("R6", "lock_discipline", "distributed/fabric.py", 24),
            ("R6", "lock_discipline", "distributed/fabric.py", 25),
            ("R6", "lock_discipline", "distributed/fabric.py", 34),
        }
        # the snapshot-then-invoke pattern stays clean
        assert not any(f.symbol.startswith("GoodRegistry") for f in fs)
        # ...and the tenancy-flavored fixed form (the discipline
        # serving/tenancy.py actually ships) stays clean too
        assert not any(f.symbol.startswith("GoodPrefixIndex") for f in fs)
        # ...and the fabric-flavored collect-then-emit form
        assert not any(f.symbol.startswith("GoodCoordinator") for f in fs)

    def test_r7_perf_contract(self):
        fs = _fixture_findings("R7")
        assert _triples(fs) == [
            ("R7", "perf_contract", 34),       # heavy op, uncoverable name
            ("R7", "perf_contract", 57),       # flag off the fingerprint
        ]
        # matmul-family dispatch name and declared estimator stay clean
        assert not any(f.symbol.startswith("good_") for f in fs)
        # neutral + fingerprinted flag reads stay clean (the `routed`
        # finding is the undeclared flag only)
        assert all("FLAGS_undeclared_routing" in f.message
                   for f in fs if f.symbol == "routed")

    def test_every_finding_on_the_reason_contract(self):
        """Static findings and runtime attributions are ONE taxonomy:
        every fixture finding carries a REASON_CODES entry with a
        REASON_HINTS hint."""
        for rule in _FIXTURE_PATHS:
            fs = _fixture_findings(rule)
            assert fs, f"{rule} fixture produced no findings"
            assert validate_findings(fs) == []
            for d in findings_to_dicts(fs):
                assert d["reason_code"] in REASON_CODES
                assert d["reason_code"] in REASON_HINTS
                assert d["hint"]


class TestCleanTree:
    """The repo itself holds the invariants the linter proves."""

    def test_repo_findings_all_baselined(self):
        findings = analyze(root=REPO)
        bl = Baseline.load(DEFAULT_BASELINE)
        live, muted = bl.split(findings)
        assert live == [], (
            "unsuppressed fusion_lint findings on the tree:\n"
            + "\n".join(f"{f.file}:{f.line} {f.rule} {f.message}"
                        for f in live))
        assert bl.stale(findings) == [], "stale baseline suppressions"

    def test_r5_contract_freeze_on_live_tree(self):
        """The R5 audit runs CLEAN on the real contracts — frozen as a
        tier-1 test so a reason code without a hint, a metric without a
        merge policy, an off-contract category, or an unregistered
        FLAGS read can never land again."""
        assert analyze(root=REPO, rules=["R5"]) == []

    def test_r6_lock_discipline_clean_on_live_tree(self):
        assert analyze(root=REPO, rules=["R6"]) == []

    def test_r7_perf_contract_on_live_tree(self):
        """Every heavy op is coverable (family name or declare_op_flops)
        and every ops/nn flag is classified — except einsum, whose
        equation-dependent cost is a deliberate, noted baseline entry."""
        fs = analyze(root=REPO, rules=["R7"])
        assert [(f.file, f.symbol) for f in fs] == \
            [("paddle_tpu/ops/einsum_op.py", "einsum")]
        bl = Baseline.load(DEFAULT_BASELINE)
        assert bl.split(fs)[0] == []

    def test_cli_gate_exits_zero(self):
        """The tier-1 CI wiring: `python tools/fusion_lint.py
        --baseline` exits 0 on the shipped tree, with no suppression
        gone stale."""
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "fusion_lint.py"),
             "--baseline"],
            capture_output=True, text=True, timeout=600,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stdout + out.stderr
        assert "0 unsuppressed finding(s)" in out.stdout


class TestCLI:
    def test_each_fixture_fails_the_gate(self):
        """Acceptance: non-zero exit on each seeded violation — all six
        rules demonstrated through the real CLI."""
        for rule, paths in sorted(_FIXTURE_PATHS.items()):
            out = subprocess.run(
                [sys.executable,
                 os.path.join(REPO, "tools", "fusion_lint.py"),
                 "--root", FIXTURES] + paths,
                capture_output=True, text=True, timeout=120,
                env={**os.environ, "JAX_PLATFORMS": "cpu"})
            assert out.returncode == 1, \
                f"{rule}: expected exit 1, got {out.returncode}\n" \
                + out.stdout + out.stderr
            assert rule in out.stdout

    def test_json_schema(self):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "fusion_lint.py"),
             "--root", FIXTURES, "--json"] + _FIXTURE_PATHS["R1"],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 1
        doc = json.loads(out.stdout)
        assert doc["version"] == 1
        assert set(doc) == {"version", "findings", "suppressed",
                            "stale_suppressions", "rules", "summary"}
        assert doc["summary"]["findings"] == len(doc["findings"]) > 0
        assert set(doc["summary"]["by_rule"]) == {"R1"}
        for f in doc["findings"]:
            assert set(f) == {"rule", "file", "line", "symbol",
                              "reason_code", "message", "hint"}
            assert f["reason_code"] in REASON_CODES
            assert f["reason_code"] in REASON_HINTS
            assert f["hint"]
        # the rule table rides along for consumers
        assert set(doc["rules"]) == {"R1", "R2", "R3", "R4", "R5", "R6",
                                     "R7"}

    def test_fix_hints_render(self):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "fusion_lint.py"),
             "--root", FIXTURES, "--fix-hints"] + _FIXTURE_PATHS["R2"],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 1
        assert "fix: " in out.stdout
        assert "rng_key_input" in out.stdout


class TestBaseline:
    def test_add_match_expire_roundtrip(self, tmp_path):
        findings = _fixture_findings("R1")
        assert findings
        path = str(tmp_path / "baseline.json")
        bl = Baseline()
        for f in findings:
            bl.add(f, note="fixture acknowledgment")
        bl.save(path)

        bl2 = Baseline.load(path)
        live, muted = bl2.split(findings)
        assert live == [] and len(muted) == len(findings)
        assert bl2.stale(findings) == []

        # the violations get fixed -> every entry expires
        dead = bl2.stale([])
        assert len(dead) == len(bl2.entries)
        removed = bl2.expire([])
        assert removed == dead and bl2.entries == []

    def test_partial_expiry_keeps_live_entries(self, tmp_path):
        r1 = _fixture_findings("R1")
        r2 = _fixture_findings("R2")
        bl = Baseline()
        for f in r1 + r2:
            bl.add(f, note="n")
        # R2's violations get fixed; R1's remain
        removed = bl.expire(r1)
        assert all(e["rule"] == "R2" for e in removed)
        assert all(e["rule"] == "R1" for e in bl.entries)
        live, muted = bl.split(r1)
        assert live == []

    def test_add_is_idempotent(self):
        f = _fixture_findings("R1")[0]
        bl = Baseline()
        e1 = bl.add(f, note="x")
        e2 = bl.add(f, note="y")
        assert e1 is e2 and len(bl.entries) == 1

    def test_checked_in_baseline_entries_all_noted(self):
        """Every shipped suppression carries a human justification."""
        bl = Baseline.load(DEFAULT_BASELINE)
        assert bl.entries, "the checked-in baseline exists"
        for e in bl.entries:
            assert e.get("note") and "fill me in" not in e["note"], e


class TestDoctorLint:
    def test_doctor_demo_with_lint_section(self):
        """`fusion_doctor --demo masked --lint --json`: the lint block
        rides the doctor report, clean on the shipped tree."""
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "fusion_doctor.py"),
             "--demo", "masked", "--steps", "8", "--lint", "--json"],
            capture_output=True, text=True, timeout=600,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr
        rep = json.loads(out.stdout)
        lint = rep["lint"]
        assert lint["findings"] == []
        assert lint["suppressed"] > 0
        assert lint["stale_suppressions"] == 0
        assert lint["predicted"] == []     # clean promotion: nothing to
        #                                    cross-reference


class TestGateCannotSilentlyPass:
    """The three silent-pass holes a lint gate must not have: a typo'd
    scan path, an unknown rule id, and an unparsable file must each
    FAIL loudly instead of scanning nothing and reporting clean."""

    def test_missing_explicit_path_is_an_error(self):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "fusion_lint.py"),
             "paddle_tpu/no_such_dir", "--baseline"],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 2
        assert "does not exist" in out.stderr

    def test_unknown_rule_id_is_an_error(self):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "fusion_lint.py"),
             "--rules", "R99"],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 2
        assert "unknown rule" in out.stderr

    def test_unparsable_file_is_an_error(self, tmp_path):
        bad = tmp_path / "broken.py"
        bad.write_text("def broken(:\n    <<<<<<< merge marker\n")
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "fusion_lint.py"),
             "--root", str(tmp_path), "broken.py"],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 2
        assert "cannot parse" in out.stderr
