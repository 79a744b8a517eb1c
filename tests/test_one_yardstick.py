"""One yardstick (ISSUE 30): the benchmark, on the chip, is the only thing
in the repository that times anything. Tier-1 runs on a CPU and asserts
counts, shapes and values — never a clock: the pre-chip measurement tools
are gone and nothing that ships names them; `slow` is the only marker; no
test module outside tests/benchmark/ asserts on a clock reading, except
those in `_CLOCK_IS_THE_SUBJECT`, whose subject IS a deadline.
"""
from __future__ import annotations

import ast
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.join(ROOT, "tests")

_SKIP_DIRS = {".git", ".cache", "__pycache__", ".pytest_cache",
              ".hypothesis", "chiprun_out", "_archive_check", "_scratch",
              "build", "dist"}
_SKIP_PREFIXES = (os.path.join("benchmark", "proof") + os.sep,)

# The deleted tools, spelt in pieces so that this file is not itself a
# place where `git grep` finds them.
_DELETED_TOOLS = {
    "bench": r"(?<!\w)bench\.py",
    "perf-smoke": "perf" + "_smoke",
    "serve-bench": "serve" + "_bench",
    "perf-baseline": "perf" + r"_baseline(?!s)",
    "perf-baselines": "perf" + "_baselines",
}


def _shipped_text_files():
    """The files that tell a reader how to build, run and measure.
    (CHANGES.md, ROADMAP.md, PERF.md, ISSUE.md, the ledger and
    benchmark/proof/ keep the tools' names as history.)"""
    for dirpath, dirnames, filenames in os.walk(ROOT):
        dirnames[:] = [d for d in dirnames if d not in _SKIP_DIRS
                       and not d.endswith(".egg-info")]
        rel_dir = os.path.relpath(dirpath, ROOT)
        for fn in filenames:
            rel = os.path.normpath(os.path.join(rel_dir, fn))
            if rel.startswith(_SKIP_PREFIXES):
                continue
            if (fn.endswith((".py", ".toml", ".json"))
                    or rel in ("README.md", ".gitignore")
                    or rel.startswith(".claude" + os.sep)):
                yield rel


@pytest.mark.parametrize("tool", sorted(_DELETED_TOOLS))
def test_no_shipped_file_names_a_deleted_tool(tool):
    pattern = re.compile(_DELETED_TOOLS[tool])
    named = []
    for rel in _shipped_text_files():
        with open(os.path.join(ROOT, rel), encoding="utf-8",
                  errors="replace") as f:
            for n, line in enumerate(f, 1):
                if pattern.search(line):
                    named.append(f"{rel}:{n}: {line.strip()[:80]}")
    assert not named, "\n".join(named)


def test_deleted_tools_are_gone():
    for parts in (("bench", ".py"), ("tools/perf", "_smoke", ".py"),
                  ("tools/serve", "_bench", ".py"),
                  ("tools/perf", "_baseline", ".py"),
                  ("tools/perf", "_baselines", ".json")):
        assert not os.path.exists(os.path.join(ROOT, "".join(parts))), parts


# ---------------------------------------------------------------------------
# markers
# ---------------------------------------------------------------------------

_PYTEST_OWN_MARKS = {"parametrize", "skip", "skipif", "xfail",
                     "usefixtures", "filterwarnings"}


def _test_modules():
    for dirpath, dirnames, filenames in os.walk(TESTS):
        dirnames[:] = [d for d in dirnames if d not in _SKIP_DIRS]
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def test_the_only_registered_marker_is_slow():
    with open(os.path.join(ROOT, "pyproject.toml"), encoding="utf-8") as f:
        text = f.read()
    block = re.search(r"^markers\s*=\s*\[(.*?)\]", text, re.S | re.M)
    assert block, "pyproject.toml registers no markers"
    names = [m.split(":")[0].strip()
             for m in re.findall(r'"([^"]*)"', block.group(1))]
    assert names == ["slow"], names
    used = set()
    for path in _test_modules():
        with open(path, encoding="utf-8") as f:
            used.update(re.findall(r"pytest\.mark\.(\w+)", f.read()))
    assert used <= _PYTEST_OWN_MARKS | {"slow"}, \
        sorted(used - _PYTEST_OWN_MARKS - {"slow"})


# ---------------------------------------------------------------------------
# no Tier-1 assertion on a CPU clock
# ---------------------------------------------------------------------------

_CLOCKS = {"perf_counter", "monotonic", "time", "perf_counter_ns",
           "monotonic_ns", "time_ns"}

# (test module, function) -> why a clock reading belongs in its assert:
# the deadline is the behaviour under test, not a speed.
_CLOCK_IS_THE_SUBJECT = {
    ("test_sentinel.py", "TestLiveWatcher.test_stall_storm_flips_"
     "split_regression_then_recovers"):
        "polls the watcher until its latch flips; the 30 s deadline only "
        "bounds a wedged watcher",
    ("test_sentinel.py",
     "TestLiveWatcher.test_decode_rebuild_flips_compile_storm"):
        "polls the watcher until its latch flips; the 30 s deadline only "
        "bounds a wedged watcher",
    ("test_sentinel.py",
     "TestHTTPSurface.test_readyz_folds_the_degraded_latch"):
        "polls /readyz until the degraded latch shows and clears; the "
        "30 s deadline only bounds a wedged server",
    ("test_native_core.py", "test_store_wait_blocks_until_set"):
        "`TCPStore.wait` must BLOCK until another thread sets the key "
        "0.15 s later: a lower bound on a wait, which load only lengthens",
    ("test_telemetry_server.py",
     "TestHealth.test_healthz_flips_within_watchdog_window_of_a_stall"):
        "the watchdog's contract: /healthz reports 503 within two step "
        "budgets of an injected stall; the budget is the flag under test",
    ("test_metrics.py", "TestGoodput.test_live_mfu_within_2pct_of_offline"):
        "compares the accountant's rate with tokens / elapsed over the "
        "SAME window: two computations of one quantity, whatever the "
        "window lasted; no speed is asserted",
    ("test_auto_parallel.py", "TestPlannerValidation._planner_ordering"):
        "reached only from two `@pytest.mark.slow` tests (opt-in, not "
        "Tier-1): the planner's cost model against this host's clock",
}


def _is_clock_call(node, bare):
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr in _CLOCKS:
        return isinstance(f.value, ast.Name) \
            and f.value.id.strip("_") == "time"
    return isinstance(f, ast.Name) and f.id in bare


def _clock_asserts(path):
    """Names of the outermost functions of `path` that assert on a value
    derived from a clock reading: taint runs from a clock call through
    assignments, loop targets, `.append`s and the returns of local or
    module-level functions, to a fixed point."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), path)
    bare = {a.asname or a.name for n in ast.walk(tree)
            if isinstance(n, ast.ImportFrom) and n.module == "time"
            for a in n.names if a.name in _CLOCKS}
    scopes = []                         # (qualified name, node)
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scopes.append((node.name, node))
        elif isinstance(node, ast.ClassDef):
            scopes += [(f"{node.name}.{m.name}", m) for m in node.body
                       if isinstance(m, (ast.FunctionDef,
                                         ast.AsyncFunctionDef))]
    tainted_fns = set()
    names = {q: set() for q, _ in scopes}

    def tainted(expr, mine):
        for n in ast.walk(expr):
            if _is_clock_call(n, bare):
                return True
            if isinstance(n, ast.Name) and n.id in mine:
                return True
            if isinstance(n, ast.Call):
                f = n.func
                callee = f.id if isinstance(f, ast.Name) else \
                    f.attr if isinstance(f, ast.Attribute) else None
                if callee in tainted_fns:
                    return True
        return False

    def bind(target, mine):
        # `x = ...`, `a, b = ...`, `d[k] = ...`; NOT `obj.field = ...`
        # (setting a deadline on a request does not make it a reading)
        if isinstance(target, ast.Name):
            mine.add(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for t in target.elts:
                bind(t, mine)
        elif isinstance(target, (ast.Subscript, ast.Starred)):
            bind(target.value, mine)

    changed = True
    while changed:
        changed = False
        for qual, scope in scopes:
            mine = names[qual]
            before = len(mine), len(tainted_fns)
            for n in ast.walk(scope):
                if isinstance(n, ast.Assign) and tainted(n.value, mine):
                    for t in n.targets:
                        bind(t, mine)
                elif isinstance(n, (ast.AugAssign, ast.AnnAssign,
                                    ast.NamedExpr)) \
                        and n.value is not None and tainted(n.value, mine):
                    bind(n.target, mine)
                elif isinstance(n, (ast.For, ast.comprehension)) \
                        and tainted(n.iter, mine):
                    bind(n.target, mine)
                elif isinstance(n, ast.Call) \
                        and isinstance(n.func, ast.Attribute) \
                        and n.func.attr in ("append", "add", "extend") \
                        and any(tainted(a, mine) for a in n.args):
                    bind(n.func.value, mine)
                elif isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    if any(isinstance(r, ast.Return) and r.value is not None
                           and tainted(r.value, mine)
                           for r in ast.walk(n)):
                        tainted_fns.add(n.name)
            changed |= before != (len(mine), len(tainted_fns))

    found = set()
    for qual, scope in scopes:
        mine = names[qual]
        for n in ast.walk(scope):
            if isinstance(n, ast.Assert) and tainted(n.test, mine):
                found.add(qual)
            elif isinstance(n, ast.Call) and any(
                    tainted(a, mine) for a in n.args):
                f = n.func
                callee = f.id if isinstance(f, ast.Name) else \
                    f.attr if isinstance(f, ast.Attribute) else ""
                if callee.startswith("assert_") or callee == "approx":
                    found.add(qual)
    return found


def test_no_tier1_test_asserts_on_a_clock():
    found = set()
    for path in _test_modules():
        rel = os.path.relpath(path, TESTS)
        if rel.split(os.sep)[0] in ("benchmark", "fixtures"):
            continue
        found |= {(rel, q) for q in _clock_asserts(path)}
    allowed = set(_CLOCK_IS_THE_SUBJECT)
    assert not found - allowed, (
        "a Tier-1 test asserts on a CPU clock (the benchmark measures "
        f"speed, on the chip): {sorted(found - allowed)}")
    assert not allowed - found, (
        f"allow-list entries that no longer read a clock: "
        f"{sorted(allowed - found)}")


def test_the_clock_walk_sees_a_ratio(tmp_path):
    """The walk itself: a wall-clock ratio behind two assignments and a
    helper's return is found; a poll loop that asserts nothing is not."""
    src = tmp_path / "test_sample.py"
    src.write_text(
        "import time\n"
        "def window(f):\n"
        "    t0 = time.perf_counter()\n"
        "    f()\n"
        "    return time.perf_counter() - t0\n"
        "def test_ratio():\n"
        "    ratios = []\n"
        "    for _ in range(3):\n"
        "        ratios.append(window(a) / window(b))\n"
        "    assert max(ratios) > 1.0\n"
        "def test_poll():\n"
        "    deadline = time.monotonic() + 5\n"
        "    while time.monotonic() < deadline and not ready():\n"
        "        time.sleep(0.01)\n"
        "    assert ready()\n")
    assert _clock_asserts(str(src)) == {"test_ratio"}
