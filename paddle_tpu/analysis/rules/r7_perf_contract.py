"""R7 perf-contract: new compiled-path surface area must stay visible to
the performance accounting plane.

The regression sentinel (profiler/sentinel.py) and the bands an operator
keeps for it are only as good as two inputs:

  * the analytic FLOPs estimator (`goodput.estimate_cycle_flops`) — an
    op that does matmul-class work but falls through to the O(numel)
    default silently deflates MFU/goodput and the drift verdicts built
    on them;
  * the AOT env fingerprint (`aot_cache.env_fingerprint`) — a flag that
    steers what a compiled program LOOKS like but is absent from the
    fingerprint lets one process deserialize another's artifacts, which
    surfaces as unexplained perf drift rather than a crash.

Two purely static checks, mirroring that split:

  * every `@register_op` function whose body touches heavy contraction
    math (einsum / matmul / tensordot / `@` / ...) must dispatch under a
    name the estimator's family heuristic recognizes ("matmul" in name,
    mm/bmm/addmm/linear, conv/attention/softmax/embedding) OR have an
    explicit `declare_op_flops("name", ...)` declaration somewhere in
    the tree;
  * every `FLAGS_*` string literal used in a module that registers ops
    must appear in the fingerprint's flag tuple (inside
    `env_fingerprint`) OR in the `FUSION_NEUTRAL_FLAGS` frozenset
    (ops/aot_cache.py) that records the deliberate judgment "this knob
    cannot change a lowered program". The flag check is skipped on
    trees that carry neither surface (isolated fixture trees).

Like every rule, findings carry a REASON_CODES entry (`perf_contract`)
shared with the runtime taxonomy, and deliberate exceptions live in
tools/fusion_lint_baseline.json (e.g. einsum, whose cost depends on the
equation string, not the operand shapes alone).
"""
from __future__ import annotations

import ast
import re

from ..analyzer import Finding, call_name, decorator_op_name, qualname_of
from . import rule

_FLAG_RE = re.compile(r"^FLAGS_[A-Za-z0-9_]+$")

# attribute names that mean "this op does contraction-class work" —
# whether called (`jnp.einsum(...)`) or passed as the kernel callable
# (`binary("inner", jnp.inner, ...)`)
_HEAVY_ATTRS = frozenset({
    "einsum", "matmul", "dot", "dot_general", "tensordot", "inner",
    "outer", "vdot", "multi_dot", "matrix_power", "kron",
})

# the wrappers whose first string argument is the dispatch name the
# goodput estimator will see as the cache key's key[0]
_DISPATCHERS = frozenset({"unary", "binary", "nary", "call_op"})

# name families `goodput._flops_of_op` recognizes analytically — keep in
# sync with that function (R7's own fixture freezes this list)
_COVERED_EXACT = frozenset({"linear", "mm", "bmm", "addmm"})
_COVERED_SUBSTR = ("matmul", "conv", "attention", "softmax", "embedding")


def _family_covered(name):
    return name in _COVERED_EXACT or any(s in name for s in _COVERED_SUBSTR)


@rule
class PerfContract:
    id = "R7"
    title = "perf-contract drift (FLOPs coverage / flag fingerprint)"
    reason_code = "perf_contract"
    hint = ("keep new compiled-path surface visible to the perf plane: "
            "give heavy ops an estimator the goodput accountant can use "
            "(dispatch under a matmul-family name or add a "
            "`declare_op_flops(\"<name>\", fn)` in profiler/goodput.py) "
            "and classify new compiled-path flags (add to the "
            "`env_fingerprint` flags tuple if they change the lowered "
            "program, to `FUSION_NEUTRAL_FLAGS` in ops/aot_cache.py with "
            "a rationale if they cannot)")

    def run(self, project):
        declared, fp_flags, neutral = self._contract_surfaces(project)
        for module in project.modules:
            parents = None
            opfuncs = [n for n in ast.walk(module.tree)
                       if isinstance(n, ast.FunctionDef)
                       and decorator_op_name(n) is not None]
            for fn in opfuncs:
                finding = self._check_flops(fn, module, declared)
                if finding is not None:
                    parents = parents or module.parents()
                    yield Finding(
                        rule=self.id, file=module.rel, line=fn.lineno,
                        reason_code=self.reason_code,
                        message=finding,
                        symbol=qualname_of(fn, parents))
            # flag classification only applies to op-registering modules
            # (the compiled-op path), and only on trees that carry the
            # fingerprint/neutral surfaces at all
            if opfuncs and (fp_flags or neutral):
                known = fp_flags | neutral
                docstrings = _docstring_nodes(module.tree)
                for node in ast.walk(module.tree):
                    if isinstance(node, ast.Constant) \
                            and isinstance(node.value, str) \
                            and _FLAG_RE.match(node.value) \
                            and id(node) not in docstrings \
                            and node.value not in known:
                        parents = parents or module.parents()
                        yield Finding(
                            rule=self.id, file=module.rel,
                            line=node.lineno,
                            reason_code=self.reason_code,
                            message=(f"compiled-path flag `{node.value}` "
                                     "is neither in the env_fingerprint "
                                     "flags tuple nor declared in "
                                     "FUSION_NEUTRAL_FLAGS"),
                            symbol=qualname_of(node, parents))

    # -- contract surface collection ----------------------------------------
    def _contract_surfaces(self, project):
        """(declared FLOPs names, fingerprinted flags, neutral flags),
        each collected from literals anywhere in the tree."""
        declared, fp_flags, neutral = set(), set(), set()
        for module in project.modules:
            for node in ast.walk(module.tree):
                if isinstance(node, ast.Call) \
                        and call_name(node) == "declare_op_flops" \
                        and node.args \
                        and isinstance(node.args[0], ast.Constant) \
                        and isinstance(node.args[0].value, str):
                    declared.add(node.args[0].value)
                elif isinstance(node, ast.FunctionDef) \
                        and node.name == "env_fingerprint":
                    for sub in ast.walk(node):
                        if isinstance(sub, ast.Constant) \
                                and isinstance(sub.value, str) \
                                and _FLAG_RE.match(sub.value):
                            fp_flags.add(sub.value)
                elif isinstance(node, ast.Assign) \
                        and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name) \
                        and node.targets[0].id == "FUSION_NEUTRAL_FLAGS":
                    vals = _frozenset_strings(node.value)
                    if vals is not None:
                        neutral |= vals
        return declared, frozenset(fp_flags), frozenset(neutral)

    # -- FLOPs coverability --------------------------------------------------
    def _check_flops(self, fn, module, declared):
        heavy = set()
        dispatch = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Attribute) \
                    and node.attr in _HEAVY_ATTRS:
                heavy.add(node.attr)
            elif isinstance(node, ast.BinOp) \
                    and isinstance(node.op, ast.MatMult):
                heavy.add("@")
            elif isinstance(node, ast.Call) \
                    and call_name(node) in _DISPATCHERS \
                    and node.args \
                    and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                dispatch.add(node.args[0].value)
        if not heavy:
            return None
        names = dispatch or {decorator_op_name(fn)}
        names = set(names) | {decorator_op_name(fn)}
        if any(_family_covered(n) or n in declared for n in names):
            return None
        pretty = ", ".join(sorted(heavy))
        return (f"op does heavy contraction work ({pretty}) but none of "
                f"its dispatch names ({', '.join(sorted(names))}) is "
                "coverable by estimate_cycle_flops — declare its cost "
                "via declare_op_flops or dispatch under a matmul-family "
                "name")


def _frozenset_strings(node):
    """{"a", "b"} out of `frozenset({...})` / a bare set literal."""
    if isinstance(node, ast.Call) and call_name(node) == "frozenset" \
            and node.args:
        node = node.args[0]
    if isinstance(node, ast.Set):
        vals = set()
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                vals.add(el.value)
            else:
                return None
        return frozenset(vals)
    return None


def _docstring_nodes(tree):
    """id()s of Constant nodes in docstring position."""
    out = set()
    for node in ast.walk(tree):
        body = getattr(node, "body", None)
        if isinstance(body, list) and body \
                and isinstance(body[0], ast.Expr) \
                and isinstance(body[0].value, ast.Constant) \
                and isinstance(body[0].value.value, str):
            out.add(id(body[0].value))
    return out
