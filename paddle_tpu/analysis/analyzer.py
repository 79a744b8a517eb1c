"""Shared AST machinery for the fusion linter.

The rules (paddle_tpu/analysis/rules/) need four capabilities beyond a
raw `ast.walk`:

  * project loading — the default scan set is the package source plus
    tools/ (never tests/, never fixtures), each file parsed
    once and shared across rules;
  * scope/closure resolution — for a `fn` passed into the dispatch
    funnel, which names does it CAPTURE from the enclosing op wrapper
    (free variables), as opposed to binding locally?
  * a light taint pass — is a captured name a Tensor/array (would make
    the op un-keyable) or a scalar/shape (keys by value)? Classified
    from the assignment forms the op corpus actually uses
    (`ensure_tensor(x)`, `x._value`, `jnp.asarray(...)`,
    `jax.random.*`), deliberately conservative: an UNKNOWN name is never
    flagged — the linter's false-positive budget is spent in the
    baseline file, not in the rules;
  * dispatch call-site discovery — every `call_op` / `call_op_multi` /
    `unary` / `binary` / `nary` call, with the fn expression resolved to
    its local def/lambda and the dispatch-input names collected.

Findings are plain records; reason codes come from the SAME public
REASON_CODES contract the flight recorder emits (profiler/events.py), so
the doctor can cross-reference a runtime split with the static finding
that predicted it.
"""
from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field

__all__ = ["Finding", "ModuleInfo", "Project", "load_project", "run_rules",
           "RULE_DOCS", "FuncIndex", "free_loads", "bound_names",
           "TaintPass", "DispatchSite", "dispatch_sites", "qualname_of",
           "decorator_op_name", "parent_map", "enclosing_function"]


# ---------------------------------------------------------------------------
# findings
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Finding:
    """One rule violation. `symbol` is the enclosing function qualname —
    the stable baseline key (line numbers drift with every edit above
    them; a suppression pinned to (rule, file, symbol) survives)."""

    rule: str            # "R1".."R6"
    file: str            # repo-relative posix path
    line: int            # 1-indexed
    reason_code: str     # a REASON_CODES entry (profiler/events.py)
    message: str         # one-line, names the offending construct
    symbol: str = ""     # enclosing function qualname ("" = module level)

    def sort_key(self):
        return (self.file, self.line, self.rule, self.message)


# one-line rule documentation, keyed by rule id — report.py renders the
# table, README mirrors it
RULE_DOCS: dict = {}


# ---------------------------------------------------------------------------
# project loading
# ---------------------------------------------------------------------------

@dataclass
class ModuleInfo:
    path: str                      # absolute
    rel: str                       # repo-relative posix path
    source: str
    tree: ast.Module
    _parents: dict = field(default=None, repr=False)

    def parents(self):
        """node -> parent map (built lazily, shared across rules)."""
        if self._parents is None:
            self._parents = parent_map(self.tree)
        return self._parents


@dataclass
class Project:
    root: str
    modules: list                  # [ModuleInfo]

    def module(self, rel):
        for m in self.modules:
            if m.rel == rel:
                return m
        return None

    def parse_errors(self):
        """[(rel, error)] for files the loader could not parse. An
        unparsable file contributes zero findings to every rule — the
        CLI treats any entry here as a hard error (exit 2), because the
        file most likely to be broken is exactly the one a silent skip
        would stop covering."""
        return [(m.rel, m.parse_error) for m in self.modules
                if getattr(m, "parse_error", None)]


_DEFAULT_SCAN = ("paddle_tpu", "tools")
_SKIP_DIRS = {"__pycache__", "tests", ".git"}


def _repo_root():
    """The checkout root: two levels above this file
    (paddle_tpu/analysis/analyzer.py)."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _iter_py(base):
    if os.path.isfile(base):
        if base.endswith(".py"):
            yield base
        return
    for dirpath, dirnames, filenames in os.walk(base):
        dirnames[:] = sorted(d for d in dirnames if d not in _SKIP_DIRS)
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)


def load_project(root=None, paths=None):
    """Parse the scan set once. `paths` (files or directories, absolute
    or root-relative) overrides the default package+tools set — that is
    how the golden known-bad fixtures run through the same pipeline.
    An EXPLICIT path that does not exist raises: a typo'd CI wiring
    must fail loudly, never scan nothing and report the tree clean."""
    root = os.path.abspath(root or _repo_root())
    bases = []
    explicit = paths is not None and len(paths) > 0
    for p in (paths if explicit else _DEFAULT_SCAN):
        ap = p if os.path.isabs(p) else os.path.join(root, p)
        if os.path.exists(ap):
            bases.append(ap)
        elif explicit:
            raise FileNotFoundError(
                f"fusion_lint: scan path does not exist: {ap}")
    modules = []
    for base in bases:
        for path in _iter_py(base):
            try:
                with open(path, "r", encoding="utf-8") as f:
                    src = f.read()
                tree = ast.parse(src, filename=path)
            except (OSError, SyntaxError) as e:
                # an unparsable file is itself a finding-worthy event,
                # but the linter must never crash on one
                modules.append(ModuleInfo(
                    path=path, rel=_rel(path, root),
                    source="", tree=ast.parse("")))
                modules[-1].parse_error = str(e)
                continue
            modules.append(ModuleInfo(path=path, rel=_rel(path, root),
                                      source=src, tree=tree))
    return Project(root=root, modules=modules)


def _rel(path, root):
    try:
        rel = os.path.relpath(path, root)
    except ValueError:
        rel = path
    return rel.replace(os.sep, "/")


def run_rules(project, rules=None):
    """Run the registered rule set over a loaded project; returns
    findings sorted by (file, line, rule). Unknown rule ids raise —
    `--rules R7` must not silently select nothing and pass the gate."""
    from .rules import RULES
    if rules is None:
        selected = RULES
    else:
        wanted = set(rules)
        unknown = wanted - {r.id for r in RULES}
        if unknown:
            raise ValueError(
                f"unknown rule id(s) {sorted(unknown)}; available: "
                f"{sorted(r.id for r in RULES)}")
        selected = [r for r in RULES if r.id in wanted]
    findings = []
    for r in selected:
        findings.extend(r.run(project))
    return sorted(set(findings), key=Finding.sort_key)


# ---------------------------------------------------------------------------
# AST utilities: parents, qualnames, decorators
# ---------------------------------------------------------------------------

def parent_map(tree):
    parents = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


_FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)


def enclosing_function(node, parents):
    """Nearest enclosing def/lambda of `node`, or None at module level."""
    cur = parents.get(node)
    while cur is not None:
        if isinstance(cur, _FUNC_NODES):
            return cur
        cur = parents.get(cur)
    return None


def qualname_of(node, parents):
    """Dotted def/class path of the scope containing `node` (for the
    baseline key)."""
    names = []
    cur = node
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.ClassDef)):
            names.append(cur.name)
        cur = parents.get(cur)
    return ".".join(reversed(names))


def decorator_op_name(funcdef):
    """The op name when `funcdef` is decorated `@register_op("name",
    ...)`, else None."""
    for dec in getattr(funcdef, "decorator_list", ()):
        if isinstance(dec, ast.Call):
            fn = dec.func
            name = fn.id if isinstance(fn, ast.Name) else (
                fn.attr if isinstance(fn, ast.Attribute) else None)
            if name == "register_op" and dec.args and \
                    isinstance(dec.args[0], ast.Constant) and \
                    isinstance(dec.args[0].value, str):
                return dec.args[0].value
    return None


def call_name(call):
    """Terminal name of a Call's callee: `foo(...)` and `a.b.foo(...)`
    both answer "foo"."""
    fn = call.func
    if isinstance(fn, ast.Name):
        return fn.id
    if isinstance(fn, ast.Attribute):
        return fn.attr
    return None


def dotted_name(node):
    """"a.b.c" for an Attribute/Name chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


# ---------------------------------------------------------------------------
# scope resolution: bindings and free variables
# ---------------------------------------------------------------------------

def _collect_bound(node, acc):
    """Names bound anywhere inside `node` (params, assignments, loop and
    with targets, defs, imports, walrus) — including nested function
    scopes. Over-approximating the bound set errs toward FEWER captures,
    the safe direction for a linter."""
    if isinstance(node, _FUNC_NODES):
        a = node.args
        for arg in (list(a.posonlyargs) + list(a.args)
                    + list(a.kwonlyargs)
                    + ([a.vararg] if a.vararg else [])
                    + ([a.kwarg] if a.kwarg else [])):
            acc.add(arg.arg)
    for child in ast.iter_child_nodes(node):
        if isinstance(child, ast.Name) and isinstance(
                child.ctx, (ast.Store, ast.Del)):
            acc.add(child.id)
        elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
            acc.add(child.name)
        elif isinstance(child, (ast.Import, ast.ImportFrom)):
            for alias in child.names:
                acc.add((alias.asname or alias.name).split(".")[0])
        elif isinstance(child, ast.ExceptHandler) and child.name:
            acc.add(child.name)
        _collect_bound(child, acc)
    return acc


def bound_names(fn_node):
    """Every name bound within `fn_node` (its params + all inner
    bindings, nested scopes included)."""
    return _collect_bound(fn_node, set())


def free_loads(fn_node):
    """{name: first_lineno} of names READ inside `fn_node` that it does
    not bind — the closure captures (plus globals/builtins; the caller
    intersects with the enclosing scope's bindings to separate them)."""
    bound = bound_names(fn_node)
    out = {}
    body = fn_node.body if isinstance(fn_node.body, list) else [fn_node.body]
    for stmt in body:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load) \
                    and node.id not in bound and node.id not in out:
                out[node.id] = node.lineno
    return out


# ---------------------------------------------------------------------------
# taint: which names hold Tensors / arrays?
# ---------------------------------------------------------------------------

# np/jnp constructors whose results are device/host ARRAYS (a captured
# array can never be value-keyed). Deliberately explicit — shape helpers
# (broadcast_shapes), dtype helpers etc. return keyable tuples/scalars.
_ARRAY_FNS = {
    "asarray", "array", "zeros", "ones", "empty", "full", "arange",
    "linspace", "eye", "tril", "triu", "concatenate", "stack", "where",
    "broadcast_to", "zeros_like", "ones_like", "full_like", "device_put",
}
_TENSOR_FNS = {"ensure_tensor", "to_tensor", "Tensor"}
_PROPAGATE_METHODS = {"astype", "reshape", "clone", "transpose", "detach",
                      "copy"}


class TaintPass:
    """Single forward pass over one function body classifying local
    names: "tensor" (a framework Tensor), "array" (a raw jax/numpy
    array), or absent (scalar/shape/unknown — never flagged). The
    classification follows the op-corpus idiom: `x = ensure_tensor(x)`
    proves x is a Tensor; `v = x._value` / `.numpy()` / `jnp.asarray(..)`
    / `jax.random.<sampler>(..)` produce arrays."""

    def __init__(self, fn_node):
        self.taints = {}
        body = fn_node.body if isinstance(fn_node.body, list) \
            else [fn_node.body]
        for stmt in body:
            self._visit_stmt(stmt)

    def of(self, name):
        return self.taints.get(name)

    # -- statements ---------------------------------------------------------
    def _visit_stmt(self, stmt):
        if isinstance(stmt, _FUNC_NODES + (ast.ClassDef,)):
            return                       # nested scope: not this frame
        if isinstance(stmt, ast.Assign):
            # tuple-to-tuple assignment taints elementwise:
            # `a, b = ensure_tensor(x), ensure_tensor(y)`
            if len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], (ast.Tuple, ast.List)) \
                    and isinstance(stmt.value, (ast.Tuple, ast.List)) \
                    and len(stmt.targets[0].elts) == len(stmt.value.elts):
                for el, val in zip(stmt.targets[0].elts, stmt.value.elts):
                    t = self.taint_of(val)
                    if t and isinstance(el, ast.Name):
                        self.taints[el.id] = t
                return
            t = self.taint_of(stmt.value)
            if t:
                for tgt in stmt.targets:
                    if isinstance(tgt, ast.Name):
                        self.taints[tgt.id] = t
                    elif isinstance(tgt, (ast.Tuple, ast.List)):
                        for el in tgt.elts:
                            if isinstance(el, ast.Name):
                                self.taints[el.id] = t
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None \
                and isinstance(stmt.target, ast.Name):
            t = self.taint_of(stmt.value)
            if t:
                self.taints[stmt.target.id] = t
        elif isinstance(stmt, ast.AugAssign) \
                and isinstance(stmt.target, ast.Name):
            t = self.taint_of(stmt.value)
            if t:
                self.taints[stmt.target.id] = t
        for child in ast.iter_child_nodes(stmt):
            if isinstance(child, (ast.stmt,)):
                self._visit_stmt(child)

    # -- expressions --------------------------------------------------------
    def taint_of(self, node):
        if isinstance(node, ast.Name):
            return self.taints.get(node.id)
        if isinstance(node, ast.Attribute):
            if node.attr == "_value":
                return "array"
            return None
        if isinstance(node, ast.Subscript):
            t = self.taint_of(node.value)
            return "array" if t else None
        if isinstance(node, ast.BinOp):
            return self.taint_of(node.left) or self.taint_of(node.right)
        if isinstance(node, ast.IfExp):
            return self.taint_of(node.body) or self.taint_of(node.orelse)
        if isinstance(node, ast.Call):
            name = call_name(node)
            if name in _TENSOR_FNS:
                return "tensor"
            if name == "numpy":
                return "array"
            if name in _PROPAGATE_METHODS \
                    and isinstance(node.func, ast.Attribute):
                inner = self.taint_of(node.func.value)
                if name == "detach" and inner:
                    return "tensor"
                return inner
            dn = dotted_name(node.func) or ""
            head = dn.split(".")[0]
            if head in ("np", "numpy", "jnp") and name in _ARRAY_FNS:
                return "array"
            if dn.startswith(("jax.random.", "random_mod.")) \
                    and name not in ("key_data", "wrap_key_data",
                                     "split", "key", "PRNGKey"):
                # a sampler result (gumbel/uniform/normal/...) is a fresh
                # array; key plumbing stays un-tainted (keys are handled
                # by R2, not R1)
                return "array"
            if dn in ("jax.device_put",):
                return "array"
        return None


# ---------------------------------------------------------------------------
# dispatch call-site discovery
# ---------------------------------------------------------------------------

# funnel entry points (ops/dispatch.py + ops/_helpers.py): positional
# layout is (name, fn, *inputs-ish)
_DISPATCH_WRAPPERS = {"call_op", "call_op_multi", "unary", "binary", "nary"}


@dataclass
class DispatchSite:
    call: ast.Call                 # the call_op(...) node
    op_name: str                   # literal op name ("" if dynamic)
    fn_expr: ast.AST               # the fn argument expression
    fn_node: ast.AST               # resolved local def/lambda, or None
    input_names: set               # Name ids appearing in the input args
    enclosing: ast.AST             # the wrapper function def (or module)

    @property
    def line(self):
        return self.call.lineno


def _resolve_local_fn(name, scope_node):
    """A local `def name(...)` or `name = lambda ...` in `scope_node`
    (not descending into nested defs)."""
    body = scope_node.body if isinstance(scope_node.body, list) else []
    for stmt in body:
        if isinstance(stmt, ast.FunctionDef) and stmt.name == name:
            return stmt
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name) \
                and stmt.targets[0].id == name \
                and isinstance(stmt.value, ast.Lambda):
            return stmt.value
        # one level of if/else nesting covers the corpus idiom
        # (`if training: ... def fn ...`)
        if isinstance(stmt, (ast.If, ast.For, ast.While, ast.With)):
            found = _resolve_local_fn(name, stmt)
            if found is not None:
                return found
    return None


def dispatch_sites(module):
    """Every funnel call in `module`, with the fn resolved and the
    dispatch-input names collected. Skips ops/dispatch.py and
    ops/_helpers.py themselves (they DEFINE the funnel)."""
    if module.rel.endswith(("ops/dispatch.py", "ops/_helpers.py")):
        return []
    parents = module.parents()
    sites = []
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        if call_name(node) not in _DISPATCH_WRAPPERS:
            continue
        if len(node.args) < 2:
            continue
        op_name = ""
        if isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            op_name = node.args[0].value
        fn_expr = node.args[1]
        enclosing = enclosing_function(node, parents) or module.tree
        fn_node = None
        if isinstance(fn_expr, ast.Lambda):
            fn_node = fn_expr
        elif isinstance(fn_expr, ast.Name):
            scope = enclosing
            while fn_node is None:
                if hasattr(scope, "body"):
                    fn_node = _resolve_local_fn(fn_expr.id, scope)
                if fn_node is not None or scope is module.tree:
                    break
                scope = enclosing_function(scope, parents) or module.tree
        input_names = set()
        for arg in node.args[2:]:
            for sub in ast.walk(arg):
                if isinstance(sub, ast.Name):
                    input_names.add(sub.id)
        sites.append(DispatchSite(call=node, op_name=op_name,
                                  fn_expr=fn_expr, fn_node=fn_node,
                                  input_names=input_names,
                                  enclosing=enclosing))
    return sites
