"""Op dispatch: the single funnel every eager op call goes through.

Reference analog: the generated `<op>_ad_func` forwards
(eager/auto_code_generator/generator/eager_gen.py:1217) — AMP cast, kernel
call, GradNode creation + Edge wiring. TPU-first: the "kernel" is a jax
callable; when grad is required the VJP is captured at forward time, so
residuals are device arrays and backward is XLA-compiled.

Compiled eager dispatch (the `<op>_ad_func` fast-path analog). The reference
beat per-op dispatch overhead with the PHI kernel library plus codegen'd C++
forwards; here the same cost is beaten with a per-op executable cache:

  key   = (op name, fn token, input (shape, dtype, weak_type) avals,
           diff mask, AMP-state token, registry override token,
           guardian check flag)
  value = a jitted forward (no-grad path), or a jitted forward+vjp pair
          (grad path) whose vjp comes back as a `jax.tree_util.Partial`
          pytree — residual buffers as leaves — applied through one shared
          jitted applier, so backward reuses a compiled executable too
          instead of re-tracing `jax.vjp` on every differentiable call.

The fn token keys the implementation by VALUE: code object + closure cell
contents, accepted only for types whose hash is value-based (scalars,
dtypes, nested tuples/functions). Anything else — arrays, Tensors in
closures, tracer inputs, jit-incompatible ops — bypasses the cache and
takes the original eager path, so caching can never change numerics, only
whether jax re-traces. Registry override (de)activation bumps a per-op
generation counter (ops/registry.py) that is part of the key, so stale
entries become unreachable and age out of the LRU. Flags:
framework/flags.py FLAGS_eager_op_cache / _size / _donate; telemetry:
paddle_tpu.profiler.dispatch_cache_stats().
"""
from __future__ import annotations

import enum
import functools
import threading
import time
import types
from collections import OrderedDict
from typing import Callable, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from ..framework.core import Tensor
from ..framework.autograd import pack_saved_values as _pack_saved, GradNode, is_grad_enabled
from ..framework.flags import _FLAGS
from ..profiler.dispatch import STATS as _STATS
from ..profiler.events import EVENTS as _EVENTS
from . import guardian as _guardian
from . import aot_cache as _aot

__all__ = ["call_op", "call_op_multi", "clear_dispatch_cache",
           "dispatch_cache_info", "mark_collective"]


def _values(tensors):
    return tuple(t._value for t in tensors)


def _scan_nan_inf(name, out_vals):
    """FLAGS_check_nan_inf: scan op outputs for non-finite values, raising
    (level 0) or warning (level >= 1) with the op name — the eager analog of
    framework/details/nan_inf_utils.h:29 CheckOpHasNanOrInf. Forces a device
    sync per inexact output (the reduction must materialize)."""
    from jax.errors import TracerBoolConversionError
    for v in out_vals:
        if not jnp.issubdtype(v.dtype, jnp.inexact):
            continue
        try:
            finite = bool(jnp.all(jnp.isfinite(v)))
        except TracerBoolConversionError:
            continue   # inside a jit trace: the fused TrainStep checks
        if not finite:
            msg = f"Operator '{name}' output contains NaN/Inf"
            if int(_FLAGS.get("FLAGS_check_nan_inf_level", 0)) == 0:
                raise FloatingPointError(msg)
            import warnings
            warnings.warn(msg)


def _sync_outputs(out_vals):
    """FLAGS_benchmark: block until the op's results are ready so per-op wall
    times are honest (platform/flags.cc FLAGS_benchmark sync semantics).
    Pure wait — no reduction, no transfer."""
    for v in out_vals:
        jax.block_until_ready(v)


def _debug_checks(name, out_vals):
    """Split debug paths: the NaN scan (device-syncing reduction) and the
    benchmark sync (pure wait) are independent helpers, so benchmark mode
    never pays the NaN reduction."""
    if _FLAGS.get("FLAGS_check_nan_inf"):
        _scan_nan_inf(name, out_vals)
    elif _FLAGS.get("FLAGS_benchmark"):
        _sync_outputs(out_vals)


def _input_aval(t):
    """(shape, dtype, weak_type) of a dispatch input. Answered from chain
    metadata for a deferred fusion placeholder (ops/fusion.py) so keying
    never forces a pending chain to materialize; None means the input is a
    tracer and the call must bypass the cache."""
    av = getattr(t, "_fusion_aval", None)
    if av is not None:
        return av
    v = t._value
    if isinstance(v, jax.core.Tracer):
        # inside an outer trace (TrainStep/to_static) the op is absorbed
        # into the enclosing jaxpr; caching per-trace executables would
        # only pollute the LRU and risk nested-jit edge cases
        return None
    return (v.shape, v.dtype, getattr(v, "weak_type", False))


def _differentiable(t):
    av = getattr(t, "_fusion_aval", None)
    if av is not None:
        return (not t.stop_gradient) and jnp.issubdtype(av[1], jnp.inexact)
    return (not t.stop_gradient) and jnp.issubdtype(t._value.dtype, jnp.inexact)


def _requires_grad(tensors):
    return is_grad_enabled() and any(_differentiable(t) for t in tensors)


def _amp_transform(op_name, tensors):
    """Apply AMP autocast policy if active (mirrors eager amp_utils.h)."""
    from ..amp.auto_cast import amp_cast_inputs
    return amp_cast_inputs(op_name, tensors)


def _make_edges(tensors):
    edges = []
    for t in tensors:
        if not _differentiable(t):
            edges.append(None)
        else:
            node = t._grad_node if t._grad_node is not None else t._ensure_grad_node()
            edges.append((node, t._out_index))
    return edges


# ---------------------------------------------------------------------------
# cache keying: hash op implementations by VALUE, or refuse
# ---------------------------------------------------------------------------

_UNKEYABLE = object()

# Per-thread keying-failure context for the fusion flight recorder: WHAT
# kind of value made the last key attempt fail (array/tensor/object/tracer)
# and the RNG epoch at the last classified bypass — together they turn an
# anonymous bypass into a `rng_rekey` / `unkeyable_closure` / `tracer_input`
# reason code (profiler/events.py). Written only on the (already slow)
# bypass path; the keyable fast path never touches it.
_keyctx = threading.local()


def _note_unkeyable(v):
    if isinstance(v, Tensor):
        _keyctx.kind = "tensor"
    elif hasattr(v, "shape") and hasattr(v, "dtype"):
        _keyctx.kind = "array"
    else:
        _keyctx.kind = "object"


def _classify_bypass(name):
    """Reason code for a key=None bypass, consuming the per-thread keying
    context. An array-like closure capture right after a global-RNG epoch
    advance is the dropout signature: the op re-keys every call."""
    kind = getattr(_keyctx, "kind", None)
    _keyctx.kind = None
    if kind == "tracer":
        return "tracer_input"
    if kind == "collective":
        # a collective op whose group/mesh could not be canonically keyed
        # (distributed/collective.py mark_collective): the cycle can never
        # promote around it — the doctor names this directly
        return "collective_unkeyed"
    if kind in ("array", "tensor"):
        from ..framework.random import rng_epoch
        ep = rng_epoch()
        seen = getattr(_keyctx, "rng_seen", None)
        _keyctx.rng_seen = ep
        # the very first classified bypass has no epoch baseline — stay
        # conservative (unkeyable_closure) rather than blaming the RNG
        if seen is not None and ep != seen:
            return "rng_rekey"
    return "unkeyable_closure"

# Types whose hash/equality is value-based and whose value cannot change
# under the key's feet. Anything outside this set (arrays, Tensors — whose
# __hash__ is id() but whose _value mutates in-place, arbitrary objects)
# makes the fn un-keyable: baking such a cell into a cached trace would go
# stale silently.
_SAFE_SCALARS = (int, float, bool, complex, str, bytes, type(None), type,
                 np.dtype, np.generic)

# callables without a __code__ object that are still safely identity-keyed:
# stateless module-level singletons (jnp.add is a jnp.ufunc; jnp.exp /
# jax.nn.* are PjitFunction wrappers; python builtins)
_SAFE_CALLABLE_TYPES = (types.BuiltinFunctionType, np.ufunc, jnp.ufunc,
                        type(jax.jit(lambda: None)))


def _token_of(v, depth):
    if depth > 4:
        return _UNKEYABLE
    if isinstance(v, _SAFE_SCALARS) or isinstance(v, enum.Enum):
        return v
    if isinstance(v, types.ModuleType):
        # a module in a closure cell (`from ...kernels import
        # flash_attention as fa` inside the op wrapper) is the same stable
        # singleton as a module global, which _globals_token keys by
        # identity
        return v
    if isinstance(v, slice):
        # slice objects are unhashable (3.10) but value-like: token their
        # (start, stop, step) so indexing ops (ops/manipulation.py slice /
        # strided_slice close over jnp.s_ tuples) stay cacheable
        parts = tuple(_token_of(p, depth + 1)
                      for p in (v.start, v.stop, v.step))
        if any(p is _UNKEYABLE for p in parts):
            return _UNKEYABLE
        return ("slice",) + parts
    if isinstance(v, (tuple, list)):
        items = tuple(_token_of(i, depth + 1) for i in v)
        if any(i is _UNKEYABLE for i in items):
            return _UNKEYABLE
        return (type(v).__name__, items)
    if isinstance(v, dict):
        try:
            keys = sorted(v)
        except TypeError:
            return _UNKEYABLE
        items = tuple((k, _token_of(v[k], depth + 1)) for k in keys)
        if any(t is _UNKEYABLE for _, t in items):
            return _UNKEYABLE
        return ("dict", items)
    if callable(v):
        return _fn_token(v, depth + 1)
    _note_unkeyable(v)
    return _UNKEYABLE


def _stable_library_fn(fn):
    """Module-level functions of the jax/numpy libraries are stable
    singletons: their behavior cannot change under an identity key, so they
    token by identity instead of a deep code/closure/globals scan — the
    same contract _globals_token applies to module-level defs. (Without
    this, a closure cell holding e.g. `lax.max` — pooling reducers — walks
    into jax internals and marks the whole op un-keyable.)"""
    import sys
    mod = getattr(fn, "__module__", None) or ""
    if not (mod in ("jax", "numpy") or mod.startswith(("jax.", "numpy."))):
        return False
    m = sys.modules.get(mod)
    return m is not None and \
        getattr(m, getattr(fn, "__qualname__", ""), None) is fn


# Collective-op keying (distributed/collective.py): a collective's fn
# closes over a compiled process-group callable — unkeyable by the closure
# scan — but its IDENTITY is fully determined by (kind, reduce op, the
# canonical mesh key of its group). mark_collective() stamps that identity
# onto the fn; _fn_token honors it before any closure walk. A collective
# whose mesh cannot be canonically keyed is stamped unkeyable and the
# bypass classifies as `collective_unkeyed`.
_COLLECTIVE_UNKEYABLE = object()


def mark_collective(fn, key):
    """Stamp a collective identity onto an op fn. `key` is a hashable
    (kind, ...) tuple ending in the mesh key (distributed/mesh.mesh_key),
    or None when the group has no canonically-keyable mesh."""
    fn._collective_key = ("collective",) + tuple(key) \
        if key is not None else _COLLECTIVE_UNKEYABLE
    return fn


def _fn_token(fn, depth=0):
    """Value-identity for an op implementation: code object plus closure
    cell / default tokens. Returns _UNKEYABLE when the fn cannot be keyed
    safely (→ the call bypasses the cache)."""
    ck = getattr(fn, "_collective_key", None)
    if ck is not None:
        if ck is _COLLECTIVE_UNKEYABLE:
            _keyctx.kind = "collective"
            return _UNKEYABLE
        return ck
    if depth > 4:
        return _UNKEYABLE
    if isinstance(fn, types.FunctionType) and _stable_library_fn(fn):
        return fn
    if isinstance(fn, functools.partial):
        inner = _fn_token(fn.func, depth + 1)
        args = _token_of(tuple(fn.args), depth + 1)
        kw = _token_of(fn.keywords or {}, depth + 1)
        if _UNKEYABLE in (inner, args, kw):
            return _UNKEYABLE
        return ("partial", inner, args, kw)
    bound_self = getattr(fn, "__self__", None)
    if bound_self is not None:
        # bound method: the code object is shared across instances, so the
        # receiver must be part of the token — which for arbitrary
        # (mutable) objects it can't be → bypass
        stok = _token_of(bound_self, depth + 1)
        inner = _fn_token(getattr(fn, "__func__", None) or fn.__call__,
                          depth + 1) if stok is not _UNKEYABLE else _UNKEYABLE
        if _UNKEYABLE in (stok, inner):
            return _UNKEYABLE
        return ("bound", stok, inner)
    code = getattr(fn, "__code__", None)
    if code is None:
        # no python code object: accept only known-stateless singleton
        # types (jnp ufuncs, jitted wrappers, builtins) whose behavior
        # cannot mutate under an identity key; arbitrary callable objects
        # may carry mutable state (e.g. a Layer's weights) → bypass
        if isinstance(fn, _SAFE_CALLABLE_TYPES):
            return fn
        return _UNKEYABLE
    cells = []
    for cell in (fn.__closure__ or ()):
        try:
            v = cell.cell_contents
        except ValueError:           # empty cell
            return _UNKEYABLE
        t = _token_of(v, depth + 1)
        if t is _UNKEYABLE:
            return _UNKEYABLE
        cells.append(t)
    dflt = _token_of(fn.__defaults__ or (), depth + 1)
    kwdflt = _token_of(getattr(fn, "__kwdefaults__", None) or {}, depth + 1)
    if _UNKEYABLE in (dflt, kwdflt):
        return _UNKEYABLE
    gtok = _globals_token(fn, code, depth)
    if gtok is None:
        return _UNKEYABLE
    return (code, tuple(cells), dflt, kwdflt, gtok)


_code_names_cache: dict = {}


def _all_code_names(code):
    """Sorted co_names of `code` and of every nested code object (inner
    defs / lambdas in co_consts), so globals read by an inner function
    still make it into the key. Code objects are immutable, so the walk is
    memoized per code object (the dict stays small: one row per distinct
    op-fn definition site)."""
    names = _code_names_cache.get(code)
    if names is None:
        def walk(c, out, depth):
            out.update(c.co_names)
            if depth <= 4:
                for const in c.co_consts:
                    if isinstance(const, types.CodeType):
                        walk(const, out, depth + 1)
        acc: set = set()
        walk(code, acc, 0)
        names = _code_names_cache[code] = tuple(sorted(acc))
    return names


def _globals_token(fn, code, depth):
    """Token for the module globals an op fn references (co_names of the fn
    AND its nested code objects, ∩ __globals__): a fn can read mutable
    module state the closure scan never sees, and baking it into a cached
    trace would go stale. Scalars are keyed by value (a changed global →
    new key); modules and module-level functions/classes are stable
    singletons keyed by identity — state read INDIRECTLY through such a
    helper's own globals is frozen at trace time, the same contract as
    jax.jit (recursing into helpers would cascade into dispatch internals
    and mark every op unkeyable); any other global — arrays, Tensors,
    stateful objects — returns None → the call bypasses the cache."""
    g = getattr(fn, "__globals__", None)
    if g is None:
        return ()
    toks = []
    for nm in _all_code_names(code):
        if nm not in g:
            continue                 # builtin or pure attribute name
        v = g[nm]
        if isinstance(v, types.ModuleType):
            continue
        if isinstance(v, (types.FunctionType, type)) \
                or isinstance(v, _SAFE_CALLABLE_TYPES):
            toks.append((nm, v))     # stable module-level def: identity
            continue
        t = _token_of(v, depth + 1)
        if t is _UNKEYABLE:
            return None
        toks.append((nm, t))
    return tuple(toks)


def _amp_token(name):
    from ..amp.auto_cast import current_amp_state
    st = current_amp_state()
    if st is None or not st.enabled:
        return None
    return (st.level, st.dtype, name in st.white, name in st.black)


def _make_key(name, fn, inputs, diff_mask, reg_token, check=False):
    """The cache key, or None when this call must bypass the cache. Takes
    the input TENSORS (not raw values) so avals of deferred fusion
    placeholders come from chain metadata instead of forcing a
    materialization. `check` (FLAGS_check_numerics) is the LAST component:
    executables built under the guardian return an extra all-finite
    scalar, so the two shapes must never share a cache entry — and
    _cached_call reads the flag back off the key to unwrap."""
    ftok = _fn_token(fn)
    if ftok is _UNKEYABLE:
        return None
    avals = []
    for t in inputs:
        av = _input_aval(t)
        if av is None:          # tracer input
            _keyctx.kind = "tracer"
            return None
        avals.append(av)
    return (name, ftok, tuple(avals), diff_mask, _amp_token(name), reg_token,
            check)


# ---------------------------------------------------------------------------
# the executable cache (LRU, FLAGS_eager_op_cache_size entries)
# ---------------------------------------------------------------------------

_BYPASS = object()        # negative-cache: this key is known un-jittable

_cache: OrderedDict = OrderedDict()
_cache_lock = threading.Lock()


def _cache_get(key):
    with _cache_lock:
        exe = _cache.get(key)
        if exe is not None:
            _cache.move_to_end(key)
        return exe


def _cache_put(key, exe):
    cap = int(_FLAGS.get("FLAGS_eager_op_cache_size", 512) or 0)
    if cap <= 0:
        # size 0 disables caching (dispatch already bypasses before keying;
        # this guards a mid-call flag flip)
        return
    with _cache_lock:
        _cache[key] = exe
        _cache.move_to_end(key)
        while len(_cache) > cap:
            _cache.popitem(last=False)
            _STATS.evictions += 1


def clear_dispatch_cache():
    """Drop every cached executable (test hook / manual invalidation),
    including the shared backward appliers' jit caches — the LRU only
    bounds forward entries; backward traces live in the appliers keyed by
    the vjp Partial treedef and are released here. Fused chain executables
    (ops/fusion.py) obey the same invalidation: registered chains,
    detection state, and the chain backward appliers are cleared too."""
    with _cache_lock:
        _cache.clear()
    for applier in (_vjp_applier, _vjp_applier_donate):
        try:
            applier.clear_cache()
        except Exception:
            pass
    if _fusion_mod is not None:
        _fusion_mod.clear_chain_cache()
    if _step_fusion_mod is not None:
        _step_fusion_mod.clear_step_cache()


def dispatch_cache_info():
    """Entry count + capacity + live keys of the executable cache."""
    with _cache_lock:
        keys = list(_cache)
    return {"entries": len(keys),
            "capacity": int(_FLAGS.get("FLAGS_eager_op_cache_size", 512)),
            "keys": keys}


def _build_fwd(name, fn, check=False):
    def traced(*vals):
        _STATS.retraces += 1      # side effect: runs only while tracing
        _EVENTS.emit("dispatch.retrace", name)
        out = fn(*vals)
        if check:
            # guardian (FLAGS_check_numerics): ONE fused all-finite scalar
            # compiled into the executable — no extra launch, no sync
            outs = out if isinstance(out, tuple) else (out,)
            return out, _guardian.finite_all(outs)
        return out
    return jax.jit(traced)


def _build_fwd_vjp(name, fn, diff_idx, check=False):
    """Jitted (out, vjp) pair. jax.vjp's pullback is a jax.tree_util.Partial
    — a pytree with the residual buffers as leaves — so it crosses the jit
    boundary; the compiled forward then emits fresh residuals every call
    with zero re-tracing, and the shared `_vjp_applier` runs the backward
    as one cached executable keyed on the Partial's (stable) treedef."""
    def traced(*vals):
        _STATS.retraces += 1
        _EVENTS.emit("dispatch.retrace", name)
        if len(diff_idx) == len(vals):
            res = jax.vjp(fn, *vals)
        else:
            def pf(*dv):
                full = list(vals)
                for i, v in zip(diff_idx, dv):
                    full[i] = v
                return fn(*full)
            res = jax.vjp(pf, *(vals[i] for i in diff_idx))
        if check:
            out = res[0]
            outs = out if isinstance(out, tuple) else (out,)
            return res, _guardian.finite_all(outs)
        return res
    return jax.jit(traced)


def _apply_vjp(vjp_fn, g):
    _STATS.retraces += 1
    return vjp_fn(g)


_vjp_applier = jax.jit(_apply_vjp)
# donating variant: hands the residual buffers to XLA on the final backward
# (gated by FLAGS_eager_op_cache_donate — see the flag's docstring for the
# aliasing hazard; donation is a warn-and-skip no-op on CPU)
_vjp_applier_donate = jax.jit(_apply_vjp, donate_argnums=(0,))


def _cached_call(key, name, fn, diff_idx, vals):
    """Run the op through the executable cache. Returns (ok, result);
    ok=False → the caller must take the uncached path (also the landing
    spot for keys negative-cached after a failed trace, so jit-incompatible
    ops fail over exactly once). Keys built under FLAGS_check_numerics
    (key[-1]) carry executables that return an extra all-finite scalar;
    it is stripped and queued for the guardian here so every caller —
    dispatch, chain-split replay, step-split replay — gets the original
    result shape."""
    check = key[-1]
    exe = _cache_get(key)
    if exe is _BYPASS:
        _STATS.bypass(name)
        _EVENTS.emit("dispatch.bypass", name, key, "unjittable")
        return False, None
    if exe is not None:
        _STATS.hit(name)
        _EVENTS.emit("dispatch.hit", name, key)
        try:
            res = exe(*vals)
        except jax.errors.JaxRuntimeError:
            _EVENTS.emit("dispatch.bypass", name, key, "exec_fault")
            # same transient-fault contract as the miss path: fall back to
            # the eager call this once, keep the executable for next time
            return False, None
        if check:
            res, fin = res
            _guardian.enqueue_fwd(name, fin)
        return True, res
    _STATS.miss(name)
    _EVENTS.emit("dispatch.miss", name, key)
    # AOT warm start (ops/aot_cache.py): a restarting worker deserializes
    # yesterday's executable instead of tracing — corrupt/skewed artifacts
    # fall through to the live build below, attributed but never fatal
    exe = _aot.load_op(key, name, fn, diff_idx, check) \
        if _aot.enabled() else None
    fresh = exe is None
    if fresh:
        exe = _build_fwd(name, fn, check) if diff_idx is None \
            else _build_fwd_vjp(name, fn, diff_idx, check)
    try:
        res = exe(*vals)
    except jax.errors.JaxRuntimeError:
        # transient execution fault (OOM, device reset): do NOT negative-
        # cache a jittable key — let the next call try again
        _EVENTS.emit("dispatch.bypass", name, key, "exec_fault")
        return False, None
    except Exception:
        # un-jittable (value-dependent python control flow, dynamic output
        # shape, ...) or a genuine user error: either way the eager path
        # owns this call — and raises the uncached error message
        _cache_put(key, _BYPASS)
        _EVENTS.emit("dispatch.bypass", name, key, "unjittable")
        return False, None
    _cache_put(key, exe)
    if fresh and _aot.enabled():
        # store-if-absent AFTER the executable proved itself on real
        # inputs (an exported unjittable op can't exist — it already ran)
        _aot.store_op(key, name, fn, diff_idx, check, vals)
    if check:
        res, fin = res
        _guardian.enqueue_fwd(name, fin)
    return True, res


def _make_cached_vjp(vjp_partial, diff_idx, n_in, multi):
    """Engine-facing pullback over the cached backward executable. The
    `donate` kwarg (passed by GradNode.collect_input_grads on the final,
    non-retained backward) routes through the donating applier. An
    AOT-restored executable hands back an AotPullback instead of a
    residual Partial — its stored rematerializing backward program plays
    the applier's role (ops/aot_cache.py)."""
    if isinstance(vjp_partial, _aot.AotPullback):
        return vjp_partial.make_wrapped(diff_idx, n_in, multi)

    def wrapped(g, donate=False):
        if multi and not isinstance(g, tuple):
            # the engine passes a bare cotangent when the op has exactly
            # one output; the vjp of a tuple-returning fn wants a tuple
            g = (g,)
        if donate and _FLAGS.get("FLAGS_eager_op_cache_donate"):
            partial = _vjp_applier_donate(vjp_partial, g)
        else:
            partial = _vjp_applier(vjp_partial, g)
        full = [None] * n_in
        for i, pg in zip(diff_idx, partial):
            full[i] = pg
        return tuple(full)
    wrapped._supports_donate = True
    return wrapped


def _slow_vjp(fn, vals, diff_idx, n_in, multi):
    """The original uncached path: eager jax.vjp at every call."""
    if not multi and len(diff_idx) == n_in:
        return jax.vjp(fn, *vals)

    def partial_fn(*diff_vals):
        full = list(vals)
        for i, v in zip(diff_idx, diff_vals):
            full[i] = v
        return fn(*full)

    out, vjp_fn = jax.vjp(partial_fn, *(vals[i] for i in diff_idx))

    def wrapped(g, _vjp=vjp_fn, _idx=diff_idx, _n=n_in):
        if multi and not isinstance(g, tuple):
            g = (g,)
        partial = _vjp(g)
        full = [None] * _n
        for i, pg in zip(_idx, partial):
            full[i] = pg
        return tuple(full)
    return out, wrapped


# ---------------------------------------------------------------------------
# the funnel
# ---------------------------------------------------------------------------

# ops/fusion.py + ops/step_fusion.py, resolved on first dispatch (lazy:
# both import framework.core/autograd, and importing them at module top
# would order the package init around the funnel instead of the other way
# around)
_fusion_mod = None
_step_fusion_mod = None


def _fusion():
    global _fusion_mod
    if _fusion_mod is None:
        from . import fusion
        _fusion_mod = fusion
    return _fusion_mod


def _step_fusion():
    global _step_fusion_mod
    if _step_fusion_mod is None:
        from . import step_fusion
        _step_fusion_mod = step_fusion
    return _step_fusion_mod


def _prologue(name, fn, inputs):
    """Shared call_op/call_op_multi preamble: registry override resolution,
    AMP input casts, and the registry part of the cache key — in one place
    so the cache logic exists exactly once. Raw value extraction is the
    caller's job AFTER the fusion step: reading `_value` here would force
    deferred chain placeholders that the fusion layer can keep symbolic."""
    from .registry import _dispatch_state
    override, active, generation = _dispatch_state(name)
    if override is not None:
        fn = override
    inputs = _amp_transform(name, inputs)
    return fn, inputs, (active, generation)


def _dispatch(name, fn, inputs, num_outputs):
    multi = num_outputs is not None
    fn, inputs, reg_token = _prologue(name, fn, inputs)
    debug = _FLAGS.get("FLAGS_check_nan_inf") or _FLAGS.get("FLAGS_benchmark")
    cache_on = bool(_FLAGS.get("FLAGS_eager_op_cache"))
    bypass_reason = None
    if cache_on and int(_FLAGS.get("FLAGS_eager_op_cache_size", 512) or 0) <= 0:
        # size 0 disables caching entirely — keyable or not, every call
        # takes the uncached path and is counted as a bypass
        cache_on = False
        bypass_reason = "cache_disabled"
        _STATS.bypass(name)
        _EVENTS.emit("dispatch.bypass", name, None, bypass_reason)

    grad_on = _requires_grad(inputs)
    diff_mask = tuple(_differentiable(t) for t in inputs) if grad_on else None

    # guardian (FLAGS_check_numerics): the check compiles INTO the cached
    # executables (keyed), so fusion stays engaged — unlike the strict
    # debug path above
    chk = _guardian.enabled()
    key = _make_key(name, fn, inputs, diff_mask, reg_token, chk) \
        if cache_on else None
    if cache_on and key is None:
        bypass_reason = _classify_bypass(name)
        _STATS.bypass(name)
        _EVENTS.emit("dispatch.bypass", name, None, bypass_reason)

    fus = _fusion()
    sf = _step_fusion()
    if debug:
        # debug modes need materialized outputs op-by-op: resolve any
        # pending replay and keep both fusion layers out of the way
        sf.STEP.interrupt()
        fus.MANAGER.flush(reason="debug_interrupt")
        fus.MANAGER.reset()
    else:
        # whole-step replay gets first crack: while it is matching, the
        # chain layer is quiescent (the fused step IS the chain)
        res = sf.STEP.step(name, fn, inputs, num_outputs, key, diff_mask,
                           bypass_reason=bypass_reason)
        if res is not sf.MISS:
            return res
        res = fus.MANAGER.step(name, fn, inputs, num_outputs, key, diff_mask,
                               bypass_reason=bypass_reason)
        if res is not fus.MISS:
            # chain-deferred ops still feed the step-cycle recorder: the
            # placeholders carry avals, so nothing materializes
            sf.STEP.record(name, fn, inputs, num_outputs, key, diff_mask,
                           tuple(res) if num_outputs is not None else (res,),
                           cached_ok=True)
            return res

    t0 = time.perf_counter_ns()
    vals = _values(inputs)

    if not grad_on:
        ok = False
        if key is not None:
            ok, out_vals = _cached_call(key, name, fn, None, vals)
        if not ok:
            out_vals = fn(*vals)
            if chk:
                _guardian.observe(name, out_vals if multi else (out_vals,))
        if _guardian._INJECTORS:
            out_vals = _guardian.maybe_inject(name, out_vals, multi)
        if multi:
            if debug:
                _debug_checks(name, out_vals)
            outs = [Tensor(v, stop_gradient=True) for v in out_vals]
            _record_dispatch(fus, ok, debug, name, fn, inputs, num_outputs,
                             key, None, outs, t0, bypass_reason)
            return outs
        if debug:
            _debug_checks(name, (out_vals,))
        out = Tensor(out_vals, stop_gradient=True)
        _record_dispatch(fus, ok, debug, name, fn, inputs, num_outputs,
                         key, None, (out,), t0, bypass_reason)
        return out

    diff_idx = tuple(i for i, d in enumerate(diff_mask) if d)
    n_in = len(inputs)

    ok = False
    if key is not None:
        ok, res = _cached_call(key, name, fn, diff_idx, vals)
    if ok:
        out_vals, vjp_partial = res
        wrapped_vjp = _make_cached_vjp(vjp_partial, diff_idx, n_in, multi)
    else:
        out_vals, wrapped_vjp = _slow_vjp(fn, vals, diff_idx, n_in, multi)
        if chk:
            _guardian.observe(name, out_vals if multi else (out_vals,))
    if _guardian._INJECTORS:
        out_vals = _guardian.maybe_inject(name, out_vals, multi)

    if debug:
        _debug_checks(name, out_vals if multi else (out_vals,))
    out_avals = tuple((v.shape, v.dtype) for v in out_vals) if multi \
        else ((out_vals.shape, out_vals.dtype),)
    node = GradNode(name, wrapped_vjp, _make_edges(inputs), out_avals)
    node.fwd_fn = fn
    node.in_vals, node.unpack_hook = _pack_saved(vals, node.edges)
    if multi:
        outs = []
        for j, v in enumerate(out_vals):
            t = Tensor(v, stop_gradient=False)
            t._grad_node = node
            t._out_index = j
            outs.append(t)
        _record_dispatch(fus, ok, debug, name, fn, inputs, num_outputs,
                         key, diff_mask, outs, t0, bypass_reason)
        return outs
    out = Tensor(out_vals, stop_gradient=False)
    out._grad_node = node
    out._out_index = 0
    _record_dispatch(fus, ok, debug, name, fn, inputs, num_outputs,
                     key, diff_mask, (out,), t0, bypass_reason)
    return out


def _record_dispatch(fus, cached_ok, debug, name, fn, inputs, num_outputs,
                     key, diff_mask, outs, t0, bypass_reason=None):
    """Feed the chain detector and the step-cycle recorder after the
    per-op path ran. Only dispatches that went through the executable
    cache are fusion material; an uncached or un-keyable call breaks the
    chain stream and poisons the step cycle (debug calls already reset
    both)."""
    if debug:
        return
    _step_fusion().STEP.record(name, fn, inputs, num_outputs, key,
                               diff_mask, tuple(outs), cached_ok=cached_ok,
                               bypass_reason=bypass_reason)
    if key is None:
        return
    if cached_ok:
        fus.MANAGER.record(name, fn, inputs, num_outputs, key, diff_mask,
                           outs, time.perf_counter_ns() - t0)
    else:
        fus.MANAGER.reset()


def _timed_dispatch(name, fn, inputs, num_outputs):
    t0 = time.perf_counter_ns()
    try:
        return _dispatch(name, fn, inputs, num_outputs)
    finally:
        _STATS.calls += 1
        _STATS.dispatch_time_ns += time.perf_counter_ns() - t0


def call_op(name: str, fn: Callable, inputs: Sequence[Tensor], **_ignored) -> Tensor:
    """Dispatch a single-output op. `fn` maps jax values -> jax value; all
    non-tensor arguments must already be closed over in `fn`."""
    return _timed_dispatch(name, fn, inputs, None)


def call_op_multi(name: str, fn: Callable, inputs: Sequence[Tensor],
                  num_outputs: int) -> list:
    """Dispatch an op whose fn returns a tuple of `num_outputs` jax values."""
    return _timed_dispatch(name, fn, inputs, num_outputs)
