"""@to_static: dygraph-to-static capture.

Reference analog: python/paddle/fluid/dygraph/jit.py:204 (declarative /
to_static) + dygraph_to_static/program_translator.py. The reference rewrites
Python AST into a ProgramDesc; TPU-first we trace the callable into a jaxpr and
run it as ONE compiled XLA executable (SURVEY.md §7 row 4: ProgramDesc +
InterpreterCore ≙ jaxpr + XLA runtime).

Autograd composition: when any input/parameter requires grad, the whole traced
function is dispatched as a single op through the eager tape (its VJP is the
XLA-compiled backward), so `loss.backward()` works unchanged but pays one
kernel launch instead of per-op dispatch.
"""
from __future__ import annotations

import functools
import threading

import numpy as np
import jax
import jax.numpy as jnp

from ..framework.core import Tensor, Parameter
from ..framework import random as _random
from ..framework.autograd import is_grad_enabled
from ..nn.layer_base import Layer
from ..ops.dispatch import call_op_multi

__all__ = ["to_static", "not_to_static", "ignore_module", "TracedLayer",
           "TranslatedLayer", "save", "load", "InputSpec"]

_ignored_modules = set()


class InputSpec:
    """Reference analog: paddle.static.InputSpec."""

    def __init__(self, shape, dtype="float32", name=None):
        self.shape = tuple(shape)
        self.dtype = dtype
        self.name = name

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype})"


def _collect_state(obj):
    """All (tensor, requires_grad) pairs the callable closes over."""
    if isinstance(obj, Layer):
        params = list(dict.fromkeys(
            p for _, p in obj.named_parameters()))
        buffers = [b for _, b in obj.named_buffers()]
        return params, buffers
    owner = getattr(obj, "__self__", None)
    if isinstance(owner, Layer):
        return _collect_state(owner)
    return [], []


class StaticFunction:
    def __init__(self, function, input_spec=None, build_strategy=None,
                 full_graph=True):
        self._function = function
        self._input_spec = input_spec
        self._layer = function if isinstance(function, Layer) else None
        functools.update_wrapper(
            self, function.forward if self._layer else function)
        self._lock = threading.Lock()
        self._jitted = {}
        self._last_out_treedef = None

    @property
    def forward_callable(self):
        if getattr(self, "_transformed_fwd", None) is not None:
            return self._transformed_fwd
        return self._layer.forward if self._layer is not None else self._function

    def _apply_dy2static(self):
        """Retry hook: rewrite data-dependent if/while via the dy2static AST
        transformer (reference analog: program_translator.py falling back to
        dygraph_to_static conversion). Returns True when a transform was
        installed."""
        if getattr(self, "_transformed_fwd", None) is not None:
            return False
        from .dy2static import ast_transform
        import types as _types
        base = self._layer.forward if self._layer is not None \
            else self._function
        new_fn = ast_transform(base)
        if new_fn is None:
            return False
        if self._layer is not None:
            new_fn = _types.MethodType(new_fn, self._layer)
        self._transformed_fwd = new_fn
        self._jitted.clear()
        return True

    def _make_pure(self, params, buffers, tensor_args_spec, static_args):
        fwd = self.forward_callable
        n_params = len(params)
        n_buffers = len(buffers)

        def pure(values, key):
            pvals = values[:n_params]
            bvals = values[n_params:n_params + n_buffers]
            avals = values[n_params + n_buffers:]
            saved_p = [p._value for p in params]
            saved_b = [b._value for b in buffers]
            saved_flags = [p.stop_gradient for p in params]
            arg_tensors = []
            try:
                for p, v in zip(params, pvals):
                    p._value = v
                    # tape must not record inside the trace; jax handles AD
                    p.stop_gradient = True
                for b, v in zip(buffers, bvals):
                    b._value = v
                args = []
                ai = 0
                for spec in tensor_args_spec:
                    if spec == "__tensor__":
                        t = Tensor(avals[ai], stop_gradient=True)
                        ai += 1
                        args.append(t)
                    else:
                        args.append(spec)
                with _random.tracing_key_scope(key):
                    from ..framework.autograd import set_grad_enabled
                    with set_grad_enabled(False):
                        out = fwd(*args, **static_args)
                flat, treedef = jax.tree_util.tree_flatten(
                    out, is_leaf=lambda x: isinstance(x, Tensor))
                out_vals = tuple(f._value if isinstance(f, Tensor)
                                 else jnp.asarray(f) for f in flat)
                self._last_out_treedef = treedef
                new_buffer_vals = tuple(b._value for b in buffers)
                return out_vals + new_buffer_vals
            finally:
                for p, v, sg in zip(params, saved_p, saved_flags):
                    p._value = v
                    p.stop_gradient = sg
                for b, v in zip(buffers, saved_b):
                    b._value = v
        return pure

    def __call__(self, *args, **kwargs):
        from . import ProgramTranslator
        if not ProgramTranslator().enable_to_static:
            # reference: ProgramTranslator.enable(False) runs dygraph
            fwd = self._layer.forward if self._layer is not None \
                else self._function
            return fwd(*args, **kwargs)
        params, buffers = _collect_state(
            self._layer if self._layer is not None else self._function)
        tensor_args = []
        spec = []
        for a in args:
            if isinstance(a, Tensor):
                spec.append("__tensor__")
                tensor_args.append(a)
            elif isinstance(a, (np.ndarray, jnp.ndarray)) and not np.isscalar(a):
                t = Tensor(a)
                spec.append("__tensor__")
                tensor_args.append(t)
            else:
                spec.append(a)

        training = self._layer.training if self._layer is not None else True
        cache_key = (
            tuple((tuple(t.shape), t._value.dtype) for t in tensor_args),
            tuple(sorted(kwargs.items())) if all(
                isinstance(v, (int, float, str, bool, type(None)))
                for v in kwargs.values()) else None,
            training,
        )
        all_inputs = params + buffers + tensor_args
        values = [t._value for t in all_inputs]
        key = _random.get_rng_key()

        def build():
            with self._lock:
                entry = self._jitted.get(cache_key)
                if entry is None:
                    pure = self._make_pure(params, buffers, spec, kwargs)
                    entry = (pure, jax.jit(pure))
                    self._jitted[cache_key] = entry
            return entry

        pure, jitted = build()

        requires_grad = is_grad_enabled() and any(
            not t.stop_gradient for t in all_inputs)
        n_out_extra = len(buffers)
        # data-dependent python control flow fails the FIRST trace of a new
        # signature; rewrite via the dy2static AST pass and retry once (no
        # extra tracing on the happy path)
        from jax.errors import JAXTypeError
        if not requires_grad:
            try:
                out_vals = jitted(values, key)
            except JAXTypeError:
                if not self._apply_dy2static():
                    raise
                pure, jitted = build()
                out_vals = jitted(values, key)
        else:
            # one GradNode for the whole compiled function
            diff_idx = [i for i, t in enumerate(all_inputs)
                        if not t.stop_gradient and
                        jnp.issubdtype(t._value.dtype, jnp.inexact)]

            def make_fn(jitted_):
                def fn(*diff_vals):
                    full = list(values)
                    for i, v in zip(diff_idx, diff_vals):
                        full[i] = v
                    return jitted_(full, key)
                return fn

            try:
                out_vals, vjp_fn = jax.vjp(
                    make_fn(jitted), *(values[i] for i in diff_idx))
            except JAXTypeError:
                if not self._apply_dy2static():
                    raise
                pure, jitted = build()
                out_vals, vjp_fn = jax.vjp(
                    make_fn(jitted), *(values[i] for i in diff_idx))

            def wrapped_vjp(gs, _vjp=vjp_fn, _idx=diff_idx,
                            _n=len(all_inputs)):
                if not isinstance(gs, tuple):
                    # engine passes a bare cotangent for single-output fns;
                    # jax.vjp of a tuple-returning fn wants a tuple
                    gs = (gs,)
                partial = _vjp(gs)
                full = [None] * _n
                for i, pg in zip(_idx, partial):
                    full[i] = pg
                return tuple(full)

            from ..framework.autograd import GradNode
            from ..ops.dispatch import _make_edges
            node = GradNode("to_static", wrapped_vjp,
                            _make_edges(all_inputs),
                            tuple((v.shape, v.dtype) for v in out_vals))

        # split model outputs from updated buffer state
        n_model_out = len(out_vals) - n_out_extra
        model_out_vals = out_vals[:n_model_out]
        new_buf_vals = out_vals[n_model_out:]
        for b, v in zip(buffers, new_buf_vals):
            b._value = v

        outs = []
        for j, v in enumerate(model_out_vals):
            t = Tensor(v, stop_gradient=not requires_grad)
            if requires_grad:
                t._grad_node = node
                t._out_index = j
                t.stop_gradient = False
            outs.append(t)
        if not hasattr(self, "_treedefs"):
            self._treedefs = {}
        if cache_key not in self._treedefs and \
                self._last_out_treedef is not None:
            self._treedefs[cache_key] = self._last_out_treedef
        treedef = self._treedefs.get(cache_key)
        if treedef is not None:
            # rebuild original structure; non-tensor leaves became tensors
            try:
                rebuilt = jax.tree_util.tree_unflatten(treedef, outs)
                return rebuilt
            except Exception:
                pass
        return outs[0] if len(outs) == 1 else tuple(outs)

    # -- program-artifact API ------------------------------------------------
    def concrete_program(self, *args):
        """Return the jaxpr for given example args (ProgramDesc analog)."""
        params, buffers = _collect_state(
            self._layer if self._layer is not None else self._function)
        tensor_args = [a if isinstance(a, Tensor) else Tensor(a) for a in args]
        pure = self._make_pure(params, buffers,
                               ["__tensor__"] * len(tensor_args), {})
        values = [t._value for t in params + buffers + tensor_args]
        key = jax.random.key(0)
        return jax.make_jaxpr(pure)(values, key)


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    """Decorator/wrapper. Accepts a Layer or a function (paddle.jit.to_static)."""
    def wrap(f):
        if type(f) is StaticFunction:
            return f
        if f in _ignored_modules if isinstance(f, type) else False:
            return f
        return StaticFunction(f, input_spec=input_spec,
                              build_strategy=build_strategy)
    if function is not None:
        return wrap(function)
    return wrap


def not_to_static(func):
    func._not_to_static = True
    return func


def ignore_module(modules):
    _ignored_modules.update(modules)


class TracedLayer:
    """Reference analog: fluid/dygraph/jit.py TracedLayer."""

    def __init__(self, static_fn):
        self._fn = static_fn

    @staticmethod
    def trace(layer, inputs):
        sf = to_static(layer)
        outs = sf(*inputs)
        return outs, TracedLayer(sf)

    def __call__(self, *args):
        return self._fn(*args)


def save(layer, path, input_spec=None, **configs):
    """jit.save: persist weights + exported StableHLO for the forward.

    Reference analog: paddle.jit.save (TranslatedLayer protocol). The artifact
    is a pickle with the state dict; where input_spec is given, an
    `jax.export`-serialized compiled forward is attached for
    deployment parity with save_inference_model.
    """
    from ..framework.io import save as fsave
    payload = {"format": "paddle_tpu.jit", "version": 1}
    if isinstance(layer, StaticFunction):
        model = layer._layer
    else:
        model = layer
    if isinstance(model, Layer):
        payload["state_dict"] = dict(model.state_dict())
        payload["class_name"] = type(model).__name__
    if input_spec:
        try:
            from jax import export as jexport
            sf = layer if isinstance(layer, StaticFunction) else to_static(layer)
            params, buffers = _collect_state(model)
            # the exact state values the export closes over, in call order —
            # state_dict() can't reconstruct this (non-persistable buffers
            # are part of the signature but not the state dict)
            payload["export_state"] = [np.asarray(t._value)
                                       for t in params + buffers]
            # map each export_state slot to its state_dict key so a
            # program-only artifact (static.serialize_program strips the
            # values) can be re-armed from deserialize_persistables
            by_id = {id(v): k for k, v in payload.get("state_dict",
                                                      {}).items()}
            payload["export_state_keys"] = [by_id.get(id(t))
                                            for t in params + buffers]
            # the exported pure fn returns model outputs + updated buffers;
            # load needs the split point
            payload["n_buffer_outputs"] = len(buffers)
            specs = [jax.ShapeDtypeStruct(
                tuple(s.shape),
                np.dtype(getattr(s, "dtype", "float32") if not hasattr(
                    s.dtype, "np_dtype") else s.dtype.np_dtype))
                for s in input_spec]
            pure = sf._make_pure(params, buffers,
                                 ["__tensor__"] * len(specs), {})
            values_spec = [jax.ShapeDtypeStruct(v._value.shape, v._value.dtype)
                          for v in params + buffers] + list(specs)
            key_spec = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
            exported = jexport.export(jax.jit(pure))(values_spec, key_spec)
            payload["stablehlo"] = exported.serialize()
        except Exception as e:  # serialization is best-effort
            payload["stablehlo_error"] = repr(e)
    fsave(payload, path if path.endswith(".pdmodel") or "." in path.split("/")[-1]
          else path + ".pdmodel")


class TranslatedLayer:
    """Callable artifact returned by jit.load (reference analog:
    fluid/dygraph/io.py TranslatedLayer): runs the jax.export-serialized
    forward with the saved weights; falls back to weights-only access when
    no compiled forward was attached."""

    def __init__(self, payload):
        self._payload = payload
        self._state_dict = payload.get("state_dict", {})
        self._exported = None
        blob = payload.get("stablehlo")
        if blob is not None:
            from jax import export as jexport
            self._exported = jexport.deserialize(blob)
        export_state = payload.get("export_state")
        if export_state is not None:
            self._param_values = [jnp.asarray(v) for v in export_state]
        else:  # older artifacts: persistable state only
            self._param_values = [t._value
                                  for t in self._state_dict.values()]

    @property
    def has_forward(self):
        return self._exported is not None

    def state_dict(self):
        return dict(self._state_dict)

    def set_state(self, state):
        """Arm a program-only artifact (static.serialize_program strips
        weights) with persistables from deserialize_persistables: values
        map into export-state slots by their state_dict keys."""
        keys = self._payload.get("export_state_keys")
        if not keys:
            raise RuntimeError(
                "this artifact predates export_state_keys; re-save it")
        aux = self._payload.get("export_state_aux") or {}
        vals = []
        for i, k in enumerate(keys):
            if k is None:
                # non-persistable buffer: not a persistable by definition —
                # its value rides with the program (export_state_aux)
                if i not in aux:
                    raise KeyError(
                        f"program artifact lacks the non-persistable "
                        f"buffer for export slot {i}")
                vals.append(jnp.asarray(aux[i]))
                continue
            if k not in state:
                raise KeyError(f"persistables missing state slot {k!r}")
            v = state[k]
            vals.append(v._value if isinstance(v, Tensor) else
                        jnp.asarray(v))
        self._param_values = vals

    def __call__(self, *args):
        if self._exported is None:
            err = self._payload.get("stablehlo_error")
            raise RuntimeError(
                "this artifact was saved without input_spec so no compiled "
                "forward is attached" + (f" (export error: {err})" if err
                                         else ""))
        vals = [a._value if isinstance(a, Tensor) else jnp.asarray(a)
                for a in args]
        out = self._exported.call(self._param_values + vals,
                                  jax.random.key(0))
        if isinstance(out, (list, tuple)):
            n_buf = self._payload.get("n_buffer_outputs", 0)
            model_out = list(out[:len(out) - n_buf]) if n_buf else list(out)
            outs = [Tensor(o, stop_gradient=True) for o in model_out]
            return outs[0] if len(outs) == 1 else outs
        return Tensor(out, stop_gradient=True)

    def eval(self):
        return self

    def train(self):
        raise RuntimeError("TranslatedLayer is inference-only; rebuild the "
                           "Layer and set_state_dict to fine-tune")


def load(path, **configs):
    from ..framework.io import load as fload
    try:
        payload = fload(path)
    except FileNotFoundError:
        payload = fload(path + ".pdmodel")
    if isinstance(payload, dict) and payload.get("format") == \
            "paddle_tpu.jit":
        return TranslatedLayer(payload)
    return payload
