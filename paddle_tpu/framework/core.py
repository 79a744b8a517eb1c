"""Tensor: the user-facing array type, wrapping a `jax.Array`.

Reference analog: phi::DenseTensor (paddle/phi/core/dense_tensor.h:38) for
storage + meta, and the eager `paddle.Tensor` (pybind/eager.cc:1148 BindEager,
eager_method.cc for methods). TPU-first: storage is an immutable jax.Array;
"in-place" paddle semantics (`_`-suffixed methods, optimizer updates) are value
swaps on the wrapper, with buffer donation handled at the jit boundary.
"""
from __future__ import annotations

import itertools

import numpy as np
import jax
import jax.numpy as jnp

from . import dtype as dtype_mod
from .dtype import convert_dtype, to_jax_dtype, get_default_dtype, DType
from .autograd import AccumulationNode, is_grad_enabled, run_backward

__all__ = ["Tensor", "Parameter", "to_tensor", "is_tensor"]

_name_counter = itertools.count()


def _auto_name(prefix="tensor"):
    return f"{prefix}_{next(_name_counter)}"


class Place:
    """Thin device handle. Reference analog: phi::Place (phi/common/place.h)."""

    def __init__(self, device):
        self._device = device  # a jax.Device or None (for traced values)

    def __repr__(self):
        if self._device is None:
            return "Place(traced)"
        return f"Place({self._device.platform}:{self._device.id})"

    def is_gpu_place(self):
        return self._device is not None and self._device.platform == "gpu"

    def is_cpu_place(self):
        return self._device is not None and self._device.platform == "cpu"

    def is_tpu_place(self):
        return self._device is not None and self._device.platform == "tpu"

    # paddle calls TPU-like pluggable backends "custom places"
    is_custom_place = is_tpu_place


class Tensor:
    """Eager tensor with paddle semantics over a jax.Array value."""

    __slots__ = ("_value", "stop_gradient", "grad", "_grad_node", "_out_index",
                 "name", "persistable", "_hooks", "_dist_attr", "__weakref__")

    def __init__(self, value, dtype=None, stop_gradient=True, name=None,
                 persistable=False):
        if isinstance(value, Tensor):
            value = value._value
        if dtype is not None:
            jd = to_jax_dtype(dtype)
            value = jnp.asarray(value, dtype=jd)
        elif not isinstance(value, (jax.Array, jax.core.Tracer)):
            value = jnp.asarray(value)
        self._value = value
        self.stop_gradient = stop_gradient
        self.grad = None
        self._grad_node = None
        self._out_index = 0
        self.name = name if name is not None else _auto_name()
        self.persistable = persistable
        self._hooks = []

    # -- meta ---------------------------------------------------------------
    @property
    def shape(self):
        return list(self._value.shape)

    @property
    def dtype(self) -> DType:
        return dtype_mod.to_paddle_dtype(self._value.dtype)

    @property
    def ndim(self):
        return self._value.ndim

    ndimension = dim = lambda self: self._value.ndim

    @property
    def size(self):
        return int(np.prod(self._value.shape)) if self._value.shape else 1

    @property
    def place(self):
        devs = getattr(self._value, "devices", None)
        if devs is None:
            return Place(None)
        try:
            return Place(next(iter(self._value.devices())))
        except Exception:
            return Place(None)

    @property
    def is_leaf(self):
        return self._grad_node is None or isinstance(self._grad_node, AccumulationNode)

    @property
    def T(self):
        from .. import ops
        return ops.manipulation.transpose(self, list(range(self.ndim))[::-1])

    def numel(self):
        return self.size

    # -- conversion ---------------------------------------------------------
    def numpy(self):
        return np.asarray(self._value)

    def item(self, *args):
        if args:
            return self.numpy().item(*args)
        return self.numpy().item()

    def tolist(self):
        return self.numpy().tolist()

    def __array__(self, dtype=None):
        arr = self.numpy()
        return arr.astype(dtype) if dtype is not None else arr

    def __dlpack__(self, *a, **k):
        return self._value.__dlpack__(*a, **k)

    # -- autograd -----------------------------------------------------------
    def _ensure_grad_node(self):
        """Leaf tensors that require grad lazily get an accumulation node."""
        if self._grad_node is None:
            self._grad_node = AccumulationNode(self)
            self._out_index = 0
        return self._grad_node

    def backward(self, grad_tensor=None, retain_graph=False):
        # whole-step fusion (ops/step_fusion.py) may consume this backward
        # as part of a fused train-step replay — before anything touches
        # _grad_node, which would force a pending placeholder
        from ..ops.step_fusion import STEP as _step_fusion
        if _step_fusion.on_backward(self, grad_tensor, retain_graph):
            return
        if self.stop_gradient and self._grad_node is None:
            raise RuntimeError(
                "Tensor.backward() called on a tensor with stop_gradient=True "
                "and no grad graph")
        if grad_tensor is None:
            from .autograd import _one_cotangent
            seed = _one_cotangent(self._value.shape, self._value.dtype)
        else:
            seed = grad_tensor._value if isinstance(grad_tensor, Tensor) \
                else jnp.asarray(grad_tensor)
        node = self._grad_node
        if node is None:
            # leaf: grad of self wrt self
            self._ensure_grad_node()
            node = self._grad_node
        run_backward(node, self._out_index, seed, retain_graph=retain_graph)
        # guardian (FLAGS_check_numerics): the backward boundary resolves
        # the queued in-graph finite checks — one batched device->host
        # transfer; a no-op (empty queue) when the flag is off
        from ..ops.guardian import maybe_flush
        maybe_flush()

    def register_hook(self, hook):
        """Register a grad hook (fires at accumulation for leaves, at the
        producing node's output otherwise). Returns a removable handle."""
        if self.is_leaf:
            self._hooks.append(hook)
            hooks_list, item = self._hooks, hook
        else:
            node, idx = self._grad_node, self._out_index
            raw = lambda g: (lambda r: None if r is None else
                             (r._value if isinstance(r, Tensor) else r))(
                                 hook(Tensor(g, stop_gradient=True)))
            node.out_hooks.setdefault(idx, []).append(raw)
            hooks_list, item = node.out_hooks[idx], raw

        class _Handle:
            def remove(self_h):
                try:
                    hooks_list.remove(item)
                except ValueError:
                    pass
        return _Handle()

    def clear_grad(self):
        self.grad = None

    clear_gradient = clear_grad

    def detach(self):
        t = Tensor(self._value, stop_gradient=True, name=self.name + ".detach")
        return t

    def clone(self):
        from ..ops.dispatch import call_op
        return call_op("clone", lambda x: x + 0, (self,))

    # -- dtype / value manipulation ------------------------------------------
    def astype(self, dtype):
        from ..ops.dispatch import call_op
        jd = to_jax_dtype(dtype)
        return call_op("cast", lambda x: x.astype(jd), (self,))

    cast = astype

    def _assign_value_(self, value):
        """Internal raw value swap (the in-place primitive)."""
        if isinstance(value, Tensor):
            value = value._value
        self._value = jnp.asarray(value, dtype=self._value.dtype)
        return self

    def set_value(self, value):
        return self._assign_value_(value)

    def copy_(self, other, blocking=True):
        return self._assign_value_(other)

    def fill_(self, value):
        self._value = jnp.full(self._value.shape, value, self._value.dtype)
        return self

    def zero_(self):
        return self.fill_(0)

    def scale_(self, scale=1.0, bias=0.0):
        self._value = self._value * scale + bias
        return self

    # -- misc ---------------------------------------------------------------
    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-D tensor")
        return self._value.shape[0]

    def __repr__(self):
        grad_info = f", stop_gradient={self.stop_gradient}"
        try:
            val = np.asarray(self._value)
            body = np.array2string(val, precision=4, separator=", ")
        except Exception:
            body = f"<traced {self._value}>"
        return (f"Tensor(shape={self.shape}, dtype={self.dtype.name}"
                f"{grad_info},\n       {body})")

    def __bool__(self):
        if self.size != 1:
            raise ValueError("The truth value of a multi-element Tensor is ambiguous")
        return bool(self.numpy())

    def __int__(self):
        return int(self.numpy())

    def __float__(self):
        return float(self.numpy())

    def __index__(self):
        return int(self.numpy())

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __hash__(self):
        return id(self)

    # arithmetic dunders are attached by paddle_tpu.ops at import time
    # (mirrors eager_math_op_patch.cc)

    def __deepcopy__(self, memo):
        # jax arrays are immutable: share the buffer, copy the wrapper
        new = self.__class__.__new__(self.__class__)
        Tensor.__init__(new, self._value, stop_gradient=self.stop_gradient,
                        name=self.name, persistable=self.persistable)
        if isinstance(new, Parameter):
            new.trainable = not self.stop_gradient
            new.optimize_attr = dict(getattr(self, "optimize_attr",
                                             {"learning_rate": 1.0}))
            new.regularizer = getattr(self, "regularizer", None)
            new.do_model_average = getattr(self, "do_model_average", None)
            new.need_clip = getattr(self, "need_clip", True)
            new.is_distributed = getattr(self, "is_distributed", False)
        memo[id(self)] = new
        return new

    def pin_memory(self):
        return self

    def cuda(self, *a, **k):
        return self

    def cpu(self):
        return Tensor(jax.device_get(self._value), stop_gradient=self.stop_gradient)

    def to(self, *args, **kwargs):
        # supports .to(dtype) / .to(device) minimal forms
        for a in list(args) + list(kwargs.values()):
            try:
                return self.astype(a)
            except TypeError:
                continue
        return self

    def value(self):
        return self

    def get_tensor(self):
        return self


class Parameter(Tensor):
    """Trainable tensor. Reference analog: python Parameter over eager Tensor
    (python/paddle/fluid/framework.py EagerParamBase)."""

    __slots__ = ("trainable", "optimize_attr", "regularizer", "do_model_average",
                 "need_clip", "is_distributed")

    def __init__(self, value, dtype=None, name=None, trainable=True):
        super().__init__(value, dtype=dtype, stop_gradient=not trainable,
                         name=name or _auto_name("param"), persistable=True)
        self.trainable = trainable
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.do_model_average = None
        self.need_clip = True
        self.is_distributed = False

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()


def is_tensor(x):
    return isinstance(x, Tensor)


def to_tensor(data, dtype=None, place=None, stop_gradient=True):
    """`paddle.to_tensor` equivalent."""
    if isinstance(data, Tensor):
        if dtype is not None and convert_dtype(dtype) != data.dtype.name:
            out = data.astype(dtype)
        else:
            out = data.clone() if not stop_gradient else Tensor(data._value)
        out.stop_gradient = stop_gradient
        return out
    if dtype is None:
        if isinstance(data, (bool, np.bool_)):
            pass  # keep bool
        elif isinstance(data, (int, np.integer)):
            dtype = "int64"
        elif isinstance(data, (float, np.floating)):
            dtype = get_default_dtype()
        elif isinstance(data, (list, tuple, np.ndarray)):
            arr = np.asarray(data)
            if arr.dtype == np.float64:
                dtype = get_default_dtype()
    return Tensor(data, dtype=dtype, stop_gradient=stop_gradient)
