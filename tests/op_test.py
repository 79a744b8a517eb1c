"""OpTest harness: forward vs NumPy reference + numeric gradient checks.

Methodology port (not code port) of the reference's OpTest base class
(python/paddle/fluid/tests/unittests/op_test.py:333): declare inputs and a
NumPy reference, check forward outputs, and check analytic gradients against
central finite differences.
"""
from __future__ import annotations

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.framework.core import Tensor


def assert_within_roundings(actual, reference, roundings=8, err_msg="",
                            scale=None):
    """`actual` equals `reference` up to `roundings` roundings of the
    reference's LARGEST magnitude: |a - b| <= roundings * eps * max|b|.
    `scale` replaces max|b| where the compared array is the END of a
    sum of larger terms (a parameter after several optimizer steps: the
    largest magnitude it took on the way).

    The claim that holds between two DIFFERENT executables of the same
    arithmetic (a program compiled whole against the same ops dispatched
    one by one: XLA fuses, contracts and reorders, so the last bits
    differ). Absolute in the array's scale, not per element: an element
    near zero carries the rounding of the large terms that cancelled
    into it. Two runs of the SAME executable are held to
    `np.testing.assert_array_equal` instead."""
    a, b = np.asarray(actual), np.asarray(reference)
    assert a.shape == b.shape and a.dtype == b.dtype, \
        f"{err_msg}: {a.dtype}{a.shape} against {b.dtype}{b.shape}"
    if scale is None:
        scale = float(np.max(np.abs(b))) if b.size else 0.0
    bound = roundings * float(np.finfo(b.dtype).eps) * scale
    worst = float(np.max(np.abs(a.astype(np.float64)
                                - b.astype(np.float64)))) if b.size else 0.0
    assert worst <= bound, (
        f"{err_msg}: largest difference {worst:.3e} is "
        f"{worst / (bound / roundings):.2f} roundings of max|reference| = "
        f"{scale:.6g} (bound {roundings})")


def check_forward(op_fn, np_fn, inputs, atol=1e-5, rtol=1e-5, **op_kwargs):
    """inputs: list of np arrays. Compares op_fn(*tensors) to np_fn(*arrays)."""
    tensors = [paddle.to_tensor(a) for a in inputs]
    out = op_fn(*tensors, **op_kwargs)
    expected = np_fn(*inputs)
    if isinstance(out, (list, tuple)):
        for o, e in zip(out, expected):
            np.testing.assert_allclose(o.numpy(), e, atol=atol, rtol=rtol)
    else:
        np.testing.assert_allclose(np.asarray(out.numpy(), np.float64)
                                   if np.asarray(expected).dtype == np.float64
                                   else out.numpy(),
                                   expected, atol=atol, rtol=rtol)
    return out


def numeric_grad(op_fn, inputs, wrt_index, delta=1e-3, **op_kwargs):
    """Central finite difference of sum(op_fn(inputs)) w.r.t. inputs[wrt]."""
    base = [np.array(a, np.float64) for a in inputs]

    def eval_sum(arrs):
        ts = [paddle.to_tensor(a.astype(np.float32)) for a in arrs]
        out = op_fn(*ts, **op_kwargs)
        if isinstance(out, (list, tuple)):
            return sum(float(np.sum(o.numpy(), dtype=np.float64)) for o in out)
        return float(np.sum(out.numpy(), dtype=np.float64))

    x = base[wrt_index]
    grad = np.zeros_like(x)
    it = np.nditer(x, flags=["multi_index"])
    while not it.finished:
        idx = it.multi_index
        orig = x[idx]
        x[idx] = orig + delta
        plus = eval_sum(base)
        x[idx] = orig - delta
        minus = eval_sum(base)
        x[idx] = orig
        grad[idx] = (plus - minus) / (2 * delta)
        it.iternext()
    return grad


def check_grad(op_fn, inputs, wrt=None, atol=5e-3, rtol=5e-3, delta=1e-3,
               **op_kwargs):
    """Compare tape gradients against finite differences (sum-of-outputs loss)."""
    wrt = wrt if wrt is not None else list(range(len(inputs)))
    tensors = [paddle.to_tensor(np.asarray(a, np.float32),
                                stop_gradient=False) for a in inputs]
    out = op_fn(*tensors, **op_kwargs)
    if isinstance(out, (list, tuple)):
        loss = out[0].sum()
        for o in out[1:]:
            loss = loss + o.sum()
    else:
        loss = out.sum()
    loss.backward()
    for i in wrt:
        assert tensors[i].grad is not None, f"no grad for input {i}"
        ng = numeric_grad(op_fn, [np.asarray(a, np.float64) for a in inputs],
                          i, delta=delta, **op_kwargs)
        np.testing.assert_allclose(tensors[i].grad.numpy(), ng,
                                   atol=atol, rtol=rtol,
                                   err_msg=f"analytic vs numeric grad "
                                           f"mismatch for input {i}")
