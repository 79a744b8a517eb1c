"""Compiled eager dispatch: the per-op executable cache (ops/dispatch.py).

Covers the cache-key contract (no collisions across dtype / shape /
stop_gradient mask / AMP state), registry-override generation invalidation,
LRU eviction at FLAGS_eager_op_cache_size, the residual-donation path, and
the tier-1 micro-benchmark: a repeated matmul+add+gelu sequence must stop
re-tracing after its first iteration and produce bitwise-identical outputs
to the uncached path.
"""
import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.ops.dispatch import (call_op, call_op_multi,
                                     clear_dispatch_cache,
                                     dispatch_cache_info)
from paddle_tpu.ops.registry import get_op, override_kernel, use_kernel
from paddle_tpu.profiler import (dispatch_cache_stats,
                                 reset_dispatch_cache_stats)

from op_test import assert_within_roundings


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_dispatch_cache()
    reset_dispatch_cache_stats()
    set_flags({"FLAGS_eager_op_cache": True,
               "FLAGS_eager_op_cache_size": 512,
               "FLAGS_eager_op_cache_donate": False})
    yield
    clear_dispatch_cache()
    reset_dispatch_cache_stats()
    set_flags({"FLAGS_eager_op_cache": True,
               "FLAGS_eager_op_cache_size": 512,
               "FLAGS_eager_op_cache_donate": False})


def _t(arr, stop_gradient=True):
    return paddle.to_tensor(np.asarray(arr), stop_gradient=stop_gradient)


_GLOBAL_SCALE = 2.0


def _gscale_op(v):
    return v * _GLOBAL_SCALE


class TestKeying:
    def test_repeat_hits(self):
        x = _t(np.linspace(-1, 1, 8, dtype=np.float32))
        a = paddle.exp(x)
        b = paddle.exp(x)
        s = dispatch_cache_stats()
        assert s["misses"] >= 1 and s["hits"] >= 1
        np.testing.assert_array_equal(a.numpy(), b.numpy())

    def test_dtype_does_not_collide(self):
        xf = _t(np.linspace(-1, 1, 8, dtype=np.float32))
        xb = paddle.to_tensor(jnp.linspace(-1, 1, 8, dtype=jnp.bfloat16))
        paddle.exp(xf)          # warm the f32 entry
        out = paddle.exp(xb)
        assert out._value.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out._value, np.float32),
            np.exp(np.asarray(xb._value, np.float32)), rtol=2e-2)

    def test_shape_does_not_collide(self):
        a = paddle.exp(_t(np.ones((3,), np.float32)))
        b = paddle.exp(_t(np.ones((2, 2), np.float32)))
        assert a.shape == [3] and b.shape == [2, 2]
        assert dispatch_cache_stats()["misses"] >= 2

    def test_stop_gradient_mask_does_not_collide(self):
        """Same op+avals with a different diff mask must compile separate
        executables — and both must produce correct grads."""
        xv = np.random.rand(4, 5).astype(np.float32)
        wv = np.random.rand(5, 3).astype(np.float32)

        x = _t(xv, stop_gradient=False)
        w = _t(wv, stop_gradient=True)      # mask (True, False)
        paddle.matmul(x, w).sum().backward()
        np.testing.assert_allclose(x.grad.numpy(),
                                   np.ones((4, 3)) @ wv.T, rtol=1e-5)
        assert w.grad is None

        x2 = _t(xv, stop_gradient=False)
        w2 = _t(wv, stop_gradient=False)    # mask (True, True)
        paddle.matmul(x2, w2).sum().backward()
        np.testing.assert_allclose(w2.grad.numpy(),
                                   xv.T @ np.ones((4, 3)), rtol=1e-5)

    def test_amp_state_does_not_collide(self):
        xv = np.random.rand(4, 4).astype(np.float32)
        x, w = _t(xv), _t(xv)
        plain = paddle.matmul(x, w)
        assert plain._value.dtype == jnp.float32
        with paddle.amp.auto_cast(level="O1"):
            amped = paddle.matmul(x, w)
        assert amped._value.dtype == jnp.bfloat16
        again = paddle.matmul(x, w)         # back outside: f32 again
        np.testing.assert_array_equal(plain.numpy(), again.numpy())

    def test_closure_scalar_in_key(self):
        """The fn token must distinguish closures over different scalars
        (same code object, different cell values)."""
        x = _t(np.ones(4, np.float32))
        a = (x + 2.0).numpy()
        b = (x + 3.0).numpy()
        np.testing.assert_array_equal(a, np.full(4, 3.0, np.float32))
        np.testing.assert_array_equal(b, np.full(4, 4.0, np.float32))

    def test_mutable_global_scalar_rekeys(self):
        """A module-global scalar read by the op fn is part of the key —
        rebinding it must NOT serve the stale cached trace."""
        global _GLOBAL_SCALE
        x = _t(np.ones(3, np.float32))
        _GLOBAL_SCALE = 2.0
        r1 = call_op("gscale_probe", _gscale_op, (x,)).numpy()
        r1b = call_op("gscale_probe", _gscale_op, (x,)).numpy()   # hit
        _GLOBAL_SCALE = 3.0
        try:
            r2 = call_op("gscale_probe", _gscale_op, (x,)).numpy()
        finally:
            _GLOBAL_SCALE = 2.0
        np.testing.assert_array_equal(r1, np.full(3, 2.0, np.float32))
        np.testing.assert_array_equal(r1b, r1)
        np.testing.assert_array_equal(r2, np.full(3, 3.0, np.float32))

    def test_global_tensor_bypasses(self):
        """An op fn reading a global Tensor's value must bypass the cache:
        in-place value swaps (optimizer updates) would go stale otherwise."""
        w = _t(np.full(3, 2.0, np.float32))

        def opw(v, _w=None):
            return v * w._value          # w is a closure cell → Tensor

        x = _t(np.ones(3, np.float32))
        r1 = call_op("wswap_probe", opw, (x,)).numpy()
        w._value = jnp.full(3, 5.0, jnp.float32)
        r2 = call_op("wswap_probe", opw, (x,)).numpy()
        np.testing.assert_array_equal(r1, np.full(3, 2.0, np.float32))
        np.testing.assert_array_equal(r2, np.full(3, 5.0, np.float32))
        assert dispatch_cache_stats()["bypasses"] >= 2

    def test_unkeyable_closure_bypasses(self):
        const = np.arange(4, dtype=np.float32)     # ndarray cell → bypass
        x = _t(np.ones(4, np.float32))
        out = call_op("bypass_probe", lambda v: v + jnp.asarray(const), (x,))
        np.testing.assert_array_equal(out.numpy(), 1.0 + const)
        assert dispatch_cache_stats()["bypasses"] >= 1

    def test_cache_disabled_flag(self):
        set_flags({"FLAGS_eager_op_cache": False})
        x = _t(np.ones(4, np.float32))
        out = paddle.exp(x)
        np.testing.assert_allclose(out.numpy(), np.e, rtol=1e-6)
        s = dispatch_cache_stats()
        assert s["hits"] == 0 and s["misses"] == 0
        assert dispatch_cache_info()["entries"] == 0


class TestOverrideInvalidation:
    def teardown_method(self, _m):
        od = get_op("exp")
        od.active = None
        od.overrides.clear()

    def test_override_after_hit_takes_effect(self):
        """A registry override activated AFTER the built-in kernel was
        cached (and hit) must serve the very next call — the per-op
        generation counter keeps the stale executable unreachable."""
        x = _t(np.zeros(3, np.float32))
        base = paddle.exp(x).numpy()
        base2 = paddle.exp(x).numpy()           # cache hit on the built-in
        assert dispatch_cache_stats()["hits"] >= 1
        np.testing.assert_array_equal(base, base2)

        gen0 = get_op("exp").generation
        override_kernel("exp", "doubled", lambda v: jnp.exp(v) * 2.0,
                        activate=True)
        assert get_op("exp").generation > gen0
        doubled = paddle.exp(x).numpy()
        np.testing.assert_allclose(doubled, 2.0 * base, rtol=1e-6)

        get_op("exp").active = None             # deactivate
        restored = paddle.exp(x).numpy()
        np.testing.assert_array_equal(restored, base)

    def test_use_kernel_scope_with_cache(self):
        x = _t(np.full(3, 0.5, np.float32))
        base = paddle.exp(x).numpy()
        override_kernel("exp", "tripled", lambda v: jnp.exp(v) * 3.0)
        with use_kernel("exp", "tripled"):
            inside = paddle.exp(x).numpy()
            inside2 = paddle.exp(x).numpy()     # hit on the override entry
        after = paddle.exp(x).numpy()
        np.testing.assert_allclose(inside, 3.0 * base, rtol=1e-6)
        np.testing.assert_array_equal(inside, inside2)
        np.testing.assert_array_equal(after, base)


class TestLRU:
    def test_eviction_at_capacity(self):
        set_flags({"FLAGS_eager_op_cache_size": 4})
        for n in range(1, 9):                   # 8 distinct shapes → keys
            paddle.exp(_t(np.ones(n, np.float32)))
        info = dispatch_cache_info()
        assert info["entries"] <= 4
        assert dispatch_cache_stats()["evictions"] >= 4

    def test_evicted_entry_recompiles_correctly(self):
        set_flags({"FLAGS_eager_op_cache_size": 1})
        a = _t(np.ones(3, np.float32))
        b = _t(np.ones(5, np.float32))
        r1 = paddle.exp(a).numpy()
        paddle.exp(b)                           # evicts the shape-3 entry
        r2 = paddle.exp(a).numpy()              # recompiles
        np.testing.assert_array_equal(r1, r2)
        assert dispatch_cache_info()["entries"] == 1


class TestGradPath:
    def test_multi_output_cached(self):
        x = _t(np.linspace(0.1, 1.0, 6, np.float32).reshape(2, 3),
               stop_gradient=False)
        fn = lambda v: (jnp.sin(v), jnp.cos(v))
        s1, c1 = call_op_multi("sincos_probe", fn, (x,), num_outputs=2)
        (s1 + c1).sum().backward()
        g1 = x.grad.numpy().copy()

        x2 = _t(x.numpy(), stop_gradient=False)
        s2, c2 = call_op_multi("sincos_probe", fn, (x2,), num_outputs=2)
        (s2 + c2).sum().backward()
        np.testing.assert_array_equal(g1, x2.grad.numpy())
        np.testing.assert_allclose(
            g1, np.cos(x.numpy()) - np.sin(x.numpy()), rtol=1e-5)
        assert dispatch_cache_stats()["hits"] >= 1

    def test_retain_graph_double_backward_run(self):
        """retain_graph=True must allow a second engine pass over the same
        cached VJP executables (no donation on non-final passes)."""
        x = _t(np.full(4, 0.5, np.float32), stop_gradient=False)
        y = paddle.tanh(x).sum()
        y.backward(retain_graph=True)
        g1 = x.grad.numpy().copy()
        x.clear_grad()
        y.backward()
        np.testing.assert_array_equal(g1, x.grad.numpy())

    def test_donate_flag_grads_correct(self):
        """FLAGS_eager_op_cache_donate routes the final backward through the
        donating applier (a warn-and-skip no-op on CPU) with exact grads."""
        import warnings
        set_flags({"FLAGS_eager_op_cache_donate": True})
        xv = np.linspace(-1, 1, 8).astype(np.float32)
        x = _t(xv, stop_gradient=False)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            paddle.exp(x).sum().backward()
        np.testing.assert_allclose(x.grad.numpy(), np.exp(xv), rtol=1e-6)

    def test_create_graph_replay_unaffected(self):
        """Double grad goes through replay (un-keyable closure → bypass) and
        must keep working with the cache on."""
        x = _t(np.array([0.7], np.float32), stop_gradient=False)
        y = (x * x * x).sum()
        (gx,) = paddle.grad([y], [x], create_graph=True)
        gx.sum().backward()
        np.testing.assert_allclose(x.grad.numpy(), 6 * 0.7, rtol=1e-5)


class TestMicroBenchmark:
    """The acceptance micro-benchmark (tier-1, not slow): repeated eager
    matmul+add+gelu with backward must hit the cache > 90% after warmup,
    stop re-tracing entirely after the first iteration, replay
    bitwise, and match the uncached path to the one bound of
    `op_test.assert_within_roundings`."""

    @staticmethod
    def _step(xv, wv, bv):
        x = _t(xv, stop_gradient=False)
        w = _t(wv, stop_gradient=False)
        b = _t(bv, stop_gradient=False)
        out = F.gelu(paddle.add(paddle.matmul(x, w), b))
        out.sum().backward()
        return (out.numpy(), x.grad.numpy(), w.grad.numpy(), b.grad.numpy())

    def test_hit_rate_zero_retraces_bitwise(self):
        rng = np.random.default_rng(7)
        xv = rng.standard_normal((8, 16)).astype(np.float32)
        wv = rng.standard_normal((16, 16)).astype(np.float32)
        bv = rng.standard_normal((16,)).astype(np.float32)

        set_flags({"FLAGS_eager_op_cache": False})
        ref = self._step(xv, wv, bv)            # uncached ground truth

        set_flags({"FLAGS_eager_op_cache": True})
        clear_dispatch_cache()
        warm = self._step(xv, wv, bv)           # iteration 1: traces
        # cached: one jitted program an op (fwd, fwd+vjp); uncached: the
        # op's jax primitives dispatched one by one. Different executables
        for r, u in zip(warm, ref):
            assert_within_roundings(r, u)
        hit = self._step(xv, wv, bv)            # iteration 2: every op a
        for r, w_ in zip(hit, warm):            # hit, the SAME executables
            np.testing.assert_array_equal(r, w_)

        reset_dispatch_cache_stats()
        for _ in range(10):
            res = self._step(xv, wv, bv)
        s = dispatch_cache_stats()
        assert s["retraces"] == 0, f"retraced after warmup: {s}"
        assert s["misses"] == 0, s
        assert s["hit_rate"] > 0.9, s
        for r, u in zip(res, ref):              # (by now the chain tier may
            assert_within_roundings(r, u)       # replay the ops as one program)

    def test_no_grad_forward_bitwise(self):
        rng = np.random.default_rng(3)
        xv = rng.standard_normal((4, 16)).astype(np.float32)
        wv = rng.standard_normal((16, 8)).astype(np.float32)
        x, w = _t(xv), _t(wv)

        set_flags({"FLAGS_eager_op_cache": False})
        ref = F.gelu(paddle.matmul(x, w)).numpy()
        set_flags({"FLAGS_eager_op_cache": True})
        clear_dispatch_cache()
        warm = F.gelu(paddle.matmul(x, w)).numpy()
        hit = F.gelu(paddle.matmul(x, w)).numpy()
        np.testing.assert_array_equal(ref, warm)
        np.testing.assert_array_equal(ref, hit)
        assert dispatch_cache_stats()["hits"] >= 2
