"""Whole-step eager fusion: the auto-TrainStep layer (ops/step_fusion.py).

Covers cycle promotion + fused replay parity against the unfused eager
path over SGD / Momentum / Adam (including grad clipping, weight decay,
and an LR schedule), split-on-escape correctness (mid-step peeks fall back
to per-op dispatch: BITWISE where both sides run the same per-op
executables, within `_ROUNDINGS` where chain fusion compiles part of either
side whole — `_split_sides`),
invalidation (param `stop_gradient` flips, registry-generation bumps,
clip-attr mutation, clear_dispatch_cache), flag interactions
(FLAGS_eager_op_cache_size=0 must leave step fusion inert), zero
post-warmup retraces, the FusedStepNode tape marking, and the acceptance
count: a promoted cycle is ONE executable launch with no chain replay
inside it.

Parity note: a fused whole-step replay compiles forward + backward +
optimizer update into ONE XLA program. XLA's layout and fusion decisions
inside a single program differ from the multi-executable eager path at the
last-ULP level — exactly as `jit.TrainStep` differs from eager — so
fused-vs-unfused TRAJECTORIES are compared with tight allclose bounds
(observed deviations are ~1e-7 relative per step). A transactional
FALLBACK (split) replays through the per-op executables: against per-op
dispatch it is asserted bitwise; against a run in which chain fusion
compiled some iterations whole, to the one bound of
`op_test.assert_within_roundings`.
"""
import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.framework.autograd import FusedStepNode
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.ops.dispatch import clear_dispatch_cache
from paddle_tpu.ops.step_fusion import step_cache_info
from paddle_tpu.ops.registry import get_op, override_kernel
from paddle_tpu.profiler import (chain_fusion_stats, dispatch_cache_stats,
                                 reset_chain_fusion_stats,
                                 reset_dispatch_cache_stats,
                                 reset_step_fusion_stats, step_fusion_stats)

from op_test import assert_within_roundings

_DEFAULT_FLAGS = {
    "FLAGS_eager_op_cache": True,
    "FLAGS_eager_op_cache_size": 512,
    "FLAGS_eager_chain_fusion": True,
    "FLAGS_eager_chain_fusion_min_count": 3,
    "FLAGS_eager_chain_cache_size": 128,
    "FLAGS_eager_chain_stitching": True,
    "FLAGS_eager_step_fusion": True,
    "FLAGS_eager_step_fusion_min_count": 4,
    "FLAGS_eager_step_fusion_cache_size": 8,
    "FLAGS_eager_step_fusion_donate_params": False,
}


@pytest.fixture(autouse=True)
def _fresh():
    set_flags(dict(_DEFAULT_FLAGS))
    clear_dispatch_cache()
    reset_dispatch_cache_stats()
    reset_chain_fusion_stats()
    reset_step_fusion_stats()
    yield
    set_flags(dict(_DEFAULT_FLAGS))
    clear_dispatch_cache()
    reset_dispatch_cache_stats()
    reset_chain_fusion_stats()
    reset_step_fusion_stats()


def _largest(tree):
    if isinstance(tree, (list, tuple)):
        return max(_largest(t) for t in tree)
    return float(np.max(np.abs(tree)))


def _split_sides(run):
    """The two sides of a split-fallback test under both settings of
    chain fusion: yields `(unfused, split, same)`, made by
    `run(step_fused, chain_fused)`, with `same(split_value,
    unfused_value)` the comparison that is TRUE of them. Chain fusion
    off: both sides dispatch through the SAME per-op executables (the
    split side flushes its deferred ops through them): array_equal.
    Chain fusion on (the default): each side runs some iterations as
    chains compiled whole and others op by op, and not the same ones —
    two executables of one arithmetic: `assert_within_roundings`, in
    roundings of the unfused RUN's largest value (the runs are training
    trajectories: a gradient that has shrunk carries the roundings of
    the larger values it was computed from)."""
    for chain in (False, True):
        reset_step_fusion_stats()
        unfused = run(False, chain)
        split = run(True, chain)
        if chain:
            scale = _largest(unfused)

            def same(a, b):
                assert_within_roundings(a, b, scale=scale)
        else:
            same = np.testing.assert_array_equal
        yield unfused, split, same


def _params(seed=7, b=8, d=16):
    rng = np.random.default_rng(seed)
    x = paddle.to_tensor(rng.standard_normal((b, d)).astype(np.float32))
    w = paddle.to_tensor(rng.standard_normal((d, d)).astype(np.float32),
                         stop_gradient=False)
    bias = paddle.to_tensor(rng.standard_normal(d).astype(np.float32),
                            stop_gradient=False)
    return x, w, bias


def _make_opt(kind, params):
    if kind == "sgd":
        return paddle.optimizer.SGD(learning_rate=0.05, parameters=params)
    if kind == "momentum":
        return paddle.optimizer.Momentum(
            learning_rate=0.05, momentum=0.9, parameters=params,
            grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
    if kind == "adam":
        sched = paddle.optimizer.lr.StepDecay(
            learning_rate=0.01, step_size=5, gamma=0.5)
        return paddle.optimizer.Adam(
            learning_rate=sched, parameters=params, weight_decay=0.01,
            grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0))
    raise AssertionError(kind)


def _cycle(x, w, b, opt, sched=None):
    y = F.gelu(paddle.add(paddle.matmul(x, w), b))
    loss = y.sum()
    loss.backward()
    opt.step()
    opt.clear_grad()
    if sched is not None:
        sched.step()
    # reading the loss AFTER the step must be served from the fused outputs
    return float(loss.numpy())


def _run(kind, fused, n=30):
    set_flags({"FLAGS_eager_step_fusion": fused})
    clear_dispatch_cache()
    x, w, b = _params()
    opt = _make_opt(kind, [w, b])
    sched = opt._learning_rate \
        if not isinstance(opt._learning_rate, float) else None
    losses = [_cycle(x, w, b, opt, sched) for _ in range(n)]
    return np.asarray(losses), w.numpy().copy(), b.numpy().copy()


class TestParity:
    @pytest.mark.parametrize("kind", ["sgd", "momentum", "adam"])
    def test_trajectory_parity(self, kind):
        """Fused whole-step replays track the unfused eager trajectory
        (incl. grad clip, weight decay, LR schedule) within single-program
        compilation noise, and actually fuse."""
        unfused, w0, b0 = _run(kind, False)
        fused, w1, b1 = _run(kind, True)
        s = step_fusion_stats()
        assert s["steps_promoted"] >= 1
        assert s["fused_steps"] >= 20, s
        assert s["fallback_splits"] == 0, s
        np.testing.assert_allclose(fused, unfused, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(w1, w0, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(b1, b0, rtol=1e-4, atol=1e-6)

    def test_lr_schedule_never_splits(self):
        """The LR value is hoisted to a scalar argument: a schedule that
        changes it every step must not break replay."""
        x, w, b = _params()
        sched = paddle.optimizer.lr.ExponentialDecay(
            learning_rate=0.05, gamma=0.9)
        opt = paddle.optimizer.SGD(learning_rate=sched, parameters=[w, b])
        for _ in range(20):
            _cycle(x, w, b, opt, sched)
        s = step_fusion_stats()
        assert s["fused_steps"] >= 10
        assert s["fallback_splits"] == 0

    def test_fused_root_is_fused_step_node(self):
        """After a fused replay the loss carries a FusedStepNode: it is not
        a leaf, and a second backward raises the consumed-graph error."""
        x, w, b = _params()
        opt = paddle.optimizer.SGD(learning_rate=0.05, parameters=[w, b])
        loss = None
        for _ in range(10):
            y = F.gelu(paddle.add(paddle.matmul(x, w), b))
            loss = y.sum()
            loss.backward()
            opt.step()
            opt.clear_grad()
        assert step_fusion_stats()["fused_steps"] > 0
        assert isinstance(loss._grad_node, FusedStepNode)
        assert not loss.is_leaf
        with pytest.raises(RuntimeError, match="fused whole-step"):
            loss.backward()


class TestSplits:
    def test_mid_step_peek_splits_bitwise(self):
        """A loss.numpy() between backward and opt.step is a mid-step peek:
        every cycle splits, nothing ever fuses, and the whole trajectory
        is the unfused path's (`_split_sides`: bitwise against per-op
        dispatch, the fallback replaying through the same per-op
        executables; within roundings against chain-fused iterations —
        which this installation's CPU shows and the seed's did not)."""
        def run(fused, chain):
            set_flags({"FLAGS_eager_step_fusion": fused,
                       "FLAGS_eager_chain_fusion": chain})
            clear_dispatch_cache()
            x, w, b = _params()
            opt = paddle.optimizer.SGD(learning_rate=0.05,
                                       parameters=[w, b])
            out = []
            for _ in range(12):
                y = F.gelu(paddle.add(paddle.matmul(x, w), b))
                loss = y.sum()
                loss.backward()
                peek = loss.numpy().copy()     # mid-step peek
                opt.step()
                opt.clear_grad()
                out.append((peek, w.numpy().copy(), b.numpy().copy()))
            return out

        for unfused, split, same in _split_sides(run):
            s = step_fusion_stats()
            assert s["fused_steps"] == 0
            assert s["fallback_splits"] > 0 and s["escapes"] > 0
            for u, f in zip(unfused, split):
                for uv, fv in zip(u, f):
                    same(fv, uv)

    def test_grad_read_pre_step_splits_and_serves_real_grads(self):
        """Reading p.grad between backward and step forces the pending
        grad placeholder: the replay splits and the grads are the real
        per-op backward results (`_split_sides`: bitwise against per-op
        dispatch, within roundings against chain-fused iterations)."""
        def run(fused, chain):
            set_flags({"FLAGS_eager_step_fusion": fused,
                       "FLAGS_eager_chain_fusion": chain})
            clear_dispatch_cache()
            x, w, b = _params()
            opt = paddle.optimizer.SGD(learning_rate=0.05,
                                       parameters=[w, b])
            grads = []
            for _ in range(10):
                y = F.gelu(paddle.add(paddle.matmul(x, w), b))
                loss = y.sum()
                loss.backward()
                grads.append(w.grad.numpy().copy())
                opt.step()
                opt.clear_grad()
            return grads

        for unfused, split, same in _split_sides(run):
            assert step_fusion_stats()["fallback_splits"] > 0
            for u, f in zip(unfused, split):
                same(f, u)

    def test_persistent_splits_deactivate(self):
        """A cycle that always peeks stops being attempted: the program is
        deactivated after its fail streak."""
        x, w, b = _params()
        opt = paddle.optimizer.SGD(learning_rate=0.05, parameters=[w, b])
        for _ in range(20):
            y = F.gelu(paddle.add(paddle.matmul(x, w), b))
            loss = y.sum()
            loss.backward()
            _ = loss.numpy()
            opt.step()
            opt.clear_grad()
        s = step_fusion_stats()
        assert s["deactivated"] >= 1
        assert s["fallback_splits"] <= 8, \
            "splits kept accruing after deactivation"

    def test_post_fire_intermediate_read_recomputes(self):
        """Reading a mid-step intermediate AFTER the fused step fired is
        served by a lazy per-op recompute from the captured inputs."""
        x, w, b = _params()
        opt = paddle.optimizer.SGD(learning_rate=0.05, parameters=[w, b])
        h = None
        for _ in range(10):
            h = paddle.add(paddle.matmul(x, w), b)
            y = F.gelu(h)
            loss = y.sum()
            loss.backward()
            opt.step()
            opt.clear_grad()
        assert step_fusion_stats()["fused_steps"] > 0
        val = h.numpy()                  # post-fire lazy recompute
        assert val.shape == (8, 16)
        assert np.isfinite(val).all()

    def test_fired_step_releases_preupdate_buffers(self):
        """ROADMAP item 4(c): a fired step must NOT retain the pre-update
        parameter values or the batch buffers into the next step. The
        ext-val store demotes to weakrefs at the fire, so when the loop
        keeps no mid-step intermediates, everything the replay captured
        is refcount-freed before optimizer.step() returns — proven with
        the cycle collector disabled."""
        import gc
        import weakref
        rng = np.random.default_rng(3)
        w = paddle.to_tensor(rng.standard_normal((16, 16))
                             .astype(np.float32), stop_gradient=False)
        b = paddle.to_tensor(rng.standard_normal(16).astype(np.float32),
                             stop_gradient=False)
        opt = paddle.optimizer.SGD(learning_rate=0.05, parameters=[w, b])
        gc.disable()
        try:
            w_ref = x_ref = None
            for _ in range(10):
                xb = paddle.to_tensor(
                    rng.standard_normal((8, 16)).astype(np.float32))
                loss = F.gelu(paddle.add(paddle.matmul(xb, w), b)).sum()
                loss.backward()
                pre_w = weakref.ref(w._value)     # about to be replaced
                pre_x = weakref.ref(xb._value)    # the batch buffer
                opt.step()
                opt.clear_grad()
                if step_fusion_stats()["fused_steps"] > 0:
                    w_ref, x_ref = pre_w, pre_x
                    del xb                        # dataloader rebinding
                    break
            assert w_ref is not None, "loop never promoted"
            assert w_ref() is None, \
                "fused step retained the pre-update params past the " \
                "step boundary"
            assert x_ref() is None, \
                "fused step retained the batch buffer past the step " \
                "boundary"
        finally:
            gc.enable()


class TestInvalidation:
    def test_param_stop_gradient_flip_splits(self):
        """Flipping a param to stop_gradient re-keys its ops (diff mask):
        the promoted program stops matching on the very next cycle."""
        x, w, b = _params()
        opt = paddle.optimizer.SGD(learning_rate=0.05, parameters=[w, b])
        for _ in range(8):
            _cycle(x, w, b, opt)
        assert step_fusion_stats()["fused_steps"] > 0
        before = step_fusion_stats()
        b.stop_gradient = True
        y = F.gelu(paddle.add(paddle.matmul(x, w), b))
        loss = y.sum()
        loss.backward()
        opt.step()
        opt.clear_grad()
        after = step_fusion_stats()
        assert after["fused_steps"] == before["fused_steps"]
        assert after["fallback_splits"] > before["fallback_splits"]
        assert w.grad is None and b.grad is None    # step+clear ran eagerly

    def test_registry_bump_invalidates(self):
        """A kernel override takes effect on the very next cycle — the
        bumped generation re-keys the op, the replay splits, and the
        override's numerics are served immediately."""
        x, w, b = _params()
        opt = paddle.optimizer.SGD(learning_rate=0.05, parameters=[w, b])
        for _ in range(8):
            _cycle(x, w, b, opt)
        base = _cycle(x, w, b, opt)
        before = step_fusion_stats()
        override_kernel(
            "gelu", "tripled",
            lambda v: jnp.asarray(0.5 * v * (1.0 + jnp.tanh(v)),
                                  v.dtype) * 3.0,
            activate=True)
        try:
            changed = _cycle(x, w, b, opt)
            after = step_fusion_stats()
            assert after["fused_steps"] == before["fused_steps"]
            assert after["fallback_splits"] > before["fallback_splits"]
            assert changed != base
        finally:
            get_op("gelu").active = None

    def test_clip_attr_mutation_kills_program(self):
        """Clip attributes are baked into the traced step: mutating them
        deactivates the stale executable instead of serving it."""
        x, w, b = _params()
        clip = paddle.nn.ClipGradByGlobalNorm(1.0)
        opt = paddle.optimizer.SGD(learning_rate=0.05, parameters=[w, b],
                                   grad_clip=clip)
        for _ in range(8):
            _cycle(x, w, b, opt)
        assert step_fusion_stats()["fused_steps"] > 0
        before = step_fusion_stats()
        clip.clip_norm = 0.01
        _cycle(x, w, b, opt)
        after = step_fusion_stats()
        assert after["fused_steps"] == before["fused_steps"]
        assert after["deactivated"] > before["deactivated"]

    def test_clear_dispatch_cache_drops_programs(self):
        x, w, b = _params()
        opt = paddle.optimizer.SGD(learning_rate=0.05, parameters=[w, b])
        for _ in range(8):
            _cycle(x, w, b, opt)
        assert step_cache_info()["library"] >= 1
        clear_dispatch_cache()
        assert step_cache_info()["library"] == 0
        assert step_cache_info()["active"] is None


class TestFlags:
    def test_disabled_never_promotes(self):
        set_flags({"FLAGS_eager_step_fusion": False})
        x, w, b = _params()
        opt = paddle.optimizer.SGD(learning_rate=0.05, parameters=[w, b])
        for _ in range(12):
            _cycle(x, w, b, opt)
        s = step_fusion_stats()
        assert s["steps_promoted"] == 0 and s["fused_steps"] == 0

    def test_op_cache_size_zero_leaves_step_fusion_inert(self):
        """FLAGS_eager_op_cache_size=0 disables the per-op cache, so cycle
        ops cannot be keyed: step fusion must observe nothing, promote
        nothing. The two sides are DIFFERENT executables (the cached side
        runs each op as one jitted program; uncached, each op's jax
        primitives are dispatched one by one), so the trajectories are
        held to `assert_within_roundings`, not to equality."""
        def run(cache_size):
            set_flags({"FLAGS_eager_op_cache_size": cache_size,
                       "FLAGS_eager_step_fusion": cache_size == 0,
                       "FLAGS_eager_chain_fusion": False})
            clear_dispatch_cache()
            x, w, b = _params()
            opt = paddle.optimizer.SGD(learning_rate=0.05,
                                       parameters=[w, b])
            return [_cycle(x, w, b, opt) for _ in range(10)], w.numpy()

        base, w0 = run(512)             # cached, no step fusion
        reset_step_fusion_stats()
        uncached, w1 = run(0)           # uncached, step fusion flag ON
        s = step_fusion_stats()
        assert s["steps_promoted"] == 0 and s["fused_steps"] == 0
        assert_within_roundings(np.asarray(uncached, np.float32),
                                np.asarray(base, np.float32))
        assert_within_roundings(w1, w0)


class TestLayerInterplay:
    def test_chain_fusion_replays_while_step_fusion_observes(self):
        """Step fusion in observation mode (threshold not reached) must not
        interfere with the chain layer: chains keep replaying and nothing
        escape-splits — the step manager's pre-forcing must never touch
        this thread's own in-flight chain pending."""
        set_flags({"FLAGS_eager_step_fusion_min_count": 1000})
        x, w, b = _params()
        opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=[w, b])
        for _ in range(20):
            _cycle(x, w, b, opt)
        c = chain_fusion_stats()
        assert c["fused_replays"] >= 10, c
        assert c["escapes"] == 0, c
        assert step_fusion_stats()["steps_promoted"] == 0


class TestZeroRetrace:
    def test_zero_retraces_after_warmup(self):
        """After promotion, 30 more cycles run with zero new traces
        anywhere — per-op, chain, or step executables — and every cycle is
        one fused replay."""
        x, w, b = _params()
        opt = paddle.optimizer.SGD(learning_rate=0.05, parameters=[w, b])
        for _ in range(10):
            _cycle(x, w, b, opt)
        d0, c0, s0 = (dispatch_cache_stats(), chain_fusion_stats(),
                      step_fusion_stats())
        assert s0["fused_steps"] > 0, "fusion never engaged during warmup"
        for _ in range(30):
            _cycle(x, w, b, opt)
        d1, c1, s1 = (dispatch_cache_stats(), chain_fusion_stats(),
                      step_fusion_stats())
        assert d1["retraces"] == d0["retraces"], "per-op retrace"
        assert c1["retraces"] == c0["retraces"], "chain retrace"
        assert s1["retraces"] == s0["retraces"], "step retrace"
        assert s1["fused_steps"] - s0["fused_steps"] == 30
        assert s1["fallback_splits"] == s0["fallback_splits"]


class TestMicroBenchmark:
    def test_fused_step_replaces_chain_replays(self):
        """What "the whole-step executable is faster" stood on, as counts
        (the speed itself is a benchmark cell's to say, on the chip): on
        the repeated matmul→add→gelu fwd+bwd+SGD loop the chain tier
        replays a chain every iteration; once the cycle is promoted every
        iteration is ONE whole-step launch, with no chain replay and no
        per-op launch inside it."""
        iters = 40

        def counts(step_fused):
            set_flags({"FLAGS_eager_step_fusion": step_fused,
                       "FLAGS_eager_step_fusion_min_count": 6})
            clear_dispatch_cache()
            x, w, b = _params(seed=3, b=32, d=64)
            opt = paddle.optimizer.SGD(learning_rate=1e-3,
                                       parameters=[w, b])
            for i in range(16 + iters):
                if i == 16:
                    reset_dispatch_cache_stats()
                    reset_chain_fusion_stats()
                    reset_step_fusion_stats()
                _cycle(x, w, b, opt)
            return (dispatch_cache_stats(), chain_fusion_stats(),
                    step_fusion_stats())

        d, c, s = counts(False)
        assert c["fused_replays"] >= iters, c   # ≥ one chain a cycle
        assert s["fused_steps"] == 0
        d, c, s = counts(True)
        assert s["fused_steps"] == iters, s
        assert s["fallback_splits"] == 0 and s["retraces"] == 0, s
        assert s["launches_saved"] >= iters, s
        assert c["fused_replays"] == 0, \
            f"a chain replayed inside a promoted cycle: {c}"
        assert d["hits"] == 0 and d["misses"] == 0, \
            f"an op ran its own executable inside a promoted cycle: {d}"


def _dropout_cycle(x, w, b, opt, p=0.3):
    y = F.dropout(F.gelu(paddle.add(paddle.matmul(x, w), b)), p)
    loss = y.sum()
    loss.backward()
    opt.step()
    opt.clear_grad()
    return float(loss.numpy())


class TestRNGHoisting:
    """Universal promotion part (a): dropout>0 loops promote to ONE fused
    executable — the PRNG key/epoch rides as hoisted device scalars and
    every key derives in-graph, bit-identical to the eager stream."""

    def test_dropout_promotes_with_parity(self):
        """The dropout loop fuses, with fused-vs-eager trajectory parity
        given the SAME seed (the key stream is bitwise shared; remaining
        deltas are single-program layout noise)."""
        def run(fused):
            set_flags({"FLAGS_eager_step_fusion": fused})
            clear_dispatch_cache()
            paddle.seed(11)
            x, w, b = _params()
            opt = paddle.optimizer.SGD(learning_rate=0.05,
                                       parameters=[w, b])
            return np.asarray([_dropout_cycle(x, w, b, opt)
                               for _ in range(25)]), w.numpy().copy()

        unfused, w0 = run(False)
        fused, w1 = run(True)
        s = step_fusion_stats()
        assert s["steps_promoted"] >= 1
        assert s["fused_steps"] >= 15, s
        assert s["fallback_splits"] == 0, s
        np.testing.assert_allclose(fused, unfused, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(w1, w0, rtol=1e-4, atol=1e-6)

    def test_dropout_zero_steady_state_retraces(self):
        paddle.seed(3)
        x, w, b = _params()
        opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=[w, b])
        retraces_at = []
        for _ in range(20):
            _dropout_cycle(x, w, b, opt)
            retraces_at.append(step_fusion_stats()["retraces"])
        assert step_fusion_stats()["fused_steps"] >= 10
        assert retraces_at[-1] == retraces_at[7], retraces_at

    def test_dropout_split_is_bitwise(self):
        """A mid-step peek in a dropout loop splits onto the SAME
        samples: the lazy key tensors materialize the exact stream keys
        the fused program would have derived (a wrong key would move
        whole elements, not last bits). `_split_sides`: bitwise against
        per-op dispatch, within roundings against chain-fused
        iterations."""
        def run(fused, chain):
            set_flags({"FLAGS_eager_step_fusion": fused,
                       "FLAGS_eager_chain_fusion": chain})
            clear_dispatch_cache()
            paddle.seed(5)
            x, w, b = _params()
            opt = paddle.optimizer.SGD(learning_rate=0.05,
                                       parameters=[w, b])
            out = []
            for _ in range(12):
                y = F.dropout(F.gelu(paddle.add(paddle.matmul(x, w), b)),
                              0.4)
                loss = y.sum()
                loss.backward()
                peek = loss.numpy().copy()     # mid-step peek → split
                opt.step()
                opt.clear_grad()
                out.append((peek, w.numpy().copy()))
            return out

        for unfused, split, same in _split_sides(run):
            assert step_fusion_stats()["fused_steps"] == 0
            assert step_fusion_stats()["fallback_splits"] > 0
            for u, f in zip(unfused, split):
                same(f[0], u[0])
                same(f[1], u[1])

    def test_mid_cycle_stateful_consumption_splits(self):
        """An EXTRA stateful key drawn between the cycle's dropouts
        shifts the recorded stream deltas: the replay must split
        (rng_rekey), never silently sample from the wrong position."""
        from paddle_tpu.framework.random import get_rng_key
        paddle.seed(7)
        x, w, b = _params()
        opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=[w, b])
        for _ in range(10):
            _dropout_cycle(x, w, b, opt)
        assert step_fusion_stats()["fused_steps"] >= 4
        fired_before = step_fusion_stats()["fused_steps"]
        y = F.dropout(paddle.matmul(x, w), 0.3)
        get_rng_key()                       # interloper consumption
        y2 = F.dropout(F.gelu(paddle.add(paddle.matmul(x, w), b)), 0.3)
        loss = y2.sum()
        loss.backward()
        opt.step()
        opt.clear_grad()
        # the shifted stream cannot have produced a fused fire for this
        # cycle's recorded positions
        s = step_fusion_stats()
        assert s["fused_steps"] == fired_before \
            or s["fallback_splits"] > 0

    def test_checkpoint_resumes_stream_exactly(self):
        """EpochRange-style snapshot/restore mid-promoted-dropout-loop:
        the restored run reproduces the uninterrupted loss trajectory
        EXACTLY — the hoisted stream is (base key, position), both
        checkpointed."""
        from paddle_tpu.framework import random as frandom

        paddle.seed(21)
        x, w, b = _params()
        opt = paddle.optimizer.SGD(learning_rate=0.05, parameters=[w, b])
        for _ in range(10):                 # promote and run fused
            _dropout_cycle(x, w, b, opt)
        assert step_fusion_stats()["fused_steps"] >= 4
        rng_snap = frandom.rng_checkpoint_state()
        w_snap, b_snap = w.numpy().copy(), b.numpy().copy()
        tail_a = [_dropout_cycle(x, w, b, opt) for _ in range(6)]
        # "restore": wind state back and replay — same stream, same losses
        frandom.set_rng_checkpoint_state(rng_snap)
        w._value = jnp.asarray(w_snap)
        b._value = jnp.asarray(b_snap)
        tail_b = [_dropout_cycle(x, w, b, opt) for _ in range(6)]
        np.testing.assert_allclose(tail_a, tail_b, rtol=1e-6, atol=1e-7)


class TestSuperCycle:
    """Universal promotion part (b): k×(fwd+bwd)+step micro-batch
    accumulation promotes to ≤2 executables (a reusable sub-executable +
    one update executable), zero retraces at ANY k."""

    def _accum_run(self, fused, n=18, k=4, kind="momentum", seed=0):
        set_flags({"FLAGS_eager_step_fusion": fused})
        clear_dispatch_cache()
        paddle.seed(seed)
        rng = np.random.default_rng(9)
        xs = [paddle.to_tensor(
            rng.standard_normal((8, 16)).astype(np.float32))
            for _ in range(k)]
        w = paddle.to_tensor(
            rng.standard_normal((16, 16)).astype(np.float32),
            stop_gradient=False)
        b = paddle.to_tensor(rng.standard_normal(16).astype(np.float32),
                             stop_gradient=False)
        opt = _make_opt(kind, [w, b])
        losses = []
        for _ in range(n):
            per = []
            for m in range(k):
                y = F.gelu(paddle.add(paddle.matmul(xs[m], w), b))
                loss = y.sum()
                loss.backward()
                per.append(loss)
            opt.step()
            opt.clear_grad()
            # post-step reads are served from the sub-executable outputs
            losses.append([float(l.numpy()) for l in per])
        return np.asarray(losses), w.numpy().copy()

    @pytest.mark.parametrize("kind", ["sgd", "adam"])
    def test_accum_parity(self, kind):
        unfused, w0 = self._accum_run(False, kind=kind)
        fused, w1 = self._accum_run(True, kind=kind)
        s = step_fusion_stats()
        assert s["steps_promoted"] >= 1
        assert s["fused_steps"] >= 10, s
        assert s["fallback_splits"] == 0, s
        np.testing.assert_allclose(fused, unfused, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(w1, w0, rtol=1e-4, atol=1e-5)

    def test_any_k_without_recompiling(self):
        """After warmup at k=2, k=4/8/3 replay with ZERO fresh retraces
        (the canonical signature is k-independent)."""
        paddle.seed(0)
        rng = np.random.default_rng(9)
        x = paddle.to_tensor(
            rng.standard_normal((8, 16)).astype(np.float32))
        w = paddle.to_tensor(
            rng.standard_normal((16, 16)).astype(np.float32),
            stop_gradient=False)
        b = paddle.to_tensor(rng.standard_normal(16).astype(np.float32),
                             stop_gradient=False)
        opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=[w, b])

        def cycle(k):
            for _ in range(k):
                y = F.dropout(
                    F.gelu(paddle.add(paddle.matmul(x, w), b)), 0.2)
                y.sum().backward()
            opt.step()
            opt.clear_grad()

        for _ in range(8):
            cycle(2)
        s0 = step_fusion_stats()
        assert s0["steps_promoted"] == 1
        # ≤2 executables: exactly one sub trace + one update trace
        assert s0["retraces"] == 2, s0["retraces"]
        for k in (4, 8, 3, 4):
            cycle(k)
        s1 = step_fusion_stats()
        assert s1["retraces"] == s0["retraces"]
        assert s1["fallback_splits"] == 0
        assert s1["fused_steps"] - s0["fused_steps"] == 4

    def test_mid_cycle_grad_peek_splits_bitwise(self):
        """Reading p.grad between micro-batches escapes the pending
        super-cycle: the replay runs every archived round's tape backward
        eagerly — accumulated grads match unfused dispatch
        (`_split_sides`: bitwise against per-op dispatch, within
        roundings against chain-fused iterations)."""
        def run(fused, chain):
            set_flags({"FLAGS_eager_step_fusion": fused,
                       "FLAGS_eager_chain_fusion": chain})
            clear_dispatch_cache()
            paddle.seed(2)
            x, w, b = _params()
            opt = paddle.optimizer.SGD(learning_rate=0.05,
                                       parameters=[w, b])
            peeks = []
            for _ in range(12):
                for m in range(3):
                    y = F.gelu(paddle.add(paddle.matmul(x, w), b))
                    y.sum().backward()
                    if m == 1:
                        peeks.append(w.grad.numpy().copy())  # escape
                opt.step()
                opt.clear_grad()
            return peeks, w.numpy().copy()

        for (pu, wu), (pf, wf), same in _split_sides(run):
            assert step_fusion_stats()["fused_steps"] == 0
            for u, f in zip(pu, pf):
                same(f, u)
            same(wf, wu)

    def test_guardian_skip_on_accumulated_grads(self):
        """FLAGS_check_numerics: a NaN poisoning ONE micro-batch makes
        the whole accumulated update a bitwise no-op — fused and eager
        agree on params AND the skip accounting."""
        from paddle_tpu.ops import guardian

        def run(fused):
            set_flags({"FLAGS_eager_step_fusion": fused,
                       "FLAGS_check_numerics": True,
                       "FLAGS_check_numerics_level": 1})
            clear_dispatch_cache()
            paddle.seed(4)
            x, w, b = _params()
            opt = paddle.optimizer.SGD(learning_rate=0.05,
                                       parameters=[w, b])
            try:
                for i in range(14):
                    for m in range(3):
                        y = F.gelu(paddle.add(paddle.matmul(x, w), b))
                        loss = y.sum()
                        if i == 10 and m == 1:
                            loss = loss * paddle.to_tensor(
                                np.float32("nan"))
                        loss.backward()
                    opt.step()
                    opt.clear_grad()
                guardian.flush()
            finally:
                set_flags({"FLAGS_check_numerics": False,
                           "FLAGS_check_numerics_level": 0})
            return w.numpy().copy(), b.numpy().copy()

        wu, bu = run(False)
        wf, bf = run(True)
        s = step_fusion_stats()
        assert s["fused_steps"] >= 6, s
        np.testing.assert_allclose(wf, wu, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(bf, bu, rtol=1e-4, atol=1e-6)
        assert np.isfinite(wf).all()

    def test_dropout_and_accum_promote(self):
        """The dropout
        loop promotes with zero steady-state retraces; the k=4
        accumulation loop runs ≤2 executables with zero retraces."""
        paddle.seed(0)
        x, w, b = _params()
        opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=[w, b])
        for _ in range(12):
            _dropout_cycle(x, w, b, opt)
        s = step_fusion_stats()
        assert s["steps_promoted"] == 1 and s["fused_steps"] >= 6
        r0 = s["retraces"]
        for _ in range(4):
            _dropout_cycle(x, w, b, opt)
        assert step_fusion_stats()["retraces"] == r0
        # accumulation leg
        clear_dispatch_cache()
        reset_step_fusion_stats()
        opt2 = paddle.optimizer.SGD(learning_rate=0.01,
                                    parameters=[w, b])
        for _ in range(10):
            for m in range(4):
                y = F.gelu(paddle.add(paddle.matmul(x, w), b))
                y.sum().backward()
            opt2.step()
            opt2.clear_grad()
        s = step_fusion_stats()
        assert s["steps_promoted"] == 1
        assert s["retraces"] == 2, s["retraces"]     # sub + update ONLY
        assert s["fallback_splits"] == 0
        assert s["fused_steps"] >= 4

    def test_reseed_between_backward_and_step_stays_eager_exact(self):
        """A reseed BETWEEN backward and step swaps the global base key
        mid-cycle: the fused fire must derive this cycle's keys from the
        base they were RESERVED against (what eager sampled), and the
        next cycle re-anchors on the new base — trajectories match."""
        def run(fused):
            set_flags({"FLAGS_eager_step_fusion": fused})
            clear_dispatch_cache()
            paddle.seed(5)
            x, w, b = _params()
            opt = paddle.optimizer.SGD(learning_rate=0.05,
                                       parameters=[w, b])
            out = []
            for i in range(16):
                y = F.dropout(F.gelu(paddle.add(paddle.matmul(x, w), b)),
                              0.4)
                loss = y.sum()
                loss.backward()
                if i == 10:
                    paddle.seed(777)       # mid-cycle reseed
                opt.step()
                opt.clear_grad()
                out.append(float(loss.numpy()))
            return np.asarray(out), w.numpy().copy()

        unfused, wu = run(False)
        fused, wf = run(True)
        s = step_fusion_stats()
        assert s["fused_steps"] >= 8, s
        np.testing.assert_allclose(fused, unfused, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(wf, wu, rtol=1e-4, atol=1e-5)


class TestRaggedTail:
    """PR 16 tentpole (c): an epoch of k−1 full micro-batches plus one
    SMALLER tail micro-batch (dataset length not divisible by the accum
    factor) promotes with ONE extra tail sub-executable keyed by the
    tail shape — ≤3 executables total, zero steady-state retraces — and
    the tail's grads ADD into the same accumulator the full rounds
    feed."""

    def _ragged_run(self, fused, n=14, k=4, kind="sgd", seed=3):
        set_flags({"FLAGS_eager_step_fusion": fused})
        clear_dispatch_cache()
        paddle.seed(seed)
        rng = np.random.default_rng(11)
        xs = [paddle.to_tensor(
            rng.standard_normal((8, 16)).astype(np.float32))
            for _ in range(k - 1)]
        # the short epoch-boundary batch: 3 rows instead of 8
        xs.append(paddle.to_tensor(
            rng.standard_normal((3, 16)).astype(np.float32)))
        w = paddle.to_tensor(
            rng.standard_normal((16, 16)).astype(np.float32),
            stop_gradient=False)
        b = paddle.to_tensor(rng.standard_normal(16).astype(np.float32),
                             stop_gradient=False)
        opt = _make_opt(kind, [w, b])
        losses = []
        for _ in range(n):
            per = []
            for x in xs:
                y = F.gelu(paddle.add(paddle.matmul(x, w), b))
                loss = paddle.mean(y)   # mean: the tail term differs
                loss.backward()
                per.append(loss)
            opt.step()
            opt.clear_grad()
            losses.append([float(l.numpy()) for l in per])
        return np.asarray(losses), w.numpy().copy()

    @pytest.mark.parametrize("kind", ["sgd", "adam"])
    def test_ragged_parity_three_executables(self, kind):
        unfused, w0 = self._ragged_run(False, kind=kind)
        fused, w1 = self._ragged_run(True, kind=kind)
        s = step_fusion_stats()
        assert s["steps_promoted"] >= 1, s
        assert s["fused_steps"] >= 5, s
        assert s["fallback_splits"] == 0, s
        # exactly 3 traces: main sub + tail sub + update — a 4th would
        # mean the tail retraces per epoch (the irregular_accum bug)
        assert s["retraces"] == 3, s["retraces"]
        np.testing.assert_allclose(fused, unfused, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(w1, w0, rtol=1e-4, atol=1e-5)

    def test_steady_state_zero_retraces(self):
        """After warmup, further ragged epochs — and a uniform epoch on
        the same params — replay with zero fresh retraces."""
        paddle.seed(6)
        rng = np.random.default_rng(13)
        full = paddle.to_tensor(
            rng.standard_normal((8, 16)).astype(np.float32))
        short = paddle.to_tensor(
            rng.standard_normal((3, 16)).astype(np.float32))
        w = paddle.to_tensor(
            rng.standard_normal((16, 16)).astype(np.float32),
            stop_gradient=False)
        b = paddle.to_tensor(rng.standard_normal(16).astype(np.float32),
                             stop_gradient=False)
        opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=[w, b])

        def epoch(xs):
            for x in xs:
                y = F.gelu(paddle.add(paddle.matmul(x, w), b))
                paddle.mean(y).backward()
            opt.step()
            opt.clear_grad()

        for _ in range(8):
            epoch([full, full, full, short])
        s0 = step_fusion_stats()
        assert s0["steps_promoted"] == 1, s0
        assert s0["retraces"] == 3, s0["retraces"]
        for _ in range(6):
            epoch([full, full, full, short])
        # an all-full epoch replays main rounds + boundary on the SAME
        # program (the tail sub simply does not fire)
        epoch([full, full, full, full])
        s1 = step_fusion_stats()
        assert s1["retraces"] == s0["retraces"], s1
        assert s1["fallback_splits"] == 0, s1
        assert s1["fused_steps"] - s0["fused_steps"] == 7, s1
