"""Eager op-chain fusion: the fused-executable layer (ops/fusion.py).

Covers parity of fused chains vs unfused per-op dispatch (fwd bitwise;
fwd+bwd within the one bound of `op_test.assert_within_roundings`: a chain
compiled whole and its ops dispatched one by one are different
executables), chain invalidation (registry-generation bump and
clear_dispatch_cache), mid-chain fallback/splitting when an intermediate
escapes the chain, the FLAGS_eager_op_cache_size=0 bypass semantics, the
chain LRU, and the tier-1 micro-benchmark: a repeated matmul→add→gelu
fwd+bwd loop must show zero post-warmup retraces and fewer executable
launches than op count (every replay saves the chain's ops less one).
"""
import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.ops.dispatch import clear_dispatch_cache, dispatch_cache_info
from paddle_tpu.ops.fusion import chain_cache_info
from paddle_tpu.ops.registry import get_op, override_kernel
from paddle_tpu.profiler import (chain_fusion_stats, dispatch_cache_stats,
                                 reset_chain_fusion_stats,
                                 reset_dispatch_cache_stats)

from op_test import assert_within_roundings

_DEFAULT_FLAGS = {
    "FLAGS_eager_op_cache": True,
    "FLAGS_eager_op_cache_size": 512,
    "FLAGS_eager_op_cache_donate": False,
    "FLAGS_eager_chain_fusion": True,
    "FLAGS_eager_chain_fusion_min_count": 3,
    "FLAGS_eager_chain_cache_size": 128,
    "FLAGS_eager_chain_stitching": True,
    # chain-layer tests must see chains, not whole-step replays
    "FLAGS_eager_step_fusion": False,
}


@pytest.fixture(autouse=True)
def _fresh_caches():
    clear_dispatch_cache()
    reset_dispatch_cache_stats()
    reset_chain_fusion_stats()
    set_flags(dict(_DEFAULT_FLAGS))
    yield
    clear_dispatch_cache()
    reset_dispatch_cache_stats()
    reset_chain_fusion_stats()
    set_flags(dict(_DEFAULT_FLAGS))


def _t(arr, stop_gradient=True):
    return paddle.to_tensor(np.asarray(arr), stop_gradient=stop_gradient)


def _mlp_inputs(b=8, i=16, o=16, stop_gradient=False):
    rng = np.random.default_rng(7)
    x = _t(rng.standard_normal((b, i)).astype(np.float32))
    w = _t(rng.standard_normal((i, o)).astype(np.float32),
           stop_gradient=stop_gradient)
    bias = _t(rng.standard_normal(o).astype(np.float32),
              stop_gradient=stop_gradient)
    return x, w, bias


def _fwd_bwd_step(x, w, b):
    """One matmul→add→gelu→sum fwd+bwd iteration; returns every numeric
    artifact for bitwise comparison."""
    y = F.gelu(paddle.add(paddle.matmul(x, w), b))
    loss = y.sum()
    loss.backward()
    out = (y.numpy().copy(), loss.numpy().copy(),
           w.grad.numpy().copy(), b.grad.numpy().copy())
    w.clear_grad()
    b.clear_grad()
    return out


def _run_loop(iters, fused, x, w, b, step=_fwd_bwd_step):
    set_flags({"FLAGS_eager_chain_fusion": fused})
    clear_dispatch_cache()
    return [step(x, w, b) for _ in range(iters)]


class TestParity:
    def test_fwd_bwd_bitwise_parity(self):
        """Fused replays against per-op dispatch: forward values, loss,
        and both parameter grads. Two DIFFERENT executables (the chain's
        fwd+vjp compiled whole against one program an op), so held to
        `assert_within_roundings` (most seen: 0.63 of a rounding, in w.grad)."""
        x, w, b = _mlp_inputs()
        unfused = _run_loop(12, False, x, w, b)
        fused = _run_loop(12, True, x, w, b)
        assert chain_fusion_stats()["fused_replays"] > 0, \
            "fusion never replayed — the parity check would be vacuous"
        for u, f in zip(unfused, fused):
            for i, (uv, fv) in enumerate(zip(u, f)):
                assert_within_roundings(fv, uv, err_msg=f"field {i}")

    def test_fwd_only_bitwise_parity(self):
        """No-grad chains (stop_gradient inputs) fuse and stay bitwise
        identical too."""
        x, w, b = _mlp_inputs(stop_gradient=True)

        def step(x, w, b):
            return F.gelu(paddle.add(paddle.matmul(x, w), b)).numpy().copy()

        unfused = _run_loop(12, False, x, w, b, step=step)
        fused = _run_loop(12, True, x, w, b, step=step)
        assert chain_fusion_stats()["fused_replays"] > 0
        for u, f in zip(unfused, fused):
            np.testing.assert_array_equal(u, f)

    def test_double_grad_parity_through_fused_chain(self):
        """create_graph=True double grad replays the fused node's recorded
        pure forward (FusedChainNode.fwd_fn) — results must match the
        unfused path bitwise."""
        def run(fused):
            set_flags({"FLAGS_eager_chain_fusion": fused})
            clear_dispatch_cache()
            rng = np.random.default_rng(11)
            x = _t(rng.standard_normal((4, 8)).astype(np.float32),
                   stop_gradient=False)
            w = _t(rng.standard_normal((8, 8)).astype(np.float32),
                   stop_gradient=False)
            b = _t(rng.standard_normal(8).astype(np.float32),
                   stop_gradient=False)
            outs = []
            for _ in range(8):
                y = F.gelu(paddle.add(paddle.matmul(x, w), b))
                (gx,) = paddle.grad([y.sum()], [x], create_graph=True)
                (ggw,) = paddle.grad([gx.sum()], [w])
                outs.append((gx.numpy().copy(), ggw.numpy().copy()))
            return outs

        unfused = run(False)
        fused = run(True)
        assert chain_fusion_stats()["fused_replays"] > 0
        for u, f in zip(unfused, fused):
            np.testing.assert_array_equal(u[0], f[0])
            np.testing.assert_array_equal(u[1], f[1])

    def test_fused_node_is_single_tape_node(self):
        """A fused chain records ONE FusedChainNode owning every op's
        outputs instead of N per-op nodes."""
        from paddle_tpu.framework.autograd import FusedChainNode
        x, w, b = _mlp_inputs()
        set_flags({"FLAGS_eager_chain_fusion": True})
        for _ in range(8):
            y = F.gelu(paddle.add(paddle.matmul(x, w), b))
            loss = y.sum()
            loss.backward()
            w.clear_grad(); b.clear_grad()
        assert chain_fusion_stats()["fused_replays"] > 0
        y = F.gelu(paddle.add(paddle.matmul(x, w), b))
        loss = y.sum()
        node = loss._grad_node
        assert isinstance(node, FusedChainNode)
        assert node.op_names == ("matmul", "add", "gelu", "sum")
        # flattened-output attribution: the loss is sum's output 0
        assert node.output_owner(loss._out_index) == ("sum", 0)
        loss.backward()
        w.clear_grad(); b.clear_grad()


class TestEscapesAndSplits:
    def test_mid_chain_value_escape_splits(self):
        """Reading an intermediate's buffer mid-chain splits the replay.
        The probed prefix (matmul, add) then runs through the SAME per-op
        executables as the unfused side: bitwise. What follows the probe
        (gelu, sum and their backward) the fused side may run as a chain
        compiled whole: `assert_within_roundings`."""
        x, w, b = _mlp_inputs()

        def step(x, w, b):
            h = paddle.add(paddle.matmul(x, w), b)
            probe = h.numpy().copy()          # escapes a pending chain
            y = F.gelu(h)
            loss = y.sum()
            loss.backward()
            out = (probe, y.numpy().copy(), loss.numpy().copy(),
                   w.grad.numpy().copy(), b.grad.numpy().copy())
            w.clear_grad(); b.clear_grad()
            return out

        unfused = _run_loop(12, False, x, w, b, step=step)
        fused = _run_loop(12, True, x, w, b, step=step)
        for u, f in zip(unfused, fused):
            np.testing.assert_array_equal(u[0], f[0], err_msg="probe")
            for i, (uv, fv) in enumerate(zip(u[1:], f[1:]), 1):
                assert_within_roundings(fv, uv, err_msg=f"field {i}")

    def test_escape_is_counted(self):
        """An intermediate forced out of a pending chain shows up in the
        escape/split telemetry."""
        x, w, b = _mlp_inputs()
        # make matmul→add→gelu→sum hot
        for _ in range(8):
            _fwd_bwd_step(x, w, b)
        assert chain_fusion_stats()["fused_replays"] > 0
        before = chain_fusion_stats()
        # now break the pattern mid-chain: force the add output while the
        # chain is still pending
        h = paddle.add(paddle.matmul(x, w), b)
        _ = h.numpy()
        after = chain_fusion_stats()
        assert after["fallback_splits"] > before["fallback_splits"]
        assert after["escapes"] > before["escapes"]
        # the escaped prefix still computes correctly
        y = F.gelu(h)
        loss = y.sum()
        loss.backward()
        assert w.grad is not None
        w.clear_grad(); b.clear_grad()

    def test_grad_through_side_output_after_split(self):
        """backward() through a mid-chain intermediate (tape read while the
        chain is pending) splits and still produces correct grads."""
        x, w, b = _mlp_inputs()
        for _ in range(8):
            _fwd_bwd_step(x, w, b)

        h = paddle.add(paddle.matmul(x, w), b)
        h.backward(paddle.ones_like(h))       # forces the pending chain
        got = w.grad.numpy().copy()
        w.clear_grad(); b.clear_grad()

        set_flags({"FLAGS_eager_chain_fusion": False})
        clear_dispatch_cache()
        h2 = paddle.add(paddle.matmul(x, w), b)
        h2.backward(paddle.ones_like(h2))
        np.testing.assert_array_equal(got, w.grad.numpy())
        w.clear_grad(); b.clear_grad()


class TestInvalidation:
    def test_clear_dispatch_cache_drops_chains(self):
        x, w, b = _mlp_inputs()
        for _ in range(8):
            _fwd_bwd_step(x, w, b)
        assert chain_cache_info()["entries"] > 0
        clear_dispatch_cache()
        assert chain_cache_info()["entries"] == 0

    def test_registry_bump_invalidates_head_op(self):
        """An override on the chain's head op takes effect on the very next
        call: the bumped generation re-keys the op, the stale chain stops
        matching."""
        x, w, b = _mlp_inputs()
        for _ in range(8):
            _fwd_bwd_step(x, w, b)
        assert chain_fusion_stats()["fused_replays"] > 0
        base = _fwd_bwd_step(x, w, b)

        gen0 = get_op("matmul").generation
        override_kernel("matmul", "doubled",
                        lambda a, bm: jnp.matmul(a, bm) * 2.0, activate=True)
        try:
            assert get_op("matmul").generation > gen0
            doubled = _fwd_bwd_step(x, w, b)
            # the head op's change must flow through everything downstream
            assert not np.array_equal(doubled[0], base[0])
            set_flags({"FLAGS_eager_chain_fusion": False})
            clear_dispatch_cache()
            ref = _fwd_bwd_step(x, w, b)
            # `doubled` ran with chain fusion on (the ops after the
            # re-keyed head may replay as a chain compiled whole), `ref`
            # op by op: different executables, one bound
            for i, (dv, rv) in enumerate(zip(doubled, ref)):
                assert_within_roundings(dv, rv, err_msg=f"field {i}")
        finally:
            get_op("matmul").active = None

    def test_registry_bump_invalidates_mid_chain_op(self):
        """An override on a MID-chain op: the replay defers the head, hits
        the key mismatch, splits, and the override still serves this very
        call — numerics never lag the registry."""
        x, w, b = _mlp_inputs()
        for _ in range(8):
            _fwd_bwd_step(x, w, b)
        base = _fwd_bwd_step(x, w, b)

        override_kernel("gelu", "scaled",
                        lambda v: jnp.asarray(
                            0.5 * v * (1.0 + jnp.tanh(v)), v.dtype) * 3.0,
                        activate=True)
        try:
            changed = _fwd_bwd_step(x, w, b)
            assert not np.array_equal(changed[0], base[0])
            set_flags({"FLAGS_eager_chain_fusion": False})
            clear_dispatch_cache()
            ref = _fwd_bwd_step(x, w, b)
            for i, (cv, rv) in enumerate(zip(changed, ref)):
                np.testing.assert_array_equal(cv, rv, err_msg=f"field {i}")
        finally:
            get_op("gelu").active = None


class TestFlags:
    def test_op_cache_size_zero_disables_caching(self):
        """FLAGS_eager_op_cache_size=0 must disable the per-op cache
        entirely — no entries, bypasses counted, numerics unchanged."""
        set_flags({"FLAGS_eager_op_cache_size": 0})
        clear_dispatch_cache()
        reset_dispatch_cache_stats()
        x = _t(np.linspace(-1, 1, 8, dtype=np.float32))
        a = paddle.exp(x).numpy()
        b = paddle.exp(x).numpy()
        np.testing.assert_allclose(
            a, np.exp(np.linspace(-1, 1, 8, dtype=np.float32)), rtol=1e-6)
        np.testing.assert_array_equal(a, b)
        s = dispatch_cache_stats()
        assert s["hits"] == 0 and s["misses"] == 0
        assert s["bypasses"] >= 2
        assert dispatch_cache_info()["entries"] == 0

    def test_chain_fusion_off_means_no_replays(self):
        set_flags({"FLAGS_eager_chain_fusion": False})
        x, w, b = _mlp_inputs()
        for _ in range(10):
            _fwd_bwd_step(x, w, b)
        s = chain_fusion_stats()
        assert s["fused_replays"] == 0 and s["chains_detected"] == 0

    def test_chain_cache_size_zero_means_no_replays(self):
        set_flags({"FLAGS_eager_chain_cache_size": 0})
        x, w, b = _mlp_inputs()
        for _ in range(10):
            _fwd_bwd_step(x, w, b)
        assert chain_fusion_stats()["fused_replays"] == 0

    def test_chain_lru_eviction(self):
        """Distinct hot chains past FLAGS_eager_chain_cache_size evict the
        least-recently-replayed one."""
        set_flags({"FLAGS_eager_chain_cache_size": 1})
        x, w, b = _mlp_inputs()
        x2, w2, b2 = _mlp_inputs(b=4, i=8, o=8)  # different avals → new keys
        for _ in range(8):
            _fwd_bwd_step(x, w, b)
        for _ in range(8):
            _fwd_bwd_step(x2, w2, b2)
        info = chain_cache_info()
        assert info["entries"] <= 1
        assert chain_fusion_stats()["evictions"] > 0


class TestWindowStitching:
    """Adjacent hot chains stitch into one longer chain (PR 3): sequences
    longer than the 8-op rolling window converge to a single launch."""

    @staticmethod
    def _op_chain(x, depth=8):
        h = x
        for _ in range(depth):
            h = paddle.tanh(h)
            h = paddle.scale(h, 0.9)
            h = paddle.exp(paddle.scale(h, 0.1))
        return h                     # 3 * depth unary ops, one dataflow

    def test_stitching_fuses_16_plus_op_chain(self):
        """A 24-op body converges past the 8-op detection window: a single
        stitched chain of ≥16 ops ends up doing the replays, bitwise equal
        to the unfused pipeline."""
        x = _t(np.linspace(-1.0, 1.0, 32, dtype=np.float32).reshape(4, 8))
        outs = []
        for _ in range(40):
            outs.append(self._op_chain(x).numpy().copy())
        s = chain_fusion_stats()
        assert s["chains_stitched"] >= 1, s
        info = chain_cache_info()
        long_replayed = [c for c in info["chains"]
                         if c["ops"] >= 16 and c["replays"] > 0]
        assert long_replayed, \
            f"no ≥16-op chain replayed: {[(c['ops'], c['replays']) for c in info['chains']]}"
        set_flags({"FLAGS_eager_chain_fusion": False})
        clear_dispatch_cache()
        ref = self._op_chain(x).numpy()
        np.testing.assert_array_equal(outs[-1], ref)

    def test_stitched_replay_counts_launches_saved_once(self):
        """Telemetry must not double-count: in the stitched steady state,
        each replay of an L-op chain adds exactly L-1 launches saved — the
        constituent chains stop replaying entirely."""
        x = _t(np.linspace(-1.0, 1.0, 32, dtype=np.float32).reshape(4, 8))
        for _ in range(40):            # converge to the stitched chain
            self._op_chain(x)
        info = chain_cache_info()
        top = max((c for c in info["chains"] if c["replays"] > 0),
                  key=lambda c: c["ops"])
        s0 = chain_fusion_stats()
        for _ in range(5):
            self._op_chain(x)
        s1 = chain_fusion_stats()
        replays = s1["fused_replays"] - s0["fused_replays"]
        saved = s1["launches_saved"] - s0["launches_saved"]
        assert replays > 0
        # every steady-state replay is the one stitched chain: launches
        # saved must be exactly (L-1) per replay, not the sum over the
        # constituent chains as well
        assert saved == replays * (top["ops"] - 1), \
            (saved, replays, top["ops"])

    def test_stitching_disabled_keeps_window_sized_chains(self):
        from paddle_tpu.ops.fusion import _WINDOW
        set_flags({"FLAGS_eager_chain_stitching": False})
        x = _t(np.linspace(-1.0, 1.0, 32, dtype=np.float32).reshape(4, 8))
        for _ in range(40):
            self._op_chain(x)
        s = chain_fusion_stats()
        assert s["chains_stitched"] == 0
        info = chain_cache_info()
        assert all(c["ops"] <= _WINDOW for c in info["chains"]), \
            [c["ops"] for c in info["chains"]]

    def test_stitched_chain_backward_parity(self):
        """Stitched chains in a grad-recording pipeline: forward values
        stay bitwise identical to the unfused path; the fused backward of
        a long (18-op) chain is ONE XLA program whose reassociation can
        differ from the per-op multiply sequence at the last ULP (the same
        single-program compilation noise as jit.TrainStep), so grads are
        checked at ULP-scale tolerance. Fallback splits remain bitwise —
        covered by TestEscapesAndSplits."""
        def run(fused):
            set_flags({"FLAGS_eager_chain_fusion": fused})
            clear_dispatch_cache()
            rng = np.random.default_rng(5)
            x = _t(rng.standard_normal((4, 8)).astype(np.float32),
                   stop_gradient=False)
            out = []
            for _ in range(30):
                y = self._op_chain(x, depth=6)     # 18 ops
                loss = y.sum()
                loss.backward()
                out.append((loss.numpy().copy(), x.grad.numpy().copy()))
                x.clear_grad()
            return out

        unfused = run(False)
        fused = run(True)
        assert chain_fusion_stats()["chains_stitched"] >= 1
        for u, f in zip(unfused, fused):
            np.testing.assert_array_equal(u[0], f[0])
            np.testing.assert_allclose(u[1], f[1], rtol=2e-6, atol=1e-12)


class TestMicroBenchmark:
    def test_zero_post_warmup_retraces_and_fewer_launches(self):
        """After warmup a 3-op matmul→add→gelu fwd+bwd chain replays with
        zero new traces anywhere (per-op AND chain executables) and fewer
        executable launches than op count."""
        x, w, b = _mlp_inputs()
        seed = paddle.ones_like(paddle.matmul(x, w))

        def step():
            y = F.gelu(paddle.add(paddle.matmul(x, w), b))
            y.backward(seed)                  # 3-op chain, no loss reduce
            w.clear_grad(); b.clear_grad()

        for _ in range(10):
            step()                            # warmup: detect + compile
        d0 = dispatch_cache_stats()
        c0 = chain_fusion_stats()
        for _ in range(30):
            step()
        d1 = dispatch_cache_stats()
        c1 = chain_fusion_stats()
        assert d1["retraces"] == d0["retraces"], "per-op retrace post-warmup"
        assert c1["retraces"] == c0["retraces"], "chain retrace post-warmup"
        replays = c1["fused_replays"] - c0["fused_replays"]
        assert replays >= 25, f"chain barely replayed: {replays}/30"
        # 3 ops per iteration, ≥2 launches saved per replay → strictly
        # fewer executable launches than op count
        saved = c1["launches_saved"] - c0["launches_saved"]
        assert saved >= 2 * replays

    def test_fused_replaces_per_op_launches(self):
        """What "fused is faster" stood on, as counts (the speed itself
        is a benchmark cell's to say, on the chip): over N iterations of
        the 4-op matmul→add→gelu→sum fwd+bwd loop the per-op cache
        launches 4 executables an iteration; with chain fusion every
        iteration is ONE fused replay, no op reaches its own executable,
        and 3 launches an iteration are saved."""
        x, w, b = _mlp_inputs(32, 64, 64)
        iters, ops = 40, 4

        def counts(fused):
            set_flags({"FLAGS_eager_chain_fusion": fused})
            clear_dispatch_cache()
            for _ in range(12):
                _fwd_bwd_step(x, w, b)
            reset_dispatch_cache_stats()
            reset_chain_fusion_stats()
            for _ in range(iters):
                _fwd_bwd_step(x, w, b)
            return dispatch_cache_stats(), chain_fusion_stats()

        d, c = counts(False)
        assert d["hits"] == ops * iters and d["misses"] == 0, d
        assert c["fused_replays"] == 0
        d, c = counts(True)
        assert c["fused_replays"] == iters, c
        assert c["launches_saved"] == (ops - 1) * iters, c
        assert c["fallback_splits"] == 0 and c["retraces"] == 0, c
        assert d["hits"] == 0 and d["misses"] == 0, \
            f"an op ran its own executable beside the chain: {d}"
