"""Distributed step fusion (ops/spmd_fusion.py): collective-aware
promotion of sharded training cycles into ONE shard_map executable per
mesh, on the 8 emulated CPU devices tests/conftest.py forces.

Covers: dp=8 fused-vs-unfused parity (loss/param trajectories, allclose
per the single-program layout caveat) with exactly one promotion and zero
post-promotion retraces; dp×sharding (ZeRO stage-1 `shard_optimizer_states`)
parity with the optimizer slots STAYING sharded through fused fires; the
guardian+GradScaler backoff where only ONE shard sees a non-finite grad
(globally-consistent skip + identical scale trajectories); probation
demotion on a sum-reduced loss (`spmd_divergence` — plain jit still
fires); mesh relayout mid-run (`mesh_mismatch` split + re-promotion on
the new mesh); collective keying in the dispatch funnel (mesh-keyed
groups key, pg-less groups poison as `collective_unkeyed` and the doctor
names it); the AOT env fingerprint's mesh-topology token; and the
`jax.shard_map` behaviours the promoter leans on (psum over donated
buffers, partial-manual `axis_names`, axis_size / pcast).
"""
import json
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
import paddle_tpu.distributed as dist
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.distributed.mesh import (build_mesh, mesh_key,
                                         set_global_mesh, topology_token,
                                         value_mesh_and_spec)
from paddle_tpu.distributed.fleet.sharding_opt import shard_optimizer_states
from paddle_tpu.ops.dispatch import clear_dispatch_cache, mark_collective
from paddle_tpu.ops.step_fusion import STEP, step_cache_info
from paddle_tpu.profiler import (reset_step_fusion_stats,
                                 step_fusion_stats)
from paddle_tpu.profiler.events import clear_fusion_events, fusion_events

_DEFAULT_FLAGS = {
    "FLAGS_eager_op_cache": True,
    "FLAGS_eager_op_cache_size": 512,
    "FLAGS_eager_chain_fusion": True,
    "FLAGS_eager_chain_fusion_min_count": 3,
    "FLAGS_eager_step_fusion": True,
    "FLAGS_eager_step_fusion_min_count": 3,
    "FLAGS_eager_step_fusion_cache_size": 8,
    "FLAGS_eager_step_fusion_spmd": True,
    "FLAGS_profiler_events": True,
    "FLAGS_check_numerics": False,
}

N_DEV = jax.device_count()
needs_mesh = pytest.mark.skipif(
    N_DEV < 8, reason="needs the 8 emulated devices (conftest XLA_FLAGS)")


@pytest.fixture(autouse=True)
def _fresh():
    prev_events = bool(
        paddle.framework.flags._FLAGS.get("FLAGS_profiler_events"))
    set_flags(dict(_DEFAULT_FLAGS))
    clear_dispatch_cache()
    reset_step_fusion_stats()
    clear_fusion_events()
    yield
    set_flags(dict(_DEFAULT_FLAGS,
                   FLAGS_profiler_events=prev_events,
                   FLAGS_check_numerics=False))
    clear_dispatch_cache()
    reset_step_fusion_stats()
    set_global_mesh(None)


def _batches(steps, b=16, din=32, dout=8, seed=0):
    rng = np.random.default_rng(seed)
    return ([rng.standard_normal((b, din)).astype(np.float32)
             for _ in range(steps)],
            [rng.standard_normal((b, dout)).astype(np.float32)
             for _ in range(steps)])


def _mlp_params(seed=1, din=32, dh=16, dout=8):
    ri = np.random.default_rng(seed)
    w1 = paddle.to_tensor((ri.standard_normal((din, dh)) * 0.1)
                          .astype(np.float32), stop_gradient=False)
    b1 = paddle.to_tensor(np.zeros(dh, np.float32), stop_gradient=False)
    w2 = paddle.to_tensor((ri.standard_normal((dh, dout)) * 0.1)
                          .astype(np.float32), stop_gradient=False)
    return [w1, b1, w2]


def _run_loop(xs, ys, fused, sharding=None, opt_fn=None, loss_kind="mean",
              scaler_args=None, shard_states=False):
    """One fresh training run; returns (losses, params, opt, scaler)."""
    set_flags({"FLAGS_eager_step_fusion": fused})
    clear_dispatch_cache()
    STEP.clear()
    paddle.seed(0)
    params = _mlp_params()
    w1, b1, w2 = params
    opt = (opt_fn or (lambda ps: paddle.optimizer.Momentum(
        learning_rate=0.05, momentum=0.9, parameters=ps)))(params)
    if shard_states:
        opt._create_accumulators(params)
        shard_optimizer_states(opt)
    scaler = paddle.amp.GradScaler(**scaler_args) if scaler_args else None
    losses, scales = [], []
    for xv, yv in zip(xs, ys):
        if sharding is not None:
            xv = jax.device_put(xv, sharding)
            yv = jax.device_put(yv, sharding)
        x = paddle.Tensor(xv, stop_gradient=True)
        y = paddle.Tensor(yv, stop_gradient=True)
        h = F.relu(paddle.add(paddle.matmul(x, w1), b1))
        out = paddle.matmul(h, w2)
        diff = paddle.subtract(out, y)
        sq = paddle.multiply(diff, diff)
        loss = paddle.sum(sq) if loss_kind == "sum" else paddle.mean(sq)
        if scaler is None:
            loss.backward()
            opt.step()
        else:
            scaler.scale(loss).backward()
            scaler.step(opt)
            scaler.update()
            scales.append(float(np.asarray(scaler._state_arrays()[0])))
        opt.clear_grad()
        losses.append(float(loss))
    return losses, [np.asarray(p._value) for p in params], opt, scales


def _dp_mesh(dp=None, sharding=1):
    dp = dp if dp is not None else N_DEV // sharding
    mesh = build_mesh(dp=dp, pp=1, sharding=sharding, sep=1, mp=1)
    set_global_mesh(mesh)
    axes = ("data",) if sharding == 1 else ("data", "sharding")
    return mesh, NamedSharding(mesh, P(axes if len(axes) > 1 else "data"))


def _events(cat=None, reason=None):
    return [e for e in fusion_events()
            if (cat is None or e["cat"] == cat)
            and (reason is None or e.get("reason") == reason)]


# ---------------------------------------------------------------------------
# dp=8: ONE shard_map executable, parity, zero retraces
# ---------------------------------------------------------------------------

@needs_mesh
class TestDataParallelPromotion:
    def test_dp8_parity_and_one_executable(self):
        xs, ys = _batches(20)
        base_l, base_p, _, _ = _run_loop(xs, ys, fused=False)
        _, sharding = _dp_mesh()
        clear_fusion_events()
        fused_l, fused_p, _, _ = _run_loop(xs, ys, fused=True,
                                           sharding=sharding)
        info = step_cache_info()
        assert len(info["programs"]) == 1
        assert info["programs"][0]["spmd"] == "data8"
        promotes = _events("step.promote")
        assert len(promotes) == 1
        assert promotes[0]["detail"]["spmd"] is True
        assert promotes[0]["detail"]["mesh"] == "data8"
        # probation validated on the first fire attempt (eager committed)
        probes = [e for e in _events("step.record")
                  if (e.get("detail") or {}).get("kind") == "spmd_probation"]
        assert len(probes) == 1 and probes[0]["detail"]["ok"] is True
        # min_count=3 → the steady signature (cycle 1 lacks the leading
        # clear_grad) promotes at boundary 4, probation commits eager at
        # step 5, the remaining steps ALL fire the one fused executable
        assert len(_events("step.fire")) == len(xs) - 5
        assert not _events("step.split")
        # trajectories agree within the single-program layout caveat
        assert np.allclose(base_l, fused_l, rtol=2e-5, atol=1e-6)
        for a, b in zip(base_p, fused_p):
            assert np.allclose(a, b, rtol=2e-5, atol=1e-6)

    def test_dp8_zero_retraces_after_promotion(self):
        xs, ys = _batches(24)
        _, sharding = _dp_mesh()
        set_flags({"FLAGS_eager_step_fusion": True})
        clear_dispatch_cache()
        STEP.clear()
        paddle.seed(0)
        params = _mlp_params()
        w1, b1, w2 = params
        opt = paddle.optimizer.Momentum(learning_rate=0.05, momentum=0.9,
                                        parameters=params)
        retraces_at = []
        for xv, yv in zip(xs, ys):
            x = paddle.Tensor(jax.device_put(xv, sharding),
                              stop_gradient=True)
            y = paddle.Tensor(jax.device_put(yv, sharding),
                              stop_gradient=True)
            h = F.relu(paddle.add(paddle.matmul(x, w1), b1))
            diff = paddle.subtract(paddle.matmul(h, w2), y)
            loss = paddle.mean(paddle.multiply(diff, diff))
            loss.backward()
            opt.step()
            opt.clear_grad()
            retraces_at.append(step_fusion_stats()["retraces"])
        # one compile (the probation fire), then a flat line: the
        # shard_map executable never re-traces on the stable sharded cycle
        assert retraces_at[-1] == retraces_at[7], retraces_at
        assert retraces_at[-1] >= 1

    def test_conv_flatten_model_lowers_spmd(self):
        """Conv nets used to demote (`flatten`/`reshape` baked the GLOBAL
        batch into their closures → shard_map trace_fail): the ops now
        emit leading-dim-polymorphic targets, so a LeNet-shaped cycle
        lowers through the mesh and its loss still falls."""
        _, sharding = _dp_mesh()
        paddle.seed(0)
        rng = np.random.default_rng(0)
        conv = paddle.nn.Conv2D(1, 2, 3)
        fc = paddle.nn.Linear(2 * 6 * 6, 4)
        params = [p for p in list(conv.parameters()) + list(fc.parameters())
                  if not p.stop_gradient]
        opt = paddle.optimizer.Adam(3e-3, parameters=params)
        x = paddle.Tensor(jax.device_put(
            rng.standard_normal((16, 1, 8, 8)).astype(np.float32),
            sharding), stop_gradient=True)
        y = paddle.Tensor(jax.device_put(
            rng.integers(0, 4, (16, 1)).astype(np.int64), sharding),
            stop_gradient=True)
        losses = []
        for _ in range(12):
            h = paddle.flatten(F.relu(conv(x)), 1)
            loss = F.cross_entropy(fc(h), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        info = step_cache_info()
        assert info["programs"] and info["programs"][0]["spmd"] == "data8"
        assert _events("step.fire") and not _events("step.split")
        assert not _events(reason="spmd_divergence")
        assert losses[-1] < losses[0]

    def test_grads_land_full_and_replicated(self):
        """p.grad from a fused fire is the POST-psum global gradient —
        what the eager path leaves after its (GSPMD) backward."""
        xs, ys = _batches(8)
        _, sharding = _dp_mesh()
        set_flags({"FLAGS_eager_step_fusion": True})
        clear_dispatch_cache()
        STEP.clear()
        paddle.seed(0)
        params = _mlp_params()
        w1, b1, w2 = params
        opt = paddle.optimizer.SGD(learning_rate=0.0, parameters=params)
        grads = []
        for _ in xs:
            # SAME batch every step (lr=0 keeps params frozen), so the
            # eager grads (head steps) and fused grads (tail steps) are
            # directly comparable
            x = paddle.Tensor(jax.device_put(xs[0], sharding),
                              stop_gradient=True)
            y = paddle.Tensor(jax.device_put(ys[0], sharding),
                              stop_gradient=True)
            h = F.relu(paddle.add(paddle.matmul(x, w1), b1))
            diff = paddle.subtract(paddle.matmul(h, w2), y)
            loss = paddle.mean(paddle.multiply(diff, diff))
            loss.backward()
            grads.append(np.asarray(w1.grad._value))
            opt.step()
            opt.clear_grad()
        # lr=0: every step sees the identical batch-grad; the fused steps
        # (tail) must agree with the eager ones (head)
        assert np.allclose(grads[0], grads[-1], rtol=2e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# dp×sharding: ZeRO stage-1 slots stay sharded through fused fires
# ---------------------------------------------------------------------------

@needs_mesh
class TestGroupShardedPromotion:
    def test_dp2_sharding4_parity_slots_stay_sharded(self):
        xs, ys = _batches(16)
        opt_fn = lambda ps: paddle.optimizer.Adam(learning_rate=0.01,
                                                  parameters=ps)
        base_l, base_p, _, _ = _run_loop(xs, ys, fused=False,
                                         opt_fn=opt_fn)
        mesh, sharding = _dp_mesh(dp=2, sharding=4)
        fused_l, fused_p, fopt, _ = _run_loop(
            xs, ys, fused=True, sharding=sharding, opt_fn=opt_fn,
            shard_states=True)
        info = step_cache_info()
        assert info["programs"][0]["spmd"] == "data2×sharding4"
        assert _events("step.fire")
        assert np.allclose(base_l, fused_l, rtol=5e-5, atol=1e-6)
        for a, b in zip(base_p, fused_p):
            assert np.allclose(a, b, rtol=5e-5, atol=1e-6)
        # the ZeRO placement survived every fused fire: each moment slot
        # is still sharded over "sharding" and device 0 holds ~1/4
        for name in ("moment1", "moment2"):
            for pname, v in fopt._accumulators[name].items():
                m, norm = value_mesh_and_spec(v)
                assert m is not None and any(
                    axes == ("sharding",) for axes in norm), (name, pname)
                frac = v.addressable_shards[0].data.nbytes / v.nbytes
                assert frac <= 0.25 + 1e-6


# ---------------------------------------------------------------------------
# guardian + GradScaler: one poisoned shard, globally-consistent skip
# ---------------------------------------------------------------------------

@needs_mesh
class TestGlobalGuardian:
    def test_scaler_backoff_single_bad_shard(self):
        set_flags({"FLAGS_check_numerics": True})
        xs, ys = _batches(18)
        bad = 12
        xs[bad] = xs[bad].copy()
        xs[bad][4:6, :] = np.inf     # rows 4–5 → ONE shard of 8
        scaler_args = dict(init_loss_scaling=1024.0,
                           incr_every_n_steps=1000,
                           decr_every_n_nan_or_inf=1)
        _, sharding = _dp_mesh()
        b_l, b_p, _, b_s = _run_loop(xs, ys, fused=False,
                                     sharding=sharding,
                                     scaler_args=scaler_args)
        f_l, f_p, _, f_s = _run_loop(xs, ys, fused=True,
                                     sharding=sharding,
                                     scaler_args=scaler_args)
        info = step_cache_info()
        assert info["programs"][0]["spmd"] == "data8"
        assert "GradScaler" in info["programs"][0]["label"]
        # the skip + backoff decision is identical on every shard and
        # between fused and eager: one bad shard halves the scale once
        assert f_s == b_s
        assert f_s[bad] == f_s[bad - 1] / 2
        for a, b in zip(b_p, f_p):
            assert np.allclose(a, b, rtol=2e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# probation: the pmean contract is verified before fused results commit
# ---------------------------------------------------------------------------

@needs_mesh
class TestProbation:
    def test_sum_loss_demotes_to_plain_jit(self):
        xs, ys = _batches(14)
        # a sum loss is 128x the mean: a tiny LR keeps the trajectory
        # numerically comparable instead of chaotic
        opt_fn = lambda ps: paddle.optimizer.SGD(learning_rate=1e-4,
                                                 parameters=ps)
        base_l, base_p, _, _ = _run_loop(xs, ys, fused=False,
                                         loss_kind="sum", opt_fn=opt_fn)
        _, sharding = _dp_mesh()
        clear_fusion_events()
        fused_l, fused_p, _, _ = _run_loop(xs, ys, fused=True,
                                           sharding=sharding,
                                           loss_kind="sum", opt_fn=opt_fn)
        divs = _events(reason="spmd_divergence")
        assert len(divs) == 1
        assert divs[0]["detail"]["why"] == "numeric_divergence"
        # demoted, not dead: the plain jit lowering fires for the rest
        assert _events("step.fire")
        assert step_cache_info()["programs"][0]["spmd"] is None
        # and numerics still match the unfused path
        assert np.allclose(base_l, fused_l, rtol=5e-5, atol=1e-6)
        for a, b in zip(base_p, fused_p):
            assert np.allclose(a, b, rtol=5e-5, atol=1e-6)

    def test_probation_step_commits_eager_bitwise(self):
        """The probation step itself must be the EAGER result: run two
        fused loops where one disables spmd — their probation-step params
        must agree bitwise (both committed by the eager optimizer)."""
        xs, ys = _batches(4)
        _, sharding = _dp_mesh()
        set_flags({"FLAGS_eager_step_fusion_spmd": False})
        plain_l, plain_p, _, _ = _run_loop(xs, ys, fused=False,
                                           sharding=sharding)
        set_flags({"FLAGS_eager_step_fusion_spmd": True})
        spmd_l, spmd_p, _, _ = _run_loop(xs, ys, fused=True,
                                         sharding=sharding)
        # 4 steps with min_count=3: promote at 3, probation at 4 — NO
        # fused fire ever committed, so the whole run is bitwise eager
        assert plain_l == spmd_l
        for a, b in zip(plain_p, spmd_p):
            assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# mesh lifecycle
# ---------------------------------------------------------------------------

@needs_mesh
class TestMeshLifecycle:
    def test_relayout_splits_mesh_mismatch_and_repromotes(self):
        xs, ys = _batches(8)
        mesh8, shard8 = _dp_mesh()
        mesh2 = build_mesh(dp=4, pp=1, sharding=2, sep=1, mp=1)
        shard2 = NamedSharding(mesh2, P(("data", "sharding")))
        set_flags({"FLAGS_eager_step_fusion": True})
        clear_dispatch_cache()
        STEP.clear()
        paddle.seed(0)
        params = _mlp_params()
        w1, b1, w2 = params
        opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=params)
        for i in range(14):
            use = shard8 if i < 8 else shard2
            x = paddle.Tensor(jax.device_put(xs[i % 8], use),
                              stop_gradient=True)
            y = paddle.Tensor(jax.device_put(ys[i % 8], use),
                              stop_gradient=True)
            h = F.relu(paddle.add(paddle.matmul(x, w1), b1))
            diff = paddle.subtract(paddle.matmul(h, w2), y)
            loss = paddle.mean(paddle.multiply(diff, diff))
            loss.backward()
            opt.step()
            opt.clear_grad()
        assert _events(reason="mesh_mismatch")
        promotes = _events("step.promote")
        assert len(promotes) == 2
        assert promotes[0]["detail"]["mesh"] == "data8"
        assert promotes[1]["detail"]["mesh"] == "data4×sharding2"

    def test_mesh_key_and_topology_token(self):
        m8 = build_mesh(dp=8, pp=1, sharding=1, sep=1, mp=1)
        m8b = build_mesh(dp=8, pp=1, sharding=1, sep=1, mp=1)
        m24 = build_mesh(dp=2, pp=1, sharding=4, sep=1, mp=1)
        assert mesh_key(m8) == mesh_key(m8b)
        assert mesh_key(m8) != mesh_key(m24)
        set_global_mesh(m8)
        t8 = topology_token()
        set_global_mesh(m24)
        t24 = topology_token()
        set_global_mesh(None)
        tnone = topology_token()
        assert t8 != t24 != tnone
        assert t8[0] == N_DEV and ("data", 8) in t8[1]

    def test_aot_fingerprint_carries_mesh_topology(self):
        from paddle_tpu.ops import aot_cache
        set_global_mesh(None)
        fp0 = dict(aot_cache.env_fingerprint())
        d0 = aot_cache.fingerprint_digest()
        set_global_mesh(build_mesh(dp=8, pp=1, sharding=1, sep=1, mp=1))
        fp8 = dict(aot_cache.env_fingerprint())
        d8 = aot_cache.fingerprint_digest()
        set_global_mesh(build_mesh(dp=2, pp=1, sharding=4, sep=1, mp=1))
        d24 = aot_cache.fingerprint_digest()
        # a single-chip artifact can never deserialize into a sharded
        # process — nor a dp=8 artifact into a dp=2×sharding=4 one
        assert fp0["mesh"] != fp8["mesh"]
        assert len({d0, d8, d24}) == 3


# ---------------------------------------------------------------------------
# collective keying in the dispatch funnel
# ---------------------------------------------------------------------------

class TestCollectiveKeying:
    def test_mesh_backed_collective_keys(self):
        from paddle_tpu.ops import dispatch as dmod
        mesh = build_mesh(dp=N_DEV, pp=1, sharding=1, sep=1, mp=1)
        fn = mark_collective(lambda v: v,
                             ("all_reduce", "sum", mesh_key(mesh)))
        t = paddle.to_tensor(np.ones(4, np.float32))
        key = dmod._make_key("dist.all_reduce", fn, [t], None, (0, 0))
        assert key is not None
        assert key[1][0] == "collective"
        # same kind+op+mesh keys equal across distinct fn objects
        fn2 = mark_collective(lambda v: v,
                              ("all_reduce", "sum", mesh_key(mesh)))
        key2 = dmod._make_key("dist.all_reduce", fn2, [t], None, (0, 0))
        assert key == key2

    def test_pg_less_group_is_collective_unkeyed(self):
        from paddle_tpu.ops import dispatch as dmod
        fn = mark_collective(lambda v: v, None)
        t = paddle.to_tensor(np.ones(4, np.float32))
        key = dmod._make_key("dist.all_reduce", fn, [t], None, (0, 0))
        assert key is None
        assert dmod._classify_bypass("dist.all_reduce") \
            == "collective_unkeyed"

    @needs_mesh
    def test_unkeyed_grad_collective_poisons_cycle(self):
        xs, ys = _batches(8)
        _, sharding = _dp_mesh()
        group = dist.collective.Group(0, N_DEV, id=91,
                                      ranks=list(range(N_DEV)))
        set_flags({"FLAGS_eager_step_fusion": True})
        clear_dispatch_cache()
        STEP.clear()
        paddle.seed(0)
        params = _mlp_params()
        w1, b1, w2 = params
        opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=params)
        for xv, yv in zip(xs, ys):
            x = paddle.Tensor(jax.device_put(xv, sharding),
                              stop_gradient=True)
            y = paddle.Tensor(jax.device_put(yv, sharding),
                              stop_gradient=True)
            h = F.relu(paddle.add(paddle.matmul(x, w1), b1))
            diff = paddle.subtract(paddle.matmul(h, w2), y)
            loss = paddle.mean(paddle.multiply(diff, diff))
            loss.backward()
            dist.all_reduce(w1.grad, group=group)
            opt.step()
            opt.clear_grad()
        assert _events(reason="collective_unkeyed")
        assert not _events("step.promote")
        from paddle_tpu.profiler.explain import explain
        rep = explain()
        assert rep["verdict"] == "never_promoted"
        assert "collective_unkeyed" in rep["headline"]

    def test_keyed_collective_via_default_group_stays_clean(self):
        """The single-controller identity path of a mesh-backed group
        must not disturb promotion (no dispatch, no poison)."""
        xs, ys = _batches(8)
        set_flags({"FLAGS_eager_step_fusion": True})
        clear_dispatch_cache()
        STEP.clear()
        paddle.seed(0)
        params = _mlp_params()
        w1, b1, w2 = params
        opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=params)
        for xv, yv in zip(xs, ys):
            x = paddle.Tensor(xv, stop_gradient=True)
            y = paddle.Tensor(yv, stop_gradient=True)
            h = F.relu(paddle.add(paddle.matmul(x, w1), b1))
            diff = paddle.subtract(paddle.matmul(h, w2), y)
            loss = paddle.mean(paddle.multiply(diff, diff))
            loss.backward()
            dist.all_reduce(loss)      # default group: identity, no-op
            opt.step()
            opt.clear_grad()
        assert _events("step.promote")
        assert not _events(reason="collective_unkeyed")


# ---------------------------------------------------------------------------
# jax.shard_map behaviours the promoter leans on
# ---------------------------------------------------------------------------

@needs_mesh
class TestShardMapContract:
    def _mesh(self):
        return build_mesh(dp=4, pp=1, sharding=2, sep=1, mp=1)

    def test_psum_over_donated_buffers(self):
        """The fused SPMD step donates its optimizer-slot buffers into a
        jit(shard_map(psum ...)) program — the exact shape the promoter
        compiles. Donation must not perturb the collective's result."""
        mesh = self._mesh()

        def body(x, acc):
            s = jax.lax.pmean(x, ("data", "sharding"))
            return s, acc + s

        fn = jax.jit(jax.shard_map(body, mesh=mesh,
                                   in_specs=(P(("data", "sharding")), P()),
                                   out_specs=(P(), P())),
                     donate_argnums=(1,))
        xs = np.arange(16, dtype=np.float32).reshape(16, 1)
        x = jax.device_put(xs, NamedSharding(mesh, P(("data", "sharding"))))
        acc = jnp.zeros((2, 1), jnp.float32)
        expected = xs.reshape(8, 2, 1).mean(axis=0)
        for i in range(3):
            out, acc = fn(x, acc)
            np.testing.assert_allclose(np.asarray(out), expected,
                                       rtol=1e-6)
        np.testing.assert_allclose(np.asarray(acc), 3 * expected,
                                   rtol=1e-6)

    def test_partial_manual_axis_names(self):
        """axis_names={"data"} maps only that axis manually; the unnamed
        axis stays with the partitioner and the result is what mapping
        every axis with replication over the unnamed ones would give."""
        mesh = self._mesh()

        def body(x):
            return jax.lax.psum(x, "data")

        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                                   out_specs=P("data"),
                                   axis_names={"data"}))
        x = np.arange(8, dtype=np.float32).reshape(4, 2)
        out = np.asarray(fn(x))
        expected = np.tile(x.sum(axis=0, keepdims=True), (4, 1))
        np.testing.assert_allclose(out, expected, rtol=1e-6)

    def test_axis_names_validated_against_mesh(self):
        mesh = self._mesh()
        with pytest.raises(ValueError, match="subset of mesh.axis_names"):
            jax.shard_map(lambda x: x, mesh=mesh, in_specs=P(),
                          out_specs=P(), axis_names={"bogus"})(
                              np.ones(4, np.float32))

    def test_axis_size_and_pcast_inside_manual_region(self):
        mesh = self._mesh()

        def body(x):
            # axis_size is a python int; the constant built from it is
            # replicated until pcast marks it varying like the shard
            n = jnp.full((1, 2), jax.lax.axis_size("data"), jnp.float32)
            return x * jax.lax.pcast(n, "data", to="varying")

        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                                   out_specs=P("data")))
        x = np.ones((4, 2), np.float32)
        np.testing.assert_allclose(np.asarray(fn(x)), 4 * x, rtol=1e-6)


# ---------------------------------------------------------------------------
# counted guard + doctor fixture
# ---------------------------------------------------------------------------

def _dp_loop():
    """A dp=N data-parallel MLP loop: batch sharded over a mesh spanning
    every device. With step fusion on, the cycle must promote through
    the SPMD lowering (ops/spmd_fusion.py): ONE shard_map executable per
    step."""
    set_flags({"FLAGS_eager_chain_fusion_min_count": 4,
               "FLAGS_eager_step_fusion_min_count": 5})
    clear_dispatch_cache()
    mesh = build_mesh(dp=N_DEV, pp=1, sharding=1, sep=1, mp=1)
    set_global_mesh(mesh)
    sharding = NamedSharding(mesh, P("data"))
    rng = np.random.default_rng(0)
    x = paddle.Tensor(jax.device_put(
        rng.standard_normal((8 * N_DEV, 32)).astype(np.float32), sharding),
        stop_gradient=True)
    y = paddle.Tensor(jax.device_put(
        rng.standard_normal((8 * N_DEV, 16)).astype(np.float32), sharding),
        stop_gradient=True)
    w1 = paddle.to_tensor(
        (rng.standard_normal((32, 64)) * 0.1).astype(np.float32),
        stop_gradient=False)
    b1 = paddle.to_tensor(np.zeros(64, np.float32), stop_gradient=False)
    w2 = paddle.to_tensor(
        (rng.standard_normal((64, 16)) * 0.1).astype(np.float32),
        stop_gradient=False)
    opt = paddle.optimizer.Momentum(learning_rate=1e-3, momentum=0.9,
                                    parameters=[w1, b1, w2])

    def step():
        h = F.relu(paddle.add(paddle.matmul(x, w1), b1))
        out = paddle.matmul(h, w2)
        diff = paddle.subtract(out, y)
        loss = paddle.mean(paddle.multiply(diff, diff))
        loss.backward()
        opt.step()
        opt.clear_grad()

    return step


@needs_mesh
class TestPerfGuards:
    def test_promoted_dp_step_is_one_executable_without_retraces(self):
        """Zero retraces after warm-up and ONE promoted executable, the
        SPMD one, fired once a step. (Its speed against eager collectives
        is a mesh cell's to say, on the chips.)"""
        step, warmup, measure = _dp_loop(), 14, 40
        for _ in range(warmup):
            step()
        s0 = step_fusion_stats()
        for _ in range(measure):
            step()
        s1 = step_fusion_stats()
        assert s1["retraces"] == s0["retraces"], "post-promotion retrace"
        assert s1["fused_steps"] - s0["fused_steps"] == measure
        assert s1["steps_promoted"] == 1
        assert s1["fallback_splits"] == s0["fallback_splits"]
        assert [p["spmd"] for p in step_cache_info()["programs"]
                if p["spmd"]] == [f"data{N_DEV}"]

    def test_doctor_demo_dp_names_collective_unkeyed(self):
        out = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(__file__), os.pardir, "tools",
                          "fusion_doctor.py"),
             "--demo", "dp", "--steps", "10", "--json"],
            capture_output=True, text=True, timeout=600,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert out.returncode == 0, out.stderr
        rep = json.loads(out.stdout)
        assert rep["verdict"] == "never_promoted"
        assert "collective_unkeyed" in rep["headline"]
        assert "dist.all_reduce" in rep["headline"]


class TestSuperCycleSPMD:
    """Universal promotion: a sharded k-micro-batch accumulation loop
    promotes under the SPMD path — the sub-executable accumulates LOCAL
    gradient sums with NO per-micro-batch collective, and the update
    executable fires ONE fused pmean over the accumulated sums (k× less
    gradient traffic), probation-validated against the bitwise eager
    replay."""

    def test_dp8_accum_promotes_with_parity(self):
        xs, _ = _batches(60)
        it = iter(xs)

        def run(fused, shard):
            set_flags({"FLAGS_eager_step_fusion": fused})
            clear_dispatch_cache()
            STEP.clear()
            paddle.seed(0)
            params = _mlp_params()
            w1, b1, w2 = params
            opt = paddle.optimizer.Momentum(
                learning_rate=0.05, momentum=0.9, parameters=params)
            losses = []
            src = iter(xs)
            for _ in range(14):
                for _m in range(3):
                    xv = next(src)
                    x = paddle.Tensor(
                        jax.device_put(xv, shard) if shard is not None
                        else jnp.asarray(xv), stop_gradient=True)
                    h = F.relu(paddle.add(paddle.matmul(x, w1), b1))
                    loss = paddle.mean(
                        paddle.multiply(paddle.matmul(h, w2),
                                        paddle.matmul(h, w2)))
                    loss.backward()
                opt.step()
                opt.clear_grad()
                # post-step read: served from the sub-executable output
                losses.append(float(loss.numpy()))
            return np.asarray(losses), w1.numpy().copy()

        base_l, base_w = run(False, None)
        _, sharding = _dp_mesh()
        clear_fusion_events()
        fused_l, fused_w = run(True, sharding)
        s = step_fusion_stats()
        assert s["steps_promoted"] == 1
        assert s["fused_steps"] >= 8, s
        assert s["fallback_splits"] == 0, s
        promo = [e for e in fusion_events("step.promote")]
        assert promo and promo[-1]["detail"]["spmd"] \
            and promo[-1]["detail"]["super"], promo
        prob = [e for e in fusion_events("step.record")
                if e.get("detail", {}).get("kind") == "spmd_probation"]
        assert prob and prob[-1]["detail"]["ok"], prob
        np.testing.assert_allclose(fused_l, base_l, rtol=2e-3, atol=1e-5)
        np.testing.assert_allclose(fused_w, base_w, rtol=2e-3, atol=1e-4)

    def test_dp8_accum_zero_retraces_any_k(self):
        """After the probation fire, k changes replay on the SAME two
        shard_map executables — zero fresh retraces."""
        _, sharding = _dp_mesh()
        set_flags({"FLAGS_eager_step_fusion": True})
        clear_dispatch_cache()
        STEP.clear()
        reset_step_fusion_stats()
        paddle.seed(0)
        params = _mlp_params()
        w1, b1, w2 = params
        opt = paddle.optimizer.SGD(learning_rate=0.05, parameters=params)
        rng = np.random.default_rng(0)

        def cycle(k):
            for _ in range(k):
                xv = rng.standard_normal((16, 32)).astype(np.float32)
                x = paddle.Tensor(jax.device_put(xv, sharding),
                                  stop_gradient=True)
                h = F.relu(paddle.add(paddle.matmul(x, w1), b1))
                loss = paddle.mean(paddle.matmul(h, w2))
                loss.backward()
            opt.step()
            opt.clear_grad()

        for _ in range(8):
            cycle(2)
        s0 = step_fusion_stats()
        assert s0["steps_promoted"] == 1
        assert s0["fused_steps"] >= 2, s0
        for k in (4, 3, 6):
            cycle(k)
        s1 = step_fusion_stats()
        assert s1["retraces"] == s0["retraces"], (s0["retraces"],
                                                 s1["retraces"])
        assert s1["fallback_splits"] == 0
