"""Non-finite step guardian (PR 5): in-graph numerics checks, skip-step
rescue, crash-safe checkpoints, and the chaos harness.

Covers the robustness contract end to end:
  * `FLAGS_check_numerics` keeps ALL THREE fusion tiers engaged (the old
    `FLAGS_check_nan_inf` forces per-op debug dispatch): a dynamic-loss-
    scaled GradScaler loop promotes to ONE fused whole-step executable,
    with unscale / found-inf / loss-scale update folded in;
  * skip-step rescue: a non-finite-gradient step is a bitwise no-op on
    params AND optimizer slots, fused and eager paths alike; the scale
    halves; the flight recorder attributes `nonfinite_skip`;
  * non-finite FORWARD outputs raise (level 0) or warn (level >= 1) at a
    flush boundary — except on AMP threads, where the scaler's backoff is
    the designed response;
  * framework/io.py writes checkpoints atomically (tmp + os.replace + CRC
    trailer) and load() raises CheckpointCorruptError on torn/garbled
    files; EpochRange round-trips optimizer/scaler/RNG state with rolling
    retention and resumes a kill -9'd run to the uninterrupted result;
  * chaos fault injection (tools/chaos.py) is attributed as
    `injected_fault` and the loop recovers.
"""
from __future__ import annotations

import importlib.util
import os
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.framework import io as fio
from paddle_tpu.framework import random as frandom
from paddle_tpu.framework.flags import set_flags
from paddle_tpu.incubate.checkpoint import (StepCheckpointer,
                                            train_epoch_range)
from paddle_tpu.ops import guardian
from paddle_tpu.ops.dispatch import clear_dispatch_cache
from paddle_tpu.profiler import (reset_step_fusion_stats, step_fusion_stats)
from paddle_tpu.profiler.events import clear_fusion_events, fusion_events
from paddle_tpu.profiler.explain import explain, format_report

_DEFAULTS = {
    "FLAGS_check_numerics": False,
    "FLAGS_check_numerics_level": 0,
    "FLAGS_check_nan_inf": False,
    "FLAGS_eager_op_cache": True,
    "FLAGS_eager_op_cache_size": 512,
    "FLAGS_eager_chain_fusion": True,
    "FLAGS_eager_chain_fusion_min_count": 3,
    "FLAGS_eager_step_fusion": True,
    "FLAGS_eager_step_fusion_min_count": 4,
    "FLAGS_eager_step_fusion_cache_size": 8,
    "FLAGS_profiler_events": False,
}


def _reset():
    set_flags(dict(_DEFAULTS))
    clear_dispatch_cache()
    clear_fusion_events()
    guardian.reset_guardian_stats()
    guardian.reset_thread_state()
    guardian.clear_faults()
    reset_step_fusion_stats()


@pytest.fixture(autouse=True)
def _fresh():
    _reset()
    yield
    _reset()


def _mk(seed=0, d=8, with_momentum=False, lr=1e-2):
    rng = np.random.default_rng(seed)
    x = paddle.to_tensor(rng.standard_normal((4, d)).astype(np.float32))
    w = paddle.to_tensor(rng.standard_normal((d, d)).astype(np.float32),
                         stop_gradient=False)
    if with_momentum:
        opt = paddle.optimizer.Momentum(learning_rate=lr, momentum=0.9,
                                        parameters=[w])
    else:
        opt = paddle.optimizer.SGD(learning_rate=lr, parameters=[w])
    return x, w, opt


def _nan_batch(d=8):
    return paddle.to_tensor(np.full((4, d), np.nan, np.float32))


def _plain_step(x, w, opt):
    F.gelu(paddle.matmul(x, w)).sum().backward()
    opt.step()
    opt.clear_grad()


def _amp_step(x, w, opt, scaler):
    loss = F.gelu(paddle.matmul(x, w)).sum()
    scaler.scale(loss).backward()
    scaler.step(opt)
    scaler.update()
    opt.clear_grad()


# ---------------------------------------------------------------------------
# crash-safe io
# ---------------------------------------------------------------------------

class TestAtomicCheckpointIO:
    def test_roundtrip_and_no_tmp_leftovers(self, tmp_path):
        path = os.path.join(tmp_path, "sub", "model.pdparams")
        fio.save({"w": paddle.to_tensor(np.arange(6.0, dtype=np.float32))},
                 path)
        out = fio.load(path)
        np.testing.assert_array_equal(np.asarray(out["w"]._value),
                                      np.arange(6.0, dtype=np.float32))
        leftovers = [f for d, _, fs in os.walk(tmp_path)
                     for f in fs if ".tmp" in f]
        assert leftovers == []

    def test_every_sync_save_carries_crc_trailer(self, tmp_path):
        import struct
        path = os.path.join(tmp_path, "x.pd")
        fio.save({"v": 1}, path)
        raw = open(path, "rb").read()
        magic, plen, _crc = struct.unpack("<QQQ", raw[-24:])
        assert magic == fio._TRAILER_MAGIC
        assert plen == len(raw) - 24

    def test_bitflip_detected(self, tmp_path):
        path = os.path.join(tmp_path, "x.pd")
        fio.save({"w": paddle.to_tensor(np.ones(32, np.float32))}, path)
        raw = bytearray(open(path, "rb").read())
        raw[len(raw) // 2] ^= 0xFF
        open(path, "wb").write(bytes(raw))
        with pytest.raises(fio.CheckpointCorruptError, match="CRC"):
            fio.load(path)
        # the dedicated error is still an IOError (pre-PR5 callers catch it)
        assert issubclass(fio.CheckpointCorruptError, IOError)

    def test_truncation_detected(self, tmp_path):
        path = os.path.join(tmp_path, "x.pd")
        fio.save({"w": paddle.to_tensor(np.ones(64, np.float32))}, path)
        raw = open(path, "rb").read()
        open(path, "wb").write(raw[: len(raw) // 2])
        with pytest.raises(fio.CheckpointCorruptError):
            fio.load(path)

    def test_file_object_path_unchanged(self, tmp_path):
        path = os.path.join(tmp_path, "x.pd")
        with open(path, "wb") as f:
            fio.save({"v": 7}, f)
        with open(path, "rb") as f:
            assert fio.load(f)["v"] == 7

    def test_failed_save_preserves_previous_checkpoint(self, tmp_path):
        path = os.path.join(tmp_path, "x.pd")
        fio.save({"v": "good"}, path)

        class Boom:
            def __reduce__(self):
                raise RuntimeError("mid-serialization crash")

        with pytest.raises(RuntimeError):
            fio.save({"v": Boom()}, path)
        assert fio.load(path)["v"] == "good"


class TestEpochRangeCheckpoints:
    def test_state_roundtrip_with_retention(self, tmp_path):
        x, w, opt = _mk(seed=3, with_momentum=True)
        scaler = paddle.amp.GradScaler(init_loss_scaling=512.0)
        paddle.seed(9)
        er = train_epoch_range(5, save_dir=str(tmp_path), run_id="t",
                               max_checkpoints=2)
        for epoch in er:
            _plain_step(x, w, opt)
            er.save(epoch, model={"w": w}, optimizer=opt, scaler=scaler,
                    extra={"epoch": epoch})
        assert er._retained_epochs() == [3, 4]
        w_final = np.asarray(w._value).copy()
        acc = {k: np.asarray(v) for k, v
               in opt._accumulators["velocity"].items()}
        rng_before = frandom.rng_checkpoint_state()

        x2, w2, opt2 = _mk(seed=99, with_momentum=True)
        w2.name = w.name
        scaler2 = paddle.amp.GradScaler(init_loss_scaling=2.0)
        paddle.seed(1234)   # scrambled on purpose; restore must undo it
        er2 = train_epoch_range(5, save_dir=str(tmp_path), run_id="t",
                                max_checkpoints=2)
        extra = er2.restore(model={"w": w2}, optimizer=opt2, scaler=scaler2)
        assert extra == {"epoch": 4}
        assert er2.restored_from == 4
        np.testing.assert_array_equal(w_final, np.asarray(w2._value))
        for k, v in acc.items():
            np.testing.assert_array_equal(
                v, np.asarray(opt2._accumulators["velocity"][k]))
        assert getattr(opt2, "_step_count") == getattr(opt, "_step_count")
        assert scaler2.get_init_loss_scaling() == 512.0
        rng_after = frandom.rng_checkpoint_state()
        assert rng_after["epoch"] == rng_before["epoch"]
        np.testing.assert_array_equal(rng_after["key_data"],
                                      rng_before["key_data"])

    def test_restore_falls_back_past_corrupt_checkpoint(self, tmp_path):
        x, w, opt = _mk(seed=4)
        er = train_epoch_range(4, save_dir=str(tmp_path), run_id="t",
                               max_checkpoints=3)
        snaps = {}
        for epoch in er:
            _plain_step(x, w, opt)
            er.save(epoch, model={"w": w})
            snaps[epoch] = np.asarray(w._value).copy()
        # garble the NEWEST checkpoint (simulated torn write on a crashed
        # filesystem that ignored fsync)
        newest = os.path.join(er.checkpoint_path(3), er.CKPT_FILE)
        raw = bytearray(open(newest, "rb").read())
        raw[len(raw) // 2] ^= 0xFF
        open(newest, "wb").write(bytes(raw))

        w2 = paddle.to_tensor(np.zeros((8, 8), np.float32),
                              stop_gradient=False)
        er2 = train_epoch_range(4, save_dir=str(tmp_path), run_id="t")
        er2.restore(model={"w": w2})
        np.testing.assert_array_equal(snaps[2], np.asarray(w2._value))
        # the range rewinds so the lost epoch is re-run
        assert er2.restored_from == 2
        assert list(er2) == [3]

    def test_restore_refuses_when_every_checkpoint_is_corrupt(self, tmp_path):
        x, w, opt = _mk(seed=5)
        er = train_epoch_range(3, save_dir=str(tmp_path), run_id="t",
                               max_checkpoints=2)
        for epoch in er:
            _plain_step(x, w, opt)
            er.save(epoch, model={"w": w})
        for e in er._retained_epochs():
            p = os.path.join(er.checkpoint_path(e), er.CKPT_FILE)
            raw = bytearray(open(p, "rb").read())
            raw[len(raw) // 2] ^= 0xFF
            open(p, "wb").write(bytes(raw))
        w2 = paddle.to_tensor(np.zeros((8, 8), np.float32),
                              stop_gradient=False)
        er2 = train_epoch_range(3, save_dir=str(tmp_path), run_id="t")
        # resuming epochs 3.. on w2's fresh zeros would be silent garbage:
        # the restore must refuse, not return None
        with pytest.raises(fio.CheckpointCorruptError,
                           match="refusing to resume"):
            er2.restore(model={"w": w2})


# ---------------------------------------------------------------------------
# GradScaler semantics
# ---------------------------------------------------------------------------

class TestGradScaler:
    def test_double_unscale_raises(self):
        x, w, opt = _mk()
        scaler = paddle.amp.GradScaler()
        scaler.scale(F.gelu(paddle.matmul(x, w)).sum()).backward()
        scaler.unscale_(opt)
        with pytest.raises(RuntimeError, match="unscale_"):
            scaler.unscale_(opt)
        # step()+update() reset the latch: the next cycle unscales fine
        scaler.step(opt)
        scaler.update()
        opt.clear_grad()
        scaler.scale(F.gelu(paddle.matmul(x, w)).sum()).backward()
        scaler.unscale_(opt)
        scaler.step(opt)
        scaler.update()

    def test_state_dict_roundtrips_growth_tracker(self):
        scaler = paddle.amp.GradScaler(init_loss_scaling=128.0,
                                       incr_every_n_steps=3,
                                       decr_every_n_nan_or_inf=2)
        # one bad step (streak 1 of 2) and two good steps (streak 2 of 3)
        scaler._found_inf = True
        scaler.update()
        scaler._found_inf = False
        scaler.update()
        scaler.update()
        state = scaler.state_dict()
        assert state["scale"] == 128.0
        assert state["bad_steps"] == 0 and state["good_steps"] == 2
        fresh = paddle.amp.GradScaler(init_loss_scaling=1.0,
                                      incr_every_n_steps=3,
                                      decr_every_n_nan_or_inf=2)
        fresh.load_state_dict(state)
        # the third good step grows the scale exactly as the original would
        fresh._found_inf = False
        fresh.update()
        assert fresh.get_init_loss_scaling() == 256.0

    def test_legacy_skip_and_backoff_without_guardian(self):
        set_flags({"FLAGS_eager_step_fusion": False})
        x, w, opt = _mk(seed=5)
        scaler = paddle.amp.GradScaler(init_loss_scaling=64.0,
                                       decr_every_n_nan_or_inf=1)
        _amp_step(x, w, opt, scaler)
        w_good = np.asarray(w._value).copy()
        _amp_step(_nan_batch(), w, opt, scaler)
        np.testing.assert_array_equal(w_good, np.asarray(w._value))
        assert scaler.get_init_loss_scaling() == 32.0
        # legacy mode: the skip happened in Python, not via the guardian
        assert guardian.guardian_stats()["steps_skipped"] == 0


# ---------------------------------------------------------------------------
# guardian, eager tier
# ---------------------------------------------------------------------------

class TestGuardianEager:
    def test_strict_mode_takes_precedence(self):
        set_flags({"FLAGS_check_numerics": True, "FLAGS_check_nan_inf": True})
        assert not guardian.enabled()
        set_flags({"FLAGS_check_nan_inf": False})
        assert guardian.enabled()

    def test_forward_nonfinite_raises_at_flush(self):
        set_flags({"FLAGS_check_numerics": True,
                   "FLAGS_eager_step_fusion": False})
        x, w, opt = _mk()
        # the raise lands at the first boundary whose pipelined batch has
        # resolved — backward on a fast device, the explicit flush at the
        # latest
        with pytest.raises(FloatingPointError, match="non-finite"):
            F.gelu(paddle.matmul(_nan_batch(), w)).sum().backward()
            guardian.flush()
        assert guardian.guardian_stats()["nonfinite_outputs"] >= 1

    def test_forward_nonfinite_warns_at_level1(self):
        set_flags({"FLAGS_check_numerics": True,
                   "FLAGS_check_numerics_level": 1,
                   "FLAGS_eager_step_fusion": False})
        x, w, opt = _mk()
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            F.gelu(paddle.matmul(_nan_batch(), w)).sum().backward()
            guardian.flush()
        assert any("non-finite" in str(r.message) for r in rec)

    def test_eager_skip_step_is_bitwise_noop(self):
        set_flags({"FLAGS_check_numerics": True,
                   "FLAGS_check_numerics_level": 1,
                   "FLAGS_eager_step_fusion": False})
        x, w, opt = _mk(seed=6, with_momentum=True)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            _plain_step(x, w, opt)
            w_good = np.asarray(w._value).copy()
            vel = np.asarray(
                next(iter(opt._accumulators["velocity"].values()))).copy()
            _plain_step(_nan_batch(), w, opt)
            guardian.flush()
        np.testing.assert_array_equal(w_good, np.asarray(w._value))
        np.testing.assert_array_equal(
            vel, np.asarray(
                next(iter(opt._accumulators["velocity"].values()))))
        stats = guardian.guardian_stats()
        assert stats["steps_skipped"] == 1
        # step counter still advanced: LR schedules see the skipped step
        assert opt._step_count == 2
        # and a good batch updates again
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            _plain_step(x, w, opt)
        assert not np.array_equal(w_good, np.asarray(w._value))

    def test_scaler_thread_never_raises_on_forward_inf(self):
        set_flags({"FLAGS_check_numerics": True,
                   "FLAGS_eager_step_fusion": False})
        x, w, opt = _mk(seed=7)
        scaler = paddle.amp.GradScaler(init_loss_scaling=64.0,
                                       decr_every_n_nan_or_inf=1)
        _amp_step(x, w, opt, scaler)
        w_good = np.asarray(w._value).copy()
        _amp_step(_nan_batch(), w, opt, scaler)
        guardian.flush()     # must NOT raise: AMP overflow is rescued
        np.testing.assert_array_equal(w_good, np.asarray(w._value))
        assert scaler.get_init_loss_scaling() == 32.0
        stats = guardian.guardian_stats()
        assert stats["steps_skipped"] == 1
        assert stats["scaler_backoffs"] == 1


# ---------------------------------------------------------------------------
# guardian, fused whole-step tier
# ---------------------------------------------------------------------------

def _amp_run(steps, nan_at=(), fused=True, seed=11, lr=1e-2):
    """Fresh AMP loop; returns (params-before-each-step, w, opt, scaler)."""
    set_flags({"FLAGS_check_numerics": True,
               "FLAGS_eager_step_fusion": fused})
    clear_dispatch_cache()
    x, w, opt = _mk(seed=seed, with_momentum=True, lr=lr)
    scaler = paddle.amp.GradScaler(init_loss_scaling=256.0,
                                   decr_every_n_nan_or_inf=1)
    before = []
    for i in range(steps):
        before.append(np.asarray(w._value).copy())
        _amp_step(_nan_batch() if i in nan_at else x, w, opt, scaler)
    guardian.flush()
    return before, w, opt, scaler


class TestGuardianFused:
    def test_amp_loop_promotes_to_one_executable(self):
        _amp_run(10)
        s = step_fusion_stats()
        assert s["steps_promoted"] == 1
        assert s["fused_steps"] >= 4
        assert s["fallback_splits"] == 0

    def test_fused_nan_step_bitwise_noop_no_split(self):
        before, w, opt, scaler = _amp_run(12, nan_at=(9,))
        s = step_fusion_stats()
        assert s["fused_steps"] >= 6 and s["fallback_splits"] == 0
        # the NaN step (9) changed nothing: params before step 10 are
        # bitwise the params before step 9
        np.testing.assert_array_equal(before[9], before[10])
        # but training continued: step 10 updated again
        assert not np.array_equal(before[10], before[11])
        assert scaler.get_init_loss_scaling() == 128.0
        stats = guardian.guardian_stats()
        assert stats["steps_skipped"] == 1
        assert stats["scaler_backoffs"] == 1

    def test_fused_and_eager_nan_handling_agree(self):
        before_f, w_f, _, sc_f = _amp_run(12, nan_at=(9,), fused=True)
        guardian.reset_thread_state()
        before_e, w_e, _, sc_e = _amp_run(12, nan_at=(9,), fused=False)
        # identical skip semantics: both paths no-op step 9 bitwise...
        np.testing.assert_array_equal(before_f[9], before_f[10])
        np.testing.assert_array_equal(before_e[9], before_e[10])
        # ...took the same scale trajectory...
        assert sc_f.get_init_loss_scaling() == sc_e.get_init_loss_scaling()
        # ...and agree on the params (to the fused-vs-unfused reduction
        # tolerance, ROADMAP follow-on (d))
        np.testing.assert_allclose(np.asarray(w_f._value),
                                   np.asarray(w_e._value),
                                   rtol=0, atol=1e-5)

    def test_fused_no_scaler_nonfinite_loss_raises(self):
        # forward-contract parity with the unfused path: a promoted loop
        # WITHOUT a GradScaler still raises on a non-finite loss at level
        # 0 (the skip-step no-op protected the params, but silently
        # stalled training is not an acceptable steady state)
        set_flags({"FLAGS_check_numerics": True})
        x, w, opt = _mk(seed=17)
        for _ in range(8):
            _plain_step(x, w, opt)
        assert step_fusion_stats()["fused_steps"] >= 1
        w_good = np.asarray(w._value).copy()
        with pytest.raises(FloatingPointError, match="non-finite"):
            _plain_step(_nan_batch(), w, opt)
            guardian.flush()
        np.testing.assert_array_equal(w_good, np.asarray(w._value))

    def test_grad_placeholders_filled_with_unscaled_grads(self):
        def run(fused):
            _reset()
            set_flags({"FLAGS_check_numerics": True,
                       "FLAGS_eager_step_fusion": fused})
            x, w, opt = _mk(seed=13)
            scaler = paddle.amp.GradScaler(init_loss_scaling=256.0)
            grads = []
            for _ in range(8):
                loss = F.gelu(paddle.matmul(x, w)).sum()
                scaler.scale(loss).backward()
                scaler.step(opt)
                scaler.update()
                grads.append(np.asarray(w.grad._value).copy())
                opt.clear_grad()
            return grads

        fused_grads = run(True)
        assert step_fusion_stats()["fused_steps"] >= 2
        eager_grads = run(False)
        # after scaler.step the user-visible p.grad holds UNSCALED grads —
        # fused fires fill the placeholders with exactly what the eager
        # unscale_ path produces
        for gf, ge in zip(fused_grads, eager_grads):
            np.testing.assert_allclose(gf, ge, rtol=0, atol=1e-5)

    def test_doctor_attributes_nonfinite_skip(self):
        set_flags({"FLAGS_profiler_events": True})
        clear_fusion_events()
        _amp_run(12, nan_at=(9,))
        skips = [e for e in fusion_events("step.record")
                 if e["reason"] == "nonfinite_skip"]
        assert skips, "nonfinite_skip never hit the flight recorder"
        rep = explain()
        assert rep["guardian"].get("nonfinite_skip", {}).get("count", 0) >= 1
        assert rep["guardian"].get("scaler_backoff", {}).get("count", 0) >= 1
        # guardian decisions are NOT cycle poisons: the loop still reads
        # as a clean promotion
        assert rep["verdict"] == "clean_promotion", rep["headline"]
        text = format_report(rep)
        assert "nonfinite_skip" in text

    def test_scaler_hyperparam_change_kills_program(self):
        set_flags({"FLAGS_profiler_events": True})
        clear_fusion_events()
        before, w, opt, scaler = _amp_run(10)
        assert step_fusion_stats()["fused_steps"] > 0
        scaler._incr_ratio = 3.0    # baked into the traced transition
        x = paddle.to_tensor(
            np.random.default_rng(0).standard_normal((4, 8))
            .astype(np.float32))
        w_before = np.asarray(w._value).copy()
        _amp_step(x, w, opt, scaler)
        splits = [e for e in fusion_events("step.split")
                  if e["reason"] == "optimizer_state_change"]
        assert splits, "stale scaler constants did not split the replay"
        # the eager fallback still trained the step
        assert not np.array_equal(w_before, np.asarray(w._value))


# ---------------------------------------------------------------------------
# state-blowup gate + step-index stamping (PR 6 guardian follow-ons)
# ---------------------------------------------------------------------------

def _spike_run(fused, spike_at=9, steps=12):
    """Loop whose gradients stay FINITE while one step's LR spike
    overflows `p - lr*g` to inf: a pure optimizer-STATE blowup. The old
    grads-only predicate waved it through the gate; the new-state fold
    must turn it into a bitwise no-op step."""
    set_flags({"FLAGS_check_numerics": True,
               "FLAGS_eager_step_fusion": fused,
               "FLAGS_profiler_events": True})
    clear_dispatch_cache()
    clear_fusion_events()
    rng = np.random.default_rng(3)
    x = paddle.to_tensor(
        (rng.standard_normal((4, 8)) * 10).astype(np.float32))
    w = paddle.to_tensor(rng.standard_normal((8, 8)).astype(np.float32),
                         stop_gradient=False)
    opt = paddle.optimizer.SGD(learning_rate=1e-3, parameters=[w])
    before = []
    for i in range(steps):
        # the LR is a hoisted scalar arg of the fused step executable, so
        # the spike neither splits nor retraces — it rides the same
        # program and the in-graph gate catches the overflow
        opt.set_lr(3e38 if i == spike_at else 1e-3)
        before.append(np.asarray(w._value).copy())
        paddle.matmul(x, w).sum().backward()
        opt.step()
        opt.clear_grad()
    guardian.flush()
    return before, w, opt


class TestStateBlowupGate:
    def test_eager_lr_spike_skips_bitwise(self):
        before, w, opt = _spike_run(fused=False)
        np.testing.assert_array_equal(before[9], before[10])   # no-op
        assert not np.array_equal(before[10], before[11])      # resumed
        assert guardian.guardian_stats()["steps_skipped"] == 1

    def test_fused_lr_spike_skips_bitwise_no_split(self):
        before, w, opt = _spike_run(fused=True)
        s = step_fusion_stats()
        assert s["fused_steps"] >= 2 and s["fallback_splits"] == 0
        np.testing.assert_array_equal(before[9], before[10])
        assert not np.array_equal(before[10], before[11])
        assert guardian.guardian_stats()["steps_skipped"] == 1

    def test_eager_and_fused_agree_bitwise(self):
        _, w_f, _ = _spike_run(fused=True)
        guardian.reset_thread_state()
        guardian.reset_guardian_stats()
        _, w_e, _ = _spike_run(fused=False)
        np.testing.assert_array_equal(np.asarray(w_f._value),
                                      np.asarray(w_e._value))

    def test_doctor_reports_which_step_skipped(self):
        for fused in (True, False):
            _reset()
            _spike_run(fused=fused)
            skips = [e for e in fusion_events("step.record")
                     if e["reason"] == "nonfinite_skip"]
            assert len(skips) == 1
            # optimizer step counter at the spike (10th step() call)
            assert skips[0]["detail"]["step"] == 10
            rep = explain()
            assert rep["guardian"]["nonfinite_skip"]["steps"] == [10]
            assert any("nonfinite_skip" in f and "at step(s) 10" in f
                       for f in rep["findings"])


# ---------------------------------------------------------------------------
# step-granular checkpoints (PR 6: save_every_n_steps)
# ---------------------------------------------------------------------------

class TestStepCheckpointer:
    def _loop(self, ck, steps, seed=0):
        rng = np.random.default_rng(seed)
        x = paddle.to_tensor(rng.standard_normal((4, 4)).astype(np.float32))
        w = paddle.to_tensor(rng.standard_normal((4, 4)).astype(np.float32),
                             stop_gradient=False)
        opt = paddle.optimizer.Momentum(learning_rate=1e-2, momentum=0.9,
                                        parameters=[w])
        for step in range(1, steps + 1):
            F.gelu(paddle.matmul(x, w)).sum().backward()
            opt.step()
            opt.clear_grad()
            ck.tick(step, model={"w": w}, optimizer=opt,
                    extra={"step": step})
        return w, opt

    def test_tick_grid_retention_and_bitwise_resume(self, tmp_path):
        ck = StepCheckpointer(str(tmp_path), save_every_n_steps=2,
                              max_checkpoints=2)
        w, opt = self._loop(ck, 6)
        # every 2nd step saved, newest 2 retained
        assert ck._retained_steps() == [4, 6]
        w2 = paddle.to_tensor(np.zeros((4, 4), np.float32),
                              stop_gradient=False)
        opt2 = paddle.optimizer.Momentum(learning_rate=1e-2, momentum=0.9,
                                         parameters=[w2])
        ck2 = StepCheckpointer(str(tmp_path), save_every_n_steps=2)
        resumed = ck2.restore(model={"w": w2}, optimizer=opt2)
        assert resumed == 6
        assert ck2.last_extra == {"step": 6}
        np.testing.assert_array_equal(np.asarray(w2._value),
                                      np.asarray(w._value))
        # optimizer step counter came back: LR schedules + step fusion
        # recording resume where the killed run stopped
        assert opt2._step_count == 6

    def test_off_grid_tick_is_a_noop(self, tmp_path):
        ck = StepCheckpointer(str(tmp_path), save_every_n_steps=100)
        assert ck.tick(7, model={}) is None
        assert ck._retained_steps() == []

    def test_restore_falls_back_past_corrupt(self, tmp_path):
        ck = StepCheckpointer(str(tmp_path), save_every_n_steps=2,
                              max_checkpoints=3)
        self._loop(ck, 6)
        newest = os.path.join(ck.checkpoint_path(6), ck.CKPT_FILE)
        with open(newest, "r+b") as f:
            f.seek(12)
            f.write(b"\xff\xff\xff")
        w2 = paddle.to_tensor(np.zeros((4, 4), np.float32),
                              stop_gradient=False)
        ck2 = StepCheckpointer(str(tmp_path), save_every_n_steps=2)
        assert ck2.restore(model={"w": w2}) == 4

    def test_refuses_when_every_snapshot_corrupt(self, tmp_path):
        ck = StepCheckpointer(str(tmp_path), save_every_n_steps=2,
                              max_checkpoints=2)
        self._loop(ck, 4)
        for s in ck._retained_steps():
            p = os.path.join(ck.checkpoint_path(s), ck.CKPT_FILE)
            with open(p, "r+b") as f:
                f.seek(12)
                f.write(b"\xff\xff\xff")
        with pytest.raises(fio.CheckpointCorruptError, match="refusing"):
            StepCheckpointer(str(tmp_path),
                             save_every_n_steps=2).restore(model={})

    def test_fresh_run_returns_minus_one(self, tmp_path):
        ck = StepCheckpointer(str(tmp_path))
        assert ck.restore(model={}) == -1


# ---------------------------------------------------------------------------
# chaos
# ---------------------------------------------------------------------------

def _load_chaos():
    spec = importlib.util.spec_from_file_location(
        "chaos", os.path.join(os.path.dirname(__file__), os.pardir,
                              "tools", "chaos.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestChaos:
    def test_nan_output_injection_skips_step(self):
        set_flags({"FLAGS_check_numerics": True,
                   "FLAGS_eager_step_fusion": False,
                   "FLAGS_eager_chain_fusion": False,
                   "FLAGS_profiler_events": True})
        clear_fusion_events()
        x, w, opt = _mk(seed=21)
        scaler = paddle.amp.GradScaler(init_loss_scaling=64.0,
                                       decr_every_n_nan_or_inf=1)
        _amp_step(x, w, opt, scaler)
        w_good = np.asarray(w._value).copy()
        inj = guardian.inject_fault("nan_output", op="matmul")
        try:
            _amp_step(x, w, opt, scaler)
        finally:
            inj.remove()
        guardian.flush()
        np.testing.assert_array_equal(w_good, np.asarray(w._value))
        stats = guardian.guardian_stats()
        assert stats["faults_injected"] == 1
        assert stats["steps_skipped"] == 1
        faults = [e for e in fusion_events("step.record")
                  if e["reason"] == "injected_fault"]
        assert len(faults) == 1

    def test_raise_injection_surfaces_and_recovers(self):
        set_flags({"FLAGS_check_numerics": True,
                   "FLAGS_eager_step_fusion": False,
                   "FLAGS_eager_chain_fusion": False})
        x, w, opt = _mk(seed=22)
        _plain_step(x, w, opt)
        w_before = np.asarray(w._value).copy()
        inj = guardian.inject_fault("raise", op="gelu")
        try:
            with pytest.raises(guardian.ChaosFault, match="injected"):
                _plain_step(x, w, opt)
        finally:
            inj.remove()
        opt.clear_grad()
        np.testing.assert_array_equal(w_before, np.asarray(w._value))
        _plain_step(x, w, opt)     # the loop keeps training
        assert not np.array_equal(w_before, np.asarray(w._value))
        assert np.all(np.isfinite(np.asarray(w._value)))

    def test_injector_after_and_times_budget(self):
        set_flags({"FLAGS_eager_chain_fusion": False,
                   "FLAGS_eager_step_fusion": False})
        x, w, opt = _mk(seed=23)
        inj = guardian.inject_fault("raise", op="matmul", after=1, times=1)
        try:
            paddle.matmul(x, w)                   # let through (after=1)
            with pytest.raises(guardian.ChaosFault):
                paddle.matmul(x, w)               # fires
            paddle.matmul(x, w)                   # disarmed (times=1)
        finally:
            inj.remove()

    def test_kill9_resume_matches_uninterrupted_run(self):
        chaos = _load_chaos()
        res = chaos.scenario_kill(epochs=3, steps=6)
        assert res["ok"], res["failures"]


class TestFusedTierFaultInjection:
    """PR 7: chaos can poison the FUSED tiers, not only raw dispatches —
    replayed chain/step ops never reach the dispatch hook, so without
    these sites the split-path recovery ladders were never exercised."""

    def test_fused_step_fault_splits_bitwise_and_recovers(self):
        """An injected fault at the fused-step fire recovers through the
        transactional per-op split: params update with the SAME values
        the eager path computes, the split is attributed
        `injected_fault`, and the next cycle replays fused again with
        zero retraces."""
        set_flags({"FLAGS_profiler_events": True})
        clear_fusion_events()
        x, w, opt = _mk(seed=31)
        for _ in range(8):
            _plain_step(x, w, opt)
        s0 = step_fusion_stats()
        assert s0["fused_steps"] > 0
        w_pre = np.asarray(w._value).copy()
        inj = guardian.inject_fault("raise", op="fused_step", times=1)
        try:
            _plain_step(x, w, opt)         # fault -> transactional split
        finally:
            inj.remove()
        s1 = step_fusion_stats()
        assert s1["fallback_splits"] == s0["fallback_splits"] + 1
        w_split = np.asarray(w._value).copy()
        _plain_step(x, w, opt)             # rejoins the fused path
        s2 = step_fusion_stats()
        assert s2["fused_steps"] > s1["fused_steps"]
        assert s2["retraces"] == s1["retraces"]
        splits = [e for e in fusion_events("step.split")
                  if e["reason"] == "injected_fault"]
        assert len(splits) == 1
        rep = explain()
        assert rep["guardian"].get("injected_fault", {}).get("count", 0) \
            >= 1
        # the split replayed through the per-op executables: its update
        # is BITWISE what an eager (unfused) step computes from the same
        # pre-split state
        set_flags({"FLAGS_eager_step_fusion": False,
                   "FLAGS_eager_chain_fusion": False})
        w2 = paddle.to_tensor(w_pre.copy(), stop_gradient=False)
        opt2 = paddle.optimizer.SGD(learning_rate=1e-2, parameters=[w2])
        _plain_step(x, w2, opt2)
        np.testing.assert_array_equal(w_split, np.asarray(w2._value))

    def test_fused_chain_nan_poison_is_detected(self):
        """Poisoning a fused CHAIN's outputs must not slip past the
        guardian: the downstream values are NaN and the flush raises,
        attributing both the injection and the non-finite output."""
        set_flags({"FLAGS_check_numerics": True,
                   "FLAGS_eager_step_fusion": False,
                   "FLAGS_profiler_events": True})
        clear_fusion_events()
        x, w, _ = _mk(seed=32)
        def fwd():
            return F.gelu(paddle.matmul(x, w)).sum()
        for _ in range(8):
            fwd().numpy()
        guardian.flush()
        inj = guardian.inject_fault("nan_output", op="fused_chain",
                                    times=1)
        try:
            y = fwd()
            assert np.isnan(y.numpy()).all()
            with pytest.raises(FloatingPointError):
                guardian.flush()
        finally:
            inj.remove()
        ev = fusion_events()
        assert any(e["reason"] == "injected_fault" for e in ev)
        assert any(e["reason"] == "nonfinite_output" for e in ev)

    def test_fused_chain_raise_splits_to_clean_values(self):
        """kind="raise" on the fused chain falls back per-op: the caller
        sees bitwise-clean values and a `chain.split` attributed
        `injected_fault` — never an exception, never NaN."""
        set_flags({"FLAGS_eager_step_fusion": False,
                   "FLAGS_profiler_events": True})
        clear_fusion_events()
        x, w, _ = _mk(seed=33)
        def fwd():
            return F.gelu(paddle.matmul(x, w)).sum()
        ref = None
        for _ in range(8):
            ref = fwd().numpy()
        inj = guardian.inject_fault("raise", op="fused_chain", times=1)
        try:
            val = fwd().numpy()
        finally:
            inj.remove()
        np.testing.assert_array_equal(ref, val)
        splits = [e for e in fusion_events("chain.split")
                  if e["reason"] == "injected_fault"]
        assert len(splits) == 1
