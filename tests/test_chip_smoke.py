"""Rehearsals of chip_smoke.py without the chip.

The script's phases at a tiny size on the CPU with the Pallas kernels in
the interpreter, its `--chips 4` phase on four of conftest's virtual
devices, and the script itself failing when JAX finds no TPU. These find
wrong paths, arguments, meshes and control flow at no chip time; what only
the chip's compiler can refuse is in tests/test_tpu_compile.py. None of
this is a chip run and nothing here reports a device number.
"""
import os
import subprocess
import sys
import warnings

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from paddle_tpu.framework.flags import get_flags, set_flags  # noqa: E402
from paddle_tpu.incubate.models import GPTConfig  # noqa: E402

SEQ = 128
# two 64-wide heads per layer: head_dim 64, which the flash kernel takes,
# and a row of 128, one whole lane tile, which the paged kernel takes
TINY = GPTConfig(vocab_size=512, hidden_size=128, num_hidden_layers=2,
                 num_attention_heads=2, intermediate_size=128,
                 max_position_embeddings=SEQ, hidden_dropout_prob=0.0,
                 attention_probs_dropout_prob=0.0)


@pytest.fixture
def kernels_interpreted(monkeypatch):
    """Steer the kernels down their TPU branches and run them in the Pallas
    interpreter — here in the test, since the program has no such mode.
    `interpret=True` lowers a kernel to plain JAX ops;
    `pltpu.force_tpu_interpret_mode()` would simulate the chip through host
    callbacks, which deadlock against eager dispatch on the CPU."""
    from jax.experimental import pallas as pl
    from paddle_tpu.kernels import (cross_entropy, flash_attention,
                                    fused_ln)
    from paddle_tpu.kernels.pallas import paged_attention
    for mod in (cross_entropy, flash_attention, fused_ln, paged_attention):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    # the flash path only pays from 1024 tokens up; let it take the
    # rehearsal's 128
    monkeypatch.setattr(flash_attention, "FLASH_MIN_SEQ", SEQ)
    pallas_call = pl.pallas_call
    monkeypatch.setattr(
        pl, "pallas_call",
        lambda *args, **kw: pallas_call(*args, **{**kw, "interpret": True}))


@pytest.fixture
def promote_early():
    """The fusion stack as a fresh process has it (earlier tests of the
    same worker may have left it off, or full of their own programs),
    promoting after 3 cycles instead of 40."""
    from paddle_tpu.ops.dispatch import clear_dispatch_cache
    flags = {"FLAGS_eager_op_cache": True, "FLAGS_eager_op_cache_size": 512,
             "FLAGS_check_nan_inf": False, "FLAGS_benchmark": False,
             "FLAGS_eager_chain_fusion": True,
             "FLAGS_eager_chain_stitching": True,
             "FLAGS_eager_step_fusion": True,
             "FLAGS_eager_chain_fusion_min_count": 3,
             "FLAGS_eager_step_fusion_min_count": 3}
    prev = get_flags(list(flags))
    set_flags(flags)
    clear_dispatch_cache()
    yield 3
    set_flags(prev)
    clear_dispatch_cache()


def run_python(*args, cwd=ROOT, **env):
    """A child Python that sees no accelerator."""
    return subprocess.run([sys.executable, *args], cwd=cwd,
                          env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
                          capture_output=True, text=True, timeout=120)


class TestNoChip:
    def test_script_fails_and_prints_no_result(self):
        res = run_python("chip_smoke.py")
        assert res.returncode != 0
        assert '"ok"' not in res.stdout
        assert "needs a TPU" in res.stderr

    def test_script_alone_fails(self, tmp_path):
        """In a directory that holds chip_smoke.py and nothing else of the
        repo there is no program to drive."""
        with open(os.path.join(ROOT, "chip_smoke.py")) as src:
            (tmp_path / "chip_smoke.py").write_text(src.read())
        elsewhere = [p for p in sys.path if os.path.abspath(p or ".") != ROOT]
        res = run_python("chip_smoke.py", cwd=tmp_path,
                         PYTHONPATH=os.pathsep.join(elsewhere))
        assert res.returncode != 0
        assert '"ok"' not in res.stdout

    @pytest.mark.parametrize("path", ["chip_smoke.py",
                                      "paddle_tpu/framework/compile_cache.py",
                                      "paddle_tpu/profiler/goodput.py",
                                      "paddle_tpu/kernels/_common.py"])
    def test_measurement_paths_hold_no_downgrade(self, path):
        with open(os.path.join(ROOT, path)) as f:
            text = f.read()
        for banned in ('setdefault("JAX_PLATFORMS"', "cpu_retry",
                       "with_retry", "_clear_backends", "conservative default",
                       '"jax_platforms", "cpu"', "--probe"):
            assert banned not in text, f"{path} still holds {banned!r}"

    def test_import_initialises_no_backend(self):
        """A parent that has touched JAX holds the chip, so the launcher
        parent (distributed/launch/main.py) and every tool that only
        imports the package must leave the backend table empty."""
        code = ("import paddle_tpu, paddle_tpu.distributed.launch.main\n"
                "from jax._src import xla_bridge\n"
                "assert not xla_bridge.backends_are_initialized()\n")
        res = run_python("-c", code)
        assert res.returncode == 0, res.stderr[-2000:]


class TestCompileCache:
    def test_environment_places_the_cache(self, monkeypatch):
        """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself and no
        code sets another directory."""
        from paddle_tpu.framework.compile_cache import enable_compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/from/outside")
        before = jax.config.jax_compilation_cache_dir
        assert enable_compile_cache() == "/from/outside"
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_one_fixed_directory_in_the_checkout(self,
                                                            monkeypatch):
        from paddle_tpu.framework.compile_cache import enable_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            want = os.path.join(ROOT, ".cache", "jax")
            assert enable_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
        finally:
            jax.config.update("jax_compilation_cache_dir", before)

    def test_native_library_builds_inside_the_checkout(self):
        from paddle_tpu.core import _build
        assert _build._cache_dir() == os.path.join(ROOT, ".cache", "native")


class TestOneChipRehearsal:
    def test_device_record(self):
        rec = chip_smoke.device_phase("/somewhere")
        assert rec["platform"] == "cpu" and rec["count"] == 8
        assert rec["jax"] == jax.__version__
        assert rec["compile_cache"] == "/somewhere"

    def test_train_step(self, kernels_interpreted):
        rec = chip_smoke.train_step_phase(TINY, batch=2, seq=SEQ, steps=3,
                                          seed=0)
        assert rec["phase"] == "train_step"
        assert len(rec["losses"]) == 3
        assert rec["losses"][-1] < rec["losses"][0]
        assert rec["compile_s"] > 0
        # interpreted kernels are plain HLO; the chip run counts these
        assert rec["tpu_custom_calls"] == 0

    def test_train_eager_promotes(self, kernels_interpreted, promote_early):
        rec = chip_smoke.train_eager_phase(TINY, batch=2, seq=SEQ,
                                           cycles=promote_early + 4, seed=0)
        assert rec["steps_promoted"] >= 1 and rec["fused_steps"] >= 3
        assert rec["events"]["step.promote"] >= 1
        assert rec["losses"][1] < rec["losses"][0]

    def test_serve_both_variants(self, kernels_interpreted):
        recs = chip_smoke.serve_phase(TINY, [8, 13, 16, 57],
                                      max_new_tokens=6, seed=0)
        assert [r["attention_kernel"] for r in recs] == ["blockwise",
                                                         "pallas"]
        for r in recs:
            assert r["requests"] == 4 and r["tokens"] == 24
            assert r["decode_compiles"] == 1
        assert recs[-1]["worst_logit_gap"] <= recs[-1]["logit_gap_slack"]

    def test_serve_latent_both_variants(self, kernels_interpreted):
        """The latent legs at a tiny size: a row of 40 + 8 values padded
        to one lane tile, pages of 16 tokens, every expert chosen."""
        sizes = dict(chip_smoke.LATENT_SMOKE, vocab_size=128,
                     hidden_size=64, ffn_hidden_size=64,
                     expert_ffn_hidden_size=32, num_attention_heads=2,
                     q_lora_rank=32, kv_lora_rank=40, qk_nope_head_dim=16,
                     qk_rope_head_dim=8, v_head_dim=16,
                     max_position_embeddings=64)
        recs = chip_smoke.latent_serve_phase(sizes, [8, 13, 16, 41],
                                             max_new_tokens=6, seed=0)
        assert [(r["phase"], r["attention_kernel"]) for r in recs] == [
            ("serve_latent", "blockwise"), ("serve_latent", "pallas")]
        for r in recs:
            assert r["requests"] == 4 and r["tokens"] == 24
            assert r["decode_compiles"] == 1
        assert recs[-1]["worst_logit_gap"] <= recs[-1]["logit_gap_slack"]

    def test_serve_over_a_state_of_two_parts(self, kernels_interpreted,
                                             monkeypatch):
        """The state legs at a tiny size: one softmax layer and three
        delta-rule layers, heads of 128 x 128 float32 (the update's kernel
        in the interpreter, as on the chip), every expert chosen."""
        from paddle_tpu.kernels import kda
        monkeypatch.setattr(kda, "on_tpu", lambda: True)
        sizes = dict(chip_smoke.STATE_SMOKE, vocab_size=128, hidden_size=64,
                     moe_intermediate_size=32, num_attention_heads=2,
                     num_key_value_heads=1, max_position_embeddings=64)
        assert kda.update_form(8, 128, 128) == "pallas"
        recs = chip_smoke.state_serve_phase(sizes, [8, 13, 16, 41],
                                            max_new_tokens=6, seed=0)
        assert [(r["phase"], r["attention_kernel"]) for r in recs] == [
            ("serve_state", "blockwise"), ("serve_state", "pallas")]
        for r in recs:
            assert r["requests"] == 4 and r["tokens"] == 24
            assert r["decode_compiles"] == 1
        assert recs[-1]["worst_logit_gap"] <= recs[-1]["logit_gap_slack"]

    def test_grouped_matmul_leg(self, monkeypatch):
        """The leg at a tiny size, the kernel in the interpreter: uneven
        groups, an idle expert, a poisoned tail. A kernel that lets the
        tail into a live row fails it."""
        from paddle_tpu.kernels.pallas import grouped_matmul as gm
        rec = chip_smoke.grouped_matmul_phase(128, 128, 256, 8, seed=0,
                                              interpret=True)
        assert rec["phase"] == "grouped_matmul"
        assert rec["live_rows"] == 112 and rec["groups"] == 8
        assert 0.0 <= rec["max_abs_gap"] <= 1e-4 * rec["max_abs"]
        assert not any("ms" in key or "seconds" in key for key in rec)
        kernel = gm.grouped_matmul
        # a kernel whose rows are not kept apart: the last row in every one
        monkeypatch.setattr(
            gm, "grouped_matmul", lambda a, w, load, interpret=False:
            kernel(a, w, load, interpret=interpret)
            + a[-1:, :1].astype("float32"))
        with pytest.raises(chip_smoke.SmokeFailure, match="poisoned"):
            chip_smoke.grouped_matmul_phase(128, 128, 256, 8, seed=0,
                                            interpret=True)

    def test_demoted_kernel_fails_the_serve_phase(self):
        """Off the chip (and not steered) `pallas` demotes to `blockwise`
        with a kernel.fallback event: legitimate in production, a failure
        here."""
        model = chip_smoke.make_model(TINY, seed=0)
        with pytest.raises(chip_smoke.SmokeFailure, match="engine runs"):
            chip_smoke.serve_requests(model, [[1, 2, 3]], 2, "pallas")

    def test_wrong_token_fails_the_reference_check(self):
        model = chip_smoke.make_model(TINY, seed=0)
        prompt = [5, 6, 7, 8]
        import jax.numpy as jnp
        good = np.asarray(model.generate(
            jnp.asarray([prompt], jnp.int32), max_new_tokens=4,
            do_sample=False)._value)[0].tolist()
        gaps, scale = chip_smoke.greedy_gaps(model, [prompt], [good], SEQ)
        assert gaps[0] <= 4 * chip_smoke.BF16_EPS * scale
        bad = list(good)
        bad[2] = (bad[2] + 1) % TINY.vocab_size
        gaps, _ = chip_smoke.greedy_gaps(model, [prompt], [bad], SEQ)
        assert gaps[0] > 4 * chip_smoke.BF16_EPS * scale

    def test_unused_donation_is_a_failure(self):
        with pytest.raises(chip_smoke.SmokeFailure, match="donation"):
            with chip_smoke.donation_honoured():
                warnings.warn("Some donated buffers were not usable: f32[4]")


class TestFourChipRehearsal:
    def test_mesh_and_control_agree(self, kernels_interpreted, monkeypatch):
        from paddle_tpu.nn.functional import attention
        # two heads, so that mp=2 has a head for each model shard
        cfg = GPTConfig(**{**vars(TINY), "hidden_size": 128,
                           "num_attention_heads": 2})
        meshes = []
        run_flash = attention._run_flash
        monkeypatch.setattr(
            attention, "_run_flash",
            lambda *a: (meshes.append(a[-1]), run_flash(*a))[1])
        devices = jax.devices()[:4]
        sizes = dict(batch=4, seq=SEQ, steps=2, seed=0)
        mesh = chip_smoke.mesh_phase(cfg, devices=devices, **sizes)
        control = chip_smoke.train_step_phase(
            cfg, donate=True, device=devices[-1], phase="mesh_control",
            **sizes)
        chip_smoke.losses_agree(mesh, control)
        # the flash kernel ran per shard under the mesh (a Mosaic kernel
        # cannot be partitioned automatically) and as is in the control
        assert meshes[0] is not None and meshes[-1] is None
        assert mesh["mesh"] == {"data": 2, "model": 2}
        assert mesh["collectives"]["all-reduce"] > 0
        # every device holds about half the state, before and after
        for held in (mesh["state_bytes_per_device_before"],
                     mesh["state_bytes_per_device_after"]):
            assert len(held) == 4 and min(held) > 0
            assert max(held) < 0.75 * mesh["state_bytes"]

    def test_parting_losses_fail(self):
        with pytest.raises(chip_smoke.SmokeFailure, match="losses part"):
            chip_smoke.losses_agree({"losses": [10.9, 10.8, 10.7]},
                                    {"losses": [10.9, 10.5, 10.7]})
