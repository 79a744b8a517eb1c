"""JoyAI-LLM-Flash through the harness on the CPU, at a tiny size
(`joyai_model/tiny_joyai.py`): its cell runs on the `train` loop beside the
tiny GPT cells without an edit to any benchmark file, and is correct; a
step that leaves the MTP term out, and the reference computed in the fp8
control, are not; the configuration keeps every published number and cuts
only counts held; the reference reads the router's width and the held
count; the counts are the hand counts; the readers read the step's own
counters and fall silent on a program that has none."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "joyai_model")]

import config_rules  # noqa: E402
import tiny_root  # noqa: E402
from tiny_joyai import TINY_JOYAI  # noqa: E402

from benchmark import correct, harness, joyai_counts, \
    run as bench_run  # noqa: E402
from benchmark.loops import train as train_loop  # noqa: E402
from benchmark.programs import paddle_train_stats  # noqa: E402
from benchmark.readers import joyai_kernel_roofline, joyai_step_mfu, \
    train_step_stat_ratio  # noqa: E402
from benchmark.reference import joyai_llm_flash as ref  # noqa: E402

REPO = tiny_root.REPO
# from readings on the CPU over seeds 3, 5, 2**31 + 7 and 3000028201 (three
# steps of 4 x 32 tokens): the program (bfloat16 weights and activations)
# reads loss_gap <= 0.00055, grad_norm_gap <= 0.034, delta_norm_gap <=
# 0.012; the fp8 control 0.0005-0.0022, 0.082-0.209, 0.021-0.045; the step
# without its MTP term 0.23, 1.0, 0.998. At this size only the gradient's
# norm parts the control from the program (the limit lies between 0.034 and
# 0.082); the other two guard against gross faults
LIMITS = {"loss_gap": 0.005, "grad_norm_gap": 0.06, "delta_norm_gap": 0.05}
MIX = dict(tiny_root.TRAFFIC["tiny_train"])
SPEC = tiny_root.spec_of("as_it_stands")
# the cell's metrics are every entry whose `workloads` names it: its own
# (`joyai.*`) and the train loop's shared ones (`train.*`; ISSUE 47)
JOYAI_METRICS = [m["name"] for m in SPEC["per_layer"]
                 if "train_joyai_mtp_4k" in m["workloads"]]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture
def joyai_root(root):
    tiny_root.add_cell(root, "joyai_cell", ("tiny_joyai", TINY_JOYAI),
                       ("joyai_mix", MIX), LIMITS,
                       ("train_tokens_per_s", *JOYAI_METRICS))
    return root


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 7])
def test_the_tiny_cell_runs_on_the_train_loop_and_is_correct(joyai_root,
                                                             seed):
    line = bench_run.run_cell(joyai_root, "joyai_cell", seed, 1.0, False,
                              require_chip=False)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(line["checks"]) == set(LIMITS)


def test_a_step_without_its_mtp_term_is_not_correct(joyai_root):
    """The broken program of `joyai_model/no_mtp_program.py`: every number
    compared is off (the loss by a quarter)."""
    tiny_root.add_cell(
        joyai_root, "no_mtp_cell",
        ("tiny_no_mtp", dict(TINY_JOYAI, program="no_mtp_program")),
        ("no_mtp_mix", MIX), LIMITS, ("train_tokens_per_s",))
    line = bench_run.run_cell(joyai_root, "no_mtp_cell", 3, 1.0, False,
                              require_chip=False)
    assert not line["correct"]
    assert line["checks"]["loss_gap"]["value"] > 0.1


@pytest.mark.parametrize("seed", [3, 3000028201])
def test_the_fp8_control_in_the_programs_place_is_not_correct(joyai_root,
                                                              seed):
    """The reference computed in the precision below the stated one, held
    to the cell's limits by the harness's own comparison: it fails by the
    gradient's norm."""
    import jax
    run = harness.Run(joyai_root, "joyai_cell", seed, 0.3, False,
                      require_chip=False)
    devices = jax.devices()[:1]
    want = correct.reference_train(TINY_JOYAI, MIX, seed, devices,
                                   train_loop.CHECKED_STEPS)
    low = correct.reference_train(TINY_JOYAI, MIX, seed, devices,
                                  train_loop.CHECKED_STEPS,
                                  TINY_JOYAI["precision"]["control"])
    numbers = correct.train_numbers(low, want)
    numbers.pop("leaves")
    verdicts = {name: run.check(name, value)
                for name, value in numbers.items()}
    assert not verdicts["grad_norm_gap"]
    assert not (bool(run.checks) and all(c[3] for c in run.checks))


def test_a_traced_run_reports_every_metric_of_the_cell(joyai_root,
                                                       monkeypatch):
    """Every metric of the cell appears, finite, with a canned device trace
    (the CPU gives the profiler no device plane) and canned peaks; a share
    stays inside 0..100 and nothing was dropped."""
    import contextlib

    @contextlib.contextmanager
    def traced_slice(self):
        yield
        self.evidence["trace"] = {
            "window_s": 1.0, "devices": 1, "busy_s": 0.9,
            "collective_s": 0.0, "collective_exposed_s": 0.0,
            "op_seconds": {"%flash_attention_fwd.1 = custom-call()": 0.2,
                           "%ragged-dot.3 = f32[] ragged-dot()": 0.1},
            "op_counts": {"%flash_attention_fwd.1 = custom-call()": 8,
                          "%ragged-dot.3 = f32[] ragged-dot()": 9},
            "gaps": [], "spans": []}
        self.evidence["peaks"] = PEAKS
    monkeypatch.setattr(harness.Run, "traced_slice", traced_slice)
    line = bench_run.run_cell(joyai_root, "joyai_cell", 5, 1.0, True,
                              require_chip=False)
    # the CPU's backend reports no memory peak, but a train cell's peak
    # holds the compiler's temporaries, so that reader reads too
    assert set(line["metrics"]) == set(JOYAI_METRICS)
    values = {k: m["value"] for k, m in line["metrics"].items()}
    assert all(np.isfinite(v) for v in values.values()), values
    for name in ("joyai.step_mfu", "joyai.flash_attn_roofline",
                 "joyai.expert_products_roofline",
                 "joyai.flash_attn_time_share",
                 "joyai.expert_products_time_share",
                 "joyai.held_choice_share", "joyai.mtp_loss_share"):
        assert 0 < values[name] <= 100, name
    assert values["joyai.dropped_assignments"] == 0
    assert values["train.step_compiles"] == 1
    # 4 of 16 ranked experts held; a largest load is at least the mean
    assert 10 < values["joyai.held_choice_share"] < 45
    assert values["joyai.expert_load_max_over_mean"] >= 1
    assert 40 < values["joyai.mtp_loss_share"] < 60


# -- the configuration and the reference's contract ----------------------

def _entry():
    return next(c for c in SPEC["configs"]
                if c["name"] == "joyai_llm_flash_share")


def _file():
    return json.load(open(os.path.join(REPO, _entry()["file"])))


def test_the_configuration_keeps_every_published_number():
    entry, cfg = _entry(), _file()
    assert config_rules.problems(entry, cfg) == []
    catalog = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 129280}
    for key, value in catalog.items():
        if key in entry["reduced"]:
            assert cfg["published"][key] == value and cfg[key] < value
        else:
            assert cfg[key] == value, key
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    # the leading dense layer and 4 expert layers; the guide's floors
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (5, 16160)
    assert cfg["n_routed_experts"] in (8, 16)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["precision"]["control"] == "fp8" and cfg["optimizer"]
    # the entry's source is the catalog's `source_url`, letter for letter;
    # the file's goes on to name where the layer equations come from
    assert entry["source"] == ("https://huggingface.co/jdopensource/"
                               "JoyAI-LLM-Flash/blob/main/config.json")
    assert cfg["source"].startswith(entry["source"] + "; ")
    cell = next(w for w in SPEC["workloads"]
                if w["name"] == "train_joyai_mtp_4k")
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("joyai_llm_flash_share", "train_4x4096", 1)
    mix = json.load(open(os.path.join(REPO, "benchmark", "traffic",
                                      "train_4x4096.json")))
    assert (mix["batch_rows"], mix["seq"], mix["donate"],
            mix["reference_rows_per_block"]) == (4, 4096, "all", 1)


@pytest.mark.parametrize("key", ["num_experts_per_tok", "kv_lora_rank",
                                 "moe_intermediate_size", "qk_head_dim"])
def test_the_rules_refuse_a_cut_of_what_is_no_count_held(key):
    entry, cfg = _entry(), _file()
    cut = dict(entry, reduced=entry["reduced"] + [key])
    found = config_rules.problems(cut, dict(cfg, changed=dict(
        cfg["changed"], **{key: "cut"})))
    assert any(f"names {key}: a width, or no kind" in f for f in found)


@pytest.mark.parametrize("held,millions", [(16, 680.4), (8, 491.7)])
def test_the_reference_meets_the_contract(held, millions):
    """Shapes from the router's PUBLISHED width and the count held; the
    count of parameters is the issue's arithmetic; the names are the
    program's, in its order; nothing of the program is imported."""
    from paddle_tpu.incubate.models import joyai_llm_flash as jy
    from benchmark.programs import paddle_joyai
    cfg = dict(_file(), n_routed_experts=held)
    shapes = ref.param_shapes(cfg)
    p = "model.layers.1.mlp."
    assert shapes[p + "gate.weight"] == (2048, 256)
    assert shapes[p + "experts.down_proj.weight"] == (held, 768, 2048)
    assert shapes["model.layers.5.eh_proj.weight"] == (4096, 2048)
    assert shapes["lm_head.weight"] == (2048, 16160)
    assert "model.layers.0.mlp.gate.weight" not in shapes      # dense
    assert ref.bias_name(1) not in shapes
    assert round(ref.num_params(cfg) / 1e6, 1) == millions
    program = jy.param_shapes(paddle_joyai._model_config(cfg))
    assert list(program) == list(shapes)
    assert {k: tuple(v) for k, v in program.items()} == shapes
    assert not any("paddle" in line for line in open(ref.__file__)
                   if line.startswith(("import", "from")))
    for name in ("forward", "loss_and_grads", "param_shapes", "num_params"):
        assert callable(getattr(ref, name))


def test_a_switch_the_program_does_not_compute_is_refused_by_name():
    from benchmark.programs import paddle_joyai
    with pytest.raises(ValueError, match="scoring_func"):
        paddle_joyai._model_config(dict(TINY_JOYAI, scoring_func="softmax"))


# -- the counts and the readers ------------------------------------------

def test_the_counts_are_the_hand_counts_at_the_tiny_size():
    """d 32, heads 4 x (8 + 4 | 6), ranks 16 and 8, dense 64, experts of
    16, router 16 wide, vocabulary 96; a dense block, 2 expert blocks and
    the MTP module's."""
    cfg = TINY_JOYAI
    attention = 32 * 16 + 16 * 4 * 12 + 32 * (8 + 4) + 8 * 4 * 14 + 24 * 32
    assert joyai_counts.attention_params(cfg) == attention == 2880
    assert joyai_counts.dense_ffn_params(cfg) == 3 * 32 * 64
    assert joyai_counts.expert_params(cfg) == 3 * 32 * 16
    assert joyai_counts.router_params(cfg) == 32 * 16
    assert joyai_counts.blocks(cfg) == (1, 3)
    every = 4 * attention + 3 * 32 * 64 + 3 * (32 * 16 + 3 * 32 * 16) \
        + 2 * 32 * 32 + 2 * 32 * 96
    assert joyai_counts.token_params(cfg) == every
    # attention at 12 | 6 over the causal half of 32 tokens, four blocks
    assert joyai_counts.attention_flops_per_token(cfg, 32) == \
        4 * 3 * 2 * 4 * (12 + 6) * 32 / 2
    assert joyai_counts.train_flops_per_token(cfg, 32, 1.5) == \
        6 * every + 6 * 1.5 * 3 * 32 * 16 + 4 * 3 * 2 * 4 * 18 * 16
    ops, moved = joyai_counts.flash_attention_train(4, 32, 4096, 192, 128)
    assert ops == 3 * 2 * 4 * 32 * 4096 * 4096 * 320 / 2
    assert moved == 6 * 4 * 4096 * 32 * 320 * 2
    ops, moved = joyai_counts.expert_products_train(cfg, 100, 3)
    assert ops == 9 * 2 * 32 * 16 * 100
    assert moved == 2 * (3 * 3 * 4 * 3 * 32 * 16 + 2 * 100 * (64 + 32))


def test_the_issues_arithmetic_at_the_cells_size():
    """MFLOP a token forward (issue 35): MLA's projections 53 and scores
    42 a block; the dense FFN 88; the shared expert and router 10.5; the
    head 66; the MTP projection 17."""
    cfg = _file()
    assert round(2 * joyai_counts.attention_params(cfg) / 1e6) == 53
    assert round(joyai_counts.attention_flops_per_token(cfg, 4096)
                 / 6 / 3 / 1e6) == 42
    assert round(2 * joyai_counts.dense_ffn_params(cfg) / 1e6) == 88
    assert round(2 * (joyai_counts.expert_params(cfg)
                      + joyai_counts.router_params(cfg)) / 1e6, 1) == 10.5
    assert round(2 * 2048 * 16160 / 1e6) == 66
    forward = 2 * joyai_counts.token_params(cfg) \
        + joyai_counts.attention_flops_per_token(cfg, 4096) / 3
    assert 0.80e9 < forward < 0.90e9


def _evidence(traced=True):
    ev = {"config": _file(), "peaks": PEAKS, "chips": 1,
          "window_tokens_per_s": 30000.0,
          "traffic": {"batch_rows": 4, "seq": 4096}}
    if traced:
        ev.update(traced_steps=10, trace={
            "devices": 1, "busy_s": 5.0,
            "op_seconds": {"%checkpoint_flash_attention_fwd.2 = x": 1.0,
                           "%flash_attention_dkv.7 = x": 1.5,
                           "%ragged-dot.1 = x": 0.2, "%fusion.9 = x": 2.0},
            "op_counts": {"%checkpoint_flash_attention_fwd.2 = x": 120,
                          "%flash_attention_dkv.7 = x": 60,
                          "%ragged-dot.1 = x": 450, "%fusion.9 = x": 5}})
    return ev


def test_the_readers_follow_the_steps_own_counters(monkeypatch):
    stats = {"routed_held": 20000.0, "routed_identity": 0.0,
             "routed_elsewhere": 635360.0, "routed_computed": 20000.0,
             "load_max": 3000.0, "loss_main": 9.7, "loss_mtp": 9.9,
             "first_step_routed_held": 21000.0,
             "first_step_routed_identity": 0.0,
             "first_step_routed_elsewhere": 634360.0}
    monkeypatch.setattr(paddle_train_stats, "newest_train_step_stats",
                        lambda: stats)
    cfg = _file()
    mfu = joyai_step_mfu.read(_evidence())
    assert mfu == pytest.approx(100 * joyai_counts.train_flops_per_token(
        cfg, 4096, 20000 / 16384) * 30000 / 197e12)
    assert 0 < mfu < 100
    flash = joyai_kernel_roofline.read(
        _evidence(), pattern="flash_attention_(fwd|dq|dkv)", kernel="flash")
    ops, _ = joyai_counts.flash_attention_train(4, 32, 4096, 192, 128)
    assert flash == pytest.approx(100 * 6 * ops / 197e12 * 10 / 2.5)
    experts = joyai_kernel_roofline.read(_evidence(), pattern="ragged",
                                         kernel="experts")
    assert 0 < experts < 100
    ratio = train_step_stat_ratio.read
    # the held share is read at the FIRST step, as the metric's file says
    share = json.load(open(os.path.join(
        REPO, "benchmark", "metrics", "joyai.held_choice_share.json")))
    assert ratio(_evidence(), **share["args"]) == pytest.approx(
        100 * 21000 / 655360)
    assert ratio(_evidence(), over=["load_max"], under=["routed_held"],
                 scale_by=["n_routed_experts"]) == pytest.approx(
        3000 * cfg["n_routed_experts"] / 20000)
    assert ratio(_evidence(), over=["routed_held"],
                 less=["routed_computed"]) == 0
    assert ratio(_evidence(), over=["loss_mtp"],
                 under=["loss_main", "loss_mtp"], scale=100.0) \
        == pytest.approx(100 * 9.9 / 19.6)


def test_a_program_without_the_counters_leaves_the_metrics_out(monkeypatch):
    """What the parent commit's `TrainStep` reports (phases and compiles,
    no counter of a model's), and a process with no `TrainStep` at all:
    each reader returns nothing and does not raise."""
    for stats in ({"steps": 5, "compiles": 1, "call_p50_ms": 3.0}, None):
        monkeypatch.setattr(paddle_train_stats, "newest_train_step_stats",
                            lambda: stats)
        assert joyai_step_mfu.read(_evidence()) is None
        assert joyai_kernel_roofline.read(_evidence(), pattern="ragged",
                                          kernel="experts") is None
        assert train_step_stat_ratio.read(
            _evidence(), over=["routed_held"],
            less=["routed_computed"]) is None
    # no trace, or a trace in which the kernel does not appear
    assert joyai_kernel_roofline.read(
        _evidence(traced=False), pattern="flash", kernel="flash") is None
    assert joyai_kernel_roofline.read(_evidence(), pattern="no_such_kernel",
                                      kernel="flash") is None
