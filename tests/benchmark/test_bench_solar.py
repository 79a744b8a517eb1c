"""The Solar-Open2 family through the benchmark, on the CPU at a tiny size
(`solar_model/tiny_solar.py`): its cell runs on the `serve_backlog` loop
with the REAL program and reference modules and is correct; served in the
fp8 control it is not, by both gaps; served with the steps not doubled or
with a matrix state no launch writes it is not; the real configuration file
keeps every published number, passes the rules, and states the publisher's
list of softmax layers in exact translation; the traffic file holds the
issue's parameters; the counts and the readers of its per-layer metrics."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "solar_model")]
import config_rules  # noqa: E402
import tiny_root  # noqa: E402
from tiny_solar import TINY_SOLAR  # noqa: E402

from benchmark import correct, harness, run as bench_run, seeded, \
    solar_counts, traffic  # noqa: E402
from benchmark.loops import serving  # noqa: E402
from benchmark.readers import solar_decode_hbm_roofline, \
    solar_kernel_roofline, solar_serve_mfu  # noqa: E402
from benchmark.reference import solar_open2 as ref  # noqa: E402

REPO = tiny_root.REPO
CELL, CONFIG = "serve_solar_longdoc_16k", "solar_open2_250b_ep8"
# from readings on the CPU over seeds 3, 5, 2**31 + 7 and 3000048201 (16
# or 12 requests a sample, some 100-190 served tokens; bfloat16 weights and
# activations at a hidden size of 64, where a rounding flips the router's
# k-th choice of 64 often): the program through the engine reads a widest
# gap 0.12-1.44 and a mean 0.004-0.046, a bfloat16 stand-in 0.12-0.89 and
# 0.002-0.020; the fp8 control's widest 2.27-4.33 and mean 0.48-0.64; the
# steps not doubled (`kda_allow_neg_eigval` left out) 2.66-2.77 and
# 0.46-0.63, a matrix state no launch updates 3.0-4.1 and 1.09-1.21. Each
# limit lies three times over the sound largest and under the smallest of
# the control's and the broken programs'. (A matrix state rounded to
# bfloat16 reads 0.0075 where float32 reads 0.0068: beside bfloat16
# ACTIVATIONS it is not to be told apart here; tests/test_solar_open2.py
# tells it apart in float32.)
LIMITS = {"logit_gap": 2.0, "logit_gap_mean": 0.15}
STREAMS, PROMPT, SERVED, PAD = 8, 8, 24, 32
SPEC = tiny_root.spec_of("as_it_stands")
SOLAR_METRICS = [m["name"] for m in SPEC["per_layer"]
                 if CELL in m["workloads"]]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture
def solar_root(root):
    tiny_root.add_cell(
        root, "solar_cell", ("tiny_solar", TINY_SOLAR),
        # 16 requests a sample: over 100 served tokens, so that a mean
        # gap is a mean
        ("solar_mix", dict(tiny_root.TRAFFIC["tiny_backlog"],
                           reference_pad_to=PAD, checked_requests=16)),
        LIMITS, ("serve_tokens_per_s", *SOLAR_METRICS))
    return root


def real_file():
    entry = next(c for c in SPEC["configs"] if c["name"] == CONFIG)
    return entry, json.load(open(os.path.join(REPO, entry["file"])))


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 7])
def test_the_tiny_cell_runs_on_the_backlog_loop_and_is_correct(solar_root,
                                                               seed):
    line = bench_run.run_cell(solar_root, "solar_cell", seed, 1.0, False,
                              require_chip=False)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert set(line["checks"]) == set(serving.COMPARED)


# the outer loop of a prompt's chunked scan, as the device trace names it:
# a `while` whose result tuple holds the stacked outputs [spans, chunks, 1,
# heads, chunk, head width] (the real cell's: 64 heads of 128, chunks of 64)
SCAN = "%while.9 = (u32[]{:T(128)}, u32[]{:T(128)}, f32[1,64,128,128]" \
    "{3,2,1,0:T(8,128)}, f32[32,8,1,64,64,128]{5,4,3,1,2,0:T(8,128)}, " \
    "bf16[32,1,64,8,64,128]{5,3,4,2,1,0}) while((u32[]{:T(128)}) %tuple.3)"
# the loop over a span's chunks inside it, and a loop of the expert block:
# neither is the scan's
INNER = "%while.10 = (u32[]{:T(128)}, u32[]{:T(128)}, f32[1,64,128,128]" \
    "{3,2,1,0}, f32[8,1,64,64,128]{4,3,2,1,0}) while((u32[]) %tuple.4)"
OTHER = "%while.4 = (s32[]{:T(128)}, f32[16384,4096]{1,0}) while((s32[]) %t)"


def canned(evidence, ops):
    evidence["trace"] = {
        "window_s": 1.0, "devices": 1, "busy_s": 0.9,
        "collective_s": 0.0, "collective_exposed_s": 0.0,
        "op_seconds": {k: v[0] for k, v in ops.items()},
        "op_counts": {k: v[1] for k, v in ops.items()},
        "gaps": [], "spans": []}
    evidence["peaks"] = PEAKS


def test_a_traced_run_reports_every_metric_of_the_cell(solar_root,
                                                       monkeypatch):
    """Every metric of the cell appears, finite, with a canned device trace
    (the CPU gives the profiler no device plane) and canned peaks; a share
    of a peak stays inside 0..100. The tiny model's 8 held of top 4 take
    the grouped products, which the CPU runs through `ragged_dot` (no
    kernel product)."""
    import contextlib

    @contextlib.contextmanager
    def traced_slice(self):
        yield
        canned(self.evidence, {
            SCAN: (0.2, 12), INNER: (0.05, 96), OTHER: (0.01, 4),
            "%kda_decode_step.3 = (f32[4,4,16]) custom-call(...)": (0.06, 90),
            "%paged_decode_attention.4 = bf16[4,16,8] custom-call(...)":
                (0.05, 60),
            "%flash_attention_fwd.2 = bf16[8,16,8] custom-call(...)":
                (0.03, 10),
            "%ragged-dot-none.7 = f32[32,32] custom-call(...)": (0.09, 300),
            "%fusion.9 = f32[4] fusion(%kda_decode_step.3)": (0.2, 10)})
    monkeypatch.setattr(harness.Run, "traced_slice", traced_slice)
    line = bench_run.run_cell(solar_root, "solar_cell", 5, 1.0, True,
                              require_chip=False)
    # the CPU's backend reports no memory peak: that reader finds
    # nothing to read
    assert set(line["metrics"]) == set(SOLAR_METRICS) - {"backlog.hbm_peak_gb"}
    value = {k: m["value"] for k, m in line["metrics"].items()}
    assert all(np.isfinite(v) for v in value.values()), value
    for name in ("solar.serve_mfu", "solar.decode_hbm_roofline",
                 "solar.kda_prefill_roofline", "solar.kda_decode_roofline",
                 "solar.expert_products_roofline"):
        assert 0 < value[name] <= 100, name
    # the anchored patterns count the scan's outer loop and the update's
    # kernel: not the loop inside, another loop, or a fusion that takes
    # the kernel's result
    assert value["solar.kda_prefill_time_share"] == \
        pytest.approx(100 * 0.2 / 0.9)
    assert value["solar.kda_decode_time_share"] == \
        pytest.approx(100 * 0.06 / 0.9)
    assert value["lfm2.prefill_attn_time_share"] == \
        pytest.approx(100 * 0.03 / 0.9)
    assert value["lfm2.expert_products_time_share"] == \
        pytest.approx(100 * 0.09 / 0.9)
    assert value["lfm2.expert_kernel_product_share"] == 0.0
    assert value["backlog.device_idle_share"] == pytest.approx(10.0)
    # the state beside what the pool holds at the window's end
    assert 0 < value["solar.state_share_of_cache_bytes"] <= 100
    for name in ("backlog.kv_pool_filled_share",
                 "backlog.pipelined_launch_share",
                 "backlog.prefill_unawaited_share",
                 "longcat.held_choice_share", "lfm2.prefill_padding_share"):
        assert 0 < value[name] <= 100, name
    assert value["longcat.expert_load_max_over_mean"] >= 1
    assert value["backlog.host_arrays_per_dispatch"] == 1.0
    assert value["backlog.programs_compile_s"] > 0


def served_in(precision, seed):
    """Requests decoded greedily by the reference computed in
    `precision`: [(prompt ids, served ids)]."""
    import jax
    import jax.numpy as jnp
    weights = correct.weight_maker(TINY_SOLAR, seed)()
    rng = seeded.host_rng(seed, 9)
    ids = np.zeros((STREAMS, PAD), np.int32)
    ids[:, :PROMPT] = rng.integers(0, TINY_SOLAR["vocab_size"],
                                   (STREAMS, PROMPT))

    @jax.jit
    def first(w, ids, at):
        return jnp.argmax(ref.forward(w, ids, TINY_SOLAR,
                                      precision)[:, at], -1)

    for at in range(PROMPT - 1, PROMPT + SERVED - 1):
        ids[:, at + 1] = np.asarray(first(weights, jnp.asarray(ids), at))
    return [(row[:PROMPT].tolist(), row[PROMPT:PROMPT + SERVED].tolist())
            for row in ids]


def checked(root, seed, streams):
    run = harness.Run(root, "solar_cell", seed, 1.0, False,
                      require_chip=False)
    serving.check_served(run, streams)
    assert set(c[0] for c in run.checks) == set(serving.COMPARED)
    return {name: ok for name, _, _, ok in run.checks}


@pytest.mark.parametrize("seed", [3, 3000048201])
@pytest.mark.parametrize("precision,correct_", [("bfloat16", True),
                                                ("fp8", False)])
def test_served_in_the_fp8_control_it_fails_both_limits(
        solar_root, seed, precision, correct_):
    checks = checked(solar_root, seed, served_in(precision, seed))
    assert checks["logit_gap_mean"] == checks["logit_gap"] == correct_


@pytest.mark.parametrize("broken,correct_", [
    (None, True), ("steps_not_doubled", False), ("state_frozen", False)])
def test_a_broken_delta_rule_is_not_correct(solar_root, monkeypatch, broken,
                                            correct_):
    """The program's own engine over fixed prompts (no clock decides the
    sample): sound it passes both numbers; with the steps left at
    sigmoid's (no negative eigenvalue), or with launches that read the
    matrix state and never write it, it fails both: every served token
    is computed from a wrong state."""
    from benchmark.programs import paddle_solar
    from paddle_tpu.kernels import kda
    seed = 3000048201
    served_cfg = TINY_SOLAR
    if broken == "steps_not_doubled":
        served_cfg = dict(TINY_SOLAR, kda_allow_neg_eigval=False)
    elif broken == "state_frozen":
        step = kda.kda_decode_step
        monkeypatch.setattr(kda, "kda_decode_step",
                            lambda *a, **kw: (step(*a, **kw)[0], a[5]))
    mix = tiny_root.TRAFFIC["tiny_backlog"]
    engine = paddle_solar.build_engine(
        served_cfg, mix, correct.weight_maker(TINY_SOLAR, seed))
    rng = seeded.host_rng(seed, 9)
    prompts = [seeded.token_ids(rng, n, TINY_SOLAR["vocab_size"])
               for n in (5, 9, 13, 7, 11, 6, 8, 12, 3, 10, 14, 4)]
    served = engine.generate(prompts, max_new_tokens=12)
    checks = checked(solar_root, seed, list(zip(prompts, served)))
    assert checks["logit_gap_mean"] == checks["logit_gap"] == correct_


# -- the real files -----------------------------------------------------------

GQA_LAYERS = list(range(0, 48, 4))
CATALOG = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "tie_word_embeddings": False, "max_position_embeddings": 1048576,
    "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
    "gqa_layers": GQA_LAYERS, "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "n_routed_experts": 320, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}


def test_the_configuration_keeps_every_published_number():
    entry, cfg = real_file()
    assert config_rules.problems(entry, cfg) == []
    for key, value in CATALOG.items():
        if key in entry["reduced"]:
            assert cfg["published"][key] == value and cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "layer_types", "n_routed_experts",
        "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["experts_held_from"], cfg["vocab_size"]) == (4, 40, 0, 24576)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert cfg["n_routed_experts"] * 8 == cfg["published"]["n_routed_experts"]
    assert set(entry["reduced"]) <= set(cfg["changed"])
    for assumed in ("kda_projections", "kda_rule", "kda_seeding",
                    "short_conv", "gqa_gate", "router", "experts", "norms",
                    "head"):
        assert cfg["assumed"][assumed]
    assert "optimizer" not in cfg
    assert cfg["precision"] == {"params": "bfloat16",
                                "activations": "bfloat16",
                                "state": "float32", "control": "fp8"}
    assert "8 chips" in cfg["deployment"] \
        and "12 pipeline stages" in cfg["deployment"] \
        and "3,308 M parameters = 6.62 GB" in cfg["deployment"]
    assert len(entry["source"]) <= 200 and entry["source"] == \
        "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/" \
        "config.json"


def test_the_layer_keys_are_the_exact_translation_of_the_publishers_list():
    """`tests/benchmark/config_rules.py` reads a pattern under
    `layer_types`; the publisher lists the softmax layers (`gqa_layers`,
    `gqa_interval` KDA layers between two of them). The file holds the
    publisher's list verbatim and uncut, and the key the rules read is its
    translation, entry for entry."""
    _, cfg = real_file()
    assert cfg["gqa_layers"] == GQA_LAYERS and cfg["gqa_interval"] == 3
    translated = ["full_attention" if i in GQA_LAYERS
                  else "linear_attention" for i in range(48)]
    assert cfg["published"]["layer_types"] == translated
    assert cfg["layer_types"] == translated[:4] == [
        "full_attention"] + ["linear_attention"] * 3
    # a whole period of 4, the beginning, both kinds in the published 1 : 3
    assert config_rules.period(translated) == 4 == cfg["num_hidden_layers"]
    assert config_rules.pattern_problems(cfg["layer_types"],
                                         translated) == []


@pytest.mark.parametrize("key", ["num_experts_per_tok", "hidden_size",
                                 "moe_intermediate_size", "head_dim",
                                 "linear_attn_config", "gqa_layers"])
def test_the_rules_refuse_a_cut_of_what_is_no_count_held(key):
    """A width, the KDA layers' group of widths, and the publisher's own
    name for the pattern: none may stand in `reduced`."""
    entry, cfg = real_file()
    cut = dict(entry, reduced=entry["reduced"] + [key])
    found = config_rules.problems(cut, dict(cfg, changed=dict(
        cfg["changed"], **{key: "cut"})))
    assert any(f"names {key}: a width, or no kind" in f for f in found)


def test_the_reference_states_the_files_shapes_and_imports_no_program():
    _, cfg = real_file()
    shapes = ref.param_shapes(cfg)
    a = "model.layers.{}.self_attn.{}"
    assert shapes[a.format(0, "q_proj.weight")] == (4096, 64 * 128)
    assert shapes[a.format(0, "k_proj.weight")] == (4096, 8 * 128)
    assert shapes[a.format(0, "g_proj.weight")] == (4096, 64 * 128)
    assert a.format(0, "A_log") not in shapes
    for layer in (1, 2, 3):
        assert shapes[a.format(layer, "k_proj.weight")] == (4096, 8192)
        assert shapes[a.format(layer, "v_conv1d.weight")] == (8192, 4)
        assert shapes[a.format(layer, "f_a_proj.weight")] == (4096, 128)
        assert shapes[a.format(layer, "g_b_proj.weight")] == (128, 8192)
        assert shapes[a.format(layer, "b_proj.weight")] == (4096, 64)
        assert shapes[a.format(layer, "A_log")] == (64,)
        assert shapes[a.format(layer, "dt_bias")] == (8192,)
        assert shapes[a.format(layer, "o_norm.weight")] == (128,)
        assert a.format(layer, "g_proj.weight") not in shapes
    assert shapes["model.layers.0.mlp.gate.weight"] == (4096, 320)
    assert shapes["model.layers.3.mlp.experts.down_proj.weight"] == \
        (40, 1280, 4096)
    assert shapes["model.layers.3.mlp.shared_experts.up_proj.weight"] == \
        (4096, 1280)
    assert shapes["lm_head.weight"] == (4096, 24576)
    assert "model.layers.4.input_layernorm.weight" not in shapes
    # the issue's arithmetic: 3,308 M parameters, 6.62 GB in bf16
    assert ref.num_params(cfg) == 3308352064
    assert not any("paddle" in line for line in open(ref.__file__)
                   if line.startswith(("import", "from")))
    assert not hasattr(ref, "loss_and_grads")        # serving only


def test_the_programs_names_map_onto_the_references():
    """The program builds its model from the file's dict; its parameters
    are the reference's, name for name, shape for shape, in order."""
    from benchmark.programs import paddle_solar
    from paddle_tpu.incubate.models import solar_open2 as so
    for file in (TINY_SOLAR, real_file()[1]):
        mine = so.param_shapes(paddle_solar._model_config(file))
        assert mine == ref.param_shapes(file)
        assert list(mine) == list(ref.param_shapes(file))


def test_the_cell_and_its_traffic_are_the_issues():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "backlog_longdoc_16k", 1)
    assert "8x its share" in cell["why"] and "host" in cell["why"]
    mix = json.load(open(os.path.join(
        REPO, "benchmark", "traffic", "backlog_longdoc_16k.json")))
    assert mix["engine"] == {"max_batch_size": 64, "block_size": 16,
                             "max_context": 17408}
    assert mix["prompt_tokens"] == {"median": 8192, "sigma": 0.6,
                                    "lo": 2049, "hi": 16384}
    assert mix["output_tokens"] == {"median": 512, "sigma": 0.5,
                                    "lo": 256, "hi": 1024}
    assert (mix["loop"], mix["queue_depth"], mix["warm_completions"],
            mix["checked_requests"], mix["reference_pad_to"],
            mix["trace_seconds"], mix["compile_tokens"]) == (
                "serve_backlog", 32, 64, 8, 17408, 4, 4)
    # the set-up sends ONE prompt of each listed length: each is its own
    # bucket (long buckets grow by steps of 4,096: serving/engine.py)
    from paddle_tpu.serving import LLMEngine
    assert mix["prefill_buckets"] == [4096, 8192, 12288, 16384]
    assert [LLMEngine._bucket_for(n) for n in mix["prefill_buckets"]] \
        == mix["prefill_buckets"]
    assert max(mix["prefill_buckets"]) + mix["compile_tokens"] \
        <= mix["engine"]["max_context"]
    prompts = traffic.stratified_lengths(mix["prompt_tokens"], mix["block"])
    outputs = traffic.stratified_lengths(mix["output_tokens"], mix["block"])
    used = sorted(LLMEngine._bucket_for(n) for n in prompts)
    assert set(used) == set(mix["prefill_buckets"])  # every bucket is used
    # no request passes the context, whichever pair a seed makes
    assert max(prompts) + max(outputs) == mix["reference_pad_to"] \
        == mix["engine"]["max_context"]
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    limits = json.load(open(os.path.join(
        REPO, "benchmark", "limits", CELL + ".json")))
    assert set(limits) == set(serving.COMPARED)


def test_every_metric_of_the_cell_names_it_and_has_its_file():
    """Membership, not a count: what PR 48 brought is among the entries
    that name the cell, beside what it joined of its loop's and its expert
    block's shared entries and what later PRs add."""
    brought = {"solar.serve_mfu", "solar.decode_hbm_roofline",
               "solar.kda_prefill_time_share", "solar.kda_prefill_roofline",
               "solar.kda_decode_time_share", "solar.kda_decode_roofline",
               "solar.expert_products_roofline",
               "solar.state_share_of_cache_bytes"}
    joined = {"backlog.kv_pool_filled_share", "backlog.attn_streamed_share",
              "backlog.prefill_span_share", "backlog.decode_span_share",
              "backlog.host_step_ms", "backlog.host_wait_ms_per_step",
              "backlog.ran_dry_dispatch_share", "backlog.programs_compile_s",
              "backlog.device_idle_share", "backlog.hbm_peak_gb",
              "backlog.prefill_unawaited_share",
              "backlog.pipelined_launch_share",
              "backlog.host_arrays_per_dispatch", "longcat.held_choice_share",
              "longcat.expert_load_max_over_mean",
              "lfm2.expert_products_time_share",
              "lfm2.expert_kernel_product_share",
              "lfm2.prefill_padding_share", "lfm2.prefill_attn_time_share"}
    assert brought | joined <= set(SOLAR_METRICS)
    # NOT joined, though its pattern matches this program's decode kernel
    # (5.5% of the first traced slice): `test_bench_harness.py` pins that
    # entry's `workloads` to two cells, and the file is the benchmark's
    assert "backlog.decode_attn_time_share" not in SOLAR_METRICS
    names = [m["name"] for m in SPEC["per_layer"]]
    # its own entries came last of what was there then, each listing it alone
    assert all(names.index(b) > names.index(j) for b in brought
               for j in joined)
    mine = [m for m in SPEC["per_layer"] if CELL in m["workloads"]]
    for m in mine:
        assert m["moves"] == ("setup_s" if m["name"].endswith(
            "programs_compile_s") else "serve_tokens_per_s")
        spec = json.load(open(os.path.join(
            REPO, "benchmark", "metrics", m["name"] + ".json")))
        assert spec["reader"].startswith("benchmark.readers.")
        if m["name"] in brought:
            assert m["workloads"] == [CELL] or CELL in m["workloads"]
    shares = [m for m in mine
              if "roofline" in m["name"] or "mfu" in m["name"]]
    assert {"solar.serve_mfu", "solar.kda_prefill_roofline",
            "solar.kda_decode_roofline"} <= {m["name"] for m in shares}
    assert all(m["unit"] == "%" and m["better"] == "higher" for m in shares)


# -- the counts and the readers -----------------------------------------------

def test_the_counts_are_the_issues_arithmetic():
    _, cfg = real_file()
    assert solar_counts.layers(cfg) == (1, 3)
    # ISSUE 48: KDA attention 137.7 M, GQA attention 109.0 M, an expert
    # 15.73 M, the router 1.31 M
    kda = 4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
    assert solar_counts.kda_params(cfg) == kda == 137_625_600
    gqa = 4096 * (8192 + 2 * 1024 + 8192) + 8192 * 4096
    assert solar_counts.gqa_params(cfg) == gqa == 109_051_904
    assert solar_counts.expert_params(cfg) == 3 * 4096 * 1280 == 15_728_640
    assert solar_counts.router_params(cfg) == 4096 * 320
    token = gqa + 3 * kda + 4 * (4096 * 320 + 15_728_640) + 4096 * 24576
    assert solar_counts.token_params(cfg) == token
    # every parameter of the file: what every token multiplies, the
    # experts held, the embedding's slice, and what is no matrix product
    # (norms, taps, A_log, dt_bias)
    small = 9 * 4096 + 3 * (3 * 8192 * 4 + 64 + 8192 + 128)
    assert token + 4 * 40 * 15_728_640 + 4096 * 24576 + small \
        == ref.num_params(cfg)
    # a cached token: 4 KB in the softmax layer; a slot's state: 4.19 MB
    # of matrix and 0.15 MB of kept inputs a KDA layer
    assert solar_counts.cached_row(cfg) * 2 == 4096
    matrix, kept = solar_counts.state_values(cfg)
    assert (matrix * 4, kept * 2) == (4_194_304, 147_456)
    assert 3 * (matrix * 4 + kept * 2) == 13_025_280        # 13.0 MB a slot
    assert solar_counts.pair_flops(cfg) == 2 * 2 * 128 * 64
    # the chunked rule a prompt token a layer, the recurrence a decoded one
    per_token = 64 * (5 * 64 * 128 + 6 * 128 * 128)
    assert solar_counts.scan_flops_per_token(cfg) == per_token == 8_912_896
    assert solar_counts.update_flops(cfg) == 64 * 7 * 128 * 128
    ops, moved = solar_counts.scan(cfg, 1000, 2)
    assert ops == per_token * 1000
    assert moved == 1000 * 64 * (4 * 128 * 2 + 129 * 4) + 2 * matrix * 4
    ops, moved = solar_counts.state_update(cfg, 10)
    assert ops == 10 * 64 * 7 * 16384
    assert moved == 10 * (2 * matrix * 4 + 64 * (4 * 128 * 2 + 129 * 4))
    assert solar_counts.serve_flops(cfg, 10, 4, 3, 7) == 2 * (
        14 * token + 3 * 15_728_640) + 32768 * 7 \
        + 3 * (per_token * 10 + 64 * 7 * 16384 * 4)
    assert solar_counts.prefill_flops(cfg, 4096, 5) == \
        solar_counts.serve_flops(cfg, 4096, 0, 5, 4096 * 4097 / 2)
    # a launch of 64 slots at 8,700 tokens that reads every held expert:
    # 1.4 GB outside the routed experts, 5.0 GB of experts, 2.3 GB of
    # keys and values, 1.67 GB of state read and written
    parts = [solar_counts.decode_bytes(cfg, *args) for args in (
        (1, 0, 0, 0), (0, 160, 0, 0), (0, 0, 64 * 8700, 0),
        (0, 0, 0, 64 * 3))]
    assert [round(p / 1e9, 2) for p in parts] == [1.38, 5.03, 2.28, 1.67]
    assert solar_counts.decode_bytes(cfg, 1, 160, 64 * 8700, 192) \
        == sum(parts)
    ops, moved = solar_counts.expert_products(cfg, 512, 8)
    assert ops == 2 * 15_728_640 * 512
    assert moved == 2 * (8 * 15_728_640 + 2 * 4096 * 512)


def _evidence(stats, trace=None):
    _, cfg = real_file()
    return {"config": cfg, "engine_stats": stats, "window": (10.0, 50.0),
            "peaks": PEAKS, "trace": trace,
            "engine_facts": {"slots": 64, "table_entries": 1088,
                             "block_size": 16, "cached_sublayers": 1}}


# a window of 40 s: 160 prompts of 8,700 tokens, 1,800 launches of 63
WINDOW = {"prefill_tokens": 1_392_000, "prefill_bucket_tokens": 1_720_000,
          "prefill_counted": 160, "decode_tokens": 113_400,
          "decode_launches": 1800, "decode_counted": 1800,
          "decode_state_updates": 3 * 113_400,
          "decode_routed_computed": 113_400 * 4 * 8 // 8,
          "prefill_routed_computed": 1_392_000 * 4 * 8 // 8,
          "decode_experts_idle": 1800 * 4 * 8, "prefill_experts_idle": 0,
          "decode_products": 1800 * 4 * 3, "prefill_products": 160 * 4 * 3,
          "attn_tokens_held": 113_400 * 9000}


def test_the_shares_of_the_peaks_follow_the_windows_counters():
    ev = _evidence(WINDOW)
    cfg = ev["config"]
    mfu = solar_serve_mfu.read(ev)
    want = 100 * solar_counts.serve_flops(
        cfg, 1_392_000, 113_400, 1_505_400 * 4,
        113_400 * 9000 + 160 * 8700 * 8701 / 2) / 40 / 197e12
    assert mfu == pytest.approx(want) and 0 < mfu < 100
    share = solar_decode_hbm_roofline.read(ev)
    want = 100 * solar_counts.decode_bytes(
        cfg, 1800, 1800 * 4 * 32, 113_400 * 9000, 3 * 113_400) / 819e9 / 40
    assert share == pytest.approx(want) and 0 < share < 100
    # fewer idle experts are more bytes a launch
    busier = dict(WINDOW, decode_experts_idle=0)
    assert solar_decode_hbm_roofline.read(_evidence(busier)) > share
    # a program that counts no state updates (a parent's) reads nothing
    older = {k: v for k, v in WINDOW.items() if k != "decode_state_updates"}
    assert solar_serve_mfu.read(_evidence(older)) is None
    assert solar_decode_hbm_roofline.read(_evidence(older)) is None


def test_a_kernels_roofline_reads_the_traced_calls_and_nothing_without():
    update = "%kda_decode_step.2 = (f32[64,64,128]) custom-call(...)"
    trace = {"devices": 1, "busy_s": 3.9, "window_s": 4.0,
             "op_seconds": {SCAN: 1.5, INNER: 0.2, update: 0.5,
                            "%ragged_expert_matmul.1 = x": 0.3,
                            "%fusion.5 = f(%kda_decode_step.2)": 0.5},
             "op_counts": {SCAN: 3 * 16, INNER: 3 * 16 * 24, update: 3 * 180,
                           "%ragged_expert_matmul.1 = x": 3 * 4 * 196,
                           "%fusion.5 = f(%kda_decode_step.2)": 99}}
    ev = _evidence(WINDOW, trace)
    cfg = ev["config"]
    scan_pattern, update_pattern = (json.load(open(os.path.join(
        REPO, "benchmark", "metrics", f"solar.kda_{phase}_roofline.json")))[
            "args"]["pattern"] for phase in ("prefill", "decode"))
    # 16 prompts traced, of the window's mean 8,700 tokens
    got = solar_kernel_roofline.read(ev, scan_pattern, "scan")
    ops, moved = solar_counts.scan(cfg, 3 * 16 * 8700, 3 * 16)
    assert got == pytest.approx(
        100 * max(ops / 197e12, moved / 819e9) / 1.5)
    # 90 operations a byte, under the chip's 240: bound by the bytes (q,
    # k, v, o and the float32 decays), though a product costs six passes
    assert moved / 819e9 > ops / 197e12
    # 180 launches traced, of the window's mean 63 active slots
    got = solar_kernel_roofline.read(ev, update_pattern, "update")
    ops, moved = solar_counts.state_update(cfg, 3 * 180 * 63)
    assert got == pytest.approx(
        100 * max(ops / 197e12, moved / 819e9) / 0.5)
    assert moved / 819e9 > ops / 197e12              # bound by the bytes
    got = solar_kernel_roofline.read(ev, r"^%\S*ragged", "experts", 3)
    calls = 4 * (1800 + 160)
    ops, moved = solar_counts.expert_products(
        cfg, 1_505_400 * 4, 1800 * 4 * 32 + 160 * 4 * 40)
    least = max(ops / 197e12, moved / 819e9) / calls * (4 * 196)
    assert got == pytest.approx(100 * least / 0.3)
    # nothing matched, no trace, or a program without the counters: nothing
    assert solar_kernel_roofline.read(ev, "^%nothing", "scan") is None
    assert solar_kernel_roofline.read(_evidence(WINDOW), scan_pattern,
                                      "scan") is None
    older = {k: v for k, v in WINDOW.items() if k != "decode_state_updates"}
    assert solar_kernel_roofline.read(_evidence(older, trace),
                                      update_pattern, "update") is None


def test_the_seed_check_holds_the_control_to_the_limits(solar_root):
    """`seedcheck_released`'s row for a control seed: the sample passes,
    the fp8 control of the same sample fails both limits."""
    seed = 3000048201
    run = harness.Run(solar_root, "solar_cell", seed, 1.0, False,
                      require_chip=False)
    numbers = serving.check_served(run, served_in("bfloat16", seed), "fp8")
    assert all(ok for *_, ok in run.checks)
    at = len(run.checks)
    for name in serving.COMPARED:
        run.check("control_" + name, numbers["control_" + name],
                  limit_key=name)
    failed = {name for name, _, _, ok in run.checks[at:] if not ok}
    assert failed == {"control_logit_gap", "control_logit_gap_mean"}
