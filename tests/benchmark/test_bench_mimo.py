"""The MiMo-V2-Flash family through the benchmark, on the CPU at a tiny size
(`mimo_model/tiny_mimo.py`): its cell runs on the `serve_backlog` loop with
the REAL program and reference modules and is correct; served in the fp8
control it is not, by the mean gap; served with a window layer that attends
beyond its window, or with the sink dropped, it is not; the real
configuration file keeps every published number, passes the rules, and
states the publisher's two lists in exact translation; the traffic file
holds the issue's parameters; the counts and the readers of its per-layer
metrics."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "mimo_model")]
import config_rules  # noqa: E402
import metric_rules  # noqa: E402
import tiny_root  # noqa: E402
from tiny_mimo import TINY_MIMO  # noqa: E402

from benchmark import correct, harness, mimo_counts, \
    run as bench_run, seeded, traffic  # noqa: E402
from benchmark.loops import serving  # noqa: E402
from benchmark.readers import mimo_decode_hbm_roofline, \
    mimo_kernel_roofline, mimo_serve_mfu  # noqa: E402
from benchmark.reference import mimo_v2_flash as ref  # noqa: E402

REPO = tiny_root.REPO
CELL, CONFIG = "serve_mimo_reasoning_8k", "mimo_v2_flash_ep32"
# from readings on the CPU over seeds 3, 5, 2**31 + 7 and 3000028201 (16
# or 12 requests a sample, some 100-190 served tokens): the program through
# the engine reads a widest gap <= 0.0079 and a mean <= 0.00011, a bfloat16
# stand-in <= 0.046 and <= 0.00028; the fp8 control's widest 0.24-0.45 and
# mean 0.019-0.026; window layers that attend twice as far 1.66 and 0.238,
# the sink dropped 0.76 and 0.047. The mean's limit lies ten times over the
# sound largest and six under the control's smallest; the widest guards
# against gross faults only: the control passes it, the broken layers do not
LIMITS = {"logit_gap": 0.6, "logit_gap_mean": 0.003}
STREAMS, PROMPT, SERVED, PAD = 8, 8, 24, 32
SPEC = tiny_root.spec_of("as_it_stands")
# the cell's metrics are every entry whose `workloads` names it: its own
# (`mimo.*`) and the shared ones of its loop and its expert block, which
# it joined (ISSUE 47; a prefix names who brought an entry, not a cell)
MIMO_METRICS = [m["name"] for m in SPEC["per_layer"]
                if CELL in m["workloads"]]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture
def mimo_root(root):
    tiny_root.add_cell(
        root, "mimo_cell", ("tiny_mimo", TINY_MIMO),
        # 16 requests a sample: over 100 served tokens, so that a mean
        # gap is a mean
        ("mimo_mix", dict(tiny_root.TRAFFIC["tiny_backlog"],
                          reference_pad_to=PAD, checked_requests=16)),
        LIMITS, ("serve_tokens_per_s", *MIMO_METRICS))
    return root


def real_file():
    entry = next(c for c in SPEC["configs"] if c["name"] == CONFIG)
    return entry, json.load(open(os.path.join(REPO, entry["file"])))


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 7])
def test_the_tiny_cell_runs_on_the_backlog_loop_and_is_correct(mimo_root,
                                                               seed):
    line = bench_run.run_cell(mimo_root, "mimo_cell", seed, 1.0, False,
                              require_chip=False)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert set(line["checks"]) == set(serving.COMPARED)


def test_a_traced_run_reports_every_metric_of_the_cell(mimo_root,
                                                       monkeypatch):
    """Every metric of the cell appears, finite, with a canned device trace
    (the CPU gives the profiler no device plane) and canned peaks; a
    share of a peak stays inside 0..100. The tiny calls are under the
    tokens from which the products are grouped, so the three metrics that
    read the grouped products find nothing (a roofline, a share of no
    products) or no instruction (a share of device time: 0.0)."""
    import contextlib

    @contextlib.contextmanager
    def traced_slice(self):
        yield
        ops = {"%full_decode_attention.3 = bf16[4,16,8] custom-call(...)":
                   (0.1, 40),
               "%window_decode_attention.4 = bf16[4,16,8] custom-call(...)":
                   (0.06, 100),
               "%flash_band_attention.2 = bf16[8,16,8] custom-call(...)":
                   (0.05, 10),
               "%fusion.9 = bf16[8] fusion(%window_decode_attention.4)":
                   (0.2, 10)}
        self.evidence["trace"] = {
            "window_s": 1.0, "devices": 1, "busy_s": 0.9,
            "collective_s": 0.0, "collective_exposed_s": 0.0,
            "op_seconds": {k: v[0] for k, v in ops.items()},
            "op_counts": {k: v[1] for k, v in ops.items()},
            "gaps": [], "spans": []}
        self.evidence["peaks"] = PEAKS
    monkeypatch.setattr(harness.Run, "traced_slice", traced_slice)
    line = bench_run.run_cell(mimo_root, "mimo_cell", 5, 1.0, True,
                              require_chip=False)
    # the CPU's backend reports no memory peak: that reader finds
    # nothing to read either
    assert set(line["metrics"]) == set(MIMO_METRICS) \
        - {"mimo.expert_products_roofline",
           "lfm2.expert_kernel_product_share", "backlog.hbm_peak_gb"}
    value = {k: m["value"] for k, m in line["metrics"].items()}
    assert all(np.isfinite(v) for v in value.values()), value
    for name in ("mimo.serve_mfu", "mimo.decode_hbm_roofline",
                 "mimo.decode_attn_roofline", "mimo.window_attn_roofline",
                 "mimo.prefill_band_roofline"):
        assert 0 < value[name] <= 100, name
    # the CPU's loop reads whole chunks of a ring's table: over 100%
    assert value["mimo.window_streamed_share"] >= 100.0
    # the anchored patterns count the kernels, not a fusion that takes
    # their result
    assert value["mimo.decode_attn_time_share"] == \
        pytest.approx(100 * 0.1 / 0.9)
    assert value["mimo.window_attn_time_share"] == \
        pytest.approx(100 * 0.06 / 0.9)
    # both prefill kernels' instructions (the canned trace holds the
    # band's alone), and what the cell joined of its loop's and its
    # expert block's shared entries (ISSUE 47)
    assert value["mimo.prefill_attn_time_share"] == \
        pytest.approx(100 * 0.05 / 0.9)
    assert value["lfm2.expert_products_time_share"] == 0.0
    assert value["backlog.device_idle_share"] == pytest.approx(10.0)
    for name in ("backlog.kv_pool_filled_share",
                 "backlog.pipelined_launch_share",
                 "backlog.prefill_unawaited_share",
                 "longcat.held_choice_share"):
        assert 0 < value[name] <= 100, name
    assert value["backlog.attn_streamed_share"] > 0
    assert value["longcat.expert_load_max_over_mean"] >= 1
    assert value["backlog.host_arrays_per_dispatch"] == 1.0
    assert value["backlog.host_step_ms"] \
        >= value["backlog.host_wait_ms_per_step"] >= 0
    assert value["backlog.programs_compile_s"] > 0


def served_in(precision, seed):
    """Requests decoded greedily by the reference computed in
    `precision`: [(prompt ids, served ids)]."""
    import jax
    import jax.numpy as jnp
    weights = correct.weight_maker(TINY_MIMO, seed)()
    rng = seeded.host_rng(seed, 9)
    ids = np.zeros((STREAMS, PAD), np.int32)
    ids[:, :PROMPT] = rng.integers(0, TINY_MIMO["vocab_size"],
                                   (STREAMS, PROMPT))

    @jax.jit
    def first(w, ids, at):
        return jnp.argmax(ref.forward(w, ids, TINY_MIMO,
                                      precision)[:, at], -1)

    for at in range(PROMPT - 1, PROMPT + SERVED - 1):
        ids[:, at + 1] = np.asarray(first(weights, jnp.asarray(ids), at))
    return [(row[:PROMPT].tolist(), row[PROMPT:PROMPT + SERVED].tolist())
            for row in ids]


def checked(root, seed, streams):
    run = harness.Run(root, "mimo_cell", seed, 1.0, False,
                      require_chip=False)
    serving.check_served(run, streams)
    assert set(c[0] for c in run.checks) == set(serving.COMPARED)
    return {name: ok for name, _, _, ok in run.checks}


@pytest.mark.parametrize("seed", [3, 3000028201])
@pytest.mark.parametrize("precision,correct_", [("bfloat16", True),
                                                ("fp8", False)])
def test_served_in_the_fp8_control_it_fails_by_the_mean_gap(
        mimo_root, seed, precision, correct_):
    checks = checked(mimo_root, seed, served_in(precision, seed))
    assert checks["logit_gap_mean"] == correct_


def drop_the_sink(monkeypatch):
    """Both attention paths of the program with the sink left out."""
    from paddle_tpu.incubate.models import mimo_v2_flash as mimo
    from paddle_tpu.nn.functional import attention as fattn
    paged, dense = (fattn.paged_banded_decode_attention,
                    mimo.MiMoV2FlashForCausalLM._causal)
    monkeypatch.setattr(
        fattn, "paged_banded_decode_attention",
        lambda *a, **kw: paged(*a, **dict(kw, sink=None)))
    monkeypatch.setattr(
        mimo.MiMoV2FlashForCausalLM, "_causal",
        lambda self, q, k, v, past, window, sink: dense(
            self, q, k, v, past, window, None))


@pytest.mark.parametrize("broken,correct_", [
    (None, True), ("beyond_the_window", False), ("sink_dropped", False)])
def test_a_broken_window_layer_is_not_correct(mimo_root, monkeypatch, broken,
                                              correct_):
    """The program's own engine over fixed prompts (no clock decides the
    sample): sound it passes both numbers; with window layers that attend
    twice as far back as the window, or with the sink left out of the
    denominator, it fails the mean (every served token is computed from a
    wrong attention)."""
    from benchmark.programs import paddle_mimo
    seed = 3000028201
    served_cfg = TINY_MIMO
    if broken == "beyond_the_window":
        served_cfg = dict(TINY_MIMO,
                          sliding_window=2 * TINY_MIMO["sliding_window"])
    elif broken == "sink_dropped":
        drop_the_sink(monkeypatch)
    mix = tiny_root.TRAFFIC["tiny_backlog"]
    engine = paddle_mimo.build_engine(
        served_cfg, mix, correct.weight_maker(TINY_MIMO, seed))
    rng = seeded.host_rng(seed, 9)
    prompts = [seeded.token_ids(rng, n, TINY_MIMO["vocab_size"])
               for n in (5, 9, 13, 7, 11, 6, 8, 12, 3, 10, 14, 4)]
    served = engine.generate(prompts, max_new_tokens=12)
    checks = checked(mimo_root, seed, list(zip(prompts, served)))
    assert checks["logit_gap_mean"] == correct_
    assert correct_ is False or checks["logit_gap"]


# -- the real files -----------------------------------------------------------

PATTERN = [0, 1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0] * 7
CATALOG = {
    "attention_value_scale": 0.707, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 16384,
    "max_position_embeddings": 262144, "model_type": "mimo_v2_flash",
    "num_attention_heads": 64, "head_dim": 192, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "layernorm_epsilon": 1e-05,
    "rope_theta": 5000000, "tie_word_embeddings": False,
    "vocab_size": 152576, "partial_rotary_factor": 0.334,
    "sliding_window": 128, "swa_rope_theta": 10000,
    "attention_bias": False, "v_head_dim": 128,
    "hybrid_layer_pattern": PATTERN, "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False, "sliding_window_size": 128,
    "attention_chunk_size": 128, "moe_layer_freq": [0] + [1] * 47,
    "moe_intermediate_size": 2048, "n_routed_experts": 256,
    "n_shared_experts": None, "num_experts_per_tok": 8,
    "norm_topk_prob": True, "scoring_func": "sigmoid", "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc",
    "routed_scaling_factor": None, "swa_num_attention_heads": 64,
    "swa_num_key_value_heads": 8, "swa_head_dim": 192,
    "swa_v_head_dim": 128}


def test_the_configuration_keeps_every_published_number():
    entry, cfg = real_file()
    assert config_rules.problems(entry, cfg) == []
    assert len(PATTERN) == 48
    for key, value in CATALOG.items():
        if key in entry["reduced"]:
            assert cfg["published"][key] == value and cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "layer_types", "n_routed_experts",
        "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (7, 8, 19072)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    assert set(cfg["changed"]) == set(entry["reduced"])
    for assumed in ("rope", "value_scale", "sink", "window", "norms",
                    "router", "expert_bias", "head", "mtp",
                    "initializer_range"):
        assert cfg["assumed"][assumed]
    assert "optimizer" not in cfg and cfg["precision"]["control"] == "fp8"
    assert "32 chips" in cfg["deployment"] \
        and "8 pipeline stages" in cfg["deployment"]
    assert len(entry["source"]) <= 200 and entry["source"].startswith(
        "https://huggingface.co/XiaomiMiMo/MiMo-V2-Flash/blob/main/"
        "config.json")


def test_the_layer_keys_are_the_exact_translation_of_the_publishers_lists():
    """`tests/benchmark/config_rules.py` reads a pattern under
    `layer_types` and the leading dense layers under
    `first_k_dense_replace`; the publisher writes `hybrid_layer_pattern`
    (0 full, 1 window) and `moe_layer_freq` (0 dense, 1 experts). The
    file holds the publisher's lists verbatim and uncut, and the keys the
    rules read are their translation, entry for entry."""
    _, cfg = real_file()
    pattern, freq = cfg["hybrid_layer_pattern"], cfg["moe_layer_freq"]
    assert pattern == PATTERN and freq == [0] + [1] * 47
    kinds = {0: "full_attention", 1: "sliding_attention"}
    translated = [kinds[k] for k in pattern]
    assert cfg["published"]["layer_types"] == translated
    assert cfg["layer_types"] == translated[:cfg["num_hidden_layers"]]
    assert cfg["first_k_dense_replace"] == freq.index(1) == 1
    assert all(freq[cfg["first_k_dense_replace"]:])
    # what the rules then see after the dense layer: S S S S F S, a whole
    # period of 6, the beginning, both kinds within one layer of its share
    kept = cfg["layer_types"][1:]
    assert config_rules.period(translated[1:]) == 6 == len(kept)
    assert config_rules.pattern_problems(kept, translated[1:]) == []
    assert kept.count("full_attention") == 1


@pytest.mark.parametrize("key", ["num_experts_per_tok", "hidden_size",
                                 "moe_intermediate_size", "sliding_window",
                                 "head_dim", "hybrid_layer_pattern",
                                 "moe_layer_freq"])
def test_the_rules_refuse_a_cut_of_what_is_no_count_held(key):
    """A width, the window, and the publisher's own names for the
    pattern: none may stand in `reduced` (which is why the file states
    the pattern under the keys the rules read)."""
    entry, cfg = real_file()
    cut = dict(entry, reduced=entry["reduced"] + [key])
    found = config_rules.problems(cut, dict(cfg, changed=dict(
        cfg["changed"], **{key: "cut"})))
    assert any(f"names {key}: a width, or no kind" in f for f in found)


def test_the_reference_states_the_files_shapes_and_imports_no_program():
    _, cfg = real_file()
    shapes = ref.param_shapes(cfg)
    a = "model.layers.{}.self_attn.{}"
    assert shapes[a.format(0, "q_proj.weight")] == (4096, 64 * 192)
    assert shapes[a.format(0, "k_proj.weight")] == (4096, 4 * 192)
    assert shapes[a.format(0, "v_proj.weight")] == (4096, 4 * 128)
    assert shapes[a.format(1, "k_proj.weight")] == (4096, 8 * 192)
    assert shapes[a.format(1, "v_proj.weight")] == (4096, 8 * 128)
    assert shapes[a.format(1, "attention_sink_bias")] == (64,)
    assert a.format(0, "attention_sink_bias") not in shapes
    assert a.format(5, "attention_sink_bias") not in shapes      # full
    assert shapes[a.format(6, "o_proj.weight")] == (64 * 128, 4096)
    assert shapes["model.layers.0.mlp.gate_proj.weight"] == (4096, 16384)
    assert shapes["model.layers.1.mlp.gate.weight"] == (4096, 256)
    assert shapes["model.layers.6.mlp.experts.down_proj.weight"] == \
        (8, 2048, 4096)
    assert shapes["lm_head.weight"] == (4096, 19072)
    assert "model.layers.7.input_layernorm.weight" not in shapes
    # the issue's arithmetic: 2,222 M parameters, 4.44 GB in bf16
    assert ref.num_params(cfg) == 2221994304
    assert not any("paddle" in line for line in open(ref.__file__)
                   if line.startswith(("import", "from")))
    assert not hasattr(ref, "loss_and_grads")        # serving only


def test_the_cell_and_its_traffic_are_the_issues():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "backlog_reasoning_8k", 1)
    mix = json.load(open(os.path.join(
        REPO, "benchmark", "traffic", "backlog_reasoning_8k.json")))
    assert mix["engine"] == {"max_batch_size": 128, "block_size": 16,
                             "max_context": 8192}
    assert mix["prompt_tokens"] == {"median": 3072, "sigma": 0.6,
                                    "lo": 1025, "hi": 6144}
    assert mix["output_tokens"] == {"median": 1536, "sigma": 0.5,
                                    "lo": 512, "hi": 2048}
    assert (mix["loop"], mix["queue_depth"], mix["block"],
            mix["warm_completions"], mix["checked_requests"],
            mix["reference_pad_to"], mix["trace_seconds"],
            mix["compile_tokens"]) == ("serve_backlog", 32, 16, 128, 16,
                                       8192, 4, 4)
    # the set-up sends ONE prompt of each listed length with
    # `compile_tokens` new tokens: the last entry is the longest prompt
    # that fits `max_context` so, and its bucket is 8192
    assert mix["prefill_buckets"] == [2048, 4096, 8188]
    bucket = lambda n: max(8, 1 << (n - 1).bit_length())
    compiled = {bucket(n) for n in mix["prefill_buckets"]}
    assert compiled == {2048, 4096, 8192}
    assert max(mix["prefill_buckets"]) + mix["compile_tokens"] \
        <= mix["engine"]["max_context"]
    prompts = traffic.stratified_lengths(mix["prompt_tokens"], mix["block"])
    outputs = traffic.stratified_lengths(mix["output_tokens"], mix["block"])
    assert sorted(bucket(n) for n in prompts) == \
        [2048] * 4 + [4096] * 7 + [8192] * 5         # every bucket is used
    # no request passes the context, whichever pair a seed makes
    assert max(prompts) + max(outputs) == mix["reference_pad_to"] \
        == mix["engine"]["max_context"]
    # contexts in flight: a prompt and half its output, about 4.1 k: the
    # full layers' pool about half full
    mean_context = sum(prompts) / 16 + sum(outputs) / 32
    assert 0.45 < mean_context / 8192 < 0.55
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    limits = json.load(open(os.path.join(
        REPO, "benchmark", "limits", CELL + ".json")))
    assert set(limits) == set(serving.COMPARED)


def test_every_metric_of_the_cell_names_it_and_has_its_file():
    """Membership, not a count: PR 42's nine (`per_layer` held 119 of 128)
    are among the entries that name the cell, beside what it joined when
    ISSUE 47 made room (what every cell of its loop reports, by its name
    in their `workloads`: no entry, no file) and what later PRs add."""
    brought = {metric_rules.today(m["name"])
               for m in metric_rules.PARENT_PER_LAYER
               if m["workloads"] == [CELL]}
    assert len(brought) == 9 and brought <= set(MIMO_METRICS)
    joined = {"backlog.kv_pool_filled_share", "backlog.attn_streamed_share",
              "backlog.prefill_span_share", "backlog.decode_span_share",
              "backlog.host_step_ms", "backlog.host_wait_ms_per_step",
              "backlog.ran_dry_dispatch_share", "backlog.programs_compile_s",
              "backlog.device_idle_share", "backlog.hbm_peak_gb",
              "backlog.prefill_unawaited_share",
              "backlog.pipelined_launch_share", "longcat.held_choice_share",
              "longcat.expert_load_max_over_mean",
              "lfm2.expert_products_time_share",
              "lfm2.expert_kernel_product_share",
              "backlog.host_arrays_per_dispatch"}
    assert joined <= set(MIMO_METRICS) and not joined & brought
    assert "mimo.prefill_attn_time_share" in MIMO_METRICS
    mine = [m for m in SPEC["per_layer"] if CELL in m["workloads"]]
    for m in mine:
        assert m["moves"] == ("setup_s" if m["name"].endswith(
            "programs_compile_s") else "serve_tokens_per_s")
        spec = json.load(open(os.path.join(
            REPO, "benchmark", "metrics", m["name"] + ".json")))
        assert spec["reader"].startswith("benchmark.readers.")
    shares = [m for m in mine
              if "roofline" in m["name"] or "mfu" in m["name"]]
    assert len(shares) >= 6
    assert all(m["unit"] == "%" and m["better"] == "higher" for m in shares)


# -- the counts and the readers -----------------------------------------------

def test_the_counts_are_the_issues_arithmetic():
    _, cfg = real_file()
    full, window = mimo_counts.FULL, mimo_counts.WINDOW
    assert mimo_counts.attention_params(cfg, full) == \
        4096 * (64 * 192 + 4 * 192 + 4 * 128) + 64 * 128 * 4096   # 89.1 M
    assert mimo_counts.attention_params(cfg, window) == \
        4096 * (64 * 192 + 8 * 192 + 8 * 128) + 64 * 128 * 4096   # 94.4 M
    assert mimo_counts.dense_ffn_params(cfg) == 3 * 4096 * 16384
    assert mimo_counts.expert_params(cfg) == 3 * 4096 * 2048      # 25.2 M
    assert mimo_counts.router_params(cfg) == 4096 * 256
    assert mimo_counts.layers(cfg) == (2, 5, 1, 6)
    token = 2 * 89128960 + 5 * 94371840 + 201326592 + 6 * 1048576 \
        + 4096 * 19072
    assert mimo_counts.token_params(cfg) == token
    # every parameter of the file: what every token multiplies, the
    # experts held, the embedding's slice, the norms and the sinks
    small = 15 * 4096 + 5 * 64
    assert token + 6 * 8 * 25165824 + 4096 * 19072 + small \
        == ref.num_params(cfg)
    # a cached token: 2,560 B in a full layer, 5,120 B in a window layer
    assert mimo_counts.cached_row(cfg, full) * 2 == 2560
    assert mimo_counts.cached_row(cfg, window) * 2 == 5120
    assert mimo_counts.pair_flops(cfg) == 2 * 320 * 64
    # a window layer over 8,192 tokens is a band: 1/32 of the triangle
    assert mimo_counts.band_pairs(cfg, 8192) == 8192 * 128 - 128 * 127 / 2
    assert mimo_counts.band_pairs(cfg, 100) == 100 * 101 / 2
    assert 31 < (8192 * 8193 / 2) / mimo_counts.band_pairs(cfg, 8192) < 33
    assert mimo_counts.serve_flops(cfg, 10, 3, 7, 5) == 2 * (
        10 * token + 3 * 25165824) + 40960 * (2 * 7 + 5 * 5)
    # a launch of 128 slots at 4,300 tokens that reads every held expert:
    # 1.9 GB outside the experts, 2.4 GB of experts, 2.8 GB of the full
    # pool, 0.42 GB of rings
    moved = mimo_counts.decode_bytes(cfg, 1, 6 * 8, 128 * 4300, 128 * 128)
    assert 7.4e9 < moved < 7.6e9
    ops, moved = mimo_counts.expert_products(cfg, 512, 8)
    assert ops == 2 * 25165824 * 512
    assert moved == 2 * (8 * 25165824 + 2 * 4096 * 512)
    ops, moved = mimo_counts.decode_attention(cfg, window, 1, 128 * 128, 128)
    assert ops == 40960 * 128 * 128
    assert moved == 2 * (8 * 320 * 128 * 128 + 64 * 320 * 128)
    ops, moved = mimo_counts.band_attention(cfg, 5, 4096)
    assert ops == 40960 * mimo_counts.band_pairs(cfg, 4096) * 5
    assert moved == 2 * 5 * 4096 * (64 * 320 + 8 * 320)


def _evidence(stats, trace=None):
    _, cfg = real_file()
    return {"config": cfg, "engine_stats": stats, "window": (10.0, 50.0),
            "peaks": PEAKS, "trace": trace,
            "engine_facts": {"slots": 128, "table_entries": 512,
                             "block_size": 16, "cached_sublayers": 2}}


# a window of 40 s: 150 prompts of 3,400 tokens, 3,000 launches of 128
WINDOW = {"prefill_tokens": 510000, "prefill_bucket_tokens": 740000,
          "prefill_counted": 150, "decode_tokens": 384000,
          "decode_launches": 3000, "decode_counted": 3000,
          "decode_routed_computed": 384000 * 6 * 8 // 32,
          "prefill_routed_computed": 510000 * 6 * 8 // 32,
          "decode_experts_idle": 3000 * 6 * 1, "prefill_experts_idle": 0,
          "decode_products": 0, "prefill_products": 150 * 6 * 3,
          "attn_tokens_held": 384000 * 4300,
          "window_tokens_held": 384000 * 128}


def test_the_shares_of_the_peaks_follow_the_windows_counters():
    ev = _evidence(WINDOW)
    cfg = ev["config"]
    mfu = mimo_serve_mfu.read(ev)
    want = 100 * mimo_counts.serve_flops(
        cfg, 894000, 894000 * 6 * 8 // 32,
        384000 * 4300 + 150 * 3400 * 3401 / 2,
        384000 * 128 + 150 * mimo_counts.band_pairs(cfg, 3400)) \
        / 40 / 197e12
    assert mfu == pytest.approx(want) and 0 < mfu < 100
    share = mimo_decode_hbm_roofline.read(ev)
    want = 100 * mimo_counts.decode_bytes(
        cfg, 3000, 3000 * 6 * 7, 384000 * 4300, 384000 * 128) / 819e9 / 40
    assert share == pytest.approx(want) and 0 < share < 100
    # fewer idle experts are more bytes a launch
    busier = dict(WINDOW, decode_experts_idle=0)
    assert mimo_decode_hbm_roofline.read(_evidence(busier)) > share


def test_a_kernels_roofline_reads_the_traced_calls_and_nothing_without():
    trace = {"devices": 1, "busy_s": 3.9, "window_s": 4.0,
             "op_seconds": {"%ragged_expert_matmul.1 = x": 0.3,
                            "%full_decode_attention.2 = y": 1.2,
                            "%window_decode_attention.3 = y": 0.6,
                            "%flash_band_attention.4 = z": 0.2,
                            "%fusion.5 = f(%full_decode_attention.2)": 0.5},
             "op_counts": {"%ragged_expert_matmul.1 = x": 3 * 6 * 15,
                           "%full_decode_attention.2 = y": 2 * 300,
                           "%window_decode_attention.3 = y": 5 * 300,
                           "%flash_band_attention.4 = z": 5 * 15,
                           "%fusion.5 = f(%full_decode_attention.2)": 99}}
    ev = _evidence(WINDOW, trace)
    cfg = ev["config"]
    read = mimo_kernel_roofline.read
    full = read(ev, "^%\\S*full_decode_attention", "full_attn")
    ops, moved = mimo_counts.decode_attention(
        cfg, mimo_counts.FULL, 6000, 384000 * 4300 * 2, 128)
    want = 100 * max(ops / 6000 * 600 / 197e12,
                     moved / 6000 * 600 / 819e9) / 1.2
    assert full == pytest.approx(want) and 0 < full < 100
    window = read(ev, "^%\\S*window_decode_attention", "window_attn")
    assert 0 < window < 100
    # the window layers' count is of the WINDOWS: a kernel given the
    # contexts' time for them would read 1/34 as much
    band = read(ev, "^%\\S*flash_band_attention", "band")
    assert 0 < band < 100
    experts = read(ev, "^%\\S*ragged", "experts", 3)
    ops, moved = mimo_counts.expert_products(
        cfg, 510000 * 6 * 8 // 32, 150 * 6 * 8)
    want = 100 * max(ops / 900 * 90 / 197e12,
                     moved / 900 * 90 / 819e9) / 0.3
    assert experts == pytest.approx(want) and 0 < experts < 100
    # no instruction matches: nothing, not 0.0
    assert read(ev, "^%\\S*no_such_kernel", "full_attn") is None
    assert read(_evidence(WINDOW), "ragged", "experts", 3) is None
    # no call of the window took the grouped form: nothing to read
    masked = dict(WINDOW, prefill_products=0)
    assert read(_evidence(masked, trace), "^%\\S*ragged", "experts",
                3) is None


def test_a_program_without_the_counters_leaves_the_metrics_out():
    """What a program without this PR's counters reports (the parent's, on
    which the driver lays these files too): each new reader returns
    nothing and does not raise."""
    bare = {"steps": 5, "attn_held_share": 0.2, "prefill_share": 0.1,
            "prefill_tokens": 10, "decode_tokens": 10, "decode_launches": 2,
            "decode_routed_computed": 4, "decode_experts_idle": 1,
            "decode_counted": 2}
    trace = {"devices": 1, "busy_s": 1.0, "window_s": 1.0,
             "op_seconds": {"%full_decode_attention.1": 0.5},
             "op_counts": {"%full_decode_attention.1": 3}}
    for ev in (_evidence(bare, trace), {"config": {}}):
        assert mimo_serve_mfu.read(ev) is None
        assert mimo_decode_hbm_roofline.read(ev) is None
        assert mimo_kernel_roofline.read(
            ev, "full_decode_attention", "full_attn") is None
