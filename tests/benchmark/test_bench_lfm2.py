"""The LFM2-MoE family through the benchmark, on the CPU at a tiny size
(`lfm2_model/tiny_lfm2.py`): its cell runs on the `serve_backlog` loop with
the REAL program and reference modules and is correct; served in the fp8
control it is not, by the mean gap; served with a convolution state that
the prefill left at zeros it is not, by both numbers; the real
configuration file keeps every published number and passes the rules; the
traffic file holds the issue's parameters; the counts and the readers of
its per-layer metrics."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "lfm2_model")]
import config_rules  # noqa: E402
import metric_rules  # noqa: E402
import tiny_root  # noqa: E402
from tiny_lfm2 import TINY_LFM2  # noqa: E402

from benchmark import correct, harness, lfm2_counts, \
    run as bench_run, seeded, traffic  # noqa: E402
from benchmark.loops import serving  # noqa: E402
from benchmark.readers import lfm2_decode_hbm_roofline, \
    lfm2_kernel_roofline, lfm2_serve_mfu  # noqa: E402
from benchmark.reference import lfm2_moe as ref  # noqa: E402

REPO = tiny_root.REPO
CELL, CONFIG = "serve_lfm2_rag_backlog", "lfm2_8b_a1b_l16"
# from readings on the CPU over seeds 3, 5, 2**31 + 7 and 3000028201 (16
# requests a sample, some 100-190 served tokens): the program through the
# engine reads a widest gap <= 0.32 and a mean <= 0.0047, a bfloat16
# stand-in <= 0.49 and <= 0.0041; the fp8 control's widest 0.28-0.77 and
# mean 0.0162-0.0403; a state the prefill left at zeros 0.97-1.75 and
# 0.074-0.173. The mean's limit lies between twice the sound largest and
# the control's smallest (so few tokens do not part them by the whole rule
# of PERF.md section 4); the widest guards against gross faults only: the
# control passes it, the zeroed state does not
LIMITS = {"logit_gap": 0.9, "logit_gap_mean": 0.01}
STREAMS, PROMPT, SERVED, PAD = 8, 8, 24, 32
SPEC = tiny_root.spec_of("as_it_stands")
# the cell's metrics are every entry whose `workloads` names it: its own
# (`lfm2.*`) and the shared ones of its loop and its expert block, which
# it joined (ISSUE 47; a prefix names who brought an entry, not a cell)
LFM2_METRICS = [m["name"] for m in SPEC["per_layer"]
                if CELL in m["workloads"]]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture
def lfm2_root(root):
    tiny_root.add_cell(
        root, "lfm2_cell", ("tiny_lfm2", TINY_LFM2),
        # 16 requests a sample: over 100 served tokens, so that a mean
        # gap is a mean
        ("lfm2_mix", dict(tiny_root.TRAFFIC["tiny_backlog"],
                          reference_pad_to=PAD, checked_requests=16)),
        LIMITS, ("serve_tokens_per_s", *LFM2_METRICS))
    return root


def real_file():
    entry = next(c for c in SPEC["configs"] if c["name"] == CONFIG)
    return entry, json.load(open(os.path.join(REPO, entry["file"])))


@pytest.mark.parametrize("seed", [5, 2 ** 31 + 7])
def test_the_tiny_cell_runs_on_the_backlog_loop_and_is_correct(lfm2_root,
                                                               seed):
    line = bench_run.run_cell(lfm2_root, "lfm2_cell", seed, 1.0, False,
                              require_chip=False)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert set(line["checks"]) == set(serving.COMPARED)


def test_a_traced_run_reports_every_metric_of_the_cell(lfm2_root,
                                                       monkeypatch):
    """Every metric of the cell appears, finite, with a canned device trace
    (the CPU gives the profiler no device plane) and canned peaks; a
    share of a peak stays inside 0..100."""
    import contextlib

    @contextlib.contextmanager
    def traced_slice(self):
        yield
        ops = {"%ragged-dot.7 = f32[512,1792] ragged-dot(...)": (0.3, 420),
               "%paged_decode_attention.3 = bf16[128,32,64] custom-call(...)":
                   (0.1, 40),
               "%flash_attention_fwd.2 = bf16[32,2048,64] custom-call(...)":
                   (0.05, 8),
               "%fusion.9 = bf16[8] fusion(%ragged-dot.7)": (0.2, 10)}
        self.evidence["trace"] = {
            "window_s": 1.0, "devices": 1, "busy_s": 0.9,
            "collective_s": 0.0, "collective_exposed_s": 0.0,
            "op_seconds": {k: v[0] for k, v in ops.items()},
            "op_counts": {k: v[1] for k, v in ops.items()},
            "gaps": [], "spans": []}
        self.evidence["peaks"] = PEAKS
    monkeypatch.setattr(harness.Run, "traced_slice", traced_slice)
    line = bench_run.run_cell(lfm2_root, "lfm2_cell", 5, 1.0, True,
                              require_chip=False)
    # the CPU's backend reports no memory peak: that one reader finds
    # nothing to read and its metric is left out, not raised
    assert set(line["metrics"]) == set(LFM2_METRICS) \
        - {"backlog.hbm_peak_gb"}
    value = {k: m["value"] for k, m in line["metrics"].items()}
    assert all(np.isfinite(v) for v in value.values()), value
    for name in ("lfm2.serve_mfu", "lfm2.decode_hbm_roofline",
                 "lfm2.expert_products_roofline",
                 "lfm2.decode_attn_roofline",
                 "backlog.kv_pool_filled_share",
                 "lfm2.prefill_padding_share",
                 "backlog.pipelined_launch_share"):
        assert 0 < value[name] <= 100, name
    # every expert is held: nothing is routed elsewhere, at any size
    assert value["longcat.held_choice_share"] == 100.0
    assert value["lfm2.expert_load_max_over_mean"] >= 1
    # the CPU's grouped products are `ragged_dot`'s, none the tiled
    # kernel's; a call's programs take ONE host array each (PR 43)
    assert value["lfm2.expert_kernel_product_share"] == 0.0
    assert value["backlog.host_arrays_per_dispatch"] == 1.0
    # the anchored patterns count the kernels, not a fusion that takes
    # their result
    assert value["lfm2.expert_products_time_share"] == \
        pytest.approx(100 * 0.3 / 0.9)
    assert value["backlog.decode_attn_time_share"] == \
        pytest.approx(100 * 0.1 / 0.9)
    assert value["lfm2.prefill_attn_time_share"] == \
        pytest.approx(100 * 0.05 / 0.9)


def served_in(precision, seed):
    """Requests decoded greedily by the reference computed in
    `precision`: [(prompt ids, served ids)]."""
    import jax
    import jax.numpy as jnp
    weights = correct.weight_maker(TINY_LFM2, seed)()
    rng = seeded.host_rng(seed, 9)
    ids = np.zeros((STREAMS, PAD), np.int32)
    ids[:, :PROMPT] = rng.integers(0, TINY_LFM2["vocab_size"],
                                   (STREAMS, PROMPT))

    @jax.jit
    def first(w, ids, at):
        return jnp.argmax(ref.forward(w, ids, TINY_LFM2,
                                      precision)[:, at], -1)

    for at in range(PROMPT - 1, PROMPT + SERVED - 1):
        ids[:, at + 1] = np.asarray(first(weights, jnp.asarray(ids), at))
    return [(row[:PROMPT].tolist(), row[PROMPT:PROMPT + SERVED].tolist())
            for row in ids]


def checked(root, seed, streams):
    run = harness.Run(root, "lfm2_cell", seed, 1.0, False,
                      require_chip=False)
    serving.check_served(run, streams)
    assert set(c[0] for c in run.checks) == set(serving.COMPARED)
    return {name: ok for name, _, _, ok in run.checks}


@pytest.mark.parametrize("seed", [3, 3000028201])
@pytest.mark.parametrize("precision,correct_", [("bfloat16", True),
                                                ("fp8", False)])
def test_served_in_the_fp8_control_it_fails_by_the_mean_gap(
        lfm2_root, seed, precision, correct_):
    checks = checked(lfm2_root, seed, served_in(precision, seed))
    assert checks["logit_gap"]              # the widest passes either way
    assert checks["logit_gap_mean"] == correct_


@pytest.mark.parametrize("broken,correct_", [(False, True), (True, False)])
def test_a_state_the_prefill_left_at_zeros_fails_by_both_numbers(
        lfm2_root, monkeypatch, broken, correct_):
    """The program's own engine over fixed prompts (no clock decides the
    sample): sound it passes both numbers; with every convolution's state
    left at zeros by the prefill it fails BOTH, the widest too (the first
    tokens behind a prompt are computed from a wrong past)."""
    from paddle_tpu.incubate.models import lfm2_moe as lfm
    from benchmark.programs import paddle_lfm2
    seed = 3000028201
    if broken:
        import jax.numpy as jnp
        sound = lfm.Lfm2MoeForCausalLM._short_conv

        def zeroed(self, u, p, state, length):
            out, new = sound(self, u, p, state, length)
            return out, (new if u.shape[1] == 1 else jnp.zeros_like(new))
        monkeypatch.setattr(lfm.Lfm2MoeForCausalLM, "_short_conv", zeroed)
    mix = tiny_root.TRAFFIC["tiny_backlog"]
    engine = paddle_lfm2.build_engine(TINY_LFM2, mix,
                                      correct.weight_maker(TINY_LFM2, seed))
    rng = seeded.host_rng(seed, 9)
    prompts = [seeded.token_ids(rng, n, TINY_LFM2["vocab_size"])
               for n in (5, 9, 13, 7, 11, 6, 8, 12, 3, 10, 14, 4)]
    served = engine.generate(prompts, max_new_tokens=12)
    checks = checked(lfm2_root, seed, list(zip(prompts, served)))
    assert checks == {"logit_gap": correct_, "logit_gap_mean": correct_}


# -- the real files -----------------------------------------------------------

def test_the_configuration_keeps_every_published_number():
    entry, cfg = real_file()
    assert config_rules.problems(entry, cfg) == []
    types = ["conv", "conv", "full_attention"] \
        + ["conv", "conv", "conv", "full_attention"] * 4 \
        + ["conv", "conv", "full_attention", "conv", "conv"]
    catalog = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "layer_types": types[:24],
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1792, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536}
    assert len(catalog["layer_types"]) == 24
    for key, value in catalog.items():
        if key in entry["reduced"]:
            assert cfg["published"][key] == value and cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers",
                                                  "layer_types"]
    assert cfg["num_hidden_layers"] == 16
    assert cfg["layer_types"] == catalog["layer_types"][:16]
    assert set(cfg["changed"]) == set(entry["reduced"])
    for assumed in ("tied_head", "head_dim", "rope", "norms",
                    "router_epsilon", "expert_bias", "initializer_range",
                    "conv_state"):
        assert cfg["assumed"][assumed]
    assert "optimizer" not in cfg and cfg["precision"]["control"] == "fp8"
    assert len(entry["source"]) <= 200 and entry["source"].startswith(
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B")


@pytest.mark.parametrize("key", ["num_experts_per_tok", "hidden_size",
                                 "moe_intermediate_size", "conv_L_cache",
                                 "num_dense_layers_x"])
def test_the_rules_refuse_a_cut_of_what_is_no_count_held(key):
    entry, cfg = real_file()
    cut = dict(entry, reduced=entry["reduced"] + [key])
    found = config_rules.problems(cut, dict(cfg, changed=dict(
        cfg["changed"], **{key: "cut"})))
    assert any(f"names {key}: a width, or no kind" in f for f in found)


def test_the_reference_states_the_files_shapes_and_imports_no_program():
    _, cfg = real_file()
    shapes = ref.param_shapes(cfg)
    assert shapes["model.layers.0.conv.in_proj.weight"] == (2048, 6144)
    assert shapes["model.layers.0.conv.conv.weight"] == (2048, 3)
    assert shapes["model.layers.2.self_attn.k_proj.weight"] == (2048, 512)
    assert shapes["model.layers.2.self_attn.q_layernorm.weight"] == (64,)
    assert shapes["model.layers.0.feed_forward.w1.weight"] == (2048, 7168)
    assert shapes["model.layers.2.feed_forward.gate.weight"] == (2048, 32)
    assert shapes["model.layers.15.feed_forward.experts.w2.weight"] == \
        (32, 1792, 2048)
    assert not any("expert_bias" in k or "lm_head" in k for k in shapes)
    assert "model.layers.16.operator_norm.weight" not in shapes
    # 2 bytes a parameter: 10.8 GB of the chip's 15.75
    assert ref.num_params(cfg) == 5399128576
    assert not any("paddle" in line for line in open(ref.__file__)
                   if line.startswith(("import", "from")))
    assert not hasattr(ref, "loss_and_grads")        # serving only


def test_the_cell_and_its_traffic_are_the_issues():
    cell = next(w for w in SPEC["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (CONFIG, "backlog_rag_2k", 1)
    mix = json.load(open(os.path.join(
        REPO, "benchmark", "traffic", "backlog_rag_2k.json")))
    assert mix["engine"] == {"max_batch_size": 128, "block_size": 16,
                             "max_context": 2048}
    assert mix["prompt_tokens"] == {"median": 768, "sigma": 0.6,
                                    "lo": 129, "hi": 1792}
    assert mix["output_tokens"] == {"median": 128, "sigma": 0.5,
                                    "lo": 32, "hi": 256}
    assert (mix["loop"], mix["queue_depth"], mix["block"],
            mix["warm_completions"], mix["checked_requests"],
            mix["reference_pad_to"], mix["trace_seconds"],
            mix["compile_tokens"]) == ("serve_backlog", 32, 16, 128, 16,
                                       2048, 4, 4)
    # the set-up sends ONE prompt of each listed length with
    # `compile_tokens` new tokens, and 2048 + 4 does not fit `max_context`:
    # the last entry is the longest prompt that does, whose bucket is 2048
    assert mix["prefill_buckets"] == [256, 512, 1024, 2044]
    bucket = lambda n: max(8, 1 << (n - 1).bit_length())
    compiled = {bucket(n) for n in mix["prefill_buckets"]}
    assert compiled == {256, 512, 1024, 2048}
    assert max(mix["prefill_buckets"]) + mix["compile_tokens"] \
        <= mix["engine"]["max_context"]
    prompts = traffic.stratified_lengths(mix["prompt_tokens"], mix["block"])
    outputs = traffic.stratified_lengths(mix["output_tokens"], mix["block"])
    assert {bucket(n) for n in prompts} == compiled  # every bucket is used
    assert max(prompts) + max(outputs) <= mix["reference_pad_to"] \
        <= mix["engine"]["max_context"]
    # prefill-heavy: six prompt tokens an output token, a pool near half
    # full (mean context over the table's 2,048)
    assert sum(prompts) > 6 * sum(outputs)
    mean_context = sum(prompts) / 16 + sum(outputs) / 32
    assert 0.35 < mean_context / 2048 < 0.55
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    limits = json.load(open(os.path.join(
        REPO, "benchmark", "limits", CELL + ".json")))
    assert set(limits) == set(serving.COMPARED)


def test_every_metric_of_the_cell_names_it_and_has_its_file():
    """Membership, not a count: what ISSUE 40 brought (the parent's
    entries that named this cell, under today's names) is all among the
    entries that name the cell, and later PRs append or join beside it."""
    brought = {metric_rules.today(m["name"])
               for m in metric_rules.PARENT_PER_LAYER
               if m["workloads"] == [CELL]}
    assert len(brought) == 22 and brought <= set(LFM2_METRICS)
    assert "lfm2.expert_kernel_product_share" in LFM2_METRICS   # ISSUE 41's
    mine = [m for m in SPEC["per_layer"] if CELL in m["workloads"]]
    for m in mine:
        assert m["moves"] == ("setup_s" if m["name"].endswith(
            "programs_compile_s") else "serve_tokens_per_s")
        spec = json.load(open(os.path.join(
            REPO, "benchmark", "metrics", m["name"] + ".json")))
        assert spec["reader"].startswith("benchmark.readers.")
    shares = [m for m in mine
              if "roofline" in m["name"] or "mfu" in m["name"]]
    assert len(shares) >= 4
    assert all(m["unit"] == "%" and m["better"] == "higher" for m in shares)


# -- the counts and the readers -----------------------------------------------

def test_the_counts_are_the_issues_arithmetic():
    _, cfg = real_file()
    assert lfm2_counts.conv_params(cfg) == 4 * 2048 * 2048          # 16.8 M
    assert lfm2_counts.attention_params(cfg) == 2048 * (2048 + 1024 + 2048)
    assert lfm2_counts.dense_ffn_params(cfg) == 3 * 2048 * 7168     # 44.0 M
    assert lfm2_counts.expert_params(cfg) == 3 * 2048 * 1792        # 11.0 M
    assert lfm2_counts.layers(cfg) == (12, 4, 2, 14)
    token = 12 * 16777216 + 4 * 10485760 + 2 * 44040192 + 14 * 65536 \
        + 2048 * 65536
    assert lfm2_counts.token_params(cfg) == token
    # every parameter of the file: what every token multiplies, the
    # experts, the norms and the convolutions' taps
    small = 33 * 2048 + 8 * 64 + 12 * 2048 * 3
    assert token + 14 * 32 * 11010048 + small == ref.num_params(cfg)
    assert lfm2_counts.serve_flops(cfg, 10, 3, 7) == 2 * (
        10 * token + 3 * 11010048) + 4 * 64 * 32 * 4 * 7
    # a launch that reads every expert and 128 x 970 tokens a layer:
    # 0.93 GB outside the experts, 9.87 GB of experts, 1.0 GB of rows
    moved = lfm2_counts.decode_bytes(cfg, 1, 14 * 32, 128 * 970 * 4, 128)
    assert 11.7e9 < moved < 11.9e9
    ops, moved = lfm2_counts.expert_products(cfg, 512, 32)
    assert ops == 2 * 11010048 * 512
    assert moved == 2 * (32 * 11010048 + 2 * 2048 * 512)
    ops, moved = lfm2_counts.decode_attention(cfg, 1, 128 * 970, 128)
    assert ops == 4 * 64 * 32 * 128 * 970
    assert moved == 2 * (2 * 512 * 128 * 970 + 2 * 2048 * 128)


def _evidence(stats, trace=None):
    _, cfg = real_file()
    return {"config": cfg, "engine_stats": stats, "window": (10.0, 50.0),
            "peaks": PEAKS, "trace": trace,
            "engine_facts": {"slots": 128, "table_entries": 128,
                             "block_size": 16, "cached_sublayers": 4}}


WINDOW = {"prefill_tokens": 870000, "prefill_bucket_tokens": 1230000,
          "prefill_counted": 1000, "decode_tokens": 140000,
          "decode_launches": 1100, "decode_counted": 1100,
          "decode_routed_computed": 140000 * 14 * 4,
          "prefill_routed_computed": 870000 * 14 * 4,
          "decode_experts_idle": 1100 * 14 * 1,
          "prefill_experts_idle": 0, "attn_held_share": 0.45}


def test_the_shares_of_the_peaks_follow_the_windows_counters():
    ev = _evidence(WINDOW)
    cfg = ev["config"]
    held = 0.45 * 1100 * 128 * 128 * 16 - 140000 * 15
    assert lfm2_serve_mfu.decode_tokens_held(WINDOW, ev["engine_facts"]) \
        == pytest.approx(held)
    mfu = lfm2_serve_mfu.read(ev)
    pairs = held + 1000 * 870 * 871 / 2
    want = 100 * lfm2_counts.serve_flops(
        cfg, 1010000, 1010000 * 56, pairs) / 40 / 197e12
    assert mfu == pytest.approx(want) and 0 < mfu < 100
    share = lfm2_decode_hbm_roofline.read(ev)
    want = 100 * lfm2_counts.decode_bytes(
        cfg, 1100, 1100 * 14 * 31, held * 4, 128) / 819e9 / 40
    assert share == pytest.approx(want) and 0 < share < 100
    # fewer idle experts are more bytes a launch
    busier = dict(WINDOW, decode_experts_idle=0)
    assert lfm2_decode_hbm_roofline.read(_evidence(busier)) > share


def test_a_kernels_roofline_reads_the_traced_calls_and_nothing_without():
    trace = {"devices": 1, "busy_s": 3.9, "window_s": 4.0,
             "op_seconds": {"%ragged-dot.1 = x": 3.2,
                            "%paged_decode_attention.2 = y": 0.4,
                            "%fusion.3 = f(%ragged-dot.1)": 0.5},
             "op_counts": {"%ragged-dot.1 = x": 3 * 14 * 210,
                           "%paged_decode_attention.2 = y": 4 * 110,
                           "%fusion.3 = f(%ragged-dot.1)": 99}}
    ev = _evidence(WINDOW, trace)
    cfg = ev["config"]
    experts = lfm2_kernel_roofline.read(ev, "^%\\S*ragged", "experts", 3)
    calls = 14 * 2100
    ops, moved = lfm2_counts.expert_products(
        cfg, 1010000 * 56, 2100 * 14 * 32 - 1100 * 14)
    traced = 14 * 210
    want = 100 * max(ops / calls * traced / 197e12,
                     moved / calls * traced / 819e9) / 3.2
    assert experts == pytest.approx(want) and 0 < experts < 100
    attn = lfm2_kernel_roofline.read(ev, "^%\\S*paged_decode_attention",
                                     "decode_attn")
    assert 0 < attn < 100
    # no instruction matches: nothing, not 0.0
    assert lfm2_kernel_roofline.read(ev, "^%\\S*no_such_kernel",
                                     "experts", 3) is None
    assert lfm2_kernel_roofline.read(_evidence(WINDOW), "ragged",
                                     "experts", 3) is None


def test_a_program_without_the_counters_leaves_the_metrics_out():
    """What a program without this PR's counters reports: each new reader
    returns nothing and does not raise."""
    bare = {"steps": 5, "attn_held_share": 0.2, "prefill_share": 0.1,
            "prefill_tokens": 10, "decode_tokens": 10, "decode_launches": 2}
    trace = {"devices": 1, "busy_s": 1.0, "window_s": 1.0,
             "op_seconds": {"%ragged-dot.1": 0.5},
             "op_counts": {"%ragged-dot.1": 3}}
    for ev in (_evidence(bare, trace), {"config": {}}):
        assert lfm2_serve_mfu.read(ev) is None
        assert lfm2_decode_hbm_roofline.read(ev) is None
        assert lfm2_kernel_roofline.read(ev, "ragged", "experts", 3) is None
