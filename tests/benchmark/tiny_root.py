"""A benchmark root of tiny cells in a temporary directory, for driving the
harness on the CPU: new configuration, traffic and limits files beside
copies of the real metric files; no file of the benchmark is edited. And
the ONE reader of the real `BENCHMARK.json` for the tests (`spec_of`,
`arrival_root`): the file as it stands, and the file after an arrival, so that
a test which pins a tail, a length or a set of names fails in the PR that
writes it and not in the PR that brings the next configuration."""
from __future__ import annotations

import copy
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_MODEL = {
    "activation_function": "gelu_new", "attn_pdrop": 0.0, "embd_pdrop": 0.0,
    "resid_pdrop": 0.0, "initializer_range": 0.02,
    "layer_norm_epsilon": 1e-05, "n_ctx": 64, "n_embd": 64, "n_head": 4,
    "n_inner": 256, "n_layer": 2, "n_positions": 64, "vocab_size": 256,
    "tie_word_embeddings": True,
    "precision": {"params": "bfloat16", "activations": "bfloat16",
                  "optimizer_state": "float32", "control": "fp8"},
    "optimizer": {"name": "AdamW", "learning_rate": 1e-4,
                  "weight_decay": 0.01, "beta1": 0.9, "beta2": 0.999,
                  "epsilon": 1e-8},
    "program": "benchmark.programs.paddle_gpt",
    "reference": "benchmark.reference.gpt",
}
# ANOTHER model than GPT (its reference and program are kept in
# `other_model/`): other key names, float32, so its control is bfloat16
OTHER_MODEL = {
    "hidden_size": 32, "intermediate_size": 48, "num_hidden_layers": 2,
    "num_attention_heads": 4, "rms_norm_eps": 1e-6, "vocab_size": 128,
    "initializer_range": 0.02, "tie_word_embeddings": False,
    "precision": {"params": "float32", "activations": "float32",
                  "optimizer_state": "float32", "control": "bfloat16"},
    "optimizer": TINY_MODEL["optimizer"],
    "program": "other_program", "reference": "other_reference",
}
TINY_SERVE = {
    "engine": {"max_batch_size": 4, "block_size": 4, "max_context": 64},
    "prefill_buckets": [8, 16], "compile_tokens": 2,
    "prompt_tokens": {"median": 8, "sigma": 0.5, "lo": 5, "hi": 16},
    "output_tokens": {"median": 6, "sigma": 0.5, "lo": 3, "hi": 12},
    "block": 8, "trace_seconds": 0.2, "checked_requests": 3,
    "reference_pad_to": 32,
}
TRAFFIC = {
    "tiny_train": {"loop": "train", "rate_metric": "train_tokens_per_s",
                   "batch_rows": 4, "seq": 32,
                   "donate": "all", "distinct_batches": 4, "readings": 9,
                   "trace_seconds": 0.2, "reference_rows_per_block": 2},
    "tiny_mesh": {"loop": "train", "rate_metric": "mesh_train_tokens_per_s",
                  "batch_rows": 4, "seq": 32,
                  "donate": True, "mesh": {"data": 2, "model": 2},
                  "distinct_batches": 4, "readings": 9,
                  "trace_seconds": 0.2},
    "tiny_backlog": {"loop": "serve_backlog", **TINY_SERVE,
                     "queue_depth": 4, "warm_completions": 4},
}
CELLS = [("tiny_train_cell", "tiny_train", 1), ("tiny_mesh_cell", "tiny_mesh", 4),
         ("tiny_backlog_cell", "tiny_backlog", 1)]
TRAIN_LIMITS = {"loss_gap": 1e-3, "grad_norm_gap": 0.05,
                "delta_norm_gap": 0.05, "state_max_share": 0.75}
SERVE_LIMITS = {"logit_gap": 0.05, "logit_gap_mean": 0.005}


def _dump(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


# -- the real file, as it stands and after an arrival ------------------------
# What a `model_config` PR brings, as files and entries: one configuration
# (a layer pattern of the irregular kind: one leading dense layer under
# `num_dense_layers`, period 3, a tail that departs from it), one cell
# appended to an end-to-end metric's `workloads` AND to the `workloads` of the
# shared per-layer entries that every cell of its loop reports (`joins`: no
# entry, no file), two per-layer entries of its own at the END of the list,
# each a data file over a reader that exists.
STANDINGS = ("as_it_stands", "with_an_arrival")
_PUBLISHED_TYPES = ["conv"] + ["full_attention", "conv", "conv"] * 3 \
    + ["full_attention", "conv"] * 2
ARRIVAL_CONFIG = {
    "hidden_size": 64, "intermediate_size": 256, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "conv_L_cache": 3,
    "num_experts": 8, "num_experts_per_tok": 2, "vocab_size": 256,
    "num_dense_layers": 1, "num_hidden_layers": 6,
    "layer_types": _PUBLISHED_TYPES[:6],
    "published": {"num_hidden_layers": len(_PUBLISHED_TYPES),
                  "layer_types": _PUBLISHED_TYPES},
    "changed": {"num_hidden_layers": "14 -> the dense layer + 5",
                "layer_types": "the first 6 of 14"},
    "source": "test", "deployment": "one chip holds the first six layers",
    "program": "arrival_program", "reference": "arrival_reference",
}
ARRIVAL = {
    "config": {"name": "arrival_hybrid", "source": "test",
               "file": "benchmark/configs/arrival_hybrid.json",
               "reduced": ["num_hidden_layers", "layer_types"],
               "why": "test"},
    "cell": {"name": "serve_arrival_decode", "config": "arrival_hybrid",
             "traffic": "arrival_backlog", "chips": 1, "why": "test"},
    "end_to_end": "serve_tokens_per_s",
    # what every cell of the `serve_backlog` loop reports is JOINED: an
    # entry of its own over `engine_stat`'s `step_ms_per_step` would be a
    # twin of this one (`test_no_two_per_layer_entries_are_twins`)
    "joins": ["backlog.host_step_ms"],
    "per_layer": {
        "arrival.conv_time_share": {
            "unit": "%", "source": "device_trace",
            "file": {"reader": "benchmark.readers.trace_op_share",
                     "args": {"pattern": "short_conv"}}},
        "arrival.decode_attn_roofline": {
            "unit": "%", "source": "device_trace", "better": "higher",
            "file": {"reader": "benchmark.readers.trace_kernel_roofline",
                     "args": {"pattern": "grouped_decode_attention"}}}},
}


def spec_of(standing):
    """The real `BENCHMARK.json` as a dict: as it stands, or with the
    arrival's entries appended (in memory: nothing is written)."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    if standing == "as_it_stands":
        return spec
    assert standing == "with_an_arrival", standing
    cell = ARRIVAL["cell"]["name"]
    spec["configs"].append(copy.deepcopy(ARRIVAL["config"]))
    spec["workloads"].append(dict(ARRIVAL["cell"]))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in (ARRIVAL["end_to_end"], *ARRIVAL["joins"]):
            m["workloads"].append(cell)
    for name, m in ARRIVAL["per_layer"].items():
        spec["per_layer"].append({
            "name": name, "unit": m["unit"],
            "better": m.get("better", "lower"), "source": m["source"],
            "layer": "test", "moves": ARRIVAL["end_to_end"],
            "workloads": [cell]})
    return spec


def arrival_root(scratch):
    """Where the data files beside `spec_of("with_an_arrival")` lie: a root
    at `scratch` that holds copies of the benchmark's data files, the
    arrival's own beside them, and the extended `BENCHMARK.json`, laid out
    as the repository is (the file as it stands has the repository itself).
    The code (`loops/`, `readers/`, `programs/`) is the repository's."""
    root = str(scratch)
    data = os.path.join(root, "benchmark")
    for kind in ("configs", "traffic", "limits", "metrics"):
        shutil.copytree(os.path.join(REPO, "benchmark", kind),
                        os.path.join(data, kind))
    _dump(os.path.join(root, ARRIVAL["config"]["file"]), ARRIVAL_CONFIG)
    _dump(os.path.join(data, "traffic",
                       ARRIVAL["cell"]["traffic"] + ".json"),
          TRAFFIC["tiny_backlog"])
    _dump(os.path.join(data, "limits", ARRIVAL["cell"]["name"] + ".json"),
          SERVE_LIMITS)
    for name, m in ARRIVAL["per_layer"].items():
        _dump(os.path.join(data, "metrics", name + ".json"), m["file"])
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec_of("with_an_arrival"), f, indent=1)
    return root


def config_cases():
    """(standing, configuration's name) for every configuration of every
    standing: what a test of one configuration is parametrised over."""
    return [(standing, c["name"]) for standing in STANDINGS
            for c in spec_of(standing)["configs"]]


def make(root):
    """Write the tiny root under `root`; returns its BENCHMARK.json dict."""
    real = spec_of("as_it_stands")
    data = os.path.join(root, "benchmark")
    shutil.copytree(os.path.join(REPO, "benchmark", "metrics"),
                    os.path.join(data, "metrics"))
    _dump(os.path.join(data, "configs", "tiny_gpt.json"), TINY_MODEL)
    for name, mix in TRAFFIC.items():
        _dump(os.path.join(data, "traffic", name + ".json"), mix)
    for cell, traffic, _ in CELLS:
        _dump(os.path.join(data, "limits", cell + ".json"),
              TRAIN_LIMITS if TRAFFIC[traffic]["loop"] == "train"
              else SERVE_LIMITS)
    kinds = {"train_124m_step": "tiny_train_cell",
             "train_1p3b_mesh4": "tiny_mesh_cell",
             "serve_124m_backlog": "tiny_backlog_cell"}

    def renamed(m):
        m = dict(m)
        if "workloads" in m:
            m["workloads"] = [kinds[w] for w in m["workloads"] if w in kinds]
        return m

    spec = dict(real, paths=["benchmark"], configs=[
        {"name": "tiny_gpt", "source": "test",
         "file": "benchmark/configs/tiny_gpt.json", "reduced": [],
         "why": "test"}],
        workloads=[{"name": c, "config": "tiny_gpt", "traffic": t,
                    "chips": n, "why": "test"} for c, t, n in CELLS],
        end_to_end=[renamed(m) for m in real["end_to_end"]],
        per_layer=[renamed(m) for m in real["per_layer"]])
    _dump(os.path.join(root, "BENCHMARK.json"), spec)
    return spec


def add_cell(root, cell, config, mix, limits, metrics):
    """One more cell in the root under `root`, added as a later PR adds
    one: files of its own and entries appended, nothing there edited.
    `config` and `mix` are (name, dict); `metrics` names the end-to-end
    and per-layer metrics whose `workloads` gain the cell."""
    data = os.path.join(root, "benchmark")
    _dump(os.path.join(data, "configs", config[0] + ".json"), config[1])
    _dump(os.path.join(data, "traffic", mix[0] + ".json"), mix[1])
    _dump(os.path.join(data, "limits", cell + ".json"), limits)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": config[0], "source": "test", "reduced": [], "why": "test",
        "file": f"benchmark/configs/{config[0]}.json"})
    spec["workloads"].append({"name": cell, "config": config[0],
                              "traffic": mix[0], "chips": 1, "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in metrics:
            m["workloads"].append(cell)
    _dump(os.path.join(root, "BENCHMARK.json"), spec)
