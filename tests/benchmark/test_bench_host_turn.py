"""The per-layer metrics of the host's turn (ISSUE 37): data files over
the readers that exist, each naming a key that the program's `stats()`
really has (a misspelt key would read null for ever), reported by the tiny
traced cells, and left out where the program has no such key."""
import json
import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import metric_rules  # noqa: E402
import tiny_root  # noqa: E402

from benchmark import harness, run as bench_run  # noqa: E402
from benchmark.readers import engine_stat, train_step_stat  # noqa: E402

REPO = tiny_root.REPO
# the names ISSUE 37 gave; since ISSUE 47 the `longcat.*` five are their
# `backlog.*` twins' entries, found through the table (`metric_rules.today`)
# metric -> (reader, key of `stats()`, scale)
PER_STEP = ("prefill_dispatch", "decode_dispatch", "decode_fetch", "stream",
            "kv_grow", "prefill_commit", "host_wait", "step_unattributed",
            "gc")
ENGINE = {"host_step_ms": "step_ms_per_step", "step_max_ms": "step_max_ms",
          "host_wait_ms_per_step": "host_wait_ms_per_step",
          "starved_dispatch_share": "starved_dispatch_share",
          "ran_dry_dispatch_share": "ran_dry_dispatch_share"}
NEW = {
    **{f"backlog.{short}_ms_per_step": ("engine_stat",
                                        f"{short}_ms_per_step")
       for short in PER_STEP},
    **{f"{cell}.{name}": ("engine_stat", key)
       for cell in ("backlog", "longcat") for name, key in ENGINE.items()},
    "train.dispatch_blocked_share": ("train_step_stat",
                                     "dispatch_blocked_share"),
    "mesh.dispatch_blocked_share": ("train_step_stat",
                                    "dispatch_blocked_share"),
    "mesh.starved_dispatch_share": ("train_step_stat",
                                    "starved_dispatch_share"),
}
CELL_OF = {"backlog": ("serve_124m_backlog", "tiny_backlog_cell"),
           "longcat": ("serve_longcat_decode", None),
           "train": ("train_124m_step", "tiny_train_cell"),
           "mesh": ("train_1p3b_mesh4", "tiny_mesh_cell")}


@pytest.fixture(scope="module")
def program_stats():
    """`stats()` of a tiny engine and a tiny `TrainStep` on the CPU."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.incubate.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.serving import LLMEngine
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=64))
    model.eval()
    engine = LLMEngine(model, max_batch_size=4, block_size=8,
                       max_context=64)
    engine.generate([[1, 2, 3], [4, 5, 6, 7]], max_new_tokens=4)
    linear = paddle.nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(0.1, parameters=linear.parameters())
    step = TrainStep(linear, lambda out, y: ((out - y) ** 2).mean(), opt)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    y = paddle.to_tensor(np.zeros((2, 2), np.float32))
    step(x, y)
    step(x, y)
    return {"engine_stat": engine.stats(), "train_step_stat": step.stats()}


@pytest.fixture(scope="module")
def traced_lines(tmp_path_factory):
    """One traced run of each tiny cell, the device trace canned (the CPU
    gives the profiler no device plane)."""
    import contextlib
    root = str(tmp_path_factory.mktemp("tiny"))
    tiny_root.make(root)
    real = harness.Run.traced_slice

    @contextlib.contextmanager
    def traced_slice(self):
        yield
        self.evidence["trace"] = {
            "window_s": 1.0, "devices": 1, "busy_s": 0.9,
            "collective_s": 0.0, "collective_exposed_s": 0.0,
            "op_seconds": {}, "op_counts": {}, "gaps": [], "spans": []}
    harness.Run.traced_slice = traced_slice
    try:
        return {cell: bench_run.run_cell(root, cell, seed=2 ** 31 + 37,
                                         seconds=0.5, traced=True,
                                         require_chip=False)
                for _, cell in CELL_OF.values() if cell}
    finally:
        harness.Run.traced_slice = real


def test_the_table_of_the_issue_is_all_there(spec):
    assert len(NEW) == 21     # ISSUE 37's nineteen and `ran_dry_…` twice
    # membership, not position: later PRs append after them (`spec` is
    # the file as it stands and again with an arrival: `conftest.py`)
    assert {metric_rules.today(n) for n in NEW} <= {
        m["name"] for m in spec["per_layer"]}


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_metric_is_a_file_over_a_reader_and_a_key_that_exist(
        spec, spec_root, program_stats, name):
    today = metric_rules.today(name)
    [entry] = [m for m in spec["per_layer"] if m["name"] == today]
    real_cell, _ = CELL_OF[name.split(".")[0]]
    assert real_cell in entry["workloads"]
    assert entry["better"] == "lower"
    counter = name.endswith("_share")
    assert entry["source"] == ("program_counter" if counter
                               else "program_span")
    assert entry["unit"] == ("%" if counter else "ms")
    rate = {"serve_124m_backlog": "serve_tokens_per_s",
            "serve_longcat_decode": "serve_tokens_per_s",
            "train_124m_step": "train_tokens_per_s",
            "train_1p3b_mesh4": "mesh_train_tokens_per_s"}[real_cell]
    assert entry["moves"] == rate
    with open(os.path.join(spec_root, "benchmark", "metrics",
                           today + ".json")) as f:
        metric = json.load(f)
    reader, key = NEW[name]
    assert metric["reader"] == "benchmark.readers." + reader
    assert os.path.exists(os.path.join(REPO, "benchmark", "readers",
                                       reader + ".py"))
    assert metric["args"] == ({"key": key, "scale": 100.0} if counter
                              else {"key": key})
    value = program_stats[reader][key]
    assert isinstance(value, (int, float)) and math.isfinite(value)


@pytest.mark.parametrize("name", sorted(n for n in NEW
                                        if not n.startswith("longcat.")))
def test_the_tiny_traced_cell_reports_the_metric(traced_lines, name):
    _, cell = CELL_OF[name.split(".")[0]]
    metrics = traced_lines[cell]["metrics"]
    assert name in metrics
    value = metrics[name]["value"]
    assert math.isfinite(value) and value >= 0
    if name.endswith("_share"):
        assert value <= 100.0


def test_the_direct_children_and_the_rest_add_up_to_the_step(traced_lines):
    m = traced_lines["tiny_backlog_cell"]["metrics"]

    def ms(short):
        return m[f"backlog.{short}"]["value"]
    assert ms("host_step_ms") > 0
    assert ms("step_max_ms") >= ms("host_step_ms")
    assert ms("host_wait_ms_per_step") <= ms("host_step_ms")
    # `engine.admit` and `engine.decode` have no metric of their own: the
    # four that do, and what no span covers, stay inside the step
    inside = sum(ms(s + "_ms_per_step") for s in (
        "stream", "kv_grow", "prefill_commit", "step_unattributed"))
    assert inside <= ms("host_step_ms")


def test_a_program_without_the_keys_leaves_the_metrics_out(monkeypatch):
    """What the parent commit gives the readers."""
    assert engine_stat.read({"engine_stats": {"steps": 3}},
                            key="step_ms_per_step") is None
    assert engine_stat.read({}, key="starved_dispatch_share",
                            scale=100.0) is None
    import paddle_tpu.jit as jit
    monkeypatch.setattr(jit, "train_step_stats",
                        lambda: [{"steps": 3, "dispatch_p50_ms": 1.0}])
    assert train_step_stat.read({}, key="dispatch_p50_ms") == 1.0
    assert train_step_stat.read({}, key="dispatch_blocked_share",
                                scale=100.0) is None
