"""The per-layer metrics that read the program's own spans and counters
(ISSUE 24): files of their own over readers that exist, reported by the
tiny traced cells with finite values, and left out, not raised, where the
program has no such counter."""
import json
import math
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny_root  # noqa: E402

from benchmark import harness, run as bench_run  # noqa: E402
from benchmark.programs import paddle_train_stats  # noqa: E402
from benchmark.readers import engine_stat, train_step_stat  # noqa: E402

REPO = tiny_root.REPO
# (`backlog.prefill_p50_ms` and `backlog.decode_dispatch_p50_ms` went with
# their files in PR 47: `test_a_retired_metric_is_gone_with_its_file`)
SERVE = {"backlog.prefill_span_share": "prefill_share",
         "backlog.decode_span_share": "decode_share",
         "backlog.stream_span_share": "stream_share",
         "backlog.step_self_share": "step_self_share",
         "backlog.programs_compile_s": "compile_s"}
TRAIN = {"host_step_p50_ms": "call_p50_ms",
         "host_gather_p50_ms": "gather_state_p50_ms",
         "host_dispatch_p50_ms": "dispatch_p50_ms",
         "host_write_back_p50_ms": "write_back_p50_ms",
         "step_compiles": "compiles"}
NEW = {**SERVE, **{f"{prefix}.{short}": key for prefix in ("train", "mesh")
                   for short, key in TRAIN.items()}}
CELL_OF = {"backlog": ("serve_124m_backlog", "tiny_backlog_cell"),
           "train": ("train_124m_step", "tiny_train_cell"),
           "mesh": ("train_1p3b_mesh4", "tiny_mesh_cell")}


@pytest.fixture(scope="module")
def traced_lines(tmp_path_factory):
    """One traced run of each tiny cell, the device trace canned (the CPU
    gives the profiler no device plane)."""
    root = str(tmp_path_factory.mktemp("tiny"))
    tiny_root.make(root)
    real = harness.Run.traced_slice
    import contextlib

    @contextlib.contextmanager
    def traced_slice(self):
        yield
        self.evidence["trace"] = {
            "window_s": 1.0, "devices": 1, "busy_s": 0.9,
            "collective_s": 0.0, "collective_exposed_s": 0.0,
            "op_seconds": {}, "op_counts": {}, "gaps": [], "spans": []}
    harness.Run.traced_slice = traced_slice
    try:
        return {cell: bench_run.run_cell(root, cell, seed=2 ** 31 + 24,
                                         seconds=0.5, traced=True,
                                         require_chip=False)
                for _, cell in CELL_OF.values()}
    finally:
        harness.Run.traced_slice = real


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_metric_is_a_file_over_a_reader_that_exists(spec, spec_root,
                                                          name):
    entry = [m for m in spec["per_layer"] if m["name"] == name]
    assert len(entry) == 1
    real_cell, _ = CELL_OF[name.split(".")[0]]
    assert real_cell in entry[0]["workloads"]
    assert entry[0]["source"] in ("program_span", "program_counter")
    with open(os.path.join(spec_root, "benchmark", "metrics",
                           name + ".json")) as f:
        metric = json.load(f)
    package, _, module = metric["reader"].rpartition(".")
    assert package == "benchmark.readers"
    assert os.path.exists(os.path.join(REPO, "benchmark", "readers",
                                       module + ".py"))
    assert metric["args"]["key"] == NEW[name]
    if entry[0]["unit"] == "%":         # a share of 0-1, shown in percent
        assert metric["args"]["scale"] == 100.0


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_tiny_traced_cell_reports_the_metric_with_a_finite_value(
        traced_lines, name):
    _, cell = CELL_OF[name.split(".")[0]]
    metrics = traced_lines[cell]["metrics"]
    assert name in metrics
    value = metrics[name]["value"]
    assert math.isfinite(value) and value >= 0
    if name.endswith("_share"):
        assert value <= 100.0
    if name.endswith("step_compiles"):
        assert value == 1


def test_the_backlog_shares_add_up_to_the_engines_time(traced_lines):
    m = traced_lines["tiny_backlog_cell"]["metrics"]
    total = sum(m[f"backlog.{k}"]["value"] for k in (
        "prefill_span_share", "decode_span_share", "stream_span_share",
        "step_self_share"))
    assert 50.0 < total <= 100.0
    assert m["backlog.programs_compile_s"]["value"] > 0


def test_the_phases_of_a_train_step_fit_inside_the_call(traced_lines):
    for prefix, (_, cell) in CELL_OF.items():
        if prefix == "backlog":
            continue
        m = traced_lines[cell]["metrics"]
        assert m[f"{prefix}.host_step_p50_ms"]["value"] > 0
        assert m[f"{prefix}.host_dispatch_p50_ms"]["value"] > 0


def test_a_program_without_the_counters_leaves_the_metrics_out(monkeypatch):
    """What a commit before PR 24 gives the readers: no accessor in
    `paddle_tpu.jit`, no such key in `engine.stats()`."""
    import paddle_tpu.jit as jit
    monkeypatch.delattr(jit, "train_step_stats")
    assert paddle_train_stats.newest_train_step_stats() is None
    assert train_step_stat.read({}, key="call_p50_ms") is None
    assert engine_stat.read({"engine_stats": {"steps": 3}},
                            key="prefill_share", scale=100.0) is None


def test_the_train_reader_reads_the_newest_live_step():
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    model = paddle.nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(0.1, parameters=model.parameters())
    step = TrainStep(model, lambda out, y: ((out - y) ** 2).mean(), opt)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    y = paddle.to_tensor(np.zeros((2, 2), np.float32))
    assert train_step_stat.read({}, key="steps") == 0
    step(x, y)
    step(x, y)
    assert train_step_stat.read({}, key="steps") == 2
    assert train_step_stat.read({}, key="compiles") == 1
    assert train_step_stat.read({}, key="call_p50_ms", scale=2.0) > 0
    assert train_step_stat.read({}, key="no_such_key") is None
