"""The harness is data: cells, configurations, mixes and per-layer metrics
are found by name. Driven on the CPU at a tiny size through `run_cell`
with the look for a chip skipped; the command line cannot skip it."""
import json
import os
import re
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import config_rules  # noqa: E402
import metric_rules  # noqa: E402
import tiny_root  # noqa: E402

from benchmark import harness, run as bench_run  # noqa: E402
from benchmark.loops import serving  # noqa: E402

REPO = tiny_root.REPO
KERNEL = '%k.1 = bf16[8] custom-call(), custom_call_target="tpu_custom_call"'
POOL_COPY = "%copy.1 = bf16[2,65,4,64]{3,2,1,0} copy(%p)"
# the decode kernel's own instruction, and one that only NAMES it
ATTENTION = "%paged_decode_attention.3 = bf16[4,1,64]{2,1,0} custom-call(%q)"
CONSUMER = "%fusion.7 = bf16[4,64]{1,0} fusion(%paged_decode_attention.3)"
FAKE_TRACE = {"window_s": 1.0, "devices": 1, "busy_s": 0.9,
              "collective_s": 0.2, "collective_exposed_s": 0.1,
              "op_seconds": {KERNEL: 0.3, POOL_COPY: 0.2, ATTENTION: 0.09,
                             CONSUMER: 0.05},
              "op_counts": {KERNEL: 6, POOL_COPY: 3, ATTENTION: 3,
                            CONSUMER: 3},
              "gaps": [("bench.step", 0.1)], "spans": []}


@pytest.fixture
def fake_trace(monkeypatch):
    """The CPU gives the profiler no device plane: hand the readers a
    canned reduction where the traced slice would leave one."""
    import contextlib

    @contextlib.contextmanager
    def traced_slice(self):
        yield
        self.evidence["trace"] = dict(FAKE_TRACE)
    monkeypatch.setattr(harness.Run, "traced_slice", traced_slice)


def _files(top):
    return {os.path.join(d, f): os.path.getmtime(os.path.join(d, f))
            for d, _, fs in os.walk(top) for f in fs
            if "__pycache__" not in d}


@pytest.mark.parametrize("cell,metric", [
    ("tiny_train_cell", "train_tokens_per_s"),
    ("tiny_backlog_cell", "serve_tokens_per_s"),
])
def test_a_cell_runs_end_to_end_and_is_correct(root, cell, metric):
    line = bench_run.run_cell(root, cell, seed=2 ** 31 + 7, seconds=1.0,
                              traced=False, require_chip=False)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["checks"] and all(
        set(c) == {"value", "limit"} and c["limit"] is not None
        for c in line["checks"].values())
    assert line["metrics"][metric]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    if cell != "tiny_train_cell":       # the tiny loss is one bf16 step off
        assert line["correct"] is True


def test_the_mesh_cell_spreads_its_state_over_four_devices(root, capsys):
    bench_run.run_cell(root, "tiny_mesh_cell", seed=5, seconds=0.5,
                       traced=False, require_chip=False)
    checks = {c["check"]: c for c in map(json.loads, filter(
        lambda l: l.startswith('{"check"'), capsys.readouterr().out
        .splitlines()))}
    assert checks["state_max_share"]["ok"]
    assert 0.25 < checks["state_max_share"]["value"] < 0.75
    assert checks["grad_norm_gap"]["ok"]


def test_a_traced_run_reports_the_per_layer_metrics_of_its_cell(
        root, fake_trace):
    line = bench_run.run_cell(root, "tiny_backlog_cell", seed=3,
                              seconds=0.5, traced=True, require_chip=False)
    m = line["metrics"]
    assert "breakdown" in line and "busy_s" in line["device"]
    assert m["backlog.device_idle_share"]["value"] == pytest.approx(10.0)
    # the pool of the tiny engine is [2, 65, 4, 64] (layers, blocks, block
    # size, heads x head size): found by shape
    assert m["backlog.kv_copy_time_share"]["value"] == pytest.approx(
        100 * 0.2 / 0.9)
    # the kernel's share by its instruction's own name, not its consumer's
    assert m["backlog.decode_attn_time_share"]["value"] == pytest.approx(
        100 * 0.09 / 0.9)
    assert m["backlog.batch_occupancy"]["value"] > 50
    assert 0 < m["backlog.kv_pool_filled_share"]["value"] <= 100
    assert not any(k.startswith(("mesh.", "train.")) for k in m)


@pytest.mark.parametrize("cell,prefix,rate", [
    ("tiny_train_cell", "train.", "train_tokens_per_s"),
    ("tiny_mesh_cell", "mesh.", "mesh_train_tokens_per_s"),
])
def test_a_traced_train_run_reports_its_own_cells_metrics(
        root, fake_trace, cell, prefix, rate):
    """The two train cells run one loop and report end-to-end rates of
    different names (the traffic file's `rate_metric`), each with the
    per-layer metrics that move it."""
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    want = {m["name"] for m in spec["per_layer"] if cell in m["workloads"]}
    assert want and all(n.startswith(prefix) for n in want)
    assert {m["moves"] for m in spec["per_layer"]
            if cell in m["workloads"]} == {rate}
    line = bench_run.run_cell(root, cell, seed=9, seconds=0.5, traced=True,
                              require_chip=False)
    m = line["metrics"]
    # the CPU has no table of peaks: the two shares of a peak stay out
    assert set(m) == want - {prefix + "step_mfu",
                             prefix + "flash_attn_roofline"}
    assert m[prefix + "median_reading_tokens_per_s"]["value"] > 0
    assert m[prefix + "stall_share"]["value"] < 100
    plain = bench_run.run_cell(root, cell, seed=9, seconds=0.5,
                               traced=False, require_chip=False)
    assert set(plain["metrics"]) == {rate, "setup_s"}


def test_new_configuration_mix_and_metric_are_files_of_their_own(
        root, fake_trace, tmp_path, monkeypatch):
    """Added as new files; nothing that was there is edited."""
    data = os.path.join(root, "benchmark")
    before = _files(root)
    repo_before = _files(os.path.join(REPO, "benchmark"))
    cfg = dict(tiny_root.TINY_MODEL, n_layer=1, n_embd=32, n_inner=128)
    mix = dict(tiny_root.TRAFFIC["tiny_train"], batch_rows=2, seq=16,
               reference_rows_per_block=2)
    tiny_root._dump(os.path.join(data, "configs", "added_gpt.json"), cfg)
    tiny_root._dump(os.path.join(data, "traffic", "added_mix.json"), mix)
    tiny_root._dump(os.path.join(data, "limits", "added_cell.json"),
                    dict(tiny_root.TRAIN_LIMITS, loss_gap=0.01))
    tiny_root._dump(os.path.join(data, "metrics", "added.steps.json"),
                    {"reader": "added_reader", "args": {"scale": 2}})
    # a metric file names its reader's module; a later PR's lies under
    # `benchmark/readers/`, this one wherever Python finds it
    os.makedirs(tmp_path / "code")
    with open(tmp_path / "code" / "added_reader.py", "w") as f:
        f.write("def read(evidence, scale):\n"
                "    return scale * evidence['readings']"
                "['steps_per_reading']\n")
    monkeypatch.syspath_prepend(str(tmp_path / "code"))
    assert all(os.path.getmtime(p) == t for p, t in before.items())
    # entries, not edits: a later PR appends to BENCHMARK.json's lists
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    spec["configs"].append({"name": "added_gpt", "source": "test",
                            "file": "benchmark/configs/added_gpt.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "added_cell", "config": "added_gpt",
                              "traffic": "added_mix", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({
        "name": "added.steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": "train_tokens_per_s", "workloads": ["added_cell"]})
    for m in spec["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("added_cell")
    tiny_root._dump(os.path.join(root, "BENCHMARK.json"), spec)
    line = bench_run.run_cell(root, "added_cell", seed=11, seconds=0.5,
                              traced=True, require_chip=False)
    assert line["correct"] is True
    assert line["metrics"]["added.steps"]["value"] > 0
    assert "train.stall_share" not in line["metrics"]   # not this cell's
    assert _files(os.path.join(REPO, "benchmark")) == repo_before


@pytest.fixture
def other_model(root, monkeypatch):
    """A configuration of ANOTHER model, added to the tiny root as files
    and entries only: other key names, its own reference and program
    modules (kept in `other_model/`, found through `sys.path`), its cell
    appended to `train_tokens_per_s`'s `workloads`."""
    monkeypatch.syspath_prepend(os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "other_model"))
    tiny_root.add_cell(
        root, "other_cell", ("other", tiny_root.OTHER_MODEL),
        ("other_mix", dict(tiny_root.TRAFFIC["tiny_train"], batch_rows=2,
                           seq=16)),
        tiny_root.TRAIN_LIMITS, ("train_tokens_per_s", "train.stall_share"))
    return tiny_root.OTHER_MODEL


def test_a_configuration_of_another_model_arrives_as_files_and_entries(
        root, other_model, fake_trace):
    """No file of the harness knows the model: its keys, its reference and
    its program are reached through the configuration file alone."""
    repo_before = _files(os.path.join(REPO, "benchmark"))
    line = bench_run.run_cell(root, "other_cell", seed=2 ** 31 + 11,
                              seconds=0.5, traced=False, require_chip=False)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["checks"]) == {"loss_gap", "grad_norm_gap",
                                   "delta_norm_gap"}
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0
    traced = bench_run.run_cell(root, "other_cell", seed=12, seconds=0.3,
                                traced=True, require_chip=False)
    # a reader that needs GPT's keys or paddle's counters finds nothing
    # to read for this model and is left out; one that needs neither reads
    assert set(traced["metrics"]) == {"train.stall_share"}
    assert _files(os.path.join(REPO, "benchmark")) == repo_before


def test_the_serve_check_reads_the_reference_its_configuration_names(
        other_model):
    """`served_gaps` needs no engine: a stream that the named reference's
    own argmax made lies 0 below its best logit, an altered token does
    not."""
    import jax.numpy as jnp
    import other_reference
    from benchmark import correct
    cfg, seed = other_model, 2 ** 31 + 13
    weights = correct.weight_maker(cfg, seed)()
    prompt, served = [5, 17, 99, 3, 64], []
    for _ in range(6):
        ids = jnp.asarray([prompt + served], jnp.int32)
        served.append(int(jnp.argmax(
            other_reference.forward(weights, ids, cfg)[0, -1])))
    worst = correct.served_gaps(cfg, seed, [(prompt, served)], pad_to=16,
                                control="bfloat16")
    assert worst["logit_gap"] == 0.0 and worst["tokens"] == 6
    assert worst["control_logit_gap"] >= 0.0
    wrong = served[:2] + [(served[2] + 1) % cfg["vocab_size"]] + served[3:]
    assert correct.served_gaps(cfg, seed, [(prompt, wrong)],
                               pad_to=16)["logit_gap"] > 0.0


def test_the_serve_check_starts_when_nothing_of_the_engine_is_alive(
        root, monkeypatch):
    """At the moment the reference's weights are made, no array that came
    to life under the engine (weights, pools, tables) is left, and the
    memory peak has been read."""
    import gc
    import jax
    from benchmark import correct
    gc.collect()
    before = {id(a) for a in jax.live_arrays()}
    seen = {}
    real = correct.served_gaps

    def served_gaps(cfg, seed, streams, pad_to, control=None):
        seen["left"] = [(a.shape, a.nbytes) for a in jax.live_arrays()
                        if id(a) not in before]
        seen["streams"] = streams
        return real(cfg, seed, streams, pad_to, control)
    monkeypatch.setattr(correct, "served_gaps", served_gaps)
    peaks = []
    real_peak = harness.Run.read_memory_peak
    monkeypatch.setattr(
        harness.Run, "read_memory_peak",
        lambda self, *a: peaks.append("left" in seen) or real_peak(self, *a))
    line = bench_run.run_cell(root, "tiny_backlog_cell", seed=8,
                              seconds=0.4, traced=False, require_chip=False)
    assert line["correct"] is True
    # all that outlives the engine is the key of the program's global
    # random generator (`paddle.seed`, 8 bytes), which the model never owned
    assert sum(n for _, n in seen["left"]) <= 16, seen["left"]
    assert peaks == [False]          # read once, before the reference
    assert all(isinstance(t, int) for prompt, served in seen["streams"]
               for t in prompt + served)


def test_a_reader_with_nothing_to_read_leaves_its_metric_out(root):
    line = bench_run.run_cell(root, "tiny_train_cell", seed=1, seconds=0.5,
                              traced=False, require_chip=False)
    run = harness.Run(root, "tiny_train_cell", 1, 0.5, True,
                      require_chip=False)
    assert run.per_layer_metrics() == {}     # no window, no trace: nothing
    assert "train.device_step_ms" not in line["metrics"]


def test_without_a_tpu_the_command_exits_non_zero_and_prints_no_metric():
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "train_124m_step", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "tokens/s" not in p.stdout
    assert "needs 1 TPU chip" in p.stderr


def test_an_unknown_workload_is_an_error(root):
    with pytest.raises(SystemExit):
        bench_run.run_cell(root, "no_such_cell", 1, 1.0, False,
                           require_chip=False)


def test_a_cell_without_limits_is_never_correct(root):
    os.remove(os.path.join(root, "benchmark", "limits",
                           "tiny_backlog_cell.json"))
    line = bench_run.run_cell(root, "tiny_backlog_cell", seed=2,
                              seconds=0.3, traced=False, require_chip=False)
    assert line["correct"] is False


# -- the timed path broken underneath: `correct` must come out false --------

def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        root, monkeypatch):
    from benchmark.programs import paddle_gpt
    real = paddle_gpt.Trainer.step

    def frozen(self, ids, labels):
        kept = {k: v + 0 for k, v in self.master_params().items()}
        loss = real(self, ids, labels)
        masters = self.opt._accumulators["master_weight"]
        named = paddle_gpt._named(self.model)
        for k, p in named.items():
            masters[p.name] = kept[k]
        return loss
    monkeypatch.setattr(paddle_gpt.Trainer, "step", frozen)
    tiny_root._dump(os.path.join(root, "benchmark", "limits",
                                 "tiny_train_cell.json"),
                    dict(tiny_root.TRAIN_LIMITS, loss_gap=0.01))
    line = bench_run.run_cell(root, "tiny_train_cell", seed=4, seconds=0.3,
                              traced=False, require_chip=False)
    assert line["correct"] is False


def test_an_altered_served_token_is_not_correct(root, monkeypatch):
    from paddle_tpu.serving import LLMEngine
    real = LLMEngine._emit_token

    def altered(self, req, tok, logp=None, alts=None):
        if len(req.generated) == 2:
            tok = (tok + 1) % 256
        return real(self, req, tok, logp=logp, alts=alts)
    monkeypatch.setattr(LLMEngine, "_emit_token", altered)
    line = bench_run.run_cell(root, "tiny_backlog_cell", seed=6,
                              seconds=0.5, traced=False, require_chip=False)
    assert line["correct"] is False
    # one wrong token in a stream: the widest gap is what catches it
    widest = line["checks"]["logit_gap"]
    assert widest["value"] > widest["limit"]


# -- BENCHMARK.json against the contract's limits ----------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


# `spec` is the file as it stands and again with an arrival appended, and
# `spec_root` the root whose data files lie beside it (`conftest.py`)

def test_benchmark_json_has_exactly_the_contracts_keys(spec, spec_root):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(spec_root, "BENCHMARK.json")) < 65536
    assert 1 <= spec["run_seconds"] <= 51
    cells = len(spec["workloads"])
    assert (2 + 14 * 24) * (spec["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= max(
        1, cells // 4)


def test_names_units_and_bounds_are_inside_the_limits(spec):
    metrics = spec["end_to_end"] + spec["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in spec["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in names
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_a_train_mix_names_the_rate_its_cell_reports(spec, spec_root):
    """The train loop reports its rate under the traffic file's
    `rate_metric`: that has to be an end-to-end metric of the cell. Any
    number of cells may report one rate."""
    seen = set()
    for w in spec["workloads"]:
        mix = json.load(open(os.path.join(spec_root, "benchmark", "traffic",
                                          w["traffic"] + ".json")))
        if mix["loop"] != "train":
            continue
        seen.add(w["name"])
        rate = [m for m in spec["end_to_end"]
                if m["name"] == mix["rate_metric"]]
        assert len(rate) == 1 and w["name"] in rate[0]["workloads"]
        assert rate[0]["unit"] == "tokens/s" and rate[0]["better"] == "higher"
    assert {"train_124m_step", "train_1p3b_mesh4"} <= seen


def test_every_cell_finds_its_files_and_reports_what_it_must(spec,
                                                             spec_root):
    configs = {c["name"]: c for c in spec["configs"]}
    cells = {w["name"] for w in spec["workloads"]}
    used = set()
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert w["chips"] in (1, 4)
        used.add(w["config"])
        cfg = configs[w["config"]]
        assert os.path.exists(os.path.join(spec_root, cfg["file"]))
        mix = os.path.join(spec_root, "benchmark", "traffic",
                           w["traffic"] + ".json")
        loop = json.load(open(mix))["loop"]
        assert os.path.exists(os.path.join(REPO, "benchmark", "loops",
                                           loop + ".py"))
        limits = json.load(open(os.path.join(spec_root, "benchmark",
                                             "limits",
                                             w["name"] + ".json")))
        if loop.startswith("serve"):
            # a serve cell compares both numbers, each against its own
            # limit: a file without either makes the cell incorrect
            assert set(serving.COMPARED) <= set(limits), w["name"]
            assert 0 < limits["logit_gap_mean"] < limits["logit_gap"]
        mine = [m for m in spec["end_to_end"]
                if w["name"] in m.get("workloads", cells)]
        assert len(mine) >= 2 and any(m["name"] == "setup_s" for m in mine)
        layer = [m for m in spec["per_layer"]
                 if w["name"] in m.get("workloads", cells)]
        assert layer
        for m in layer:
            assert w["name"] in [c for e in spec["end_to_end"]
                                 if e["name"] == m["moves"]
                                 for c in e.get("workloads", cells)]
    assert used == set(configs)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert set(m.get("workloads", [])) <= cells


def test_metric_files_and_per_layer_metrics_are_the_same_set(spec,
                                                             spec_root):
    """Each per-layer metric has its file, each file its metric, and
    every file names a reader kept under `benchmark/readers/`."""
    top = os.path.join(spec_root, "benchmark", "metrics")
    assert sorted(n[:-len(".json")] for n in os.listdir(top)) == sorted(
        m["name"] for m in spec["per_layer"])
    for m in spec["per_layer"]:
        reader = json.load(open(os.path.join(top, m["name"] + ".json")))[
            "reader"]
        package, _, module = reader.rpartition(".")
        assert package == "benchmark.readers", reader
        assert os.path.exists(os.path.join(REPO, "benchmark", "readers",
                                           module + ".py")), reader


def _file_of(spec_root):
    return lambda m: metric_rules.metric_file(spec_root, m["name"])


def test_no_two_per_layer_entries_are_twins(spec, spec_root):
    """One reader over one key, moving one end-to-end metric in one unit,
    is ONE entry whose `workloads` lists its cells (`metric_rules`): the
    test that would have kept the list from filling. A cell that arrives
    JOINS such an entry (`tiny_root.ARRIVAL["joins"]`)."""
    assert metric_rules.twins(spec["per_layer"], _file_of(spec_root)) == []


def test_the_twin_rule_sees_a_twin_written_out_again(spec, spec_root):
    """What PRs 32-42 did once a cell: the same file under another name,
    its summed lists in another order."""
    [shared] = [m for m in spec["per_layer"]
                if m["name"] == "longcat.held_choice_share"]
    file_of = _file_of(spec_root)
    again = dict(shared, name="next.held_choice_share",
                 workloads=["next_cell"])
    turned = file_of(shared)
    turned["args"]["under"].reverse()

    def with_next(m):
        return turned if m["name"] == again["name"] else file_of(m)
    assert metric_rules.twins(spec["per_layer"] + [again], with_next) == [
        ["longcat.held_choice_share", "next.held_choice_share"]]
    # another end-to-end metric moved: the four-chip cell's own entry
    assert metric_rules.twins(
        spec["per_layer"] + [dict(again, moves="mesh_train_tokens_per_s")],
        with_next) == []


def test_per_layer_is_inside_its_limit_with_room_to_spare(spec):
    n = len(spec["per_layer"])
    free = metric_rules.PER_LAYER_MAX - n
    assert n <= metric_rules.PER_LAYER_MAX, (
        f"`per_layer` holds {n} entries, {-free} over the "
        f"{metric_rules.PER_LAYER_MAX} it may hold: merge twins "
        "(`metric_rules.twins`) or retire what tells nothing")
    # ISSUE 47 left at most 100: a PR that brings the list back over 120
    # says so here, with the room that is left, before the list is full
    assert n <= 120, f"{free} entries free of {metric_rules.PER_LAYER_MAX}"


def test_a_shared_entry_is_read_for_each_of_its_cells_and_no_third(root):
    """One entry whose `workloads` names two cells is read for each of
    them, through the one file, and for no cell it does not name."""
    mix = ("second_mix", tiny_root.TRAFFIC["tiny_backlog"])
    shared = "backlog.host_step_ms"
    tiny_root.add_cell(root, "second_cell", ("second_gpt",
                                             tiny_root.TINY_MODEL), mix,
                       tiny_root.SERVE_LIMITS,
                       ("serve_tokens_per_s", shared))
    tiny_root.add_cell(root, "third_cell", ("third_gpt",
                                            tiny_root.TINY_MODEL), mix,
                       tiny_root.SERVE_LIMITS, ("serve_tokens_per_s",))
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    [entry] = [m for m in spec["per_layer"] if m["name"] == shared]
    assert entry["workloads"] == ["tiny_backlog_cell", "second_cell"]
    read = {}
    for cell in ("tiny_backlog_cell", "second_cell", "third_cell"):
        run = harness.Run(root, cell, 1, 0.5, True, require_chip=False)
        run.evidence["engine_stats"] = {"step_ms_per_step": 2.5,
                                        "occupancy_mean": 0.5}
        read[cell] = run.per_layer_metrics()
    for cell in entry["workloads"]:
        assert read[cell][shared] == {"value": 2.5, "unit": "ms"}
    assert shared not in read["third_cell"]
    # an entry that names one cell is that cell's alone
    assert read["tiny_backlog_cell"]["backlog.batch_occupancy"][
        "value"] == 50.0
    assert "backlog.batch_occupancy" not in read["second_cell"]
    assert read["third_cell"] == {}


@pytest.mark.parametrize("name,cells,kernel", [
    ("longcat.decode_attn_time_share", ["serve_longcat_decode"],
     "latent_decode_attention"),
    # LFM2's decode kernel is GPT's: one pattern, so one entry (ISSUE 47)
    ("backlog.decode_attn_time_share",
     ["serve_124m_backlog", "serve_lfm2_rag_backlog"],
     "paged_decode_attention"),
])
def test_a_decode_kernels_share_of_device_time_is_a_data_file(
        spec, spec_root, name, cells, kernel):
    """ISSUE 39's two: entries and data files over the reader that was
    there, matching the kernel's own instruction (the `pallas_call`'s
    name) and no instruction that takes its result."""
    [entry] = [m for m in spec["per_layer"] if m["name"] == name]
    [twin] = [m for m in spec["per_layer"]
              if m["name"] == "backlog.kv_copy_time_share"]
    assert entry == dict(twin, name=name, workloads=cells)
    metric = json.load(open(os.path.join(spec_root, "benchmark", "metrics",
                                         name + ".json")))
    assert metric["reader"] == "benchmark.readers.trace_op_share"
    pattern = re.compile(metric["args"]["pattern"])
    assert pattern.search(f"%{kernel}.9 = f32[128,64,512]{{2,1,0}} "
                          "custom-call(%q, %pool)")
    assert not pattern.search(f"%fusion.3 = f32[8]{{0}} fusion(%{kernel}.9)")


@pytest.mark.parametrize("name", [
    "backlog.prefill_wall_share", "backlog.decode_step_p50_ms",
    *(old for old, new in metric_rules.RENAMED.items()
      if new == metric_rules.RETIRED)])
def test_a_retired_metric_is_gone_with_its_file(spec, spec_root, name):
    """Read nothing since PR 31 (`PERF.md` section 6, PR 39); the mid of a
    log bucket beside its exact `*_ms_per_step` twin (PR 47)."""
    assert name not in {m["name"] for m in spec["per_layer"]}
    assert not os.path.exists(os.path.join(spec_root, "benchmark",
                                           "metrics", name + ".json"))


def test_an_arrival_appends_and_edits_nothing_that_was_there():
    """What the second case of every `spec` test stands on: the arrival
    is entries at the END of each list, one cell more in the `workloads`
    of one end-to-end metric and of the shared per-layer entries it joins,
    and nothing else."""
    before = tiny_root.spec_of("as_it_stands")
    after = tiny_root.spec_of("with_an_arrival")
    cell = tiny_root.ARRIVAL["cell"]["name"]
    own = list(tiny_root.ARRIVAL["per_layer"])
    joined = (tiny_root.ARRIVAL["end_to_end"], *tiny_root.ARRIVAL["joins"])
    for key, more in (("configs", 1), ("workloads", 1),
                      ("per_layer", len(own))):
        assert len(after[key]) == len(before[key]) + more
    for key in ("configs", "workloads"):
        assert after[key][:len(before[key])] == before[key]
    assert [m["name"] for m in after["per_layer"][-len(own):]] == own
    seen = set()
    for was, now in zip(before["end_to_end"] + before["per_layer"],
                        after["end_to_end"] + after["per_layer"]):
        if now["name"] in joined:
            assert now == dict(was, workloads=was["workloads"] + [cell])
            seen.add(now["name"])
        else:
            assert now == was
    assert seen == set(joined)
    for key in ("command", "paths", "run_seconds"):
        assert after[key] == before[key]


@pytest.mark.parametrize("standing,name", tiny_root.config_cases(),
                         indirect=["standing"])
def test_configurations_keep_the_published_widths(spec, spec_root, name):
    """Each configuration is held to its own source: what it cuts is of a
    kind a cell may cut and never a width, and the file says where it
    comes from and where it departs (`config_rules`). A configuration of
    the GPT reference is also held to GPT's own shape."""
    [entry] = [c for c in spec["configs"] if c["name"] == name]
    cfg = json.load(open(os.path.join(spec_root, entry["file"])))
    assert config_rules.problems(entry, cfg) == []
    if cfg["reference"] == "benchmark.reference.gpt":
        assert cfg["n_embd"] % cfg["n_head"] == 0
        assert cfg["n_inner"] == 4 * cfg["n_embd"]
    if entry["name"] == "gpt2_124m":
        assert (cfg["n_layer"], cfg["n_embd"], cfg["n_head"],
                cfg["n_positions"]) == (12, 768, 12, 1024)


# a configuration of another family, cut to one chip's share as the
# `model-configs` guide's section 4 cuts one: what the rules let through
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
CUT = {"hidden_size": 2048, "moe_intermediate_size": 1536,
       "num_experts_per_tok": 4, "first_k_dense_replace": 1,
       "num_hidden_layers": 5, "n_routed_experts": 8, "vocab_size": 19360,
       "source": "s", "deployment": "d", "program": "p", "reference": "r",
       "published": {"num_hidden_layers": 47, "n_routed_experts": 64,
                     "vocab_size": 154880},
       "changed": {"num_hidden_layers": "47 -> 1 dense + 4",
                   "n_routed_experts": "8 of 64 held",
                   "vocab_size": "an eighth"}}


def _cut(also=(), reduced=None, drop=(), **keys):
    cfg = {k: v for k, v in dict(CUT, **keys).items() if k not in drop}
    return {"reduced": reduced or REDUCED + list(also)}, cfg


@pytest.mark.parametrize("case,says", [
    (_cut(), None),
    (_cut(also=["moe_intermediate_size"]), "a width"),
    (_cut(also=["kv_lora_rank"]), "a width"),
    (_cut(also=["num_experts_per_tok"]), "a width"),
    (_cut(also=["rope_theta"]), "no kind"),
    (_cut(num_hidden_layers=4), "under four layers"),
    (_cut(n_routed_experts=4), "under 8 routed experts"),
    (_cut(vocab_size=19359), "under an eighth"),
    (_cut(drop=("deployment",)), "no `deployment`"),
    (_cut(drop=("reference",)), "no `reference`"),
    (_cut(changed={}), "neither under"),
    (_cut(reduced=["vocab_size"]), "not in `reduced`"),
], ids=lambda x: x if isinstance(x, str) else None)
def test_the_rules_let_a_chips_share_through_and_no_width(case, says):
    found = config_rules.problems(*case)
    if says is None:
        assert found == []
    else:
        assert any(says in f for f in found), found


def test_a_layer_pattern_keeps_a_whole_period():
    pattern = ["linear", "linear", "linear", "full"] * 8
    entry = {"reduced": ["layer_types", "num_hidden_layers"]}
    cfg = dict(CUT, first_k_dense_replace=0, num_hidden_layers=4,
               layer_types=pattern[:4],
               published={"layer_types": pattern, "num_hidden_layers": 32},
               changed={"layer_types": "one period",
                        "num_hidden_layers": "32 -> 4"})
    assert config_rules.period(pattern) == 4
    assert config_rules.problems(entry, cfg) == []
    cfg["layer_types"] = pattern[:3]
    assert any("whole period" in f
               for f in config_rules.problems(entry, cfg))


# layer patterns as their publishers print them, a letter a layer, after
# the leading dense layers: a tail that departs from the period (a last
# attention layer one layer early) leaves the period what it is
HYBRID = list("ccAcccAcccAcccAcccAccAcc")     # 24 layers, the first 2 dense


@pytest.mark.parametrize("pattern,p", [
    ("lllf" * 8, 4),
    ("".join(HYBRID[2:]), 4),                 # Acccx4, Acc, Acc: not 19
    ("AcccAcccAcccAcccAcccAcccAcccAcccAcccAc", 4),
    ("mmmmmammmmmmmmmammmmmmmmmammmmmmmmmammmm", 10),
    ("AccAccAccAcAc", 3),                     # `tiny_root.ARRIVAL`'s
    ("c" * 15 + "A" + "c" * 15 + "A" + "c" * 15 + "A", 16),
    ("f" * 48, 1),
    ("abcdefg", 7),                           # no repetition: its length
])
def test_the_period_is_the_one_the_publisher_counts(pattern, p):
    assert config_rules.period(list(pattern)) == p


def _hybrid(kept_after_dense):
    """The 24-layer pattern above cut in depth, `num_dense_layers` 2."""
    kept = HYBRID[:2] + list(kept_after_dense)
    entry = {"reduced": ["num_hidden_layers", "layer_types"]}
    cfg = dict({k: v for k, v in CUT.items()
                if k != "first_k_dense_replace"},
               num_dense_layers=2, num_hidden_layers=len(kept),
               layer_types=kept, n_routed_experts=64, vocab_size=154880,
               published={"num_hidden_layers": 24, "layer_types": HYBRID},
               changed={"num_hidden_layers": "24 -> 2 dense + the rest",
                        "layer_types": "its beginning"})
    return entry, cfg


@pytest.mark.parametrize("case,says", [
    (_hybrid("AcccAcccAcccAc"), None),        # 16 of 24: 3.5 periods
    (_hybrid("Accc"), None),                  # one whole period
    (_hybrid("Acc"), "under four layers"),
    (_hybrid("Acc"), "whole period"),
    (_hybrid("cccc"), "keeps no A layer"),
    (_hybrid("cAcccAccc"), "not the beginning"),
    (_hybrid("AAcccAcccAcccA"), "not the beginning"),
    (_hybrid("AcccAAAA"), "from the published share"),
], ids=lambda x: x if isinstance(x, str) else None)
def test_a_cut_pattern_is_the_published_ones_beginning_in_its_shares(
        case, says):
    found = config_rules.problems(*case)
    if says is None:
        assert found == []
    else:
        assert any(says in f for f in found), found


def test_leading_dense_layers_count_under_either_key():
    entry, cfg = _hybrid("Acc")
    assert config_rules.leading_dense(cfg) == 2
    assert config_rules.leading_dense(dict(CUT)) == 1
    assert config_rules.leading_dense({"n_layer": 12}) == 0
    assert any("under four layers after the 2 leading dense" in f
               for f in config_rules.problems(entry, cfg))
    # counted as 0, the two dense layers would pass for two of the four
    del cfg["num_dense_layers"]
    assert not any("under four layers" in f
                   for f in config_rules.problems(entry, cfg))
