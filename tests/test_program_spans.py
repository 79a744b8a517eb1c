"""The program's own spans (`profiler.RecordEvent`) inside
`TrainStep.__call__` and `LLMEngine.step()`: on the profiler's clock (the
host plane of the one `.xplane.pb`), nested as the tables of ISSUE 24 say,
each feeding the phase histogram that `stats()` reports; silent when no
`Profiler` listens."""
import gc
import glob
import logging
import math
import os

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import profiler
from paddle_tpu.incubate.models import GPTConfig, GPTForCausalLM
from paddle_tpu.jit import TrainStep, train_step_stats
from paddle_tpu.serving import LLMEngine
from paddle_tpu.serving.engine import ServeStats

# span -> the span it must lie inside, on the same thread
TRAIN_PARENTS = {
    "train_step.call": "caller.window",
    "train_step.build": "train_step.gather_state",
    "train_step.gather_state": "train_step.call",
    "train_step.dispatch": "train_step.call",
    "train_step.write_back": "train_step.call",
}
ENGINE_PARENTS = {
    "engine.step": "caller.window",
    "engine.admit": "engine.step",
    "engine.prefill": "engine.admit",
    "engine.prefill.dispatch": "engine.prefill",
    "engine.prefill.commit": "engine.step",
    "engine.prefill.wait": "engine.prefill.commit",
    "engine.compile": "engine.step",
    "engine.kv_grow": "engine.step",
    "engine.decode": "engine.step",
    "engine.decode.dispatch": "engine.decode",
    "engine.decode.wait": "engine.decode",
    "engine.decode.fetch": "engine.decode",
    "engine.stream": "engine.step",
    # a collection forced from an `on_token` callback
    "engine.gc": "engine.stream",
}
PROMPTS = [[1, 2, 3, 4, 5], [7, 8, 9], [3] * 12]


def tiny_train_step():
    paddle.seed(0)
    model = paddle.nn.Linear(8, 4)
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    return TrainStep(model, lambda out, y: F.cross_entropy(out, y), opt)


def batch(rows):
    rng = np.random.default_rng(rows)
    return (paddle.to_tensor(rng.standard_normal((rows, 8), np.float32)),
            paddle.to_tensor(rng.integers(0, 4, (rows,))))


def tiny_engine(**kwargs):
    paddle.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=64))
    model.eval()
    return LLMEngine(model, max_batch_size=4, block_size=8, max_context=64,
                     **kwargs)


def host_spans(trace_dir):
    """{thread line: [(name, start_ns, end_ns)]} of the newest xplane's
    host planes, and how many `.xplane.pb` files the session wrote."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    lines = {}
    for plane in ProfileData.from_file(files[-1]).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            lines[(plane.name, line.name)] = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for e in line.events]
    return lines, len(files)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One `jax.profiler` session over a tiny TrainStep and a tiny
    engine (cold, then warm), inside the caller's own annotation."""
    out = str(tmp_path_factory.mktemp("trace"))
    step, x, y = tiny_train_step(), *batch(16)
    engine = tiny_engine()
    forced = []

    def collect_once(req, tok, text):
        # a decoded token (a first token is emitted under
        # `engine.prefill.commit`, every later one under `engine.stream`)
        if len(req.generated) == 2 and not forced:
            forced.append(gc.collect())

    gc.disable()        # the only collection of the session is the forced
    jax.profiler.start_trace(out)
    try:
        with jax.profiler.TraceAnnotation("caller.window"):
            for _ in range(3):
                step(x, y)
            for p in PROMPTS:
                engine.add_request(p, max_new_tokens=4,
                                   on_token=collect_once)
            engine.run()
        with jax.profiler.TraceAnnotation("caller.warm"):
            engine.generate(PROMPTS, max_new_tokens=4)
    finally:
        jax.profiler.stop_trace()
        gc.enable()
    lines, files = host_spans(out)
    return {"lines": lines, "files": files}


def _named(lines, name):
    return [(key, a, b) for key, events in lines.items()
            for n, a, b in events if n == name]


@pytest.mark.parametrize("name,parent", sorted(
    {**TRAIN_PARENTS, **ENGINE_PARENTS}.items()))
def test_a_span_is_on_the_host_plane_inside_its_parent(traced, name, parent):
    assert traced["files"] == 1         # spans and device ops: one file
    found = _named(traced["lines"], name)
    assert found, f"no {name} on the host plane"
    in_window = [f for f in found if any(
        key == f[0] and a <= f[1] and f[2] <= b
        for key, a, b in _named(traced["lines"], "caller.window"))]
    assert in_window
    for key, a, b in in_window:
        assert any(k == key and pa <= a and b <= pb
                   for k, pa, pb in _named(traced["lines"], parent)), \
            f"{name} outside every {parent}"


def test_the_first_call_of_each_program_is_under_engine_compile(traced):
    lines = traced["lines"]
    window = _named(lines, "caller.window")[0]
    compiles = [c for c in _named(lines, "engine.compile")
                if window[1] <= c[1] and c[2] <= window[2]]
    # PROMPTS fall in buckets 8 and 16: two prefill programs + the decode
    assert len(compiles) == 3
    for _, a, b in compiles:
        inside = [n for name in ("engine.prefill.dispatch",
                                 "engine.decode.dispatch")
                  for n in _named(lines, name) if a <= n[1] and n[2] <= b]
        assert len(inside) == 1


def _inside(lines, name, window):
    return [f for f in _named(lines, name)
            if f[0] == window[0] and window[1] <= f[1] and f[2] <= window[2]]


def test_a_prefill_is_dispatched_and_its_wait_sits_under_the_commit(traced):
    """A prefill's span holds its dispatch and no wait: the wait for a
    boundary's prefills is the commit's. The buckets warm, that commit
    lies past the admission and the table growth of the NEXT step,
    before that step's decode launch."""
    lines = traced["lines"]
    for outer in ("caller.window", "caller.warm"):
        window = _named(lines, outer)[0]
        prefills = _inside(lines, "engine.prefill", window)
        assert len(prefills) == 3
        for prefill in prefills:
            assert len(_inside(lines, "engine.prefill.dispatch",
                               prefill)) == 1
            assert not _inside(lines, "engine.prefill.wait", prefill)
        waits = _inside(lines, "engine.prefill.wait", window)
        assert waits and all(
            any(c[1] <= w[1] and w[2] <= c[2]
                for c in _inside(lines, "engine.prefill.commit", window))
            for w in waits)
    warm = _named(lines, "caller.warm")[0]
    steps = sorted(_inside(lines, "engine.step", warm), key=lambda s: s[1])
    [commit] = _inside(lines, "engine.prefill.commit", warm)
    # three prefills behind one launch, one commit, a step later
    assert _inside(lines, "engine.prefill", steps[0]) \
        and not _inside(lines, "engine.prefill.commit", steps[0])
    assert _inside(lines, "engine.prefill.commit", steps[1]) == [commit]
    assert not _inside(lines, "engine.admit", commit) \
        and not any(a[1] <= commit[1] and commit[2] <= a[2]
                    for a in _inside(lines, "engine.admit", steps[1]))
    [grow] = _inside(lines, "engine.kv_grow", steps[1])
    [launch] = _inside(lines, "engine.decode.dispatch", steps[1])
    assert grow[2] <= commit[1] and commit[2] <= launch[1]


def test_train_step_stats_count_every_call_and_only_the_contract_keys():
    step, x, y = tiny_train_step(), *batch(16)
    for _ in range(5):
        step(x, y)
    stats = step.stats()
    phases = ("call", "gather_state", "dispatch", "write_back")
    assert set(stats) == {
        "steps", "compiles", "flash_width_fallbacks", "dispatches",
        "dispatches_device_idle", "dispatches_blocked",
        "starved_dispatch_share", "dispatch_blocked_share"} | {
        f"{p}_{q}_ms" for p in phases + ("gc",)
        for q in ("p50", "p99", "max")}
    assert stats["flash_width_fallbacks"] == 0
    assert stats["steps"] == 5
    hists = step._stats.phase
    for p in phases:
        assert hists[f"train_step.{p}"].count == 5
        assert 0 < stats[f"{p}_p50_ms"] <= stats[f"{p}_p99_ms"]
        assert stats[f"{p}_max_ms"] == pytest.approx(
            1e3 * hists[f"train_step.{p}"].max)
    # a parent's time covers its children's
    assert hists["train_step.call"].sum >= sum(
        hists[f"train_step.{p}"].sum for p in phases[1:])


def test_train_step_compiles_count_traced_programs():
    step = tiny_train_step()
    step.lower(*batch(16)).compile()    # the same trace the call reuses
    for _ in range(4):
        step(*batch(16))
    assert step.stats()["compiles"] == 1
    step(*batch(8))
    assert step.stats()["compiles"] == 2
    step(*batch(16))
    assert step.stats()["compiles"] == 2


def test_the_accessor_returns_live_train_steps_oldest_first():
    first, second = tiny_train_step(), tiny_train_step()
    second(*batch(16))
    mine = train_step_stats()[-2:]
    assert [s["steps"] for s in mine] == [0, 1]
    del second
    import gc
    gc.collect()
    assert train_step_stats()[-1]["steps"] == 0
    assert first.stats()["steps"] == 0


def test_engine_phase_counts_and_shares():
    engine = tiny_engine()
    for p in PROMPTS:
        engine.add_request(p, max_new_tokens=6)
    calls = 0
    while engine.step():
        calls += 1
    calls += 1
    stats, phase = engine.stats(), engine._stats.phase
    assert phase["engine.step"].count == calls
    assert phase["engine.prefill"].count == stats["prefills"] == 3
    assert phase["engine.decode.dispatch"].count == stats["steps"]
    assert phase["engine.stream"].count == stats["steps"]
    assert phase["engine.compile"].count == 3
    shares = [stats[k] for k in ("prefill_share", "decode_share",
                                 "stream_share", "step_self_share")]
    assert all(0.0 <= s <= 1.0 for s in shares)
    assert stats["step_self_share"] >= 0.0
    assert 0.5 < sum(shares) <= 1.0
    assert stats["prefill_p50_ms"] > 0
    assert stats["decode_dispatch_p50_ms"] > 0
    assert stats["compile_s"] == pytest.approx(phase["engine.compile"].sum)


def test_reset_stats_zeroes_the_phases_and_keeps_compile_seconds():
    engine = tiny_engine()
    engine.generate(PROMPTS, max_new_tokens=3)
    before = engine.stats()
    assert before["compile_s"] > 0 and before["prefill_share"] > 0
    engine.reset_stats()
    after = engine.stats()
    assert after["compile_s"] == before["compile_s"]
    for key in ("prefill_share", "decode_share", "stream_share",
                "step_self_share", "prefill_p50_ms",
                "decode_dispatch_p50_ms"):
        assert after[key] == 0.0
    assert all(h.count == 0 for name, h in engine._stats.phase.items()
               if name != "engine.compile")
    turn = [k for k in after if k.endswith(("_ms_per_step", "_max_ms"))
            or "dispatch" in k]
    assert len(turn) == 2 * 13 + 2 + 15 + 1     # 13 spans, 2 derived,
    assert all(after[k] == 0 for k in turn)     # 15 counters, the old p50
    assert before["dispatches"] > 0 and before["step_max_ms"] > 0
    assert before["slowest_step"]["seconds"] > 0
    assert after["slowest_step"] is None and after["gc_collections"] == 0
    # the next window compiles nothing, so the sum stands still
    engine.generate(PROMPTS, max_new_tokens=3)
    assert engine.stats()["compile_s"] == before["compile_s"]
    assert engine.stats()["prefill_share"] > 0


def _short(span):
    return span.split(".", 1)[1].replace(".", "_")


def _count_spans(engine):
    """Every span the engine opens from now on, counted by name."""
    opened, span = {}, engine._span

    def counting(name):
        opened[name] = opened.get(name, 0) + 1
        return span(name)
    engine._span = counting
    return opened


def test_every_span_of_a_step_feeds_a_histogram_of_its_own():
    """The known traffic, warm: each of the twelve phases has as many
    observations as its span was opened, a step's direct children and
    what is left add up to the step, and a maximum is no less than a
    mean."""
    engine = tiny_engine()
    engine.generate(PROMPTS, max_new_tokens=6)      # compiles
    engine.reset_stats()
    opened = _count_spans(engine)
    engine.generate(PROMPTS, max_new_tokens=6)
    stats, phase = engine.stats(), engine._stats.phase
    assert len(ServeStats.PHASES) == 12
    for name in ServeStats.PHASES:
        assert phase[name].count == opened[name] > 0, name
    assert "engine.compile" not in opened
    assert opened["engine.prefill.dispatch"] == opened["engine.prefill"] == 3
    assert opened["engine.decode.dispatch"] == stats["steps"]
    assert opened["engine.prefill.commit"] == opened["engine.prefill.wait"] \
        == 1                            # one fetch for the boundary's three
    steps = stats["steps"]
    for name in ServeStats.PHASES + ("engine.gc",):
        hist, short = phase[name], _short(name)
        assert stats[short + "_ms_per_step"] == pytest.approx(
            1e3 * hist.sum / steps)
        assert stats[short + "_max_ms"] == pytest.approx(
            1e3 * (hist.max or 0.0))
        if hist.count:
            assert stats[short + "_max_ms"] >= \
                stats[short + "_ms_per_step"] * steps / hist.count * (1 - 1e-9)
    children = sum(stats[_short(n) + "_ms_per_step"]
                   for n in ServeStats.DIRECT)
    assert stats["step_unattributed_ms_per_step"] >= 0
    assert children + stats["step_unattributed_ms_per_step"] == \
        pytest.approx(stats["step_ms_per_step"])
    assert children > 0.5 * stats["step_ms_per_step"]
    assert stats["host_wait_ms_per_step"] == pytest.approx(
        stats["decode_wait_ms_per_step"] + stats["prefill_wait_ms_per_step"])
    # the longest step's own anatomy obeys the same identity
    slowest = stats["slowest_step"]
    spent = slowest["phases"]
    assert set(spent) == set(ServeStats.PHASES)
    assert spent["engine.step"] == slowest["seconds"] == pytest.approx(
        1e-3 * stats["step_max_ms"])
    assert 0 <= slowest["index"] < phase["engine.step"].count
    assert sum(spent[n] for n in ServeStats.DIRECT) <= \
        slowest["seconds"] * (1 + 1e-9)


def test_a_host_that_comes_late_finds_the_device_idle_at_every_dispatch():
    """One request at a time, and a host so slow that every program has
    finished before the next is dispatched (here: it waits for the newest
    result first): the device's queue is empty at every call."""
    engine = tiny_engine()
    call = engine._call_program

    def late(name, fn, args, first):
        if engine._newest_result is not None:
            engine._newest_result.block_until_ready()
        return call(name, fn, args, first)

    engine._call_program = late
    for p in PROMPTS:
        engine.generate([p], max_new_tokens=4)
    stats = engine.stats()
    # buckets 8 and 16 and the decode program: three first calls
    assert stats["prefill_dispatches"] == stats["prefills"] - 2 == 1
    assert stats["decode_dispatches"] == stats["decode_launches"] - 1 == 8
    assert stats["dispatches"] == 9
    for kind in ("", "prefill_", "decode_"):
        assert stats[kind + "dispatches_device_idle"] == \
            stats[kind + "dispatches"]
        assert stats[kind + "starved_dispatch_share"] == 1.0
        assert stats[kind + "dispatches_ran_dry"] == 0
        assert stats[kind + "ran_dry_dispatch_share"] == 0.0


def test_the_loop_counts_every_call_but_a_programs_first():
    engine = tiny_engine()
    engine.generate(PROMPTS, max_new_tokens=6)
    stats = engine.stats()
    assert stats["dispatches"] == \
        stats["prefills"] + stats["decode_launches"] - 3
    assert stats["dispatches"] == \
        stats["prefill_dispatches"] + stats["decode_dispatches"]
    for kind in ("", "prefill_", "decode_"):
        # idle before the call, or run dry during it, or neither
        assert 0 <= stats[kind + "dispatches_device_idle"] \
            + stats[kind + "dispatches_ran_dry"] <= stats[kind + "dispatches"]
        assert 0.0 <= stats[kind + "starved_dispatch_share"] \
            + stats[kind + "ran_dry_dispatch_share"] <= 1.0
    engine.reset_stats()
    assert engine.stats()["dispatches"] == 0
    assert engine.stats()["starved_dispatch_share"] == 0.0


def test_a_caller_that_waits_for_each_loss_starves_every_dispatch():
    step, x, y = tiny_train_step(), *batch(16)
    for _ in range(6):
        float(step(x, y))
    stats = step.stats()
    assert stats["dispatches"] == 5     # the call that traces is left out
    assert stats["dispatches_device_idle"] == 5
    assert stats["dispatches_blocked"] == 0
    assert stats["starved_dispatch_share"] == 1.0
    assert stats["dispatch_blocked_share"] == 0.0
    step(*batch(8))                     # a new shape traces: not counted
    assert step.stats()["dispatches"] == 5


def test_train_step_dispatches_are_sorted_by_the_loss_before():
    stats = tiny_train_step()._stats
    for at_entry, at_return in ((True, True), (False, True), (False, True),
                                (False, False)):
        stats.count_dispatch(at_entry, at_return)
    snap = stats.snapshot()
    assert (snap["dispatches"], snap["dispatches_device_idle"],
            snap["dispatches_blocked"]) == (4, 1, 2)
    assert snap["starved_dispatch_share"] == 0.25
    assert snap["dispatch_blocked_share"] == 0.5


def test_engine_dispatches_are_sorted_by_the_program_before():
    stats = ServeStats()
    for kind, at_entry, at_return in (
            ("prefill", True, True), ("prefill", False, True),
            ("decode", False, True), ("decode", False, False),
            ("decode", False, False)):
        stats.count_dispatch(kind, at_entry, at_return)
    snap = stats.snapshot()
    assert [snap[k] for k in ("dispatches", "dispatches_device_idle",
                              "dispatches_ran_dry")] == [5, 1, 2]
    assert [snap["prefill_" + k] for k in (
        "dispatches", "dispatches_device_idle", "dispatches_ran_dry",
        "starved_dispatch_share", "ran_dry_dispatch_share")] == \
        [2, 1, 1, 0.5, 0.5]
    assert snap["decode_ran_dry_dispatch_share"] == pytest.approx(1 / 3)
    assert snap["decode_starved_dispatch_share"] == 0.0
    assert snap["starved_dispatch_share"] == 0.2
    assert snap["ran_dry_dispatch_share"] == 0.4


def _hand_step(stats, seconds, compiles=False, **phases):
    """One `step()` as `ServeStats` sees it, its spans' seconds set by
    hand (no clock): `phases` by short name."""
    stats.step_begin()
    for short, spent in phases.items():
        stats.phase["engine." + short.replace("_", ".")].observe(spent)
    if compiles:
        stats.compile_hist.observe(seconds)
    stats.phase["engine.step"].observe(seconds)
    stats.step_end()


@pytest.fixture
def serving_log(caplog):
    caplog.set_level(logging.WARNING, logger="paddle_tpu.serving")
    return caplog


def test_the_slowest_step_keeps_its_anatomy(serving_log):
    stats = ServeStats()
    assert stats.snapshot()["slowest_step"] is None
    _hand_step(stats, 0.010, admit=0.004, decode=0.003, stream=0.002)
    _hand_step(stats, 0.030, admit=0.020, prefill=0.019, decode=0.004,
               decode_wait=0.001, gc=0.015)
    _hand_step(stats, 0.020, admit=0.019)
    slowest = stats.snapshot()["slowest_step"]
    assert slowest["index"] == 1 and slowest["seconds"] == \
        pytest.approx(0.030)
    assert slowest["gc_s"] == pytest.approx(0.015)
    spent = slowest["phases"]
    assert spent["engine.admit"] == pytest.approx(0.020)
    assert spent["engine.prefill"] == pytest.approx(0.019)
    assert spent["engine.stream"] == 0.0 and "engine.gc" not in spent
    unattributed = spent["engine.step"] - sum(
        spent[n] for n in ServeStats.DIRECT)
    assert unattributed == pytest.approx(0.006)
    assert not serving_log.records      # nothing near the rule
    stats.reset()
    assert stats.snapshot()["slowest_step"] is None


def test_a_stalled_step_says_which_phase_held_it_once(serving_log):
    stats = ServeStats()
    for _ in range(99):
        _hand_step(stats, 0.010, decode=0.004)
    # the window holds 99 steps: too few to know its mean
    _hand_step(stats, 0.9, decode=0.8)
    assert not serving_log.records
    _hand_step(stats, 0.010, decode=0.004)
    # a step that compiled is slow by right
    _hand_step(stats, 3.0, compiles=True, admit=2.9)
    # 0.2 s is 20 x the mean and under a quarter second; 0.3 s is over a
    # quarter second and under 20 x the mean by now (0.048 s)
    _hand_step(stats, 0.2, decode=0.19)
    _hand_step(stats, 0.3, decode=0.29)
    assert not serving_log.records
    _hand_step(stats, 2.5, admit=0.1, decode=2.3, decode_wait=2.2,
               stream=0.05, gc=2.1)
    # not the window's longest, over the rule all the same, and inside
    # the second of the line before it
    _hand_step(stats, 2.0, stream=1.9)
    [record] = serving_log.records
    assert record.levelno == logging.WARNING
    line = record.getMessage()
    assert "step 104 took 2.500 s" in line
    assert "engine.decode 2.300 s, engine.decode.wait 2.200 s, " \
        "engine.admit 0.100 s" in line
    assert line.endswith("gc 2.100 s")
    assert stats.snapshot()["slowest_step"]["index"] == 101     # compiled


@pytest.fixture
def only_forced_collections():
    gc.collect()
    gc.disable()
    yield
    gc.enable()


def test_one_gc_callback_serves_every_watcher_and_forgets_the_dropped(
        only_forced_collections):
    import weakref

    def ours():
        return [cb for cb in gc.callbacks if cb is profiler._on_gc]
    engines = [tiny_engine(), tiny_engine()]
    step = tiny_train_step()
    assert len(ours()) == 1
    hists = [e._stats.phase["engine.gc"] for e in engines] \
        + [step._stats.phase["train_step.gc"]]
    gc.collect()
    assert [h.count for h in hists] == [1, 1, 1]
    assert engines[0].stats()["gc_collections"] == 1
    gc.collect()
    assert [h.count for h in hists] == [2, 2, 2]
    assert engines[1].stats()["gc_collections"] == 2
    assert engines[1].stats()["gc_max_ms"] > 0
    assert step.stats()["gc_max_ms"] > 0
    # a new window's histogram is watched in the old one's place
    old = weakref.ref(hists.pop(0))
    engines[0].reset_stats()
    gc.collect()
    assert engines[0].stats()["gc_collections"] == 1 and old() is None
    # a dropped engine takes its watcher along
    dropped = weakref.ref(hists.pop(0))
    del engines[1]
    gc.collect()
    gc.collect()        # the start of a collection forgets the dead
    assert dropped() is None
    assert all(ref() is not None for _, ref in profiler._gc_watchers)
    assert len(ours()) == 1 and not profiler._gc_open
    assert hists[0].count == 5


@pytest.fixture
def no_native_library(monkeypatch):
    """Any look for the native library through the host tracer fails the
    test, and the in-process recorder starts empty."""
    import paddle_tpu.core as core

    def load_library():
        raise AssertionError("a span loaded the native library")
    monkeypatch.setattr(core, "load_library", load_library)
    assert profiler._active_profiler is None
    profiler._recorder.drain()


def test_a_thousand_spans_with_nobody_listening_leave_nothing(
        no_native_library):
    hist = profiler.LogHistogram()
    for _ in range(1000):
        with profiler.RecordEvent("quiet.span", hist=hist):
            pass
    event = profiler.RecordEvent("quiet.begin_end")
    event.begin()
    event.end()
    event.end()                         # a second end is a no-op
    assert hist.count == 1000
    assert profiler._recorder.events == []


def test_a_thousand_engine_steps_hold_no_span_record(no_native_library):
    engine = tiny_engine()
    steps = 0
    while steps < 1000:
        for p in PROMPTS:
            engine.add_request(p, max_new_tokens=48)
        while engine.step():
            steps += 1
    assert engine._stats.phase["engine.step"].count >= 1000
    assert profiler._recorder.events == []


def test_train_steps_with_nobody_listening_hold_no_span_record(
        no_native_library):
    step, x, y = tiny_train_step(), *batch(16)
    for _ in range(50):
        step(x, y)
    assert step.stats()["steps"] == 50
    assert profiler._recorder.events == []


def test_an_active_profiler_still_gets_the_spans_in_its_chrome_export(
        tmp_path):
    step, x, y = tiny_train_step(), *batch(16)
    engine = tiny_engine()
    prof = profiler.Profiler(
        targets=[profiler.ProfilerTarget.CPU],
        on_trace_ready=profiler.export_chrome_tracing(str(tmp_path)))
    with prof:
        step(x, y)
        engine.generate(PROMPTS[:1], max_new_tokens=2)
        with profiler.RecordEvent("user.region"):
            pass
    loaded = profiler.load_profiler_result(prof._export_path)
    names = {e["name"] for e in loaded.trace_events if e.get("ph") == "X"}
    assert {"user.region", "train_step.call", "train_step.dispatch",
            "engine.step", "engine.prefill", "engine.decode",
            "engine.stream"} <= names
    durations = [e["dur"] for e in loaded.trace_events
                 if e.get("ph") == "X" and e["name"] == "train_step.call"]
    assert durations and all(math.isfinite(d) and d > 0 for d in durations)
    # and once it has stopped, nothing accumulates again
    profiler._recorder.drain()
    step(x, y)
    assert profiler._recorder.events == []


def test_the_flash_kernels_carry_their_names():
    from paddle_tpu.kernels.flash_attention import _flash_bwd, _flash_fwd
    q = jax.numpy.zeros((1, 128, 2, 64), jax.numpy.float32)

    def both(q, k, v):
        out, lse = _flash_fwd(q, k, v, True, 0.125, interpret=True)
        return _flash_bwd(q, k, v, out, lse, out, True, 0.125,
                          interpret=True)
    text = str(jax.make_jaxpr(both)(q, q, q))
    for name in ("flash_attention_fwd", "flash_attention_dq",
                 "flash_attention_dkv"):
        assert name in text


@pytest.mark.parametrize("variant", ["reference", "blockwise", "pallas"])
def test_the_decode_attention_and_its_kv_write_are_scoped(variant):
    from paddle_tpu.nn.functional.attention import paged_decode_attention
    jnp = jax.numpy
    q = jnp.zeros((2, 1, 2, 64), jnp.float32)
    pool = jnp.zeros((2, 5, 16, 2 * 64), jnp.float32)
    tables = jnp.zeros((2, 2), jnp.int32)
    lens = jnp.asarray([3, 0], jnp.int32)
    active = jnp.asarray([True, False])

    def fn(q, pool):
        return paged_decode_attention(q, q, q, pool, pool, 1, tables, lens,
                                      active, 16, kernel=variant,
                                      interpret=True)
    text = jax.jit(fn).lower(q, pool).as_text(debug_info=True)
    assert "paged_attention" in text and "paged_kv_write" in text


def test_sampling_scatter_head_and_loss_are_scoped():
    engine = tiny_engine()
    engine.generate(PROMPTS[:1], max_new_tokens=2)
    decode = engine._decode_fn.lower(*engine._decode_args())
    text = decode.as_text(debug_info=True)
    for scope in ("paged_attention", "paged_kv_write", "sample_tokens",
                  "lm_head"):
        assert scope in text, scope
    req = engine.add_request(PROMPTS[0], max_new_tokens=2)
    req.slot, req.blocks = 0, []
    prefill = engine._prefill_fns[8].lower(
        *engine._prefill_args(req.prompt, 8, req))
    assert "scatter_prefill" in prefill.as_text(debug_info=True)
    from paddle_tpu.incubate.models import GPTPretrainingCriterion
    crit = GPTPretrainingCriterion()

    def loss(logits, labels):
        return crit(paddle.Tensor(logits), paddle.Tensor(labels))._value
    text = jax.jit(loss).lower(
        jax.numpy.zeros((1, 4, 8), jax.numpy.float32),
        jax.numpy.zeros((1, 4), jax.numpy.int32)).as_text(debug_info=True)
    assert "lm_loss" in text
