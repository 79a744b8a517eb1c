"""The program's own spans (`profiler.RecordEvent`) inside
`TrainStep.__call__` and `LLMEngine.step()`: on the profiler's clock (the
host plane of the one `.xplane.pb`), nested as the tables of ISSUE 24 say,
each feeding the phase histogram that `stats()` reports; silent when no
`Profiler` listens."""
import glob
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
    """One `jax.profiler` session over a tiny TrainStep and tiny engines
    (plain and pipelined), inside the caller's own annotation."""
    out = str(tmp_path_factory.mktemp("trace"))
    step, x, y = tiny_train_step(), *batch(16)
    engine = tiny_engine(pipeline_decode=False)
    piped = tiny_engine(pipeline_decode=True)
    jax.profiler.start_trace(out)
    try:
        with jax.profiler.TraceAnnotation("caller.window"):
            for _ in range(3):
                step(x, y)
            engine.generate(PROMPTS, max_new_tokens=4)
        with jax.profiler.TraceAnnotation("caller.pipelined"):
            piped.generate(PROMPTS, max_new_tokens=4)
        with jax.profiler.TraceAnnotation("caller.warm"):
            piped.generate(PROMPTS, max_new_tokens=4)
    finally:
        jax.profiler.stop_trace()
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


def test_the_pipelined_tail_opens_the_same_spans(traced):
    lines = traced["lines"]
    window = _named(lines, "caller.pipelined")[0]
    names = {n for events in lines.values() for n, a, b in events
             if window[1] <= a and b <= window[2]}
    assert {n for n in ENGINE_PARENTS} <= names


def _inside(lines, name, window):
    return [f for f in _named(lines, name)
            if f[0] == window[0] and window[1] <= f[1] and f[2] <= window[2]]


def test_a_prefill_is_dispatched_and_its_wait_sits_under_the_commit(traced):
    """A prefill's span holds its dispatch and no wait: the wait for a
    boundary's prefills is the commit's. In the pipelined loop, the
    buckets warm, that commit lies past the admission and the table
    growth of the NEXT step, before that step's decode launch."""
    lines = traced["lines"]
    for outer in ("caller.window", "caller.pipelined", "caller.warm"):
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
    assert set(stats) == {"steps", "compiles", "flash_width_fallbacks"} | {
        f"{p}_{q}_ms" for p in phases for q in ("p50", "p99")}
    assert stats["flash_width_fallbacks"] == 0
    assert stats["steps"] == 5
    hists = step._stats.phase
    for p in phases:
        assert hists[f"train_step.{p}"].count == 5
        assert 0 < stats[f"{p}_p50_ms"] <= stats[f"{p}_p99_ms"]
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


@pytest.mark.parametrize("pipelined", [False, True])
def test_engine_phase_counts_and_shares(pipelined):
    engine = tiny_engine(pipeline_decode=pipelined)
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
    if not pipelined:
        assert phase["engine.decode"].count == stats["steps"]
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
    # the next window compiles nothing, so the sum stands still
    engine.generate(PROMPTS, max_new_tokens=3)
    assert engine.stats()["compile_s"] == before["compile_s"]
    assert engine.stats()["prefill_share"] > 0


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
    prefill = engine._prefill_fns[8].lower(*engine._kv_args(
        np.zeros((1, 8), np.int32), np.int32(5),
        np.zeros(engine.max_blocks_per_seq, np.int32), np.float32(0),
        np.int32(0), np.float32(1), np.float32(1), np.uint32(0),
        np.int32(0), engine._tokens, engine._firsts,
        engine._k_pools, engine._v_pools))
    assert "scatter_prefill" in prefill.as_text(debug_info=True)
    from paddle_tpu.incubate.models import GPTPretrainingCriterion
    crit = GPTPretrainingCriterion()

    def loss(logits, labels):
        return crit(paddle.Tensor(logits), paddle.Tensor(labels))._value
    text = jax.jit(loss).lower(
        jax.numpy.zeros((1, 4, 8), jax.numpy.float32),
        jax.numpy.zeros((1, 4), jax.numpy.int32)).as_text(debug_info=True)
    assert "lm_loss" in text
