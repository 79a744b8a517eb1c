"""The trace reduction, the peaks table and the kernel counts, on a small
hand-written trace whose answers can be worked out on paper."""
import os

import pytest

from benchmark import flops, peaks, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
US = 1e-6


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "two_devices.xspace.txt")) as f:
        data = ProfileData.from_text_proto(f.read())
    return trace.reduce(trace.from_profile(data))


def test_window_is_the_benchmark_span(reduced):
    assert reduced["window_s"] == pytest.approx(10 * US)
    assert reduced["devices"] == 2


def test_busy_is_the_union_averaged_over_devices(reduced):
    # device 0: 0-3 and 5-6.5 = 4.5 us; device 1: 4 us
    assert reduced["busy_s"] == pytest.approx(4.25 * US)


def test_operations_by_name_and_pattern(reduced):
    seconds, events = trace.seconds_matching(reduced, r"[\]})] fusion\(")
    assert seconds == pytest.approx(3 * US)      # (2 + 4) / 2 devices
    assert events == pytest.approx(1.0)
    pool, _ = trace.seconds_matching(reduced, r"\[2,9,4,4,16\]\S* copy\(")
    assert pool == pytest.approx(0.5 * US)


def test_collectives_and_their_exposed_part(reduced):
    # device 0: all-reduce 1 us alone, all-gather-done 1 us of which
    # 0.5 us runs under the copy; device 1 has none
    assert reduced["collective_s"] == pytest.approx(1.0 * US)
    assert reduced["collective_exposed_s"] == pytest.approx(0.75 * US)


def test_idle_gaps_carry_the_span_the_host_was_in(reduced):
    gaps = sorted(reduced["gaps"], key=lambda g: -g[1])
    # device 0 idle: 3-5 us (midpoint in the 2nd step), 6.5-10 (8.25: none)
    assert gaps[0] == ("no_span", pytest.approx(3.5 * US))
    assert gaps[1] == ("bench.step", pytest.approx(2 * US))


def test_breakdown_is_what_the_result_line_carries(reduced):
    b = trace.breakdown(reduced)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    name, seconds = b["device_ops"][0]
    assert name == "fusion.1_fusion_bf16_8_8" and seconds == pytest.approx(3 * US)
    assert all(set(n) <= set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRS"
                             "TUVWXYZ0123456789_.-") and len(n) <= 64
               for n, _ in b["device_ops"])
    assert b["idle_gaps"][0] == ["total:no_span", pytest.approx(3.5 * US)]


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "spans": []})


def test_interval_arithmetic():
    assert trace.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert trace.length([(0, 3), (5, 6)]) == 4


def test_unknown_device_kind_raises():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(LookupError):
        peaks.peaks_for("cpu")


def test_flash_attention_counts():
    # one head, one row, seq 1024, dim 64: six products of 2*s*s*d, halved
    ops, moved = flops.flash_attention_train(1, 1, 1024, 64)
    assert ops == 6 * 2 * 1024 * 1024 * 64 / 2
    assert moved == 12 * 1024 * 64 * 2
    v5e = peaks.peaks_for("TPU v5 lite")
    least, bound = flops.roofline_seconds(ops, moved, v5e)
    assert bound == "compute" and least == pytest.approx(ops / 197e12)
    assert flops.roofline_seconds(1.0, 819e9, v5e) == (1.0, "memory")


def test_train_flops_per_token_is_the_programs_count():
    # 6 N + 12 L h s, as GPTForCausalLM.flops_per_token counts
    assert flops.train_flops_per_token(100, 2, 8, 16) == 600 + 12 * 2 * 8 * 16
