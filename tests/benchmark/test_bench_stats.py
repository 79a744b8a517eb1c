"""Metric arithmetic on made-up windows, and the traffic generator."""
import collections
import json
import os

import pytest

from benchmark import stats, traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _mix(name):
    with open(os.path.join(REPO, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


def _ready(steps, step_s, stall_at=None, stall_s=0.0):
    t, out = 0.0, []
    for i in range(steps):
        t += step_s + (stall_s if i == stall_at else 0.0)
        out.append(t)
    return out


def _window(steps, step_s, stall_at=None, stall_s=0.0):
    """What `loops.train.drive` returns for a made-up window: the queue
    is two deep, so the last two ready moments belong to the drain."""
    ready = _ready(steps, step_s, stall_at, stall_s)
    return {"steps": steps, "window_s": ready[-1], "ready_s": ready[:-2]}


def test_the_rate_is_all_tokens_over_the_whole_window():
    w = _window(113, 0.1)
    assert stats.window_rate(w["steps"], 1000, w["window_s"]) == \
        pytest.approx(10000.0)


def test_readings_are_equal_steps_and_their_median_is_a_steps_pace():
    w = _window(113, 0.1)
    r = stats.train_readings(w["ready_s"], 1000, 9)
    assert r["steps_per_reading"] == 12 and len(r["tokens_per_s"]) == 9
    assert stats.median_rate(r) == pytest.approx(10000.0)
    assert stats.stall_share(r, 10000.0) == pytest.approx(0.0, abs=1e-9)


@pytest.mark.parametrize("stall_at", [0, 50, 112])
def test_one_stall_moves_the_rate_and_shows_in_the_stall_share(stall_at):
    """Wherever it falls: in the queue-fill steps, in a reading, in the
    drain. The median reading alone would not see it."""
    w = _window(113, 0.1, stall_at=stall_at, stall_s=0.35)
    rate = stats.window_rate(w["steps"], 1000, w["window_s"])
    assert rate == pytest.approx(113 * 1000 / (11.3 + 0.35))
    assert rate < 0.975 * 10000.0
    r = stats.train_readings(w["ready_s"], 1000, 9)
    assert stats.median_rate(r) == pytest.approx(10000.0)
    assert stats.stall_share(r, rate) == pytest.approx(
        100 * 0.35 / (11.3 + 0.35))


def test_a_stall_in_every_nth_step_moves_the_rate_by_its_share():
    ready, t = [], 0.0
    for i in range(113):
        t += 0.1 + (0.02 if i % 4 == 0 else 0.0)
        ready.append(t)
    rate = stats.window_rate(113, 1000, ready[-1])
    assert rate == pytest.approx(113 * 1000 / (11.3 + 29 * 0.02))


def test_too_few_steps_for_the_readings_is_an_error():
    with pytest.raises(ValueError):
        stats.train_readings(_ready(8, 0.1), 1000, 9)


@pytest.mark.parametrize("name", ["backlog_mixed"])
def test_lengths_stay_inside_the_prefill_buckets(name):
    mix = _mix(name)
    prompts = traffic.stratified_lengths(mix["prompt_tokens"], 280)
    outputs = traffic.stratified_lengths(mix["output_tokens"], 280)
    assert min(prompts) >= 33 and max(prompts) <= 512
    assert min(outputs) >= 16 and max(outputs) <= 128
    buckets = {max(8, 1 << (n - 1).bit_length()) for n in prompts}
    assert buckets == set(mix["prefill_buckets"])
    assert max(prompts) + max(outputs) <= mix["reference_pad_to"] \
        <= mix["engine"]["max_context"]


def test_filler_blocks_hold_the_same_multiset():
    mix = _mix("backlog_mixed")
    gen = traffic.filler_requests(mix, 5, 50304, 5)
    blocks = [[next(gen) for _ in range(mix["block"])] for _ in range(2)]
    for pick in (lambda p, o: len(p), lambda p, o: o):
        sizes = [collections.Counter(pick(p, o) for p, o in b)
                 for b in blocks]
        assert sizes[0] == sizes[1]


def test_the_pool_filled_share_is_the_mean_of_the_blocks_held():
    from benchmark.readers import pool_filled_share
    evidence = {"pool_blocks_held": [10, 20, 30],
                "engine_facts": {"pool_blocks": 80}}
    assert pool_filled_share.read(evidence) == pytest.approx(25.0)
    assert pool_filled_share.read({"engine_facts": {"pool_blocks": 80}}) \
        is None


def test_a_spread_is_the_quartile_distance_over_the_median():
    """`proof` reports spreads as the contract defines them."""
    from benchmark import proof
    values = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    # statistics.quantiles(n=4), exclusive: 100.75 and 104.25
    assert proof.spread(values) == pytest.approx(3.5 / 102.5)
    assert proof.spread([7.0, 7.0, 7.0]) == 0.0
