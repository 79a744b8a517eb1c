"""What a serve cell compares: the widest gap AND the mean gap of the
served tokens' logits below the reference's best, each against its own
limit. Where the model makes no discrete choice the widest catches one
altered token; where it routes its tokens to experts one rounding flips a
choice in a sound program too, only the mean tells bfloat16 from fp8, and
NEITHER number is shown to catch one altered token. Driven on the CPU at a
tiny size."""
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(HERE, "other_model")]
import tiny_root  # noqa: E402

from benchmark import correct, harness, run as bench_run, seeded  # noqa: E402
from benchmark.loops import serving  # noqa: E402

# a routed model small enough for a test and still ruled by its router:
# 64 experts, 2 a token, weights N(0, 0.1), so that among the 512 served
# tokens of a case a flipped choice sets the widest gap in bfloat16 as in
# fp8 (other sizes tried: with 16 experts or N(0, 0.02) bfloat16 flips no
# choice in 512 tokens and the widest gap alone tells the two apart)
ROUTED = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 48,
    "n_routed_experts": 64, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "routed_scaling_factor": 1.8, "norm_topk_prob": True,
    "first_k_dense_replace": 1, "num_hidden_layers": 5,
    "num_attention_heads": 4, "head_dim": 16, "rms_norm_eps": 1e-5,
    "vocab_size": 512, "initializer_range": 0.1,
    "precision": {"params": "bfloat16", "activations": "bfloat16",
                  "control": "fp8"},
    "program": "none_is_served", "reference": "routed_reference",
}
STREAMS, PROMPT, SERVED, PAD = 16, 8, 32, 48
# from readings over ten seeds (1-8 and the two large ones below; on the
# CPU): the bfloat16 stand-in's widest gap 0.73-1.62 and mean 0.0076-0.0195,
# the fp8 control's 1.95-2.59 and 0.141-0.211. By the rule of PERF.md
# section 4: at least twice the stand-in's largest, and for the mean at
# most half the control's smallest. The control's WIDEST gap lies under
# its limit too: no limit on the widest gap parts the two.
ROUTED_LIMITS = {"logit_gap": 3.3, "logit_gap_mean": 0.05}


@pytest.fixture
def routed_root(root):
    """The routed model's serve cell, added to the tiny root as files and
    entries only."""
    tiny_root.add_cell(
        root, "routed_cell", ("routed", ROUTED),
        ("routed_mix", dict(tiny_root.TRAFFIC["tiny_backlog"],
                            reference_pad_to=PAD)),
        ROUTED_LIMITS, ("serve_tokens_per_s",))
    return root


def served_in(precision, seed):
    """STREAMS requests decoded greedily by the routed fixture computed in
    `precision`: every served token is that arithmetic's own first choice
    given the tokens before it. [(prompt ids, served ids)]."""
    import jax
    import jax.numpy as jnp
    import routed_reference
    weights = correct.weight_maker(ROUTED, seed)()
    rng = seeded.host_rng(seed, 9)
    ids = np.zeros((STREAMS, PAD), np.int32)
    ids[:, :PROMPT] = rng.integers(0, ROUTED["vocab_size"], (STREAMS, PROMPT))

    @jax.jit
    def first(w, ids, at):
        return jnp.argmax(routed_reference.forward(
            w, ids, ROUTED, precision)[:, at], -1)

    for at in range(PROMPT - 1, PROMPT + SERVED - 1):
        ids[:, at + 1] = np.asarray(first(weights, jnp.asarray(ids), at))
    return [(row[:PROMPT].tolist(), row[PROMPT:PROMPT + SERVED].tolist())
            for row in ids]


def rest_of_a_run(root, cell, seed, streams):
    """What a serve loop does once its window has closed and the engine is
    gone: the check of the sampled streams, and the result line."""
    run = harness.Run(root, cell, seed, 1.0, False, require_chip=False)
    run.claim_devices()
    serving.check_served(run, streams)
    return run.result({"serve_tokens_per_s": 1.0, "setup_s": 1.0},
                      attempted=len(streams), failed=0)


@pytest.mark.parametrize("seed", [3, 5, 2 ** 31 + 7, 3000028201])
@pytest.mark.parametrize("precision,correct_", [("bfloat16", True),
                                                ("fp8", False)])
def test_a_routed_model_served_in_fp8_fails_by_the_mean_gap_alone(
        routed_root, precision, correct_, seed):
    """The case the mean gap is compared for. Every served token is the
    first choice of the routed fixture computed in fp8, the control of a
    configuration that states bfloat16: the widest gap PASSES a limit set
    from the bfloat16 stand-in (one flipped expert sets it on either
    side), the mean gap does not, and `correct` is false. The stand-in
    itself passes both, each with the room the rule asks for."""
    line = rest_of_a_run(routed_root, "routed_cell", seed,
                         served_in(precision, seed))
    widest, mean = (line["checks"][name] for name in serving.COMPARED)
    assert set(line["checks"]) == set(serving.COMPARED)
    # a flipped choice somewhere in the 512 tokens: the widest gap is of
    # the size of the logits' own spread, in the sound program too
    assert 0.5 < widest["value"] <= widest["limit"]
    if correct_:
        assert 2 * widest["value"] <= widest["limit"]
        assert 2 * mean["value"] <= mean["limit"]
    else:
        assert mean["value"] >= 2 * mean["limit"]
    assert line["correct"] is correct_


@pytest.mark.parametrize("seed", [3, 5, 2 ** 31 + 7, 3000028201])
def test_one_altered_token_in_a_routed_cell_is_caught_only_by_chance(
        routed_root, seed):
    """What neither number promises of a model that routes. The last
    served token of one sound (bfloat16) stream is replaced by the token
    the reference ranks in the MIDDLE of the vocabulary at that position,
    a wrong token of the most ordinary kind (the last, so that no later
    position reads an altered context). The mean moves by a 512th of that
    token's gap and passes. The widest gap reads the token's gap, and
    fails it only where that exceeds the limit that sound flips force
    (3.3): under three of these seeds it reads 2.25-2.72 and `correct`
    stays TRUE, under one 3.48. Of all (position, wrong token) pairs of a
    sample 16-21% lie over the limit (on the CPU). What would catch the
    rest is named in PERF.md section 7: the widest gap over the positions
    at which every router's margin in the reference exceeds a rounding."""
    import jax.numpy as jnp
    import routed_reference
    streams = served_in("bfloat16", seed)
    ids = jnp.asarray([p + s for p, s in streams], jnp.int32)
    logits = np.asarray(routed_reference.forward(
        correct.weight_maker(ROUTED, seed)(), ids, ROUTED))
    at = slice(PROMPT - 1, PROMPT + SERVED - 1)      # t chooses token t + 1
    gaps = np.max(logits[:, at], -1, keepdims=True) - logits[:, at]
    limit = ROUTED_LIMITS["logit_gap"]
    assert 0.1 < np.mean(gaps > limit) < 0.3
    ordinary = int(np.argsort(gaps[0, -1])[ROUTED["vocab_size"] // 2])
    streams[0] = (streams[0][0], streams[0][1][:-1] + [ordinary])
    line = rest_of_a_run(routed_root, "routed_cell", seed, streams)
    widest, mean = (line["checks"][name] for name in serving.COMPARED)
    assert widest["value"] == pytest.approx(gaps[0, -1, ordinary], abs=1e-4)
    assert widest["value"] > 2.0
    assert mean["value"] <= mean["limit"]
    assert line["correct"] is bool(gaps[0, -1, ordinary] <= limit)
    assert line["correct"] is (seed != 3000028201)


def test_the_drive_of_the_chip_readings_runs_at_a_tiny_size():
    """`routed_drive.read_seed`, which the chip runs at published widths:
    the harness's own `served_gaps` reads the two arithmetics' gaps, and
    the flipped share stands beside them."""
    import routed_drive
    low, control = routed_drive.read_seed(ROUTED, 2 ** 31 + 7, 3, PAD)
    assert (low["arithmetic"], control["arithmetic"]) == ("bfloat16", "fp8")
    for row in (low, control):
        assert row["tokens"] == 3 * (PAD - 1)
        assert 0.0 <= row["control_logit_gap_mean"] \
            <= row["control_logit_gap_p99"] <= row["control_logit_gap"]
        assert len(row["flipped_share_by_layer"]) == 4
        assert max(row["flipped_share_by_layer"]) <= row["flipped_share"] <= 1
        # the rows' "served" ids are random: an altered token's gap
        assert row["logit_gap_mean"] > 1.0
    assert control["control_logit_gap_mean"] > \
        3 * low["control_logit_gap_mean"]
    assert control["flipped_share"] > low["flipped_share"] > 0.0


def test_the_routed_fixture_computes_what_a_gather_of_experts_gives():
    """The fixture computes every expert on every token and masks; the
    same layer written token by token, with only the chosen experts
    gathered, gives the same sum, and k experts are chosen."""
    import jax
    import jax.numpy as jnp
    import routed_reference as ref
    cfg = dict(ROUTED, initializer_range=0.5)
    w = jax.tree.map(lambda x: x.astype(jnp.float32),
                     correct.weight_maker(cfg, 11)())
    p = "layers.1."
    x = jax.random.normal(jax.random.key(0), (2, 7, cfg["hidden_size"]))
    mm = lambda a, b: jnp.matmul(a, b, precision="highest")
    gates, chosen = ref.route(x, w[p + "router.weight"],
                              w[p + "router.bias"], cfg)
    got = ref._experts(x, gates, w[p + "experts.gate_up.weight"],
                       w[p + "experts.down.weight"], mm)
    assert np.all(np.sum(np.asarray(chosen), -1) == 2)
    scores = jax.nn.sigmoid(mm(x, w[p + "router.weight"]))
    for r, t in [(0, 0), (1, 3), (1, 6)]:
        picked = np.argsort(-np.asarray(
            scores[r, t] + w[p + "router.bias"]))[:2]
        share = scores[r, t, picked] / jnp.sum(scores[r, t, picked])
        want = sum(
            1.8 * share[j] * ref._gated(
                x[r, t], w[p + "experts.gate_up.weight"][e],
                w[p + "experts.down.weight"][e], mm)
            for j, e in enumerate(picked))
        assert np.allclose(got[r, t], want, rtol=1e-4, atol=1e-6)
    assert ref.num_params(cfg) == sum(
        int(np.prod(v.shape)) for v in w.values())


def test_the_published_widths_the_chip_reads_at_fill_a_chip():
    """`routed_drive.PUBLISHED`: 4.5 billion parameters, 9 GB in
    bfloat16, beside which one row's logits fit a 16 GB chip."""
    import routed_drive
    import routed_reference as ref
    cfg = routed_drive.PUBLISHED
    assert (cfg["hidden_size"], cfg["moe_intermediate_size"],
            cfg["n_routed_experts"], cfg["num_experts_per_tok"],
            cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
            cfg["vocab_size"]) == (2048, 1536, 64, 4, 6, 154880)
    assert 8.9e9 < 2 * ref.num_params(cfg) < 9.1e9


# -- the numbers themselves ---------------------------------------------------

@pytest.mark.parametrize("gaps,widest,mean", [
    ([[1.0, 1.0], [0.0] * 6], 1.0, 0.25),       # not (1 + 0) / 2
    ([[0.5], [0.25, 0.75, 0.5]], 0.75, 0.5),
    ([[0.0, 0.0, 4.0]], 4.0, 4.0 / 3),
])
def test_the_mean_gap_is_over_tokens_not_over_requests(gaps, widest, mean):
    got = correct.gap_numbers("g", gaps)
    assert set(got) == {"g", "g_mean", "g_p99"}
    assert got["g"] == widest and got["g_mean"] == pytest.approx(mean)
    assert got["g_p99"] <= got["g"]


def test_served_gaps_weighs_two_streams_by_their_tokens():
    """Two hand-made streams held to the other model's reference: a short
    one of altered tokens and a long one of the reference's own choices.
    The mean is the sum of all gaps over all served tokens."""
    import jax.numpy as jnp
    import other_reference
    cfg, seed = tiny_root.OTHER_MODEL, 2 ** 31 + 13
    weights = correct.weight_maker(cfg, seed)()

    def decode(prompt, n, shift):
        served = []
        for _ in range(n):
            ids = jnp.asarray([prompt + served], jnp.int32)
            best = int(jnp.argmax(
                other_reference.forward(weights, ids, cfg)[0, -1]))
            served.append((best + shift) % cfg["vocab_size"])
        return prompt, served
    short, long_ = decode([5, 17, 99], 2, shift=1), decode([3, 64], 10, 0)
    one = correct.served_gaps(cfg, seed, [short], pad_to=16)
    other = correct.served_gaps(cfg, seed, [long_], pad_to=16)
    both = correct.served_gaps(cfg, seed, [short, long_], pad_to=16,
                               control="bfloat16")
    assert (one["tokens"], other["tokens"], both["tokens"]) == (2, 10, 12)
    assert one["logit_gap_mean"] > 0.0 and other["logit_gap_mean"] == 0.0
    assert both["logit_gap_mean"] == pytest.approx(
        one["logit_gap_mean"] * 2 / 12)
    assert both["logit_gap"] == one["logit_gap"]
    assert 0.0 <= both["control_logit_gap_mean"] \
        <= both["control_logit_gap_p99"] + 1e-12 \
        <= both["control_logit_gap"] + 1e-12


# -- a limits file holds both numbers, or the cell is never correct -----------

@pytest.mark.parametrize("missing", serving.COMPARED)
def test_a_serve_cell_whose_limits_lack_a_number_is_never_correct(
        root, missing):
    limits = {k: v for k, v in tiny_root.SERVE_LIMITS.items()
              if k != missing}
    assert limits
    tiny_root._dump(os.path.join(root, "benchmark", "limits",
                                 "tiny_backlog_cell.json"), limits)
    line = bench_run.run_cell(root, "tiny_backlog_cell", seed=2,
                              seconds=0.3, traced=False, require_chip=False)
    assert line["correct"] is False
    assert line["checks"][missing] == {
        "value": line["checks"][missing]["value"], "limit": None}
    kept = next(iter(limits))
    assert line["checks"][kept]["value"] <= line["checks"][kept]["limit"]


def test_the_seed_check_holds_the_control_to_the_cells_limits(root):
    """`seedcheck.serve`, which the limits are set from: every number
    goes through `Run.check` against the cell's limits file, the
    control's too, so a row says by the harness's own comparison that the
    sound program is correct and that the control in its place is not.
    With 64 requests checked the tiny cell reads, over these seeds,
    widest <= 0.0031 sound and >= 0.0142 for the fp8 control, mean
    <= 1.2e-5 and >= 6.9e-5 (on the CPU): limits between them."""
    from benchmark import seedcheck
    limits = {"logit_gap": 0.0075, "logit_gap_mean": 3e-5}
    data = os.path.join(root, "benchmark")
    tiny_root._dump(os.path.join(data, "limits", "tiny_backlog_cell.json"),
                    limits)
    tiny_root._dump(os.path.join(data, "traffic", "tiny_backlog.json"),
                    dict(tiny_root.TRAFFIC["tiny_backlog"],
                         checked_requests=64))
    run = harness.Run(root, "tiny_backlog_cell", 2, 0.3, False,
                      require_chip=False)
    rows = []
    seedcheck.serve(run, [2, 2 ** 31 + 5, 7], {2, 7}, 0.3, rows.append)
    assert [r["correct"] for r in rows] == [True] * 3
    assert [r.get("control_correct") for r in rows] == [False, None, False]
    for row in rows:
        names = [prefix + name for prefix in
                 (("", "control_") if "control_correct" in row else ("",))
                 for name in serving.COMPARED]
        assert list(row["checks"]) == names
        for name in names:
            check = row["checks"][name]
            assert check["value"] == row[name]
            assert check["limit"] == limits[name.removeprefix("control_")]
            assert check["ok"] is (check["value"] <= check["limit"])
            assert check["ok"] is not name.startswith("control_")


def test_a_window_that_finished_nothing_is_not_correct(root):
    line = rest_of_a_run(root, "tiny_backlog_cell", 1, [])
    assert line["correct"] is False
    assert {k: c["value"] for k, c in line["checks"].items()} == {
        "logit_gap": None, "logit_gap_mean": None}
