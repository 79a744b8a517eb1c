"""The plain float32 reference against the system at a tiny size on the
CPU, and the same comparison failing at reduced precision."""
import functools
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny_root  # noqa: E402

from benchmark import correct, seeded  # noqa: E402
from benchmark.reference import gpt as ref  # noqa: E402

CFG = dict(tiny_root.TINY_MODEL, precision=dict(
    tiny_root.TINY_MODEL["precision"], params="float32"))
MIX = {"batch_rows": 4, "seq": 32, "donate": True,
       "reference_rows_per_block": 2}
SEED = 3_000_000_011


@functools.lru_cache(maxsize=None)
def _system():
    """The program's model in float32 on the seed's weights, and one
    batch."""
    import jax
    import paddle_tpu as paddle
    from benchmark.programs import paddle_gpt
    from paddle_tpu.incubate.models import GPTForCausalLM
    model = GPTForCausalLM(paddle_gpt._model_config(CFG))
    paddle_gpt._set_weights(model, correct.weight_maker(CFG, SEED))
    ids, labels = seeded.make_batches(1, 4, 32, CFG["vocab_size"], SEED)[0]
    weights = correct.weight_maker(CFG, SEED)()
    return jax, paddle, model, weights, ids, labels


def test_parameter_names_and_shapes_are_the_programs():
    _, _, model, weights, _, _ = _system()
    named = {n[len("gpt."):]: tuple(p.shape)
             for n, p in model.named_parameters()}
    assert named == {k: tuple(v.shape) for k, v in weights.items()}
    assert ref.num_params(CFG) == model.num_params()


def test_forward_logits_agree():
    _, paddle, model, weights, ids, _ = _system()
    with paddle.no_grad():
        got = np.asarray(model(paddle.Tensor(ids, stop_gradient=True))._value)
    want = np.asarray(ref.forward(weights, ids, CFG))
    # float32 both sides; only the order of summation differs
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_loss_and_gradients_agree():
    jax, paddle, model, weights, ids, labels = _system()
    from paddle_tpu.incubate.models import GPTPretrainingCriterion
    loss = GPTPretrainingCriterion()(
        model(paddle.Tensor(ids, stop_gradient=True)),
        paddle.Tensor(labels, stop_gradient=True))
    loss.backward()
    want_loss, want = ref.loss_and_grads(weights, ids, labels, CFG,
                                         rows_per_block=2)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    for name, p in model.named_parameters():
        np.testing.assert_allclose(
            np.asarray(p.grad._value), np.asarray(want[name[4:]]),
            atol=1e-6, rtol=2e-4, err_msg=name)
    model.clear_gradients()


def test_rows_in_blocks_give_the_whole_batchs_gradient():
    _, _, _, weights, ids, labels = _system()
    l1, g1 = ref.loss_and_grads(weights, ids, labels, CFG)
    l2, g2 = ref.loss_and_grads(weights, ids, labels, CFG, rows_per_block=1)
    assert float(l1) == pytest.approx(float(l2), rel=1e-6)
    for k in g1:
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g2[k]),
                                   atol=1e-7, rtol=1e-4)


@pytest.fixture(scope="module")
def train_readings():
    import jax
    cfg = dict(CFG, precision=tiny_root.TINY_MODEL["precision"])
    devices = jax.devices()[:1]
    want = correct.reference_train(cfg, MIX, SEED, devices, 3)
    from benchmark.loops import train as loop
    from benchmark.programs import paddle_gpt

    class Run:
        config = cfg
    trainer = paddle_gpt.build_trainer(cfg, MIX,
                                       correct.weight_maker(cfg, SEED),
                                       devices)
    ring = seeded.make_batches(3, 4, 32, cfg["vocab_size"], SEED)
    got = loop.checked_steps(Run, trainer, ring)
    low = {p: correct.reference_train(cfg, MIX, SEED, devices, 3, p)
           for p in ("fp8",)}
    return cfg, want, got, low, trainer, ring


def test_train_step_follows_the_reference(train_readings):
    _, want, got, _, _, _ = train_readings
    n = correct.train_numbers(got, want)
    # bf16 program against the float32 reference: the loss is returned in
    # bf16 (8 bits), norms agree to a few bf16 roundings
    assert n["loss_gap"] < 2 ** -7
    assert n["grad_norm_gap"] < 0.02
    assert n["delta_norm_gap"] < 0.1


def test_the_control_in_lower_precision_fails(train_readings):
    _, want, got, low, _, _ = train_readings
    sound = correct.train_numbers(got, want)["grad_norm_gap"]
    control = correct.train_numbers(low["fp8"], want)["grad_norm_gap"]
    assert control > 3 * sound
    assert control > 0.02          # the limit the sound program passed


def test_a_step_that_returns_its_state_unchanged_is_caught(train_readings):
    _, want, got, _, _, _ = train_readings
    frozen = dict(got, delta_norms={k: 0.0 for k in got["delta_norms"]})
    assert correct.train_numbers(frozen, want)["delta_norm_gap"] \
        == pytest.approx(1.0)


def test_a_part_of_the_batch_left_out_moves_the_gradient(train_readings):
    cfg, want, _, _, _, _ = train_readings
    import jax
    half = dict(MIX, batch_rows=2, reference_rows_per_block=2)
    part = correct.reference_train(cfg, half, SEED, jax.devices()[:1], 3)
    assert correct.train_numbers(part, want)["grad_norm_gap"] > 0.1


def test_worst_leaf_gap_uses_the_median_leaf_as_floor():
    want = {"a": 1.0, "b": 2.0, "c": 1e-9}
    got = {"a": 1.1, "b": 2.0, "c": 2e-9}
    gap, leaf = correct.worst_leaf_gap(got, want)
    assert leaf == "a" and gap == pytest.approx(0.1)


@pytest.fixture(scope="module")
def served():
    """Eight streams through the engine's prefill and paged decode."""
    from benchmark.programs import paddle_gpt
    cfg = dict(CFG, precision=tiny_root.TINY_MODEL["precision"])
    mix = tiny_root.TRAFFIC["tiny_backlog"]
    engine = paddle_gpt.build_engine(cfg, mix,
                                     correct.weight_maker(cfg, SEED))
    rng = seeded.host_rng(SEED, 9)
    reqs = [engine.add_request(seeded.token_ids(rng, n, cfg["vocab_size"]),
                               max_new_tokens=8)
            for n in (5, 8, 9, 13, 16, 7, 11, 6)]
    while engine.step():
        pass
    return cfg, [(r.prompt, r.generated) for r in reqs]


def test_served_tokens_are_the_references_choice(served):
    cfg, streams = served
    worst = correct.served_gaps(cfg, SEED, streams, 32, control="fp8")
    assert worst["tokens"] == 64
    # bf16 engine: a served token lies within a bf16 rounding or two of
    # the reference's best logit (logits here are below 1 in magnitude)
    assert worst["logit_gap"] < 0.02
    assert worst["control_logit_gap"] > 3 * max(worst["logit_gap"], 1e-3)
    # the mean over the 64 tokens, the other number compared: the engine's
    # tokens are all the reference's best, one of the control's is not
    assert worst["control_logit_gap_mean"] > worst["logit_gap_mean"] == 0.0


def test_an_altered_token_is_caught(served):
    cfg, streams = served
    prompt, tokens = streams[0]
    wrong = list(tokens)
    wrong[3] = (wrong[3] + 1) % cfg["vocab_size"]
    worst = correct.served_gaps(cfg, SEED, [(prompt, wrong)], 32)
    assert worst["logit_gap"] > 0.02
