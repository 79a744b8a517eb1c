"""JoyAI-LLM-Flash (the DeepSeek-V3 family) on the CPU at a tiny size that
keeps EVERY mechanism (`tests/benchmark/joyai_model/tiny_joyai.py`): a
leading dense layer, two expert layers, 16 sigmoid-routed experts top 4 of
which 4 are held, a shared expert, the MTP module, latent attention whose
q/k heads (12) are wider than its v heads (6). The model trains through
`jit.TrainStep` and is held to the benchmark's plain reference
(`benchmark/reference/joyai_llm_flash.py`, written apart from it).

Tolerances. Both sides compute in float32 with products at "highest", so
they differ only in the ORDER of sums (a grouped product against a masked
one, a scatter-add against a sum over experts, fused cross entropy): TOL =
1e-4 of the largest value compared. The same comparison with the
reference's matrix products rounded to bfloat16 is off by 1e-3 of the loss
(10 x TOL) and more in a gradient
(`test_bfloat16_where_float32_is_stated_fails`), so a lower precision than
stated fails it."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "tests", "benchmark",
                                   "joyai_model")]

import paddle_tpu as paddle  # noqa: E402
from benchmark import seeded  # noqa: E402
from benchmark.programs import paddle_joyai  # noqa: E402
from benchmark.reference import common, joyai_llm_flash as ref  # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import (  # noqa: E402
    grouped_experts, held_experts)
from paddle_tpu.incubate.models import joyai_llm_flash as jy  # noqa: E402
from paddle_tpu.jit import TrainStep  # noqa: E402
from paddle_tpu.kernels import flash_attention  # noqa: E402
from tiny_joyai import TINY_JOYAI  # noqa: E402

TOL = 1e-4
ROWS, SEQ = 2, 16
EXPERTS, HELD, TOPK = 16, 4, 4
EXPERT_LAYERS = (1, 2, 3)        # layer 0 is dense, layer 3 the MTP module
F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def weights_of(file, seed=3, std=0.3):
    """Float32 seeded weights by the reference's names."""
    return dict(seeded.make_weights(ref.param_shapes(file), seed, F32, std))


def biases_of(file, seed=3, std=0.05):
    """A non-zero router bias for every expert layer (the tests carry it:
    the benchmark holds it at zeros)."""
    rng = np.random.default_rng(seed)
    ranked = file["published"]["n_routed_experts"]
    return {i: jnp.asarray(rng.normal(0, std, ranked), F32)
            for i in EXPERT_LAYERS}


def batch(seed=3, rows=ROWS, seq=SEQ):
    (ids, labels), = seeded.make_batches(1, rows, seq,
                                         TINY_JOYAI["vocab_size"], seed)
    return ids, labels


def model_of(file, weights, biases=None, **config):
    cfg = paddle_joyai._model_config(file)
    for key, value in config.items():
        setattr(cfg, key, value)
    model = jy.JoyAIFlashForCausalLM(cfg, weights=dict(weights))
    for layer, b in (biases or {}).items():
        model.router_bias(layer)._value = b
    return model


def with_biases(weights, biases):
    return {**weights, **{ref.bias_name(i): b for i, b in biases.items()}}


def close(got, want, tol=TOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(np.abs(want).max(), 1e-30)
    assert np.abs(got - want).max() <= tol * scale, \
        (np.abs(got - want).max() / scale)


def trainer(model, lr=1e-3):
    opt = paddle.optimizer.AdamW(
        learning_rate=lr, weight_decay=0.01, beta1=0.9, beta2=0.999,
        epsilon=1e-8, parameters=model.parameters(), multi_precision=True)
    return TrainStep(model, None, opt), opt


def tensors(*arrays):
    return [paddle.Tensor(a, stop_gradient=True) for a in arrays]


# -- (a) the step against the reference -------------------------------------

@pytest.mark.parametrize("seed", [2 ** 31 + 7])
def test_three_steps_follow_the_reference(seed):
    """Loss, both loss terms, every leaf's first gradient (Adam's first
    moment after one step / 0.1) and every leaf after three AdamW steps."""
    w, biases = weights_of(TINY_JOYAI, seed), biases_of(TINY_JOYAI, seed)
    model = model_of(TINY_JOYAI, w, biases)
    step, opt = trainer(model)
    hyper = {"learning_rate": 1e-3, "weight_decay": 0.01, "beta1": 0.9,
             "beta2": 0.999, "epsilon": 1e-8}
    p = dict(w)
    m1 = {k: jnp.zeros_like(v) for k, v in p.items()}
    m2 = dict(m1)
    for t in (1, 2, 3):
        ids, labels = batch(seed + t)
        loss = float(step(*tensors(ids, labels))._value)
        full = with_biases(p, biases)
        want, grads = ref.loss_and_grads(full, ids, labels, TINY_JOYAI,
                                         "float32", 1)
        main, mtp = ref.loss_terms(full, ids, labels, TINY_JOYAI)
        stats = step.stats()
        close(loss, want)
        close(stats["loss_main"], main)
        close(stats["loss_mtp"], mtp)
        close(loss, main + TINY_JOYAI["mtp_loss_weight"] * mtp)
        # the first step's counters stay beside the newest step's
        if t == 1:
            first = {k: v for k, v in stats.items()
                     if k.startswith("first_step_")}
            assert first["first_step_loss_main"] == stats["loss_main"]
        assert first == {k: stats[k] for k in first}
        assert (t == 1) == (stats["loss_main"]
                            == stats["first_step_loss_main"])
        for i in EXPERT_LAYERS:          # b has no gradient
            assert not np.any(np.asarray(grads[ref.bias_name(i)]))
        grads = {k: grads[k] for k in p}
        if t == 1:
            for name, param in model.named_parameters():
                close(np.asarray(opt._accumulators["moment1"][param.name])
                      / 0.1, grads[name])
        p, m1, m2 = common.adamw_step(p, m1, m2, grads, t, hyper)
    for name, param in model.named_parameters():
        close(param._value, p[name])


def test_bfloat16_where_float32_is_stated_fails():
    """The tolerance parts float32 from the precision below it: the
    reference with both operands of every product rounded to bfloat16 is
    further from itself than TOL, in the loss and in a gradient."""
    w, (ids, labels) = weights_of(TINY_JOYAI), batch()
    want, grads = ref.loss_and_grads(w, ids, labels, TINY_JOYAI, "float32")
    low, low_grads = ref.loss_and_grads(w, ids, labels, TINY_JOYAI,
                                        "bfloat16")
    assert abs(float(low) - float(want)) > 5 * TOL * float(want)
    leaf = "model.layers.0.mlp.down_proj.weight"
    with pytest.raises(AssertionError):
        close(low_grads[leaf], grads[leaf])


def test_the_engine_refuses_the_model_by_name():
    from paddle_tpu.serving import LLMEngine
    model = model_of(TINY_JOYAI, weights_of(TINY_JOYAI))
    with pytest.raises(NotImplementedError, match="JoyAIFlashForCausalLM"):
        LLMEngine(model, max_batch_size=2, block_size=4, max_context=16)


def test_one_mtp_module_or_none_is_refused_by_name():
    cfg = paddle_joyai._model_config(TINY_JOYAI)
    cfg.num_nextn_predict_layers = 2
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        jy.JoyAIFlashForCausalLM(cfg)


# -- the expert block --------------------------------------------------------

D, F = 32, 16


def block_inputs(seed=0, tokens=48, held=HELD, std=0.3):
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(0, std, shape), F32)
    return {"u": draw(tokens, D), "router": draw(D, EXPERTS),
            "gate": draw(held, D, F), "up": draw(held, D, F),
            "down": draw(held, F, D)}


def grouped(x, bias=None, first=4, topk=TOPK):
    return grouped_experts.grouped_held_expert_block(
        x["u"], x["router"], bias, x["gate"], x["up"], x["down"], topk=topk,
        scaling=2.5, first_held=first)


def masked(x, bias=None, first=4, topk=TOPK):
    """Every held expert over EVERY token, masked by the choice: the
    reference's form, from the block's own router."""
    chosen, weights = held_experts.route(x["u"], x["router"], bias, topk,
                                         2.5, scoring="sigmoid",
                                         normalise=True)
    mm = lambda a, b: jnp.matmul(a, b, precision=HI)
    out = jnp.zeros(x["u"].shape, F32)
    for e in range(x["gate"].shape[0]):
        mine = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        out = out + mine[:, None] * mm(
            jax.nn.silu(mm(x["u"], x["gate"][e])) * mm(x["u"], x["up"][e]),
            x["down"][e])
    return out


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("biased", [False, True])
def test_the_grouped_form_equals_the_masked_form(seed, biased):
    """(f) forward and every gradient."""
    x = block_inputs(seed)
    bias = jnp.asarray(np.random.default_rng(seed).normal(0, 0.05, EXPERTS),
                       F32) if biased else None
    with jax.default_matmul_precision("highest"):
        close(grouped(x, bias)[0], masked(x, bias))
        probe = jnp.asarray(np.random.default_rng(9).normal(
            0, 1, x["u"].shape), F32)
        got = jax.grad(lambda x: jnp.sum(grouped(x, bias)[0] * probe))(x)
        want = jax.grad(lambda x: jnp.sum(masked(x, bias) * probe))(x)
    for leaf in x:
        close(got[leaf], want[leaf])


def test_no_assignment_is_dropped_at_any_load():
    """(c) every token forced onto ONE held expert (and three held
    elsewhere): its load is every token, all are computed, and the result
    is the masked form's."""
    x = block_inputs(2, tokens=64)
    bias = jnp.zeros((EXPERTS,), F32).at[jnp.asarray([5, 0, 1, 12])].set(10.)
    with jax.default_matmul_precision("highest"):
        out, counters, ranked = grouped(x, bias)
        close(out, masked(x, bias))
    counted = dict(zip(grouped_experts.COUNTERS, np.asarray(counters)))
    assert counted["routed_held"] == counted["routed_computed"] == 64
    assert counted["load_max"] == 64 and counted["experts_idle"] == 3
    assert counted["routed_elsewhere"] == 3 * 64
    assert counted["routed_identity"] == 0
    assert np.asarray(ranked).tolist() == [
        64 if e in (0, 1, 5, 12) else 0 for e in range(EXPERTS)]


def test_more_choices_than_experts_held_still_fits_the_buffer():
    """Top 8 over 4 held: a token can hold at most 4 assignments here, and
    with every held expert chosen by every token the buffer is exactly
    full."""
    x = block_inputs(3, tokens=32)
    bias = jnp.zeros((EXPERTS,), F32).at[4:8].set(10.)
    with jax.default_matmul_precision("highest"):
        out, counters, _ = grouped(x, bias, topk=8)
        close(out, masked(x, bias, topk=8))
    counted = dict(zip(grouped_experts.COUNTERS, np.asarray(counters)))
    assert counted["routed_held"] == counted["routed_computed"] == 4 * 32


def test_rows_past_the_groups_reach_no_token_and_no_gradient(monkeypatch):
    """A grouped product may leave ANYTHING in the rows past its groups,
    forward and backward: a TPU leaves what the memory held (the cell's
    first run on the chip read an infinite gradient of the embedding),
    the CPU zeros. With a product that leaves NaN there, on both passes,
    the block's result and every gradient are still the masked form's."""
    real = jax.lax.ragged_dot

    def poisoned(lhs, rhs, group_sizes, **kw):
        past = (jnp.arange(lhs.shape[0]) >= jnp.sum(group_sizes))[:, None]

        @jax.custom_vjp
        def product(lhs, rhs):
            return jnp.where(past, jnp.nan, real(lhs, rhs, group_sizes,
                                                 **kw))

        def forward(lhs, rhs):
            return product(lhs, rhs), (lhs, rhs)

        def backward(saved, g):
            # what the groups' rows give, and NaN in the rows past them
            d_lhs, d_rhs = jax.vjp(lambda a, b: real(
                a, b, group_sizes, **kw), *saved)[1](jnp.where(past, 0., g))
            return jnp.where(past, jnp.nan, d_lhs).astype(lhs.dtype), d_rhs

        product.defvjp(forward, backward)
        return product(lhs, rhs)

    x = block_inputs(6)
    probe = jnp.asarray(np.random.default_rng(9).normal(
        0, 1, x["u"].shape), F32)
    with jax.default_matmul_precision("highest"):
        want_out = masked(x)
        want = jax.grad(lambda x: jnp.sum(masked(x) * probe))(x)
        monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
        out, counters, _ = grouped(x)
        got = jax.grad(lambda x: jnp.sum(grouped(x)[0] * probe))(x)
    counted = dict(zip(grouped_experts.COUNTERS, np.asarray(counters)))
    assert counted["routed_held"] < 48 * TOPK     # rows ARE left over
    close(out, want_out)
    for leaf in x:
        assert np.all(np.isfinite(np.asarray(got[leaf]))), leaf
        close(got[leaf], want[leaf])


@pytest.mark.parametrize("seed", [0, 1])
def test_the_shares_add_up_to_the_uncut_layer(seed):
    """(b) four shares of four experts, the shared expert counted once,
    against the reference's expert layer holding all sixteen: the outputs,
    and the gradients of the token, the router and every expert."""
    rng = np.random.default_rng(seed)
    draw = lambda *shape: jnp.asarray(rng.normal(0, 0.3, shape), F32)
    whole = block_inputs(seed, held=EXPERTS)
    shared = {"gate": draw(D, F), "up": draw(D, F), "down": draw(F, D)}
    bias = draw(EXPERTS) * 0.2
    probe = draw(*whole["u"].shape)
    file = dict(TINY_JOYAI, n_routed_experts=EXPERTS, experts_held_from=0)
    mm = lambda a, b: jnp.matmul(a, b)

    def uncut(whole, shared):
        leaves = {"gate.weight": whole["router"],
                  **{f"experts.{k}_proj.weight": whole[k]
                     for k in ("gate", "up", "down")},
                  **{f"shared_experts.{k}_proj.weight": shared[k]
                     for k in ("gate", "up", "down")}}
        return ref.expert_layer(whole["u"][None], leaves.__getitem__, bias,
                                file, mm)[0]

    def parts(whole, shared):
        total = mm(jax.nn.silu(mm(whole["u"], shared["gate"]))
                   * mm(whole["u"], shared["up"]), shared["down"])
        for first in range(0, EXPERTS, HELD):
            share = dict(whole, **{k: whole[k][first:first + HELD]
                                   for k in ("gate", "up", "down")})
            total = total + grouped(share, bias, first=first)[0]
        return total

    with jax.default_matmul_precision("highest"):
        close(parts(whole, shared), uncut(whole, shared))
        got = jax.grad(lambda *a: jnp.sum(parts(*a) * probe), (0, 1))(
            whole, shared)
        want = jax.grad(lambda *a: jnp.sum(uncut(*a) * probe), (0, 1))(
            whole, shared)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        close(g, w)


def test_the_bias_moves_the_choice_and_never_the_weight():
    """(d) `noaux_tc`: the chosen set follows scores + b; a chosen
    expert's weight is its OWN score over the chosen scores' sum; b gets
    no gradient."""
    x = block_inputs(4)
    bias = jnp.zeros((EXPERTS,), F32).at[3].set(5.0)
    route = lambda b: held_experts.route(x["u"], x["router"], b, TOPK, 2.5,
                                         scoring="sigmoid", normalise=True)
    plain, _ = route(None)
    chosen, weights = route(bias)
    assert np.all(np.any(np.asarray(chosen) == 3, -1))
    assert not np.all(np.any(np.asarray(plain) == 3, -1))
    scores = jax.nn.sigmoid(jnp.matmul(x["u"], x["router"], precision=HI))
    picked = jnp.take_along_axis(scores, chosen, -1)
    close(weights, 2.5 * picked / jnp.sum(picked, -1, keepdims=True), 1e-6)
    close(jnp.sum(weights, -1), jnp.full((48,), 2.5), 1e-6)
    grad = jax.grad(lambda b: jnp.sum(grouped(x, b)[0]))(bias)
    assert not np.any(np.asarray(grad))


def test_the_balance_rule_moves_the_bias_against_the_load():
    """(d) fed by the step's own count of each router's load."""
    w = weights_of(TINY_JOYAI)
    model = model_of(TINY_JOYAI, w)
    step, _ = trainer(model)
    ids, labels = batch()
    step(*tensors(ids, labels))
    loads = np.asarray(model._buffers["expert_load"]._value)
    assert loads.shape == (len(EXPERT_LAYERS), EXPERTS)
    assert np.all(loads.sum(-1) == ROWS * SEQ * TOPK)
    before = [np.asarray(model.router_bias(i)._value) for i in EXPERT_LAYERS]
    model.balance_router_bias()
    gamma = TINY_JOYAI["bias_update_speed"]
    for row, (layer, was) in enumerate(zip(EXPERT_LAYERS, before)):
        moved = np.asarray(model.router_bias(layer)._value) - was
        np.testing.assert_allclose(
            moved, gamma * np.sign(loads[row].mean() - loads[row]),
            atol=1e-9)
        assert moved[loads[row].argmax()] < 0 < moved[loads[row].argmin()]
    # the moved bias is what the next step routes by, and it trains on
    assert np.isfinite(float(step(*tensors(ids, labels))._value))


# -- (e) the MTP module's targets -----------------------------------------

def loss_terms_of(model, ids, labels):
    model(*tensors(ids, labels))
    counted = dict(zip(model.train_counter_names, np.asarray(
        model._buffers["train_counters"]._value)))
    return counted["loss_main"], counted["loss_mtp"]


def test_a_rows_last_position_has_no_second_target():
    """The MTP module predicts the token after next at every position but
    a row's last: the last INPUT token reaches the MTP term through that
    position alone, so the term does not move with it; the main term
    does. The label after next is labels[i + 1]: the MTP term moves with
    labels[:, 1] and not with labels[:, 0] as a TARGET."""
    w = weights_of(TINY_JOYAI)
    model = model_of(TINY_JOYAI, w)
    ids, labels = batch()
    main, mtp = loss_terms_of(model, ids, labels)
    other = ids.at[:, -1].set((ids[:, -1] + 1) % TINY_JOYAI["vocab_size"])
    main2, mtp2 = loss_terms_of(model, other, labels)
    assert mtp2 == mtp and main2 != main
    want_main, want_mtp = ref.loss_terms(w, ids, labels, TINY_JOYAI)
    close(main, want_main)
    close(mtp, want_mtp)


def _first_gradients(weight):
    """Every leaf's first gradient with the MTP term weighed `weight`."""
    model = model_of(TINY_JOYAI, weights_of(TINY_JOYAI),
                     mtp_loss_weight=weight)
    step, opt = trainer(model)
    step(*tensors(*batch()))
    return {name: np.asarray(opt._accumulators["moment1"][name]) / 0.1
            for name, _ in model.named_parameters()}


@pytest.fixture(scope="module")
def gradients_with_and_without_the_mtp_term():
    return _first_gradients(0.3), _first_gradients(0.0)


@pytest.mark.parametrize("leaf", ["model.embed_tokens.weight",
                                  "lm_head.weight"])
def test_the_shared_embedding_and_head_receive_both_terms(
        leaf, gradients_with_and_without_the_mtp_term):
    """Their gradient with the MTP term weighed 0.3, less their gradient
    with it weighed 0, is 0.3 x the reference's gradient of the MTP term
    alone, which is not zero."""
    both, main_only = gradients_with_and_without_the_mtp_term
    ids, labels = batch()
    mtp_grad = jax.grad(lambda p: ref.loss_terms(
        p, ids, labels, TINY_JOYAI)[1])(weights_of(TINY_JOYAI))[leaf]
    assert np.abs(np.asarray(mtp_grad)).max() > 1e-4
    close(both[leaf] - main_only[leaf], 0.3 * np.asarray(mtp_grad), 1e-3)


# -- the flash kernel at unequal head widths ---------------------------------

def plain_attention(q, k, v, scale):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) * scale
    n = q.shape[1]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                      precision=HI)


@pytest.mark.parametrize("qk,vd", [(192, 128), (64, 64), (24, 16)])
def test_the_flash_kernels_take_a_value_width_of_their_own(qk, vd):
    """Forward, dq, dk and dv in the Pallas interpreter against plain
    attention (float32 throughout: 1e-5 of the largest value)."""
    rng = np.random.default_rng(0)
    q, k = (jnp.asarray(rng.normal(0, 1, (1, 256, 2, qk)), F32)
            for _ in "qk")
    v = jnp.asarray(rng.normal(0, 1, (1, 256, 2, vd)), F32)
    g = jnp.asarray(rng.normal(0, 1, (1, 256, 2, vd)), F32)
    scale = qk ** -0.5
    out, lse = flash_attention._flash_fwd(q, k, v, True, scale, 128, 128,
                                          interpret=True)
    want, vjp = jax.vjp(lambda q, k, v: plain_attention(q, k, v, scale),
                        q, k, v)
    close(out, want, 1e-5)
    got = flash_attention._flash_bwd(q, k, v, out, lse, g, True, scale, 128,
                                     128, interpret=True)
    for a, b in zip(got, vjp(g)):
        assert a.shape == b.shape
        close(a, b, 1e-5)


def test_a_width_the_kernels_refuse_is_counted_on_a_tpu(monkeypatch):
    """A causal self-attention of FLASH_MIN_SEQ tokens or more that a TPU
    sends to the N^2 path for its head widths alone is counted, and
    `TrainStepStats` reports the count; a short one, a cross-attention and
    a CPU are not."""
    shape = lambda n, d: jax.ShapeDtypeStruct((2, n, 4, d), jnp.bfloat16)
    eligible = lambda q, k, v, causal=True: flash_attention.is_eligible(
        q, k, v, None, 0.0, is_causal=causal)
    # a counter of this test's own: the process's stays where it was
    monkeypatch.setattr(flash_attention, "_width_fallbacks", [0])
    before = flash_attention.width_fallbacks()
    assert not eligible(shape(1024, 96), shape(1024, 96), shape(1024, 96))
    assert flash_attention.width_fallbacks() == before        # a CPU
    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)
    assert eligible(shape(1024, 192), shape(1024, 192), shape(1024, 128))
    assert eligible(shape(1024, 64), shape(1024, 64), shape(1024, 64))
    assert not eligible(shape(512, 96), shape(512, 96), shape(512, 96))
    assert not eligible(shape(1024, 96), shape(2048, 96), shape(2048, 96),
                        causal=False)
    assert flash_attention.width_fallbacks() == before
    assert not eligible(shape(1024, 96), shape(1024, 96), shape(1024, 96))
    assert not eligible(shape(1024, 128), shape(1024, 128),
                        shape(1024, 192))
    assert flash_attention.width_fallbacks() == before + 2
    model = model_of(TINY_JOYAI, weights_of(TINY_JOYAI))
    step, _ = trainer(model)
    assert step.stats()["flash_width_fallbacks"] == before + 2
