"""GPT model family + driver entry points (tiny configs on the CPU mesh)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.incubate.models import (GPTConfig, GPTForCausalLM,
                                        GPTPretrainingCriterion, gpt2_124m,
                                        shard_gpt)


def tiny_cfg(**kw):
    base = dict(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                num_attention_heads=4, intermediate_size=64,
                max_position_embeddings=32, hidden_dropout_prob=0.0,
                attention_probs_dropout_prob=0.0)
    base.update(kw)
    return GPTConfig(**base)


def test_forward_shape_and_tied_head():
    model = GPTForCausalLM(tiny_cfg())
    ids = paddle.to_tensor(np.random.randint(0, 128, (2, 16)))
    logits = model(ids)
    assert logits.shape == [2, 16, 128]
    # tied embeddings: no separate lm_head parameter
    names = [n for n, _ in model.named_parameters()]
    assert not any("lm_head" in n for n in names)


def test_config_presets():
    cfg = gpt2_124m()
    model = GPTForCausalLM(cfg)
    n = model.num_params()
    assert 120e6 < n < 130e6, f"GPT-2 124M param count off: {n}"


def test_training_reduces_loss():
    paddle.seed(0)
    model = GPTForCausalLM(tiny_cfg())
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    crit = GPTPretrainingCriterion()
    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(rng.integers(0, 128, (4, 16)).astype(np.int64))
    labels = paddle.to_tensor(rng.integers(0, 128, (4, 16)).astype(np.int64))
    losses = []
    for _ in range(10):
        loss = crit(model(ids), labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_train_step_fused():
    paddle.seed(0)
    model = GPTForCausalLM(tiny_cfg())
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    crit = GPTPretrainingCriterion()
    from paddle_tpu.jit import TrainStep
    step = TrainStep(model, lambda l, y: crit(l, y), opt)
    rng = np.random.default_rng(0)
    x = paddle.to_tensor(rng.integers(0, 128, (4, 16)).astype(np.int64))
    y = paddle.to_tensor(rng.integers(0, 128, (4, 16)).astype(np.int64))
    l0 = float(step(x, y))
    for _ in range(10):
        last = float(step(x, y))
    assert last < l0


def test_sharded_training_on_mesh():
    """tp+dp+sharding over the 8-device CPU mesh (the dryrun path)."""
    import sys
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as g
    g.dryrun_multichip(8)


def test_entry_compiles():
    import sys
    import jax
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as g
    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (1, 128, 50304)


def test_kv_cache_decode_matches_full_forward():
    paddle.seed(0)
    model = GPTForCausalLM(tiny_cfg())
    model.eval()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 128, (2, 8)).astype(np.int64)
    full = model(paddle.to_tensor(ids)).numpy()

    caches = model.gen_caches(batch_size=2)
    outs = []
    for t in range(8):
        step_ids = paddle.to_tensor(ids[:, t:t + 1])
        logits, caches = model(step_ids, caches=caches)
        outs.append(logits.numpy())
    decoded = np.concatenate(outs, axis=1)
    np.testing.assert_allclose(decoded, full, atol=2e-4, rtol=2e-3)


# ---- serving decode ---------------------------------------------------------

def test_generate_matches_eager_greedy_loop():
    """model.generate (one compiled program: prefill + lax.scan over static
    KV buffers) produces the same tokens as the eager dynamic-cache loop."""
    paddle.seed(0)
    model = GPTForCausalLM(tiny_cfg(use_flash_attention=False))
    model.eval()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 128, (2, 5)).astype(np.int64)
    out = np.asarray(model.generate(paddle.to_tensor(ids),
                                    max_new_tokens=6)._value)
    caches = model.gen_caches(batch_size=2)
    logits, caches = model(paddle.to_tensor(ids), caches=caches)
    tok = np.argmax(np.asarray(logits._value)[:, -1, :], -1)
    ref = [tok]
    for _ in range(5):
        lg, caches = model(paddle.to_tensor(tok[:, None].astype(np.int64)),
                           caches=caches)
        tok = np.argmax(np.asarray(lg._value)[:, -1, :], -1)
        ref.append(tok)
    np.testing.assert_array_equal(out, np.stack(ref, 1))


def test_generate_sampling_reproducible():
    paddle.seed(0)
    model = GPTForCausalLM(tiny_cfg(use_flash_attention=False))
    model.eval()
    ids = paddle.to_tensor(np.random.randint(0, 128, (2, 4)))
    a = np.asarray(model.generate(ids, max_new_tokens=8, do_sample=True,
                                  top_k=5, temperature=0.8, seed=7)._value)
    b = np.asarray(model.generate(ids, max_new_tokens=8, do_sample=True,
                                  top_k=5, temperature=0.8, seed=7)._value)
    c = np.asarray(model.generate(ids, max_new_tokens=8, do_sample=True,
                                  top_k=5, temperature=0.8, seed=8)._value)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (2, 8)
    assert not np.array_equal(a, c)      # different seed, different draw
    assert (a >= 0).all() and (a < 128).all()


def test_decode_step_predictor_roundtrip(tmp_path):
    """Save the GPTDecodeStep artifact, reload through the inference
    Predictor, and drive batched decode — tokens must match generate()."""
    import jax.numpy as jnp
    from paddle_tpu.incubate.models import GPTDecodeStep
    from paddle_tpu.jit import save as jit_save, InputSpec
    from paddle_tpu.inference import Config, create_predictor

    paddle.seed(0)
    cfg = tiny_cfg(use_flash_attention=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    B, P, N = 2, 4, 5
    T = P + N
    L, H = cfg.num_hidden_layers, cfg.num_attention_heads
    D = cfg.hidden_size // H
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 128, (B, P)).astype(np.int64)
    want = np.asarray(model.generate(paddle.to_tensor(ids),
                                     max_new_tokens=N)._value)

    step = GPTDecodeStep(model)
    path = str(tmp_path / "gpt_decode")
    jit_save(step, path, input_spec=[
        InputSpec([B, 1], "int64"), InputSpec([L, B, T, H, D], "float32"),
        InputSpec([L, B, T, H, D], "float32"), InputSpec([], "int32")])

    config = Config(path)
    predictor = create_predictor(config)

    # prefill eagerly (dynamic cache), pack buffers
    caches = model.gen_caches(batch_size=B)
    logits, caches = model(paddle.to_tensor(ids), caches=caches)
    kb = np.zeros((L, B, T, H, D), np.float32)
    vb = np.zeros((L, B, T, H, D), np.float32)
    for l, (ck, cv) in enumerate(caches):
        kb[l, :, :P] = np.asarray(ck._value)
        vb[l, :, :P] = np.asarray(cv._value)
    tok = np.argmax(np.asarray(logits._value)[:, -1, :], -1)
    got = [tok]
    for i in range(N - 1):
        outs = predictor.run([tok[:, None].astype(np.int64), kb, vb,
                              np.asarray(P + i, np.int32)])
        lg, kb, vb = outs[0], outs[1], outs[2]
        tok = np.argmax(lg[:, -1, :], -1)
        got.append(tok)
    np.testing.assert_array_equal(np.stack(got, 1), want)


def test_static_cache_multi_token_prefill_matches_full_forward():
    """Feeding the whole prompt through the static cache (multi-token
    chunk) must equal the plain forward — the chunk mask is causal within
    the chunk (regression: rows after the first could not see themselves)."""
    import jax.numpy as jnp
    paddle.seed(0)
    model = GPTForCausalLM(tiny_cfg(use_flash_attention=False))
    model.eval()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 128, (2, 6)).astype(np.int64)
    full = model(paddle.to_tensor(ids)).numpy()
    caches = [(k, v, paddle.Tensor(jnp.asarray(0, jnp.int32)))
              for k, v in model.gen_static_caches(batch_size=2, max_len=8)]
    logits, _ = model(paddle.to_tensor(ids), caches=caches)
    np.testing.assert_allclose(logits.numpy(), full, atol=2e-4, rtol=2e-3)
