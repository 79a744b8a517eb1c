"""Kernel tier suite (ISSUE 11): blockwise/Pallas paged decode attention
+ the int8 KV cache.

Contracts pinned here:

  * fused-vs-reference parity — the blockwise (lax.scan online-softmax)
    and Pallas (interpret=True on CPU) variants match the dense
    gather-by-block-table oracle to fp32 tolerance, share its exact
    write path bitwise, and agree on every edge shape: seq_len at an
    exact block boundary, a slot right after prefill (zero generated
    tokens), and an inactive slot whose table still points at null
    block 0;
  * fp32 softmax numerics — bf16 serving computes scores/softmax/PV in
    fp32 (the satellite fix), so the bf16 paged path tracks an all-fp32
    computation to input-rounding error, not accumulation error;
  * int8 KV — quantize->dequantize error is bounded by half a quant step
    per element (per-block-per-head scales), greedy decode through the
    int8 pool is token-identical to fp32 KV on the tiny-GPT fixture
    (incl. under preemption churn), and the same byte budget admits
    >= 1.8x the concurrent streams before the pool runs dry;
  * keying — FLAGS_serve_attention_kernel is keyed into the per-op
    dispatch cache (each variant is a distinct executable) and the AOT
    env fingerprint / decode digest (kernel flips never deserialize a
    stale artifact); kernel fallbacks are attributed `kernel.fallback`
    events, never silent;
  * the length-bounded blockwise loop (ISSUE 29) — bitwise the same
    recurrence run over every table entry, whatever the mix of
    lengths; entries a step leaves out are not read (NaN behind them
    stays there); the engine's host-side count of streamed entries
    is the device loop's own trip count and step widths;
  * counted floors — the traced blockwise program holds no dense
    context at seq 1k, and an int8 engine compiles decode exactly once
    under churn.
"""
from __future__ import annotations

import os

import numpy as np
import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.framework.flags import get_flags, set_flags
from paddle_tpu.incubate.models import GPTConfig, GPTForCausalLM
from paddle_tpu.nn.functional.attention import (_dense_gather_attention,
                                                paged_decode_attention,
                                                resolve_paged_kernel,
                                                PAGED_KERNELS)
from paddle_tpu.kernels.pallas import paged_attention
from paddle_tpu.kernels.pallas.paged_attention import (
    _blockwise_plan, _step_widths, blockwise_paged_attention,
    blockwise_streamed_entries)
from paddle_tpu.quantization.kv_cache import (QMAX, quantize_scatter,
                                              quantize_block_write,
                                              dequantize)
from paddle_tpu.serving import LLMEngine, num_blocks_for_bytes
from paddle_tpu.profiler.events import (clear_fusion_events, fusion_events,
                                        EVENTS)

from serving_reference import SAMPLERS, Reference, stream_of

VOCAB = 128

VARIANTS = ("reference", "blockwise", "pallas")


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
                    num_attention_heads=4, intermediate_size=64,
                    max_position_embeddings=64, hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0,
                    use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _prompt(length, seed=0):
    rng = np.random.default_rng(seed * 1000 + length)
    return rng.integers(0, VOCAB, length).tolist()


_REF_CACHE = {}


def _ref(model, prompt, n):
    key = (tuple(prompt), n)
    if key not in _REF_CACHE:
        out = model.generate(paddle.Tensor(np.asarray([prompt], np.int64)),
                             max_new_tokens=n, do_sample=False)
        _REF_CACHE[key] = np.asarray(out._value)[0].tolist()
    return _REF_CACHE[key]


# the stacked pools of the hand-built states hold LAYERS layers; the
# attention under test writes and reads LAYER, the others must not move
LAYERS, LAYER = 3, 1


def _paged_state(S=4, H=3, D=16, bs=4, M=6, lens=(0, 4, 8, 23),
                 active=(True, True, True, True), seed=0,
                 dtype=jnp.float32):
    """A filled paged-cache state: per-slot dense-prefix block tables over
    disjoint pool blocks, stacked pools ``[L, num_blocks, bs, H*D]``
    populated with random history in every layer."""
    rng = np.random.default_rng(seed)
    nb = S * M + 1
    mk = lambda sh: jnp.asarray(
        rng.standard_normal(sh).astype(np.float32)).astype(dtype)
    q, kn, vn = mk((S, 1, H, D)), mk((S, 1, H, D)), mk((S, 1, H, D))
    kp, vp = mk((LAYERS, nb, bs, H * D)), mk((LAYERS, nb, bs, H * D))
    tables = jnp.asarray(np.stack(
        [1 + s * M + np.arange(M) for s in range(S)]).astype(np.int32))
    return (q, kn, vn, kp, vp, tables,
            jnp.asarray(np.asarray(lens, np.int32)),
            jnp.asarray(np.asarray(active, bool)))


def _run(variant, state, bs, **kw):
    q, kn, vn, kp, vp, tables, lens, active = state
    interpret = variant == "pallas"
    return paged_decode_attention(q, kn, vn, kp, vp, LAYER, tables, lens,
                                  active, bs, kernel=variant,
                                  interpret=interpret, **kw)


def _quantized(pools, heads):
    """int8 pools + per-block-per-head scales holding `pools`' values to
    half a quant step."""
    nl, nb, bs, hd = pools.shape
    vals = np.asarray(pools, np.float32).reshape(nl, nb, bs, heads, -1)
    scales = np.maximum(np.abs(vals).max(axis=(2, 4)), 1e-8)
    q = np.clip(np.round(vals * (QMAX / scales)[:, :, None, :, None]),
                -QMAX, QMAX).astype(np.int8)
    return jnp.asarray(q.reshape(pools.shape)), jnp.asarray(scales)


# ---------------------------------------------------------------------------
# fused-vs-reference parity + edge cases
# ---------------------------------------------------------------------------

class TestVariantParity:
    @pytest.mark.parametrize("pool", ("bf16", "int8"))
    @pytest.mark.parametrize("variant", VARIANTS)
    def test_stacked_pool_with_layer_index(self, variant, pool):
        """The attention works on the STACKED pool and a layer index: the
        output is the dense oracle's over that layer of the written pool,
        and every OTHER layer of the returned pools (and scale tables) is
        bit-identical to what came in."""
        bs, H = 4, 3
        q, kn, vn, kp, vp, tables, lens, active = _paged_state(
            bs=bs, H=H, dtype=jnp.bfloat16)
        scales = {}
        if pool == "int8":
            (kp, ks), (vp, vs) = _quantized(kp, H), _quantized(vp, H)
            scales = {"k_scales": ks, "v_scales": vs}
        state = (q, kn, vn, kp, vp, tables, lens, active)
        out, *new = _run(variant, state, bs, **scales)
        eff = jnp.where(active, lens, 0).astype(jnp.int32)
        oracle = _dense_gather_attention(q[:, 0], new[0], new[1], LAYER,
                                         tables, eff, bs, *new[2:])
        np.testing.assert_allclose(
            np.asarray(out[:, 0], np.float32),
            np.asarray(oracle, np.float32), rtol=0.0, atol=2e-2)
        others = [l for l in range(LAYERS) if l != LAYER]
        for got, came in zip(new, (kp, vp) + tuple(scales.values())):
            assert got.shape == came.shape and got.dtype == came.dtype
            assert np.array_equal(np.asarray(got)[others],
                                  np.asarray(came)[others])
            assert not np.array_equal(np.asarray(got)[LAYER],
                                      np.asarray(came)[LAYER])

    def test_blockwise_and_pallas_match_dense_oracle(self):
        """Core parity: identical semantics across the three variants to
        fp32 tolerance (the Pallas kernel runs interpret=True on CPU),
        and a BITWISE-identical pool write path."""
        bs = 4
        state = _paged_state(bs=bs)
        o_ref, k_ref, v_ref = _run("reference", state, bs)
        o_bw, k_bw, v_bw = _run("blockwise", state, bs)
        o_pl, k_pl, v_pl = _run("pallas", state, bs)
        act = np.asarray(state[-1])
        for name, o in (("blockwise", o_bw), ("pallas", o_pl)):
            np.testing.assert_allclose(
                np.asarray(o)[act], np.asarray(o_ref)[act],
                rtol=1e-5, atol=1e-5, err_msg=name)
        for k in (k_bw, k_pl):
            assert np.array_equal(np.asarray(k), np.asarray(k_ref))
        for v in (v_bw, v_pl):
            assert np.array_equal(np.asarray(v), np.asarray(v_ref))

    @pytest.mark.parametrize("variant", VARIANTS)
    def test_seq_len_at_exact_block_boundary(self, variant):
        """len == k*block_size: the new token opens a FRESH block (write
        at offset 0 of table entry k) and attention spans the boundary."""
        bs = 4
        for length in (bs, 2 * bs, 5 * bs):
            state = _paged_state(S=2, M=6, bs=bs,
                                 lens=(length, length - 1),
                                 active=(True, True), seed=length)
            o_ref, k_ref, _ = _run("reference", state, bs)
            if variant == "reference":
                # the boundary write must land at (table[len//bs], 0)
                tables = np.asarray(state[5])
                blk = tables[0, length // bs]
                written = np.asarray(k_ref)[LAYER, blk, 0]
                expect = np.asarray(state[1])[0, 0].reshape(-1)
                np.testing.assert_allclose(written, expect, rtol=1e-6)
                continue
            out, k_pool, _ = _run(variant, state, bs)
            np.testing.assert_allclose(np.asarray(out), np.asarray(o_ref),
                                       rtol=1e-5, atol=1e-5)
            assert np.array_equal(np.asarray(k_pool), np.asarray(k_ref))

    @pytest.mark.parametrize("variant", VARIANTS)
    def test_inactive_slot_null_table_does_not_perturb_neighbors(
            self, variant):
        """An inactive slot whose table still points at null block 0:
        its garbage stays in block 0, active slots' outputs equal the
        solo computation, and no NaN leaks anywhere."""
        bs = 4
        state = _paged_state(S=3, M=4, bs=bs, lens=(7, 0, 5),
                             active=(True, False, True))
        q, kn, vn, kp, vp, tables, lens, active = state
        # zero the inactive slot's table entirely (the engine's cleared
        # slot shape)
        tables = tables.at[1].set(0)
        out, new_k, new_v = paged_decode_attention(
            q, kn, vn, kp, vp, LAYER, tables, lens, active, bs,
            kernel=variant, interpret=(variant == "pallas"))
        solo = paged_decode_attention(
            q, kn, vn, kp, vp, LAYER, tables,
            lens, jnp.asarray([True, True, True]), bs, kernel="reference")
        # active rows agree with a run where slot 1's table is unchanged
        np.testing.assert_allclose(np.asarray(out)[[0, 2]],
                                   np.asarray(solo[0])[[0, 2]],
                                   rtol=1e-5, atol=1e-5)
        assert np.isfinite(np.asarray(out)[[0, 2]]).all()
        # only the null block and the two active write targets changed
        diff = np.where(np.any(np.asarray(new_k) != np.asarray(kp),
                               axis=(0, 2, 3)))[0]
        tables_np = np.asarray(tables)
        allowed = {0, int(tables_np[0, 7 // bs]), int(tables_np[2, 5 // bs])}
        assert set(diff.tolist()) <= allowed

    def test_zero_generated_tokens_right_after_prefill(self, model):
        """The first decode step after admission (cached_len == prompt
        len, nothing generated yet) produces exactly the reference's
        first token — for every kernel variant and the int8 pool."""
        p = _prompt(9, seed=11)
        first = _ref(model, p, 1)[0]
        for kw in ({"attention_kernel": "reference"},
                   {"attention_kernel": "blockwise"},
                   {"kv_dtype": "int8"}):
            engine = LLMEngine(model, max_batch_size=2, block_size=4, **kw)
            req = engine.add_request(p, max_new_tokens=3)
            engine.step()
            assert req.generated[:1] == [first], kw


# ---------------------------------------------------------------------------
# fp32 softmax numerics (bf16 serving keeps its tail tokens)
# ---------------------------------------------------------------------------

class TestBf16Numerics:
    @pytest.mark.parametrize("variant", ("reference", "blockwise"))
    def test_bf16_paged_attention_tracks_fp32(self, variant):
        """Scores + softmax + PV accumulate in fp32 even for bf16
        inputs: the bf16 path must track the all-fp32 computation to
        INPUT-rounding error (~1e-2 for bf16), with a long history whose
        tail would vanish under bf16 accumulation."""
        bs = 4
        st16 = _paged_state(S=2, H=2, D=8, M=16, bs=bs, lens=(60, 31),
                            active=(True, True), dtype=jnp.bfloat16)
        st32 = tuple(x.astype(jnp.float32)
                     if x.dtype == jnp.bfloat16 else x for x in st16)
        out16 = _run(variant, st16, bs)[0]
        out32 = _run("reference", st32, bs)[0]
        assert out16.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(out16, np.float32), np.asarray(out32),
            rtol=0.0, atol=2e-2)


# ---------------------------------------------------------------------------
# int8 KV cache
# ---------------------------------------------------------------------------

class TestInt8KV:
    def test_quantize_roundtrip_error_bound_per_block(self):
        """quantize->dequantize error <= half a quant step per element,
        where the step is that block's per-head scale / 127."""
        rng = np.random.default_rng(3)
        bs, H, D, nb = 4, 3, 8, 9
        T = 24
        vals = jnp.asarray(rng.standard_normal((T, H, D)).astype(np.float32)
                           * rng.uniform(0.1, 10.0, (T, 1, 1)))
        pool = jnp.zeros((LAYERS, nb, bs, H * D), jnp.int8)
        scales = jnp.full((LAYERS, nb, H), 7.7,       # stale tenant scale
                          jnp.float32)
        block_row = jnp.asarray([1, 2, 3, 4, 5, 6, 0, 0], jnp.int32)
        pidx = np.arange(T)
        blocks = jnp.asarray(np.where(pidx < 22, block_row[pidx // bs], 0)
                             .astype(np.int32))
        offs = jnp.asarray((pidx % bs).astype(np.int32))
        pool, scales = quantize_scatter(pool, scales, LAYER, vals, blocks,
                                        offs, block_row, jnp.int32(22))
        deq = np.asarray(dequantize(
            pool[LAYER].reshape(nb, bs, H, D), scales[LAYER]))
        sc = np.asarray(scales)[LAYER]
        for t in range(22):
            b, o = int(blocks[t]), int(offs[t])
            err = np.abs(deq[b, o] - np.asarray(vals)[t])
            bound = sc[b][:, None] / QMAX * 0.5 + 1e-6
            assert (err <= bound).all(), f"token {t}"

    def test_block_write_requant_is_stable_and_bounded(self):
        """Appending tokens one by one into a block: stored values stay
        within half a quant step of the LAST-written fp values (requant
        is exact while the scale does not grow), and the scale is the
        running per-head amax."""
        rng = np.random.default_rng(4)
        bs, H, D = 8, 2, 4
        pool = jnp.zeros((LAYERS, 3, bs, H * D), jnp.int8)
        scales = jnp.zeros((LAYERS, 3, H), jnp.float32)
        written = []
        for i in range(bs):
            vec = jnp.asarray(
                rng.standard_normal((1, H, D)).astype(np.float32) * (i + 1))
            written.append(np.asarray(vec)[0])
            pool, scales = quantize_block_write(
                pool, scales, LAYER, vec, jnp.asarray([1], jnp.int32),
                jnp.asarray([i], jnp.int32))
        deq = np.asarray(dequantize(pool[LAYER, 1].reshape(bs, H, D),
                                    scales[LAYER, 1]))      # [bs, H, D]
        sc = np.asarray(scales)[LAYER, 1]                   # [H]
        amax = np.abs(np.stack(written)).max(axis=(0, 2))
        np.testing.assert_allclose(sc, amax, rtol=1e-5)
        for i, vec in enumerate(written):
            # requant error accrues only on scale-raising writes: each of
            # the <= bs regrids adds at most half a (then-current <=
            # final) quant step — this schedule raises the scale on EVERY
            # write, the worst case
            bound = sc[:, None] / QMAX * (0.5 * bs)
            assert (np.abs(deq[i] - vec) <= bound + 1e-6).all(), i

    @pytest.mark.parametrize("pool", ["roomy", "tight"])
    def test_int8_greedy_decode_token_identical_to_fp32(self, model, pool):
        """End-to-end: the int8-KV engine reproduces the fp32 reference
        stream token for token on the tiny-GPT fixture — including under
        preemption churn (evict -> requeue -> re-prefill requantizes)."""
        if pool == "roomy":
            prompts = [_prompt(n, seed=21) for n in (11, 5, 17, 3)]
            engine = LLMEngine(model, max_batch_size=4, block_size=4,
                               kv_dtype="int8")
        else:
            # tight pool: eviction + resume stays token-identical on int8
            prompts = [_prompt(n, seed=22) for n in (11, 12, 10, 5)]
            engine = LLMEngine(model, max_batch_size=3, block_size=4,
                               num_blocks=10, watermark_blocks=1,
                               kv_dtype="int8")
        outs = engine.generate(prompts, max_new_tokens=10)
        assert outs == [_ref(model, p, 10) for p in prompts]
        st = engine.stats()
        assert st["kv_dtype"] == "int8"
        assert (st["evictions"] >= 1) == (pool == "tight")
        assert st["decode_compiles"] == 1

    def test_int8_admits_1p8x_streams_at_same_pool_bytes(self, model):
        """The capacity win: with the SAME byte budget, the int8 pool
        admits >= 1.8x the concurrent streams before it runs dry
        (admission here is pure host-side block accounting — no
        compiles)."""
        cfg = model.config
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        budget = 96 * 1024

        def admitted(kv_dtype, dt):
            nb = num_blocks_for_bytes(budget, cfg.num_hidden_layers,
                                      cfg.num_attention_heads, head_dim,
                                      4, dt)
            eng = LLMEngine(model, max_batch_size=96, block_size=4,
                            num_blocks=nb, watermark_blocks=1,
                            kv_dtype=kv_dtype)
            for i in range(96):
                eng.add_request(_prompt(8, seed=30 + i), max_new_tokens=8)
            n = 0
            while eng.scheduler.try_admit() is not None:
                n += 1
            return n

        n_fp32 = admitted(None, jnp.float32)
        n_int8 = admitted("int8", jnp.int8)
        assert n_int8 >= 1.8 * n_fp32, (n_int8, n_fp32)


# ---------------------------------------------------------------------------
# the blockwise loop is bounded by the lengths it is given (ISSUE 29)
# ---------------------------------------------------------------------------

def _every_entry_attention(q, k_pools, v_pools, layer, block_tables, lens,
                           block_size, k_scales=None, v_scales=None,
                           chunk_blocks=1):
    """The online-softmax recurrence run over EVERY table entry of every
    slot, whatever the lengths: `blockwise_paged_attention` as it was
    before its loop was bounded, kept here as the yardstick the bounded
    loop is bitwise equal to at the same chunk size."""
    s, h, d = q.shape
    m = block_tables.shape[1]
    bs = int(block_size)
    n_chunks = -(-m // chunk_blocks)
    tables = jnp.pad(block_tables,
                     ((0, 0), (0, n_chunks * chunk_blocks - m)))
    tabs = jnp.swapaxes(tables.reshape(s, n_chunks, chunk_blocks), 0, 1)
    q32 = q.astype(jnp.float32) * (1.0 / np.sqrt(d)).astype(np.float32)
    t_chunk = chunk_blocks * bs
    offs = jnp.arange(t_chunk, dtype=jnp.int32)

    def step(carry, xs):
        acc, mx, l = carry
        ci, bids = xs
        kc, vc = k_pools[layer, bids], v_pools[layer, bids]
        if k_scales is not None:
            split = (s, chunk_blocks, bs, h, d)
            kc = dequantize(kc.reshape(split), k_scales[layer, bids])
            vc = dequantize(vc.reshape(split), v_scales[layer, bids])
        kc = kc.astype(jnp.float32).reshape(s, t_chunk, h, d)
        vc = vc.astype(jnp.float32).reshape(s, t_chunk, h, d)
        scores = jnp.einsum("shd,sthd->sht", q32, kc)
        valid = (ci * t_chunk + offs)[None, :] <= lens[:, None]
        scores = jnp.where(valid[:, None, :], scores, jnp.float32(-1e30))
        m_new = jnp.maximum(mx, jnp.max(scores, axis=-1))
        p = jnp.where(valid[:, None, :],
                      jnp.exp(scores - m_new[..., None]), 0.0)
        alpha = jnp.exp(mx - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("sht,sthd->shd", p, vc)
        return (acc, m_new, l), None

    init = (jnp.zeros((s, h, d), jnp.float32),
            jnp.full((s, h), -1e30, jnp.float32),
            jnp.zeros((s, h), jnp.float32))
    (acc, _, l), _ = jax.lax.scan(
        step, init, (jnp.arange(n_chunks, dtype=jnp.int32), tabs))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)


# table of 12 entries of 4 tokens, read in chunks of 2 entries = 8 tokens:
# six chunks, so a bound has something to cut
BOUND_BS, BOUND_M, BOUND_CHUNK = 4, 12, 2
BOUND_MAX = BOUND_BS * BOUND_M - 1          # a slot at max_context

# name -> (lens, active); None = every slot active
LENGTH_MIXES = {
    "all_inactive": ((5, 17, 0, 40, 9, 9, 31, 2), (False,) * 8),
    "one_at_max_context_among_short": (
        (3, 1, 6, 2) * 4 + (BOUND_MAX,) + (4, 0, 7) * 5, None),
    "block_and_chunk_boundaries": (
        tuple(b * BOUND_BS + e for b in (1, 2, 3, 4, 6, 11)
              for e in (-1, 0)) + (0, 1, 7, 8, 9, 15, 16, 47) * 3, None),
    "odd_slot_count_some_inactive": (
        tuple(int(x) for x in np.random.default_rng(29).integers(
            0, BOUND_MAX + 1, 37)),
        tuple(bool(x) for x in np.random.default_rng(30).random(37) < 0.8)),
    "one_slot": ((21,), None),
    "eight_slots": ((0, 4, 8, 23, 31, 5, 12, 40), None),
    "the_cells_128_slots": (
        tuple(int(x) for x in np.random.default_rng(31).integers(
            0, BOUND_MAX + 1, 128) // np.random.default_rng(32).integers(
                1, 5, 128)), None),
}


def _bounded_case(lens, active, pool, seed=0):
    active = (True,) * len(lens) if active is None else active
    H = 3
    q, _, _, kp, vp, tables, lens, active = _paged_state(
        S=len(lens), H=H, bs=BOUND_BS, M=BOUND_M, lens=lens, active=active,
        seed=seed, dtype=jnp.bfloat16)
    scales = ()
    if pool == "int8":
        (kp, ks), (vp, vs) = _quantized(kp, H), _quantized(vp, H)
        scales = (ks, vs)
    eff = jnp.where(active, lens, 0).astype(jnp.int32)
    return q[:, 0], kp, vp, tables, eff, scales


@pytest.fixture(params=[None, 8], ids=["widths_as_shipped", "widths_to_8"])
def min_width(request, monkeypatch):
    """The narrowest step as shipped (one width under 128 slots), and 8:
    three and four widths at the tests' slot counts."""
    if request.param is not None:
        monkeypatch.setattr(paged_attention, "_MIN_WIDTH_SLOTS",
                            request.param)
    return paged_attention._MIN_WIDTH_SLOTS


class TestLengthBoundedLoop:
    @pytest.mark.parametrize("pool", ("bf16", "int8"))
    @pytest.mark.parametrize("mix", sorted(LENGTH_MIXES))
    def test_bounded_loop_is_the_every_entry_recurrence(self, mix, pool,
                                                        min_width):
        """Whatever the mix of lengths, the loop that leaves out what no
        slot holds gives BITWISE what the same recurrence gives over
        every table entry at the same chunk size (a chunk that is not
        read adds exactly nothing), and the dense oracle's values."""
        q, kp, vp, tables, eff, scales = _bounded_case(
            *LENGTH_MIXES[mix], pool)
        args = (q, kp, vp, LAYER, tables, eff, BOUND_BS) + scales
        got = blockwise_paged_attention(*args, chunk_blocks=BOUND_CHUNK)
        every = _every_entry_attention(*args, chunk_blocks=BOUND_CHUNK)
        assert got.dtype == q.dtype and got.shape == q.shape
        assert np.array_equal(np.asarray(got, np.float32),
                              np.asarray(every, np.float32))
        oracle = _dense_gather_attention(*args)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(oracle, np.float32),
                                   rtol=0.0, atol=2e-2)
        # another chunk size: the same values in another summation order
        other = blockwise_paged_attention(*args, chunk_blocks=5)
        np.testing.assert_allclose(np.asarray(other, np.float32),
                                   np.asarray(every, np.float32),
                                   rtol=0.0, atol=2e-2)

    def test_the_plan_follows_the_shapes(self, monkeypatch):
        """One width for the 8-slot engines (the loop only stops at the
        longest context), all the slots or the longer half at the backlog
        cell's 128, the chunk the head shape gave before."""
        assert _blockwise_plan(8, 64, 16, 12, 64) == ((8,), 5, 13)
        assert _blockwise_plan(126, 64, 16, 12, 64) == ((126,), 5, 13)
        assert _blockwise_plan(128, 64, 16, 12, 64) == ((128, 64), 5, 13)
        assert _blockwise_plan(130, 12, 4, 3, 16, 2) == ((130, 65), 2, 6)
        assert _blockwise_plan(1000, 12, 4, 3, 16, 99)[0] == (
            1000, 500, 250, 125)
        monkeypatch.setattr(paged_attention, "_MIN_WIDTH_SLOTS", 8)
        assert _blockwise_plan(37, 12, 4, 3, 16, 2) == ((37, 19, 10), 2, 6)
        assert _blockwise_plan(1, 12, 4, 3, 16, 2) == ((1,), 2, 6)
        assert _blockwise_plan(31, 12, 4, 3, 16, 99) == ((31, 16, 8), 12, 1)

    @pytest.mark.parametrize("pool", ("bf16", "int8"))
    def test_entries_a_step_leaves_out_are_not_read(self, pool):
        """NaN in the V rows of pool blocks that only unread entries point
        to (a slot's chunks past the loop's bound, and its chunks in steps
        narrower than its rank): the bounded loop never gathers them, so
        its output is finite and bitwise the clean pool's; the loop over
        every entry multiplies them by a zero probability and gets NaN."""
        S = 128                     # widths 128 and 64, as the cell's
        lens = tuple(i % 11 for i in range(64)) + tuple(
            17 + i % 20 for i in range(64))
        q, kp, vp, tables, eff, scales = _bounded_case(lens, None, pool)
        widths, chunk, n_chunks = _blockwise_plan(
            S, BOUND_M, BOUND_BS, 3, 16, BOUND_CHUNK)
        assert widths == (128, 64)
        order = np.argsort(-np.asarray(eff), kind="stable")
        trips, which = _step_widths(np.asarray(eff)[order], widths,
                                    chunk * BOUND_BS, n_chunks, np)
        # 36 // 8 + 1 chunks; from the third on only the 64 longest
        assert int(trips) == 5
        assert [widths[i] for i in which[:trips]] == [128, 128, 64, 64, 64]
        read = np.zeros((S, n_chunks), bool)
        for ci in range(int(trips)):
            read[order[:widths[which[ci]]], ci] = True
        poisoned = np.zeros(kp.shape[1], bool)
        t = np.asarray(tables).reshape(S, n_chunks, chunk)
        poisoned[t[~read]] = True
        assert poisoned.sum() == (64 * 4 + 64 * 1) * chunk
        bad = jnp.asarray(np.where(poisoned[None, :, None, None],
                                   np.nan if pool == "bf16" else 0,
                                   np.asarray(vp, np.float32))).astype(
                                       vp.dtype)
        bad_scales = scales
        if pool == "int8":
            # an int8 pool holds no NaN: poison the blocks' scales
            bad = vp
            bad_scales = (scales[0], jnp.where(
                jnp.asarray(poisoned)[None, :, None], jnp.nan, scales[1]))
        run = lambda fn, v, sc: np.asarray(fn(
            q, kp, v, LAYER, tables, eff, BOUND_BS, *sc,
            chunk_blocks=BOUND_CHUNK), np.float32)
        clean = run(blockwise_paged_attention, vp, scales)
        got = run(blockwise_paged_attention, bad, bad_scales)
        assert np.isfinite(got).all()
        assert np.array_equal(got, clean)
        assert not np.isfinite(
            run(_every_entry_attention, bad, bad_scales)).all()

    @pytest.mark.parametrize("slots", (1, 8, 37, 128, 200))
    def test_host_count_is_the_device_loops_trip_count(self, slots,
                                                       min_width):
        """`blockwise_streamed_entries` (numpy, the engine's counter) and
        the program (jax.numpy, traced) share `_blockwise_plan` and
        `_step_widths`: for the same lengths the trip count and every
        step's width are equal, and the streamed entries are those steps'
        slots x entries, between the held entries and the whole table."""
        M, bs, H, D = 64, 16, 12, 64
        widths, chunk, n_chunks = _blockwise_plan(slots, M, bs, H, D)
        on_device = jax.jit(lambda lens: _step_widths(
            lens, widths, chunk * bs, n_chunks, jnp))
        rng = np.random.default_rng(slots)
        for draw in range(6):
            lens = rng.integers(0, M * bs, slots).astype(np.int32)
            lens //= rng.integers(1, 6, slots).astype(np.int32)
            active = rng.random(slots) < (0.0, 0.5, 0.9, 1, 1, 1)[draw]
            if draw == 5:
                lens[:] = M * bs - 1
            eff = -np.sort(-np.where(active, lens, 0).astype(np.int32))
            trips_d, which_d = on_device(jnp.asarray(eff))
            trips_h, which_h = _step_widths(eff, widths, chunk * bs,
                                            n_chunks, np)
            assert int(trips_d) == int(trips_h) == eff[0] // (chunk * bs) + 1
            assert np.asarray(which_d).tolist() == which_h.tolist()
            assert which_h[0] == 0          # the first chunk is every slot's
            for c in range(1, int(trips_h)):
                reach = int((eff // (chunk * bs) >= c).sum())
                assert widths[which_h[c]] >= reach
                assert which_h[c] == len(widths) - 1 or \
                    widths[which_h[c] + 1] < reach
            streamed, held = blockwise_streamed_entries(
                lens, active, M, bs, H, D)
            entries = [min(chunk, M - c * chunk) for c in range(n_chunks)]
            assert streamed == sum(widths[which_h[c]] * entries[c]
                                   for c in range(int(trips_h)))
            assert held == sum(int(n) // bs + 1
                               for n in np.where(active, lens, 0)[active])
            assert held <= streamed <= slots * M
            if draw == 5:
                assert streamed == held == slots * M

    def test_engine_reports_streamed_and_held_share(self):
        """`stats()` sums the helper's counts over the decode launches:
        streamed lies between held and 1, `reset_stats()` zeroes the
        window, and the same requests stream a smaller share of a table
        twice as long."""
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=VOCAB, hidden_size=32,
                        num_hidden_layers=1, num_attention_heads=4,
                        intermediate_size=64, max_position_embeddings=2048,
                        hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0,
                        use_flash_attention=False)
        model = GPTForCausalLM(cfg)
        model.eval()
        prompts = [_prompt(n, seed=29) for n in (5, 30, 12, 21, 9)]
        shares, streams = {}, {}
        for ctx in (1024, 2048):
            engine = LLMEngine(model, max_batch_size=4, block_size=16,
                               max_context=ctx, num_blocks=64)
            assert engine.stats()["attn_streamed_share"] == 0.0
            streams[ctx] = engine.generate(prompts, max_new_tokens=4)
            st = engine.stats()
            assert st["attention_kernel"] == "blockwise"
            assert 0.0 < st["attn_held_share"] < st["attn_streamed_share"] < 1.0
            shares[ctx] = (st["attn_streamed_share"], st["attn_held_share"])
            engine.reset_stats()
            st = engine.stats()
            assert st["attn_streamed_share"] == st["attn_held_share"] == 0.0
        assert streams[1024] == streams[2048]
        # chunks of 512 tokens: one of two, then one of four
        assert shares[1024][0] == pytest.approx(0.5)
        assert shares[2048][0] == pytest.approx(0.25)
        assert shares[2048][1] == pytest.approx(shares[1024][1] / 2)
        # a variant that reads the whole table says so
        engine = LLMEngine(model, max_batch_size=4, block_size=16,
                           max_context=1024, num_blocks=64,
                           attention_kernel="reference")
        assert engine.generate(prompts, max_new_tokens=4) == streams[1024]
        st = engine.stats()
        assert st["attn_streamed_share"] == 1.0
        assert st["attn_held_share"] == pytest.approx(shares[1024][1])

    @pytest.mark.parametrize("sampler", [SAMPLERS[0], SAMPLERS[4]],
                             ids=["greedy", "penalty"])
    def test_engine_at_two_widths_serves_generates_tokens(self, model,
                                                          sampler):
        """128 slots run at two widths: the ordered decode step serves
        exactly `model.generate`'s tokens, compiled once; seeded, the
        streams of one request at a time through the dense forward."""
        prompts = [_prompt(3 + (i * 5) % 23, seed=29) for i in range(140)]
        engine = LLMEngine(model, max_batch_size=128, block_size=4)
        assert _blockwise_plan(128, engine.max_blocks_per_seq, 4, 4, 8)[0] \
            == (128, 64)
        reqs = [engine.add_request(p, max_new_tokens=6,
                                   **stream_of(sampler, i))
                for i, p in enumerate(prompts)]
        engine.run()
        Reference(model).assert_served(reqs)
        if not sampler:
            for p, r in zip(prompts, reqs):
                assert r.generated == _ref(model, p, 6)
        st = engine.stats()
        assert st["decode_compiles"] == 1 and st["completed"] == 140


# ---------------------------------------------------------------------------
# the Pallas kernel (ISSUE 33): only the pages that hold tokens are copied
# ---------------------------------------------------------------------------

RAGGED_BS, RAGGED_M = 16, 20        # the plan's groups of 16 pages: two a slot


def _ragged_case(heads, head_dim, dtype, seed=0):
    """One batch of ragged lengths at a real block size: an inactive slot
    (effective length 0, its table cleared to the null block), the last
    position of a page and of a group, the first of the next, one slot at
    `max_context`; every table padded with the null block past the pages
    its slot holds."""
    lens = np.asarray([0, RAGGED_BS - 1, RAGGED_BS, 8 * RAGGED_BS - 1,
                       8 * RAGGED_BS, 201, 16 * RAGGED_BS - 1,
                       16 * RAGGED_BS, RAGGED_M * RAGGED_BS - 1], np.int32)
    S = len(lens)
    rng = np.random.default_rng(seed)
    nb = 1 + S * RAGGED_M
    mk = lambda sh: jnp.asarray(
        rng.standard_normal(sh).astype(np.float32)).astype(dtype)
    q = mk((S, heads, head_dim))
    kp = mk((LAYERS, nb, RAGGED_BS, heads * head_dim))
    vp = mk((LAYERS, nb, RAGGED_BS, heads * head_dim))
    pages = lens // RAGGED_BS + 1
    tables = np.zeros((S, RAGGED_M), np.int32)
    ids = rng.permutation(np.arange(1, nb))     # a churned allocator's
    for s in range(1, S):
        tables[s, :pages[s]] = ids[s * RAGGED_M:s * RAGGED_M + pages[s]]
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(lens)


class TestRaggedPallasKernel:
    @pytest.mark.parametrize("dtype", ("float32", "bfloat16"))
    @pytest.mark.parametrize("heads,head_dim", ((12, 64), (16, 128)),
                             ids=("12x64", "16x128"))
    def test_ragged_lengths_match_the_dense_oracle(self, heads, head_dim,
                                                   dtype):
        """Every slot of a ragged batch, the inactive one included, reads
        what the dense gather reads, in float32 and bf16 pools, at both
        models' head shapes and at every number of pages a group."""
        dtype = jnp.dtype(dtype)
        q, kp, vp, tables, lens = _ragged_case(heads, head_dim, dtype)
        oracle = np.asarray(_dense_gather_attention(
            q, kp, vp, LAYER, tables, lens, RAGGED_BS), np.float32)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        for pages in (None, 8, 3, 1):
            got = paged_attention.pallas_paged_attention(
                q, kp, vp, LAYER, tables, lens, RAGGED_BS, interpret=True,
                group_pages=pages)
            assert got.dtype == q.dtype and got.shape == q.shape
            np.testing.assert_allclose(np.asarray(got, np.float32), oracle,
                                       rtol=0.0, atol=tol,
                                       err_msg=f"group_pages={pages}")

    @pytest.mark.parametrize("dtype", ("float32", "bfloat16"))
    def test_pages_that_hold_no_attended_token_are_never_copied(self,
                                                                dtype):
        """NaN in every page that holds no token some slot attends to:
        the pages a table names past its slot's length, every block no
        table names, and all of the other layers. The kernel is bounded
        by the lengths and not only masked (a copied NaN would reach the
        output through a zero probability), so its output is bitwise the
        clean pools'."""
        dtype = jnp.dtype(dtype)
        q, kp, vp, tables, lens = _ragged_case(12, 64, dtype, seed=3)
        # the tables name blocks past their slots' lengths too
        full = np.asarray(tables).copy()
        spare = iter(np.setdiff1d(np.arange(1, kp.shape[1]), full))
        pages = np.asarray(lens) // RAGGED_BS + 1
        for s in range(1, full.shape[0]):
            for j in range(pages[s], RAGGED_M):
                full[s, j] = next(spare)
        attended = np.zeros(kp.shape[1], bool)
        for s in range(full.shape[0]):
            attended[full[s, :pages[s]]] = True     # slot 0: the null block
        poison = np.ones(kp.shape[:2], bool)
        poison[LAYER] = ~attended
        assert poison[LAYER].sum() > kp.shape[1] // 2
        bad = lambda pool: jnp.where(jnp.asarray(poison)[:, :, None, None],
                                     jnp.nan, pool).astype(pool.dtype)
        run = lambda k, v: np.asarray(paged_attention.pallas_paged_attention(
            q, k, v, LAYER, jnp.asarray(full), lens, RAGGED_BS,
            interpret=True), np.float32)
        got = run(bad(kp), bad(vp))
        assert np.isfinite(got).all()
        assert np.array_equal(got, run(kp, vp))
        oracle = _dense_gather_attention(q, kp, vp, LAYER, tables, lens,
                                         RAGGED_BS)
        np.testing.assert_allclose(
            got, np.asarray(oracle, np.float32), rtol=0.0,
            atol=2e-2 if dtype == jnp.bfloat16 else 2e-5)

    @pytest.mark.parametrize("slots", (1, 8, 128))
    def test_host_count_is_the_pages_the_plan_copies(self, slots):
        """`pallas_copied_pages` (numpy, the engine's counter) and the
        kernel (jax.numpy on the scalars it prefetches) share
        `_slot_pages`: for the same lengths the host counts the pages the
        kernel's trip counts add up to: the held pages, and one for each
        inactive slot; a full batch copies exactly what it holds."""
        M, bs = 64, 16
        on_device = jax.jit(lambda lens: paged_attention._slot_pages(
            lens, np.int32(bs), np.int32(M), jnp))
        rng = np.random.default_rng(slots)
        for draw in range(5):
            lens = rng.integers(0, M * bs, slots).astype(np.int32)
            lens //= rng.integers(1, 6, slots).astype(np.int32)
            active = rng.random(slots) < (0.0, 0.5, 0.9, 1, 1)[draw]
            if draw == 4:
                lens[:] = M * bs - 1
            eff = np.where(active, lens, 0).astype(np.int32)
            copied, held = paged_attention.pallas_copied_pages(
                lens, active, M, bs)
            assert copied == int(np.asarray(on_device(jnp.asarray(eff))).sum())
            assert held == blockwise_streamed_entries(
                lens, active, M, bs, 12, 64)[1]
            assert copied == held + int((~active).sum())
            if active.all():
                assert copied == held <= slots * M
            if draw == 4:
                assert copied == slots * M

    def test_engine_serves_through_the_kernel_it_chooses(self, monkeypatch):
        """On a TPU (here: told so, the kernel in the interpreter) an
        engine that is asked for no variant chooses `pallas` for a
        per-head fp pool of whole tiles, serves `model.generate`'s tokens
        through it, and counts the pages it copies: the held share, plus
        a page for every inactive slot of a launch."""
        paddle.seed(0)
        cfg = GPTConfig(vocab_size=VOCAB, hidden_size=128,
                        num_hidden_layers=2, num_attention_heads=2,
                        intermediate_size=128, max_position_embeddings=256,
                        hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0,
                        use_flash_attention=False)
        wide = GPTForCausalLM(cfg)
        wide.eval()
        prompts = [_prompt(n, seed=33) for n in (5, 30, 17, 21)]
        expect = LLMEngine(wide, max_batch_size=4, block_size=8,
                           attention_kernel="reference").generate(
                               prompts, max_new_tokens=5)
        monkeypatch.setattr(paged_attention, "_on_tpu", lambda: True)
        kernel = paged_attention.pallas_paged_attention
        monkeypatch.setattr(
            paged_attention, "pallas_paged_attention",
            lambda *args, interpret=False, **kw: kernel(
                *args, interpret=True, **kw))
        engine = LLMEngine(wide, max_batch_size=4, block_size=8)
        assert engine.stats()["attention_kernel"] == "pallas"
        assert engine.generate(prompts, max_new_tokens=5) == expect
        st, raw = engine.stats(), engine._stats
        assert st["decode_compiles"] == 1
        assert 0.0 < st["attn_held_share"] <= st["attn_streamed_share"] < 0.5
        idle = raw.launches * 4 - raw.decode_tokens
        assert raw.attn_entries_streamed == raw.attn_entries_held + idle
        # a row off the lane tiles, an int8 pool: the loop, and no event
        # (nothing was asked for)
        clear_fusion_events()
        assert LLMEngine(wide, max_batch_size=4, block_size=8,
                         kv_dtype="int8").stats()["attention_kernel"] \
            == "blockwise"
        assert fusion_events("kernel.fallback") == []


# ---------------------------------------------------------------------------
# the Pallas kernel over a latent pool (ISSUE 38): the same plan, one pool
# ---------------------------------------------------------------------------

LATENT_BS, LATENT_M, LATENT_GROUP = 8, 12, 4    # three groups of four pages
LATENT_W, LATENT_ROW, LATENT_VALUE, LATENT_HEADS = 24, 128, 16, 4
LATENT_SCALE = 0.3

# (length, active): an inactive slot whose length the mask must hide, an
# empty one, the last position of a page and the first of the next, of a
# group and of the next, one group + 1, a slot whose LAST group is partial
# followed by a slot with one page (the buffers' parity crosses the grid
# step there), the table's last entry, and a one-page slot at the end
LATENT_SLOTS = ((37, False), (0, True), (LATENT_BS - 1, True),
                (LATENT_BS, True), (LATENT_GROUP * LATENT_BS - 1, True),
                (LATENT_GROUP * LATENT_BS, True),
                (LATENT_GROUP * LATENT_BS + 1, True), (75, True), (3, True),
                (LATENT_M * LATENT_BS - 1, True), (5, True))


def _latent_case(dtype, seed=0):
    """A latent pool with a row of `LATENT_W` values padded with zeros to
    `LATENT_ROW`, a churned allocator's tables cleared to the null block
    past the pages each slot holds (an inactive slot holds none), and the
    new token's row in its two parts."""
    lens = np.asarray([n for n, _ in LATENT_SLOTS], np.int32)
    active = np.asarray([a for _, a in LATENT_SLOTS])
    S = len(lens)
    rng = np.random.default_rng(seed)
    nb = 1 + S * LATENT_M
    mk = lambda *sh: jnp.asarray(rng.standard_normal(sh), jnp.float32)
    pool = mk(LAYERS, nb, LATENT_BS, LATENT_ROW)
    pool = pool.at[..., LATENT_W:].set(0.0).astype(dtype)
    pages = np.where(active, lens // LATENT_BS + 1, 0)
    tables = np.zeros((S, LATENT_M), np.int32)
    ids = rng.permutation(np.arange(1, nb))
    for s in range(S):
        tables[s, :pages[s]] = ids[s * LATENT_M:s * LATENT_M + pages[s]]
    new = (mk(S, LATENT_VALUE).astype(dtype),
           mk(S, LATENT_W - LATENT_VALUE).astype(dtype))
    return (mk(S, LATENT_HEADS, LATENT_W), new, pool, jnp.asarray(tables),
            jnp.asarray(lens), jnp.asarray(active))


def _latent_attend(case, kernel, **kw):
    from paddle_tpu.nn.functional.attention import \
        paged_latent_decode_attention
    q, new, pool, tables, lens, active = case
    with jax.default_matmul_precision("highest"):
        out, written = paged_latent_decode_attention(
            q, new, pool, LAYER, tables, lens, active, LATENT_BS,
            value_width=LATENT_VALUE, scale=LATENT_SCALE, kernel=kernel,
            **kw)
    return np.asarray(out), written


class TestRaggedLatentKernel:
    @pytest.mark.parametrize("dtype", ("float32", "bfloat16"))
    @pytest.mark.parametrize("other", ("reference", "blockwise"))
    def test_ragged_lengths_match_the_oracle_and_the_loop(self, other,
                                                          dtype):
        """Every active slot of a ragged batch reads what the dense gather
        and what the blockwise loop read, over float32 and bf16 rows,
        through the entry the model calls; the result is float32 and the
        written pool is the other variant's."""
        case = _latent_case(jnp.dtype(dtype))
        got, written = _latent_attend(case, "pallas", interpret=True)
        want, same = _latent_attend(case, other)
        assert got.dtype == np.float32 and got.shape == want.shape
        assert np.array_equal(np.asarray(written, np.float32),
                              np.asarray(same, np.float32))
        active = np.asarray(case[-1])
        # (bf16: the kernel and the loop round the queries to the rows'
        # type, the oracle does not)
        np.testing.assert_allclose(
            got[active], want[active], rtol=0.0,
            atol=2e-2 if dtype == "bfloat16" else 2e-5)

    @pytest.mark.parametrize("dtype", ("float32", "bfloat16"))
    @pytest.mark.parametrize("pages", (LATENT_GROUP, 3, 1, LATENT_M))
    def test_every_group_size_gives_the_same_attention(self, pages, dtype):
        """Groups of four pages (the lengths' edges are this size's), of
        three (no length is on its boundaries), of one page, and one
        group for the whole table: the plan changes, the output does
        not."""
        q, new, pool, tables, lens, active = _latent_case(jnp.dtype(dtype),
                                                          seed=1)
        eff = jnp.where(active, lens, 0)
        run = lambda pages: np.asarray(
            paged_attention.pallas_latent_attention(
                q, pool, LAYER, tables, eff, LATENT_BS, LATENT_VALUE,
                LATENT_SCALE, interpret=True, group_pages=pages))
        with jax.default_matmul_precision("highest"):
            loop = np.asarray(paged_attention.blockwise_latent_attention(
                q, pool, LAYER, tables, eff, LATENT_BS, LATENT_VALUE,
                LATENT_SCALE))
            got = run(pages)
        assert got.shape == (len(LATENT_SLOTS), LATENT_HEADS, LATENT_VALUE)
        # an inactive slot reads its one page, the null block: numbers
        assert np.isfinite(got).all()
        np.testing.assert_allclose(
            got, loop, rtol=0.0, atol=5e-3 if dtype == "bfloat16" else 2e-5)

    @pytest.mark.parametrize("dtype", ("float32", "bfloat16"))
    def test_pages_that_hold_no_attended_token_are_never_copied(self,
                                                                dtype):
        """NaN in every page that holds no token some slot attends to (the
        pages a table names past its slot's length, every block no table
        names, all of the other sublayer): the kernel is bounded by the
        lengths and not only masked, so its output is bitwise the clean
        pool's."""
        q, new, pool, tables, lens, active = _latent_case(jnp.dtype(dtype),
                                                          seed=3)
        eff = np.where(np.asarray(active), np.asarray(lens), 0)
        full = np.asarray(tables).copy()
        spare = iter(np.setdiff1d(np.arange(1, pool.shape[1]), full))
        pages = eff // LATENT_BS + 1
        attended = np.zeros(pool.shape[1], bool)
        for s in range(full.shape[0]):
            if active[s]:
                for j in range(pages[s], LATENT_M):
                    full[s, j] = next(spare)
            attended[full[s, :pages[s]]] = True
        poison = np.ones(pool.shape[:2], bool)
        poison[LAYER] = ~attended
        assert poison[LAYER].sum() > pool.shape[1] // 2
        bad = jnp.where(jnp.asarray(poison)[:, :, None, None], jnp.nan,
                        pool).astype(pool.dtype)
        run = lambda pool: np.asarray(
            paged_attention.pallas_latent_attention(
                q, pool, LAYER, jnp.asarray(full), jnp.asarray(eff),
                LATENT_BS, LATENT_VALUE, LATENT_SCALE, interpret=True,
                group_pages=LATENT_GROUP))
        got = run(bad)
        assert np.isfinite(got).all()
        assert np.array_equal(got, run(pool))

    def test_the_plan_is_one_pools(self):
        """A latent group is `_LATENT_GROUP_TOKENS` tokens of pages, within
        the bytes a group of one pool may take and within the table; the
        host's count of what the kernel copies is `pallas_copied_pages`,
        whatever the pool holds."""
        tokens = paged_attention._LATENT_GROUP_TOKENS
        plan = lambda *a: paged_attention._group_pages(*a, tokens)
        assert plan(128, 16, 640, jnp.bfloat16) == tokens // 16
        assert plan(8, 16, 640, jnp.bfloat16) == 8
        assert plan(128, 16, 640, jnp.float32) == min(
            tokens // 16, paged_attention._GROUP_BYTES_MAX // (16 * 640 * 4))
        lens = np.asarray([n for n, _ in LATENT_SLOTS])
        active = np.asarray([a for _, a in LATENT_SLOTS])
        copied, held = paged_attention.pallas_copied_pages(
            lens, active, LATENT_M, LATENT_BS)
        assert held == int((lens // LATENT_BS + 1)[active].sum())
        assert copied == held + 1


# ---------------------------------------------------------------------------
# keying: dispatch cache, AOT fingerprint, fallback attribution
# ---------------------------------------------------------------------------

class TestKernelKeying:
    def test_variant_is_keyed_into_dispatch_cache(self, model):
        """Flipping the kernel variant re-keys the paged attention op in
        the per-op executable cache: each variant is a distinct MISS,
        repeats are HITS — never a stale replay of the other variant."""
        from paddle_tpu.framework.core import Tensor
        from paddle_tpu.serving.cache import PagedCacheView

        cfg = model.config
        attn = model.gpt.h[0].attn
        S, bs, M = 2, 4, 4
        nb = S * M + 1
        rng = np.random.default_rng(5)
        x = Tensor(jnp.asarray(rng.standard_normal(
            (S, 1, cfg.hidden_size)).astype(np.float32)),
            stop_gradient=True)
        pools = jnp.asarray(rng.standard_normal(
            (cfg.num_hidden_layers, nb, bs,
             cfg.hidden_size)).astype(np.float32))
        tables = jnp.asarray(np.stack(
            [1 + s * M + np.arange(M) for s in range(S)]).astype(np.int32))
        lens = jnp.asarray([3, 5], jnp.int32)
        active = jnp.ones((S,), bool)

        prev = get_flags(["FLAGS_profiler_events"])
        set_flags({"FLAGS_profiler_events": True})
        clear_fusion_events()
        try:
            for variant in ("reference", "blockwise",
                            "reference", "blockwise"):
                view = PagedCacheView(pools, pools, 0, tables, lens,
                                      active, bs, kernel=variant)
                attn(x, cache=view)
        finally:
            set_flags(prev)
        ev = [e for e in fusion_events("dispatch")
              if e["op"] == "gpt_paged_decode_attention"]
        misses = [e for e in ev if e["cat"] == "dispatch.miss"]
        hits = [e for e in ev if e["cat"] == "dispatch.hit"]
        assert len(misses) == 2, [e["cat"] for e in ev]
        assert len(hits) == 2, [e["cat"] for e in ev]

    def test_flag_keyed_into_aot_env_fingerprint(self):
        """A kernel flip re-fingerprints the AOT store so a stale
        artifact misses by construction."""
        from paddle_tpu.ops import aot_cache
        prev = get_flags(["FLAGS_serve_attention_kernel"])
        try:
            set_flags({"FLAGS_serve_attention_kernel": "blockwise"})
            d_block = aot_cache.fingerprint_digest()
            fp = aot_cache.env_fingerprint()
            assert ("FLAGS_serve_attention_kernel", "blockwise") \
                in fp["flags"]
            set_flags({"FLAGS_serve_attention_kernel": "reference"})
            d_ref = aot_cache.fingerprint_digest()
            assert d_block != d_ref
            set_flags({"FLAGS_serve_attention_kernel": "blockwise"})
            assert aot_cache.fingerprint_digest() == d_block
        finally:
            set_flags(prev)

    def test_decode_digest_rekeys_on_kernel_and_kv_dtype(self, model):
        """The engine's AOT decode digest separates kernel variants and
        KV dtypes — a blockwise/int8 artifact never replays elsewhere."""
        digs = set()
        for kw in ({"attention_kernel": "reference"},
                   {"attention_kernel": "blockwise"},
                   {"kv_dtype": "int8"}):
            eng = LLMEngine(model, max_batch_size=2, block_size=4, **kw)
            d = eng._aot_decode_digest()
            assert d is not None
            digs.add(d)
        assert len(digs) == 3

    def test_pallas_fallback_is_attributed_not_silent(self):
        """Requesting the Pallas kernel off-TPU demotes to blockwise AND
        emits a kernel.fallback event with the why."""
        prev = get_flags(["FLAGS_profiler_events"])
        set_flags({"FLAGS_profiler_events": True})
        clear_fusion_events()
        try:
            got = resolve_paged_kernel("pallas", num_heads=12, head_dim=64,
                                       block_size=16)
        finally:
            set_flags(prev)
        assert got == "blockwise"
        ev = [e for e in fusion_events("kernel.fallback")]
        assert len(ev) == 1
        assert ev[0]["reason"] == "kernel_fallback"
        assert ev[0]["detail"]["requested"] == "pallas"
        assert ev[0]["detail"]["actual"] == "blockwise"
        assert ev[0]["detail"]["why"] == "not_on_tpu"

    def test_kv_quantized_engine_is_attributed(self, model):
        """Building an int8-KV engine leaves a kv_quantized marker in
        the flight recorder and the doctor's kernel section/hints."""
        from paddle_tpu.profiler.explain import explain, REASON_HINTS
        prev = get_flags(["FLAGS_profiler_events"])
        set_flags({"FLAGS_profiler_events": True})
        clear_fusion_events()
        try:
            LLMEngine(model, max_batch_size=2, block_size=4,
                      kv_dtype="int8")
        finally:
            set_flags(prev)
        ev = fusion_events("kernel.quantized")
        assert any(e["reason"] == "kv_quantized" for e in ev)
        # the marker is informational: it must NOT pollute the fallback
        # (demotion) stream
        assert fusion_events("kernel.fallback") == []
        report = explain(fusion_events())
        assert "kernel" in report
        assert "kv_quantized" in report["kernel"]["reasons"]
        assert any("kv_quantized" in f for f in report["findings"])
        assert "kv_quantized" in REASON_HINTS
        assert "kernel_fallback" in REASON_HINTS

    def test_unknown_variant_rejected(self):
        with pytest.raises(ValueError, match="unknown paged attention"):
            resolve_paged_kernel("warp")
        assert set(PAGED_KERNELS) == {"pallas", "blockwise", "reference"}


# ---------------------------------------------------------------------------
# counted floors: what the traced program holds, how often it compiles
# ---------------------------------------------------------------------------

def _traced_shapes(fn, *args):
    """Shapes of every value the traced `fn(*args)` computes, loop and
    branch bodies included."""
    shapes = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            shapes.update(tuple(v.aval.shape) for v in eqn.outvars
                          if hasattr(v.aval, "shape"))
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return shapes


class TestCountedFloors:
    def test_blockwise_holds_no_dense_context_at_seq_1k(self):
        """The kernel tier's reason to exist, read from the traced
        programs (how fast each runs is the `serve_124m_backlog` cell's
        to say, on the chip): at seq 1k `reference` materializes the
        dense [S, T, H, D] context; `blockwise` computes no value of that
        size over the slots — its largest is one chunk of the table —
        and both agree to the parity tolerance."""
        S, H, D, bs, M = 8, 4, 32, 16, 64          # seq = 1024
        T = M * bs
        state = _paged_state(S=S, H=H, D=D, bs=bs, M=M,
                             lens=(1000,) * S, active=(True,) * S)
        q, kn, vn, kp, vp, tables, lens, active = state

        def program(kernel):
            def f(q, kn, vn, kp, vp):
                return paged_decode_attention(
                    q, kn, vn, kp, vp, LAYER, tables, lens, active, bs,
                    kernel=kernel)[0]
            return f

        def context_sized(shapes):
            # a value over the slots as large as all their contexts
            # (the pools are [LAYERS, num_blocks, ...]: not slot-major)
            return {sh for sh in shapes if sh and sh[0] == S
                    and int(np.prod(sh)) >= S * T * H * D}

        dense = _traced_shapes(program("reference"), q, kn, vn, kp, vp)
        block = _traced_shapes(program("blockwise"), q, kn, vn, kp, vp)
        assert (S, T, H, D) in context_sized(dense)
        widest = max(int(np.prod(sh)) for sh in block if sh and sh[0] == S)
        assert widest * 2 <= S * T * H * D, widest
        np.testing.assert_allclose(
            np.asarray(jax.jit(program("blockwise"))(q, kn, vn, kp, vp)),
            np.asarray(jax.jit(program("reference"))(q, kn, vn, kp, vp)),
            rtol=1e-5, atol=1e-5)

    def test_int8_decode_compiles_once_under_churn(self, model):
        """int8 KV is value edits + two extra donated side-tables —
        never a shape change: 24 churning streams, ONE decode trace."""
        prompts = [_prompt(3 + (i % 9), seed=40) for i in range(24)]
        engine = LLMEngine(model, max_batch_size=4, block_size=4,
                           kv_dtype="int8")
        engine.generate(prompts, max_new_tokens=5)
        st = engine.stats()
        assert st["decode_compiles"] == 1
        assert st["completed"] == 24
