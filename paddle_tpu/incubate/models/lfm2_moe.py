"""LFM2-MoE (decoder-only: gated short convolutions and grouped-query
attention in one stack, sparse experts), for serving.

Source: `config.json` (`model_type` `lfm2_moe`) and `modeling_lfm2_moe.py`
of huggingface.co/LiquidAI/LFM2-8B-A1B. What differs from `gpt.py` and
`longcat_flash.py`:

  * a layer is ``h = h + op(RMS(h)); h = h + ffn(RMS(h))`` and `op` is of
    TWO KINDS (`layer_types`): a GATED SHORT CONVOLUTION, ``[B | C | x] =
    u W_in``, ``z = B * x``, a causal depthwise convolution of kernel
    `conv_L_cache` over z, ``(C * conv) W_out``; or ATTENTION;
  * a convolution layer is not attention and still keeps something of the
    past: the last ``conv_L_cache - 1`` values of z, a fixed block a
    sequence. `cache_spec()` describes it as the per-slot state beside
    the paged pool (serving/cache.py `CacheSpec`): a prefill hands the
    state back as it stands after the prompt's true length, a decode
    launch shifts it where it lies;
  * attention has FEWER key/value heads than query heads
    (`num_key_value_heads`): the pool's row is the key/value heads', query
    head i reads key/value head ``i // group``; an RMS norm over each head
    of q and of k with one learned scale; rotary positions over the whole
    head, halves rotated;
  * the first `num_dense_layers` layers' `ffn` is a dense SwiGLU, every
    other layer's an expert block that holds EVERY expert: sigmoid scores,
    the top `num_experts_per_tok` of score + bias, weights normalised over
    the chosen (`incubate/distributed/models/moe/held_experts.py`, which
    chooses the products' form from the call's shape);
  * RMSNorm, no bias anywhere, the head tied to the embedding.

Serving only: no loss, no gradient path is kept (the forward is plain
`jax.numpy` over the parameters' values). `LLMEngine` reads of the class
what it reads of `LongCatFlashForCausalLM`: `cache_spec()`,
`serve_weights_as_arguments` (10.8 GB of weights cannot be compiled into
five programs as constants) and `serve_counter_names`.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np
import jax
import jax.numpy as jnp

from ...nn.layer_base import Layer
from ...framework.core import Tensor, Parameter
from ...incubate.distributed.models.moe.held_experts import (
    held_expert_block, products_run, COUNTERS)
from .mla import rms as _rms

__all__ = ["Lfm2MoeConfig", "Lfm2MoeForCausalLM"]

CONV, ATTENTION = "conv", "full_attention"


@dataclass
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    layer_types: tuple = field(default_factory=lambda: tuple(
        ([CONV, CONV, ATTENTION] + [CONV, CONV, CONV, ATTENTION] * 4
         + [CONV, CONV, ATTENTION, CONV, CONV])[:24]))
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    router_epsilon: float = 1e-6
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    max_position_embeddings: int = 128000
    initializer_range: float = 0.02
    # the experts THIS program holds: (first id, how many); None is all of
    # them (the served configuration). The router's width never follows it
    experts_held: tuple | None = None

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"{len(self.layer_types)} layer_types for "
                f"{self.num_hidden_layers} layers")
        if set(self.layer_types) - {CONV, ATTENTION}:
            raise ValueError(f"layer_types {set(self.layer_types)}: "
                             f"{CONV!r} or {ATTENTION!r}")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def held(self):
        return self.experts_held or (0, self.num_experts)

    @property
    def conv_state(self):
        """What a convolution layer keeps of a sequence, as a trailing
        shape: the last ``conv_L_cache - 1`` values of z, side by side in
        ONE row (whole lane tiles a slot: as ``(2, 2048)`` the two rows
        would be a sublane tile's 16, and the TPU's compiler gives a
        donated argument of that shape another layout than the host's,
        tests/test_tpu_compile.py)."""
        return ((self.conv_L_cache - 1) * self.hidden_size,)


def param_shapes(cfg):
    """{name: shape} of the parameters, in the order the forward pass
    meets them (`benchmark/reference/lfm2_moe.py` states the same).
    Matrices are stored [in, out]; the experts stacked."""
    d, hd = cfg.hidden_size, cfg.head_dim
    h, kh = cfg.num_attention_heads, cfg.num_key_value_heads
    ff, fe = cfg.intermediate_size, cfg.moe_intermediate_size
    held = cfg.held[1]
    shapes = {"model.embed_tokens.weight": (cfg.vocab_size, d)}
    for i, kind in enumerate(cfg.layer_types):
        p = f"model.layers.{i}."
        shapes[p + "operator_norm.weight"] = (d,)
        if kind == CONV:
            shapes.update({
                p + "conv.in_proj.weight": (d, 3 * d),
                p + "conv.conv.weight": (d, cfg.conv_L_cache),
                p + "conv.out_proj.weight": (d, d)})
        else:
            a = p + "self_attn."
            shapes.update({
                a + "q_proj.weight": (d, h * hd),
                a + "k_proj.weight": (d, kh * hd),
                a + "v_proj.weight": (d, kh * hd),
                a + "q_layernorm.weight": (hd,),
                a + "k_layernorm.weight": (hd,),
                a + "out_proj.weight": (h * hd, d)})
        shapes[p + "ffn_norm.weight"] = (d,)
        f = p + "feed_forward."
        if i < cfg.num_dense_layers:
            shapes.update({f + "w1.weight": (d, ff), f + "w3.weight": (d, ff),
                           f + "w2.weight": (ff, d)})
        else:
            shapes.update({f + "gate.weight": (d, cfg.num_experts),
                           f + "experts.w1.weight": (held, d, fe),
                           f + "experts.w3.weight": (held, d, fe),
                           f + "experts.w2.weight": (held, fe, d)})
    shapes["model.embedding_norm.weight"] = (d,)
    return shapes


def expert_bias_name(layer):
    """The router's `expert_bias` of a layer: a buffer, not a parameter
    (it moves the choice, never the weight, and the source moves it only
    while training)."""
    return f"model.layers.{layer}.feed_forward.expert_bias"


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def _rotate_half(x, pos, theta):
    """Rotary positions over the whole last axis of x ``[B, T, heads, r]``,
    halves rotated: ``x cos + (-x2 | x1) sin`` with position t turning
    pair (i, i + r/2) by ``t * theta^(-2i / r)``; pos ``[B, T]`` int."""
    r = x.shape[-1]
    freq = jnp.float32(theta) ** (-jnp.arange(0, r, 2, dtype=jnp.float32)
                                  / r)
    ang = pos.astype(jnp.float32)[..., None, None] * freq  # [B, T, 1, r/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :r // 2], x32[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


def grouped_causal_attention(q, k, v, past):
    """Causal attention of q ``[B, T, H, D]`` over k, v ``[B, total,
    KH, D]``, `past` rows of which precede q's own: a prompt of whole
    tiles on a TPU through the flash kernel, the key/value heads
    repeated in front of it (8 MB a layer at 2,048 tokens; the kernel
    file is not touched, so no other model's step lowers anew), else
    one ``[B, H, T, total]`` array of scores a group of queries."""
    from ...kernels import flash_attention as fa
    b, t, h, hd = q.shape
    kh = k.shape[2]
    group = h // kh
    if past == 0 and fa.is_eligible(q, q, q, None, 0.0, is_causal=True):
        with jax.named_scope("prefill_flash_attention"):
            return fa.flash_attention_bnhd(
                q, jnp.repeat(k, group, axis=2),
                jnp.repeat(v, group, axis=2), True,
                1.0 / math.sqrt(hd))
    total = k.shape[1]
    s = jnp.einsum("bqkgd,btkd->bkgqt", q.reshape(b, t, kh, group, hd),
                   k, preferred_element_type=jnp.float32) \
        / math.sqrt(hd)
    keep = jnp.arange(total)[None, :] <= past + jnp.arange(t)[:, None]
    prob = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bkgqt,btkd->bqkgd", prob, v,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, t, h, hd).astype(v.dtype)


class Lfm2MoeForCausalLM(Layer):
    """The whole model as one `Layer`: its parameters by the names of
    `param_shapes`, its forward in `jax.numpy`.

    `weights` ({name: array}) are taken as the parameters' values where
    given, so a chip-filling model is never initialised and then
    overwritten (both would not fit); otherwise each is drawn
    N(0, `initializer_range`), norm scales 1."""

    # `LLMEngine` passes this model's weights to its programs as arguments
    serve_weights_as_arguments = True
    # what a forward through a cache leaves in `pop_serve_counters()`: the
    # expert blocks' counters, then the grouped products the forward ran
    # and those of them the tiled kernel ran (constants of the program:
    # `held_experts.products_run`)
    serve_counter_names = COUNTERS + ("products", "kernel_products")

    def __init__(self, config: Lfm2MoeConfig, weights=None):
        super().__init__()
        self.config = config
        shapes = param_shapes(config)
        if weights is not None and set(weights) != set(shapes):
            raise ValueError("weights do not name the model's parameters: "
                             f"{sorted(set(weights) ^ set(shapes))[:6]}")
        rng = np.random.default_rng(0)
        for name, shape in shapes.items():
            if weights is not None:
                value = weights[name]
                if tuple(value.shape) != tuple(shape):
                    raise ValueError(f"{name}: got {tuple(value.shape)}, "
                                     f"the model has {tuple(shape)}")
            elif len(shape) == 1:
                value = jnp.ones(shape, jnp.float32)
            else:
                value = jnp.asarray(rng.normal(
                    0.0, config.initializer_range, shape), jnp.float32)
            # kept under its dotted name: `named_parameters()` then
            # yields the reference's names as they are
            self._parameters[name] = Parameter(value, name=name)
        for i in range(config.num_dense_layers, config.num_hidden_layers):
            self._buffers[expert_bias_name(i)] = Tensor(jnp.zeros(
                (config.num_experts,), jnp.float32))
        self._counters = None

    def _w(self, name):
        return self._parameters[name]._value

    def expert_bias(self, layer):
        return self._buffers[expert_bias_name(layer)]

    # -- what the engine reads ------------------------------------------------
    def cache_spec(self):
        from ...serving.cache import CacheSpec
        cfg = self.config
        kinds = cfg.layer_types
        return CacheSpec.per_head(
            kinds.count(ATTENTION), cfg.num_key_value_heads, cfg.head_dim,
            query_heads=cfg.num_attention_heads,
            state_layers=kinds.count(CONV),
            state_parts=(("conv", cfg.conv_state, None),))

    def pop_serve_counters(self):
        """The counters of the forward just traced, summed over the
        layers (int32 [len(serve_counter_names)])."""
        counters, self._counters = self._counters, None
        return counters

    def gen_caches(self, batch_size, dtype=None):
        """Dense caches with no token in them, in the order a forward takes
        them: a (keys, values) pair for each attention layer, then the
        state's one part (zeros: what lies before a sequence), in a
        tuple, for each convolution layer."""
        cfg = self.config
        dtype = dtype or self._w("model.embedding_norm.weight").dtype
        kinds = cfg.layer_types
        empty = Tensor(jnp.zeros(
            (batch_size, 0, cfg.num_key_value_heads, cfg.head_dim), dtype))
        state = Tensor(jnp.zeros((batch_size,) + cfg.conv_state, dtype))
        return [(empty, empty)] * kinds.count(ATTENTION) \
            + [(state,)] * kinds.count(CONV)

    # -- the two kinds of `op` ------------------------------------------------
    @jax.named_scope("short_conv")
    def _short_conv(self, u, p, state, length):
        """The gated short convolution over u ``[B, T, d]`` behind `state`
        ``[B, (L - 1) * d]``, the z of the L - 1 positions before u side
        by side, oldest first (zeros before a sequence). Returns (out
        ``[B, T, d]``, the state after `length` ``[B]`` of u's positions:
        behind a bucket's padding the state is taken where the prompt
        ends)."""
        keep = self.config.conv_L_cache - 1
        b, t, d = u.shape
        gates = u @ self._w(p + "in_proj.weight")
        z = gates[..., :d] * gates[..., 2 * d:]
        zz = jnp.concatenate([state.reshape(b, keep, d).astype(z.dtype), z],
                             axis=1)
        w = self._w(p + "conv.weight")                       # [d, L]
        c = sum(w[:, j] * zz[:, j:j + t] for j in range(keep + 1))
        out = (gates[..., d:2 * d] * c) @ self._w(p + "out_proj.weight")
        # positions length - keep .. length - 1 of u lie at length ..
        # length + keep - 1 of zz
        at = length[:, None] + jnp.arange(keep, dtype=jnp.int32)[None, :]
        return out, jnp.take_along_axis(zz, at[:, :, None],
                                        axis=1).reshape(b, keep * d)

    @jax.named_scope("short_conv")
    def _paged_conv(self, u, p, view):
        """One token a slot behind the slot's state where it lies: the
        state of an ACTIVE slot is shifted by the token's z, an inactive
        slot's stays (its output is garbage by design). `_short_conv`'s
        mathematics over ``[S, .]`` rows, slots on the sublanes and values
        on the lanes as the state itself lies: through arrays of ``[S, 1,
        d]`` the TPU's compiler turns the whole donated state into another
        layout and back, every launch (tests/test_tpu_compile.py)."""
        keep = self.config.conv_L_cache - 1
        d = u.shape[-1]
        (states,), layer = view.slot_state, view.state_layer
        state = states[layer]                            # [S, (L-1) * d]
        gates = u[:, 0] @ self._w(p + "in_proj.weight")
        z = gates[:, :d] * gates[:, 2 * d:]
        past = state.astype(z.dtype)
        w = self._w(p + "conv.weight")                       # [d, L]
        c = w[:, keep] * z + sum(w[:, j] * past[:, j * d:(j + 1) * d]
                                 for j in range(keep))
        out = (gates[:, d:2 * d] * c) @ self._w(p + "out_proj.weight")
        new = jnp.concatenate([past[:, d:], z], axis=-1).astype(state.dtype)
        new = jnp.where(view.active[:, None], new, state)
        return out[:, None], view.updated(
            slot_state=(states.at[layer].set(new),))

    def _attention(self, u, pos, p, cache):
        cfg = self.config
        h, kh, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        b, t, _ = u.shape
        q = (u @ self._w(p + "q_proj.weight")).reshape(b, t, h, hd)
        k = (u @ self._w(p + "k_proj.weight")).reshape(b, t, kh, hd)
        v = (u @ self._w(p + "v_proj.weight")).reshape(b, t, kh, hd)
        q = _rotate_half(_rms(q, self._w(p + "q_layernorm.weight"),
                              cfg.norm_eps), pos, cfg.rope_theta)
        k = _rotate_half(_rms(k, self._w(p + "k_layernorm.weight"),
                              cfg.norm_eps), pos, cfg.rope_theta)
        if cache is not None and hasattr(cache, "block_tables"):
            from ...nn.functional.attention import paged_decode_attention
            o, k_pools, v_pools = paged_decode_attention(
                q, k, v, cache.k_pools, cache.v_pools, cache.layer,
                cache.block_tables, cache.seq_lens, cache.active,
                cache.block_size, kernel=cache.kernel)
            cache = cache.updated(k_pools, v_pools)
        else:
            past = 0
            if cache is not None:
                past = cache[0].shape[1]
                k = jnp.concatenate([cache[0]._value.astype(k.dtype), k], 1)
                v = jnp.concatenate([cache[1]._value.astype(v.dtype), v], 1)
                cache = (Tensor(k), Tensor(v))
            o = grouped_causal_attention(q, k, v, past)
        return o.reshape(b, t, h * hd) @ self._w(p + "out_proj.weight"), \
            cache

    # -- the model ------------------------------------------------------------
    def forward(self, input_ids, position_ids=None, caches=None,
                valid=None):
        """Logits ``[B, T, vocabulary]`` of ids ``[B, T]``; with `caches`
        (a `PagedCacheView` in a list, or `gen_caches`' layout: a (keys,
        values) pair for each attention layer, then a state, its one part
        in a tuple, for each convolution layer) also the caches after the
        call. `valid`
        ``[B, T]`` bool marks the prompt inside its bucket (a prefix of
        each row): it keeps padding out of the expert blocks' counters,
        and the convolutions' states are taken where it ends (the logits
        at valid positions do not depend on it)."""
        cfg = self.config
        ids = jnp.asarray(getattr(input_ids, "_value", input_ids))
        b, t = ids.shape
        paged = caches is not None and hasattr(caches[0], "block_tables")
        if position_ids is not None:
            pos = jnp.asarray(getattr(position_ids, "_value", position_ids))
        elif paged:
            lens = caches[0].seq_lens
            pos = jnp.asarray(getattr(lens, "_value", lens)).astype(
                jnp.int32)[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
        else:
            past = caches[0][0].shape[1] if caches is not None else 0
            pos = jnp.broadcast_to(
                past + jnp.arange(t, dtype=jnp.int32)[None], (b, t))
        if paged:
            valid = caches[0].active[:, None] if valid is None else valid
        elif valid is None:
            valid = jnp.ones((b, t), bool)
        length = jnp.sum(valid, axis=1, dtype=jnp.int32)
        x = self._w("model.embed_tokens.weight")[ids]
        view = caches[0] if paged else None
        n_attn = cfg.layer_types.count(ATTENTION)
        pairs, states = [], []
        counters = jnp.zeros(len(COUNTERS), jnp.int32)
        products = np.zeros(2, np.int32)
        first, held = cfg.held
        for i, kind in enumerate(cfg.layer_types):
            p = f"model.layers.{i}."
            u = _rms(x, self._w(p + "operator_norm.weight"), cfg.norm_eps)
            if kind == CONV:
                if paged:
                    a, view = self._paged_conv(u, p + "conv.", view)
                else:
                    state = (jnp.zeros((b,) + cfg.conv_state, u.dtype)
                             if caches is None
                             else caches[n_attn + len(states)][0]._value)
                    a, state = self._short_conv(u, p + "conv.", state,
                                                length)
                    states.append((Tensor(state),))
            else:
                cache = view if paged else None if caches is None \
                    else caches[len(pairs)]
                a, cache = self._attention(u, pos, p + "self_attn.", cache)
                if paged:
                    view = cache
                else:
                    pairs.append(cache)
            x = x + a
            u = _rms(x, self._w(p + "ffn_norm.weight"), cfg.norm_eps)
            f = p + "feed_forward."
            if i < cfg.num_dense_layers:
                x = x + _swiglu(u, self._w(f + "w1.weight"),
                                self._w(f + "w3.weight"),
                                self._w(f + "w2.weight"))
                continue
            with jax.named_scope("held_experts"):
                m, counted = held_expert_block(
                    u.reshape(b * t, -1), self._w(f + "gate.weight"),
                    self.expert_bias(i)._value if cfg.use_expert_bias
                    else None,
                    self._w(f + "experts.w1.weight"),
                    self._w(f + "experts.w3.weight"),
                    self._w(f + "experts.w2.weight"),
                    topk=cfg.num_experts_per_tok,
                    real_experts=cfg.num_experts,
                    scaling=cfg.routed_scaling_factor, first_held=first,
                    valid=jnp.reshape(valid, (b * t,)), scoring="sigmoid",
                    normalise=cfg.norm_topk_prob,
                    epsilon=cfg.router_epsilon)
            counters = counters + counted
            products += products_run(
                b * t, cfg.num_experts_per_tok, held, cfg.hidden_size,
                cfg.moe_intermediate_size, u.dtype)
            x = x + m.reshape(b, t, -1).astype(x.dtype)
        x = _rms(x, self._w("model.embedding_norm.weight"), cfg.norm_eps)
        with jax.named_scope("lm_head"):
            # the head is the embedding's own matrix
            logits = Tensor(jnp.einsum(
                "btd,vd->btv", x, self._w("model.embed_tokens.weight")))
        self._counters = jnp.concatenate([counters, jnp.asarray(products)])
        if caches is None:
            return logits
        return logits, ([view] if paged else pairs + states)

    def generate(self, input_ids, max_new_tokens=32, do_sample=False):
        """Greedy continuation, a token at a time through the dense caches
        (the engine's degraded-mode fallback; no compiled loop)."""
        if do_sample:
            raise ValueError("Lfm2MoeForCausalLM.generate is greedy")
        ids = jnp.asarray(getattr(input_ids, "_value", input_ids))
        logits, caches = self(ids, caches=self.gen_caches(ids.shape[0]))
        out = []
        for _ in range(int(max_new_tokens)):
            nxt = jnp.argmax(logits._value[:, -1], -1).astype(ids.dtype)
            out.append(nxt)
            logits, caches = self(nxt[:, None], caches=caches)
        return Tensor(jnp.stack(out, axis=1))
