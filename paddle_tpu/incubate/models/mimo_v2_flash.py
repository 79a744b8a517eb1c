"""MiMo-V2-Flash (decoder-only: window and full attention in one stack,
each kind with its own count of key/value heads and a cache of its own,
sparse experts), for serving.

Source: `config.json` (`model_type` `mimo_v2_flash`) of
huggingface.co/XiaomiMiMo/MiMo-V2-Flash. What differs from `gpt.py`,
`longcat_flash.py` and `lfm2_moe.py`:

  * a layer is ``h = x + attn(RMS(x)); y = h + ffn(RMS(h))`` and `attn` is
    of TWO KINDS (`layer_types`): FULL attention, causal over the whole
    context, `num_key_value_heads` (4) key/value heads; or WINDOW
    attention, position i seeing j with ``i - sliding_window < j <= i``,
    `swa_num_key_value_heads` (8) key/value heads and ONE LEARNED SINK a
    query head, a scalar that joins the softmax's denominator and adds no
    value: ``p_ij = exp(a_ij) / (exp(s_h) + sum_j exp(a_ij))``;
  * in both kinds q and k are `head_dim` (192) wide and v `v_head_dim`
    (128); rotary positions turn only the first
    ``round(partial_rotary_factor * head_dim)`` (64) values of each q and
    k head, halves rotated, at a base a kind (`rope_theta` full,
    `swa_rope_theta` window); v is scaled by `attention_value_scale`;
  * the two kinds keep different things of the past, and `cache_spec()`
    says so (serving/cache.py `CacheSpec`): full layers in the paged pool
    at rows of 4 x 192 and 4 x 128, window layers in a ring of blocks a
    slot at rows of 8 x 192 and 8 x 128 that holds the window and no
    more;
  * the first `first_k_dense_replace` layers' `ffn` is a dense SwiGLU,
    every other layer's an expert block that holds a SHARE of the
    experts: sigmoid scores over all `n_routed_experts`, the top
    `num_experts_per_tok` of score + bias chosen, weights normalised over
    the chosen, no shared expert; what a chosen expert held elsewhere
    would add is left out
    (`incubate/distributed/models/moe/held_experts.py`, which chooses the
    products' form from the call's shape);
  * RMSNorm, no bias anywhere, an untied head.

Serving only: no loss, no gradient path is kept (the forward is plain
`jax.numpy` over the parameters' values). `LLMEngine` reads of the class
what it reads of `Lfm2MoeForCausalLM`: `cache_spec()`,
`serve_weights_as_arguments` and `serve_counter_names`.
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

__all__ = ["MiMoV2FlashConfig", "MiMoV2FlashForCausalLM"]

FULL, WINDOW = "full_attention", "sliding_attention"


def _published_pattern():
    """`hybrid_layer_pattern` as `layer_types`: 0 full, 1 window."""
    pattern = [0, 1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0] * 7
    return tuple(WINDOW if kind else FULL for kind in pattern)


@dataclass
class MiMoV2FlashConfig:
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 48
    layer_types: tuple = field(default_factory=_published_pattern)
    first_k_dense_replace: int = 1
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    swa_num_key_value_heads: int = 8
    head_dim: int = 192
    v_head_dim: int = 128
    partial_rotary_factor: float = 0.334
    rope_theta: float = 5e6
    swa_rope_theta: float = 1e4
    sliding_window: int = 128
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    attention_value_scale: float = 0.707
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    layernorm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    # the experts THIS program holds: (first id, how many); None is all of
    # them. The router's width never follows it
    experts_held: tuple | None = None

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"{len(self.layer_types)} layer_types for "
                f"{self.num_hidden_layers} layers")
        if set(self.layer_types) - {FULL, WINDOW}:
            raise ValueError(f"layer_types {set(self.layer_types)}: "
                             f"{FULL!r} or {WINDOW!r}")
        if self.routed_scaling_factor is None:       # the source's null
            self.routed_scaling_factor = 1.0
        if self.rotary_dim % 2:
            raise ValueError(f"{self.rotary_dim} rotary values a head: "
                             "not whole pairs")

    @property
    def held(self):
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def rotary_dim(self):
        return round(self.partial_rotary_factor * self.head_dim)

    def kv_heads(self, kind):
        return self.swa_num_key_value_heads if kind == WINDOW \
            else self.num_key_value_heads

    def has_sink(self, kind):
        return self.add_swa_attention_sink_bias if kind == WINDOW \
            else self.add_full_attention_sink_bias


def param_shapes(cfg):
    """{name: shape} of the parameters, in the order the forward pass
    meets them (`benchmark/reference/mimo_v2_flash.py` states the same).
    Matrices are stored [in, out]; the experts stacked."""
    d, dk, dv = cfg.hidden_size, cfg.head_dim, cfg.v_head_dim
    h = cfg.num_attention_heads
    ff, fe = cfg.intermediate_size, cfg.moe_intermediate_size
    held = cfg.held[1]
    shapes = {"model.embed_tokens.weight": (cfg.vocab_size, d)}
    for i, kind in enumerate(cfg.layer_types):
        p = f"model.layers.{i}."
        kh = cfg.kv_heads(kind)
        a = p + "self_attn."
        shapes[p + "input_layernorm.weight"] = (d,)
        shapes.update({a + "q_proj.weight": (d, h * dk),
                       a + "k_proj.weight": (d, kh * dk),
                       a + "v_proj.weight": (d, kh * dv)})
        if cfg.has_sink(kind):
            shapes[a + "attention_sink_bias"] = (h,)
        shapes[a + "o_proj.weight"] = (h * dv, d)
        shapes[p + "post_attention_layernorm.weight"] = (d,)
        f = p + "mlp."
        if i < cfg.first_k_dense_replace:
            shapes.update({f + "gate_proj.weight": (d, ff),
                           f + "up_proj.weight": (d, ff),
                           f + "down_proj.weight": (ff, d)})
        else:
            shapes.update({f + "gate.weight": (d, cfg.n_routed_experts),
                           f + "experts.gate_proj.weight": (held, d, fe),
                           f + "experts.up_proj.weight": (held, d, fe),
                           f + "experts.down_proj.weight": (held, fe, d)})
    shapes["model.norm.weight"] = (d,)
    shapes["lm_head.weight"] = (d, cfg.vocab_size)
    return shapes


def expert_bias_name(layer):
    """The router's `e_score_correction_bias` of a layer (`noaux_tc`): a
    buffer, not a parameter (it moves the choice, never the weight)."""
    return f"model.layers.{layer}.mlp.gate.e_score_correction_bias"


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _rotate_part(x, pos, theta, rotary):
    """Rotary positions over the FIRST `rotary` values of x's last axis
    (``[B, T, heads, D]``), halves rotated: position t turns pair
    (i, i + rotary/2) by ``t * theta^(-2i / rotary)``; the other
    ``D - rotary`` values pass as they are. pos ``[B, T]`` int."""
    freq = jnp.float32(theta) ** (-jnp.arange(0, rotary, 2,
                                              dtype=jnp.float32) / rotary)
    ang = pos.astype(jnp.float32)[..., None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x[..., :rotary].astype(jnp.float32)
    a, b = x32[..., :rotary // 2], x32[..., rotary // 2:]
    turned = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)
    return jnp.concatenate([turned.astype(x.dtype), x[..., rotary:]], -1)


class MiMoV2FlashForCausalLM(Layer):
    """The whole model as one `Layer`: its parameters by the names of
    `param_shapes`, its forward in `jax.numpy`.

    `weights` ({name: array}) are taken as the parameters' values where
    given, so a chip-filling model is never initialised and then
    overwritten; otherwise each matrix is drawn N(0, `initializer_range`),
    norm scales 1, sinks 0."""

    # `LLMEngine` passes this model's weights to its programs as arguments
    serve_weights_as_arguments = True
    # what a forward through a cache leaves in `pop_serve_counters()`: the
    # expert blocks' counters, then the grouped products the forward ran
    # and those of them the tiled kernel ran (`held_experts.products_run`)
    serve_counter_names = COUNTERS + ("products", "kernel_products")

    def __init__(self, config: MiMoV2FlashConfig, weights=None):
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
                value = (jnp.zeros if name.endswith("sink_bias")
                         else jnp.ones)(shape, jnp.float32)
            else:
                value = jnp.asarray(rng.normal(
                    0.0, config.initializer_range, shape), jnp.float32)
            self._parameters[name] = Parameter(value, name=name)
        for i in range(config.first_k_dense_replace,
                       config.num_hidden_layers):
            self._buffers[expert_bias_name(i)] = Tensor(jnp.zeros(
                (config.n_routed_experts,), jnp.float32))
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
            kinds.count(FULL), cfg.num_key_value_heads, cfg.head_dim,
            value_dim=cfg.v_head_dim, query_heads=cfg.num_attention_heads,
            window_layers=kinds.count(WINDOW), window=cfg.sliding_window,
            window_parts=((cfg.swa_num_key_value_heads, cfg.head_dim),
                          (cfg.swa_num_key_value_heads, cfg.v_head_dim)))

    def pop_serve_counters(self):
        """The counters of the forward just traced, summed over the
        layers (int32 [len(serve_counter_names)])."""
        counters, self._counters = self._counters, None
        return counters

    def gen_caches(self, batch_size, dtype=None):
        """Dense caches with no token in them, in the order a forward takes
        them (`CacheSpec.empty_prefill`'s): a (keys, values) pair for each
        full layer, then one for each window layer."""
        cfg = self.config
        dtype = dtype or self._w("model.norm.weight").dtype
        out = []
        for kind in (FULL, WINDOW):
            kh = cfg.kv_heads(kind)
            pair = (Tensor(jnp.zeros((batch_size, 0, kh, cfg.head_dim),
                                     dtype)),
                    Tensor(jnp.zeros((batch_size, 0, kh, cfg.v_head_dim),
                                     dtype)))
            out += [pair] * cfg.layer_types.count(kind)
        return out

    # -- attention ------------------------------------------------------------
    def _attention(self, u, pos, p, kind, cache):
        """One layer's attention over u ``[B, T, d]``: through a
        `PagedCacheView` (a decode launch: the layer's own cache, by its
        kind), or dense, behind the (keys, values) of `cache` where
        given."""
        cfg = self.config
        h, kh = cfg.num_attention_heads, cfg.kv_heads(kind)
        dk, dv = cfg.head_dim, cfg.v_head_dim
        window = cfg.sliding_window if kind == WINDOW else None
        theta = cfg.swa_rope_theta if kind == WINDOW else cfg.rope_theta
        b, t, _ = u.shape
        q = (u @ self._w(p + "q_proj.weight")).reshape(b, t, h, dk)
        k = (u @ self._w(p + "k_proj.weight")).reshape(b, t, kh, dk)
        v = (u @ self._w(p + "v_proj.weight")).reshape(b, t, kh, dv)
        v = v * jnp.asarray(cfg.attention_value_scale, v.dtype)
        q = _rotate_part(q, pos, theta, cfg.rotary_dim)
        k = _rotate_part(k, pos, theta, cfg.rotary_dim)
        sink = self._w(p + "attention_sink_bias").astype(jnp.float32) \
            if cfg.has_sink(kind) else None
        if cache is not None and hasattr(cache, "block_tables"):
            from ...nn.functional.attention import (
                paged_banded_decode_attention)
            # the layer's own cache, by its kind: the paged pools behind
            # the slots' tables, or the rings (no table: a slot's own)
            if window is None:
                pools, layer, tables, name = (
                    (cache.k_pools, cache.v_pools), cache.layer,
                    cache.block_tables, "full_decode_attention")
            else:
                pools, layer, tables, name = (
                    cache.window_pools, cache.window_layer, None,
                    "window_decode_attention")
            o, *pools = paged_banded_decode_attention(
                q, k, v, *pools, layer, tables, cache.seq_lens, cache.active,
                cache.block_size, window=window, sink=sink,
                kernel=cache.kernel, name=name)
            cache = cache.updated(*pools) if window is None \
                else cache.updated(window_pools=tuple(pools))
        else:
            past = 0
            if cache is not None:
                past = cache[0].shape[1]
                k = jnp.concatenate([cache[0]._value.astype(k.dtype), k], 1)
                v = jnp.concatenate([cache[1]._value.astype(v.dtype), v], 1)
                cache = (Tensor(k), Tensor(v))
            o = self._causal(q, k, v, past, window, sink)
        return o.reshape(b, t, h * dv) @ self._w(p + "o_proj.weight"), cache

    def _causal(self, q, k, v, past, window, sink):
        """Causal attention of q ``[B, T, H, Dk]`` over k ``[B, total, KH,
        Dk]``, v ``[B, total, KH, Dv]``, `past` rows of which precede q's
        own; inside a band of `window` where given, with `sink` ``[H]`` in
        the denominator where given. A prompt of whole tiles on a TPU goes
        through the flash kernels: a window layer through the BAND kernel,
        which copies two blocks of keys a block of queries whatever the
        prompt (kernels/flash_attention.py), a full layer through the
        causal kernel, its key/value heads repeated in front of it. Else
        one ``[B, KH, group, T, total]`` array of scores."""
        from ...kernels import flash_attention as fa
        b, t, h, dk = q.shape
        kh = k.shape[2]
        group = h // kh
        scale = 1.0 / math.sqrt(dk)
        if past == 0 and window is not None \
                and fa.is_band_eligible(q, k, v, window):
            return fa.flash_band_attention_bnhd(q, k, v, window, sink, scale)
        if past == 0 and window is None and sink is None \
                and fa.is_eligible(q, k, v, None, 0.0, is_causal=True):
            with jax.named_scope("prefill_flash_attention"):
                return fa.flash_attention_bnhd(
                    q, jnp.repeat(k, group, axis=2),
                    jnp.repeat(v, group, axis=2), True, scale)
        total = k.shape[1]
        s = jnp.einsum("bqkgd,btkd->bkgqt", q.reshape(b, t, kh, group, dk),
                       k, preferred_element_type=jnp.float32) * scale
        at = past + jnp.arange(t)[:, None]
        keys = jnp.arange(total)[None, :]
        keep = keys <= at
        if window is not None:
            keep = keep & (at - keys < window)
        s = jnp.where(keep, s, -jnp.inf)
        if sink is not None:
            s = jnp.concatenate([s, jnp.broadcast_to(
                sink.reshape(1, kh, group, 1, 1), s.shape[:-1] + (1,))], -1)
        prob = jax.nn.softmax(s, axis=-1)[..., :total]
        o = jnp.einsum("bkgqt,btkd->bqkgd", prob, v,
                       preferred_element_type=jnp.float32)
        return o.reshape(b, t, h, -1).astype(v.dtype)

    # -- the model ------------------------------------------------------------
    def forward(self, input_ids, position_ids=None, caches=None,
                valid=None):
        """Logits ``[B, T, vocabulary]`` of ids ``[B, T]``; with `caches`
        (a `PagedCacheView` in a list, or `gen_caches`' layout) also the
        caches after the call. `valid` ``[B, T]`` bool marks the prompt
        inside its bucket (a prefix of each row): it keeps padding out of
        the expert blocks' counters and products (the logits at valid
        positions do not depend on it)."""
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
        x = self._w("model.embed_tokens.weight")[ids]
        view = caches[0] if paged else None
        # the dense caches after the call, by kind (`gen_caches`' layout:
        # the full layers' pairs, then the window layers')
        n_full = cfg.layer_types.count(FULL)
        pairs = {FULL: [], WINDOW: []}
        counters = jnp.zeros(len(COUNTERS), jnp.int32)
        products = np.zeros(2, np.int32)
        first, held = cfg.held
        for i, kind in enumerate(cfg.layer_types):
            p = f"model.layers.{i}."
            u = _rms(x, self._w(p + "input_layernorm.weight"),
                     cfg.layernorm_epsilon)
            if paged:
                cache = view
            elif caches is None:
                cache = None
            else:
                cache = caches[len(pairs[kind]) + (n_full if kind == WINDOW
                                                   else 0)]
            with jax.named_scope("window_attention" if kind == WINDOW
                                 else "full_attention"):
                a, cache = self._attention(u, pos, p + "self_attn.", kind,
                                           cache)
            if paged:
                view = cache
            else:
                pairs[kind].append(cache)
            x = x + a
            u = _rms(x, self._w(p + "post_attention_layernorm.weight"),
                     cfg.layernorm_epsilon)
            f = p + "mlp."
            if i < cfg.first_k_dense_replace:
                x = x + _swiglu(u, self._w(f + "gate_proj.weight"),
                                self._w(f + "up_proj.weight"),
                                self._w(f + "down_proj.weight"))
                continue
            with jax.named_scope("held_experts"):
                m, counted = held_expert_block(
                    u.reshape(b * t, -1), self._w(f + "gate.weight"),
                    self.expert_bias(i)._value,
                    self._w(f + "experts.gate_proj.weight"),
                    self._w(f + "experts.up_proj.weight"),
                    self._w(f + "experts.down_proj.weight"),
                    topk=cfg.num_experts_per_tok,
                    real_experts=cfg.n_routed_experts,
                    scaling=cfg.routed_scaling_factor, first_held=first,
                    valid=jnp.reshape(valid, (b * t,)), scoring="sigmoid",
                    normalise=cfg.norm_topk_prob)
            counters = counters + counted
            products += products_run(
                b * t, cfg.num_experts_per_tok, held, cfg.hidden_size,
                cfg.moe_intermediate_size, u.dtype)
            x = x + m.reshape(b, t, -1).astype(x.dtype)
        x = _rms(x, self._w("model.norm.weight"), cfg.layernorm_epsilon)
        with jax.named_scope("lm_head"):
            logits = Tensor(x @ self._w("lm_head.weight"))
        self._counters = jnp.concatenate([counters, jnp.asarray(products)])
        if caches is None:
            return logits
        return logits, ([view] if paged else pairs[FULL] + pairs[WINDOW])

    def generate(self, input_ids, max_new_tokens=32, do_sample=False):
        """Greedy continuation, a token at a time through the dense caches
        (the engine's degraded-mode fallback; no compiled loop)."""
        if do_sample:
            raise ValueError("MiMoV2FlashForCausalLM.generate is greedy")
        ids = jnp.asarray(getattr(input_ids, "_value", input_ids))
        logits, caches = self(ids, caches=self.gen_caches(ids.shape[0]))
        out = []
        for _ in range(int(max_new_tokens)):
            nxt = jnp.argmax(logits._value[:, -1], -1).astype(ids.dtype)
            out.append(nxt)
            logits, caches = self(nxt[:, None], caches=caches)
        return Tensor(jnp.stack(out, axis=1))
