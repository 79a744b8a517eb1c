"""LongCat-Flash (decoder-only, shortcut-connected experts, latent attention),
for serving.

Source: `config.json` and `modeling_longcat_flash.py` of
huggingface.co/meituan-longcat/LongCat-Flash-Chat, and the LongCat-Flash
technical report (arXiv:2509.01322). What differs from `gpt.py`:

  * a LAYER is two attention sublayers and two dense SwiGLU FFNs with ONE
    expert block between them: the block reads the first sublayer's
    normed activations and its result is added at the layer's END (the
    shortcut), so nothing between depends on it;
  * attention is multi-head LATENT attention: queries through a low-rank
    bottleneck, keys and values expanded from one compressed row
    (`kv_lora_rank` values) a token, plus ONE rotary key of
    `qk_rope_head_dim` values that all heads share (`mla.py`, shared with
    the models that train it). Only that row is cached. Prefill expands it
    into per-head keys and values; decode
    against the paged cache runs ABSORBED: a head's query is carried into
    the row's space, attends over the rows, and its output is expanded by
    the value half of the expansion, so no key or value of any head is made
    for a cached token;
  * the expert block routes over `n_routed_experts` real and
    `zero_expert_num` identity experts (a scale of the token, no matrix
    product) and holds a SHARE of the real ones (`experts_held`): see
    `incubate/distributed/models/moe/held_experts.py`;
  * RMSNorm, rotary positions (pairs interleaved), an untied head, no bias.

Serving only: no loss, no gradient path is kept (the forward is plain
`jax.numpy` over the parameters' values). `LLMEngine` reads two facts of
the class: `cache_spec()`, from which the cache manager builds the latent
pool, and `serve_weights_as_arguments`: 10 GB of weights cannot be compiled
into five programs as constants.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp

from ...nn.layer_base import Layer
from ...framework.core import Tensor, Parameter
from ...incubate.distributed.models.moe.held_experts import (
    held_expert_block, COUNTERS)
from . import mla
from .mla import rms as _rms

__all__ = ["LongCatFlashConfig", "LongCatFlashForCausalLM"]

# a latent row in the paged pool: the compressed row and the rotary key
# side by side in ONE pool, padded to whole 128-lane tiles (PERF.md
# section 4 has the chip readings against a pool for each part). The
# blockwise loop over such rows: chunks of 192 tokens at widths down to
# 32 slots, so that the few longest contexts are not read for 64 slots
# (a 32-slot chunk is then as many bytes as a 64-slot chunk of the
# plan's own 96 tokens, a size this chip has run)
LATENT_TILE = 128
LATENT_CHUNK_TOKENS = 192
LATENT_MIN_WIDTH_SLOTS = 32


@dataclass
class LongCatFlashConfig:
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    n_routed_experts: int = 512        # real experts the router ranks
    zero_expert_num: int = 256         # identity experts ranked beside them
    moe_topk: int = 12
    routed_scaling_factor: float = 6.0
    rope_theta: float = 1e7
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    # the real experts THIS program holds: (first id, how many); None is
    # all of them. The router's width never follows it
    experts_held: tuple | None = None

    @property
    def held(self):
        return self.experts_held or (0, self.n_routed_experts)


def param_shapes(cfg):
    """{name: shape} of the parameters, in the order the forward pass
    meets them (`benchmark/reference/longcat_flash.py` states the same)."""
    d, h = cfg.hidden_size, cfg.num_attention_heads
    ff, fe = cfg.ffn_hidden_size, cfg.expert_ffn_hidden_size
    held = cfg.held[1]
    shapes = {"model.embed_tokens.weight": (cfg.vocab_size, d)}
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        for j in (0, 1):
            a = f"{p}self_attn.{j}."
            shapes.update({
                f"{p}input_layernorm.{j}.weight": (d,),
                a + "q_a_proj.weight": (d, cfg.q_lora_rank),
                a + "q_a_layernorm.weight": (cfg.q_lora_rank,),
                a + "q_b_proj.weight": (
                    cfg.q_lora_rank,
                    h * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)),
                a + "kv_a_proj_with_mqa.weight": (
                    d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
                a + "kv_a_layernorm.weight": (cfg.kv_lora_rank,),
                a + "kv_b_proj.weight": (
                    cfg.kv_lora_rank,
                    h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                a + "o_proj.weight": (h * cfg.v_head_dim, d),
                f"{p}post_attention_layernorm.{j}.weight": (d,),
            })
            if j == 0:
                shapes.update({
                    p + "mlp.router.classifier.weight": (
                        d, cfg.n_routed_experts + cfg.zero_expert_num),
                    p + "mlp.experts.gate_proj.weight": (held, d, fe),
                    p + "mlp.experts.up_proj.weight": (held, d, fe),
                    p + "mlp.experts.down_proj.weight": (held, fe, d),
                })
            m = f"{p}mlps.{j}."
            shapes.update({m + "gate_proj.weight": (d, ff),
                           m + "up_proj.weight": (d, ff),
                           m + "down_proj.weight": (ff, d)})
    shapes.update({"model.norm.weight": (d,), "lm_head.weight":
                   (d, cfg.vocab_size)})
    return shapes


def router_bias_name(layer):
    """The router's `e_score_correction_bias` of a layer: a buffer, not a
    parameter (the source registers it as one, zeros, and moves it only
    while training)."""
    return f"model.layers.{layer}.mlp.router.e_score_correction_bias"


def _swiglu(x, gate_w, up_w, down_w):
    return (jax.nn.silu(x @ gate_w) * (x @ up_w)) @ down_w


class LongCatFlashForCausalLM(Layer):
    """The whole model as one `Layer`: its parameters by the names of
    `param_shapes`, its forward in `jax.numpy`.

    `weights` ({name: array}) are taken as the parameters' values where
    given, so a chip-filling model is never initialised and then
    overwritten (both would not fit); otherwise each is drawn
    N(0, `initializer_range`), norm scales 1."""

    # `LLMEngine` passes this model's weights to its programs as arguments
    serve_weights_as_arguments = True
    # what a forward through a cache leaves in `pop_serve_counters()`
    serve_counter_names = COUNTERS

    def __init__(self, config: LongCatFlashConfig, weights=None):
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
        for i in range(config.num_layers):
            self._buffers[router_bias_name(i)] = Tensor(jnp.zeros(
                (config.n_routed_experts + config.zero_expert_num,),
                jnp.float32))
        self._counters = None

    def _w(self, name):
        return self._parameters[name]._value

    def router_bias(self, layer):
        return self._buffers[router_bias_name(layer)]

    # -- what the engine reads ------------------------------------------------
    def cache_spec(self):
        from ...serving.cache import CacheSpec
        cfg = self.config
        row = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        padded = -(-row // LATENT_TILE) * LATENT_TILE
        return CacheSpec(
            "latent", 2 * cfg.num_layers,
            ((cfg.kv_lora_rank,), (cfg.qk_rope_head_dim,)), (padded,),
            num_heads=1, head_dim=padded,
            chunk_tokens=LATENT_CHUNK_TOKENS,
            min_width_slots=LATENT_MIN_WIDTH_SLOTS)

    def pop_serve_counters(self):
        """The expert blocks' counters of the forward just traced, summed
        over the layers (int32 [len(serve_counter_names)])."""
        counters, self._counters = self._counters, None
        return counters

    def gen_caches(self, batch_size, dtype=None):
        cfg = self.config
        dtype = dtype or self._w("model.norm.weight").dtype
        return [(Tensor(jnp.zeros((batch_size, 0, cfg.kv_lora_rank), dtype)),
                 Tensor(jnp.zeros((batch_size, 0, cfg.qk_rope_head_dim),
                                  dtype)))
                for _ in range(2 * cfg.num_layers)]

    # -- attention ----------------------------------------------------------
    def _leaf(self, prefix):
        """A sublayer's weights by the leaf's name, for `mla`."""
        return lambda leaf: self._w(prefix + leaf)

    def _absorbed(self, q_nope, q_rope, c_kv, k_rope, prefix, view):
        """One token a slot against the paged latent pool: the query goes
        into the row's space, attends over the rows the slot's table
        names, and the output is expanded by the value half of
        `kv_b_proj`. Returns (out ``[S, 1, d]``, the view over the
        written pools)."""
        from ...nn.functional.attention import paged_latent_decode_attention
        cfg = self.config
        h, nope, vd, r = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                          cfg.v_head_dim, cfg.kv_lora_rank)
        s = q_nope.shape[0]
        w_kvb = self._w(prefix + "kv_b_proj.weight").reshape(r, h, nope + vd)
        q_lat = jnp.einsum("shn,chn->shc", q_nope[:, 0], w_kvb[..., :nope],
                           preferred_element_type=jnp.float32)
        q = jnp.concatenate([q_lat, q_rope[:, 0].astype(jnp.float32)], -1)
        out, pool = paged_latent_decode_attention(
            q, (c_kv[:, 0], k_rope[:, 0]), view.k_pools, view.layer,
            view.block_tables, view.seq_lens, view.active, view.block_size,
            value_width=r,
            scale=1.0 / math.sqrt(nope + cfg.qk_rope_head_dim),
            kernel=view.kernel or "blockwise",
            **self.cache_spec().loop_plan(view.block_size))
        o = jnp.einsum("shc,chv->shv", out.astype(c_kv.dtype),
                       w_kvb[..., nope:])
        o = o.reshape(s, 1, h * vd) @ self._w(prefix + "o_proj.weight")
        return o, view.updated(pool, view.v_pools)

    def _attention(self, x, pos, prefix, cache):
        q_nope, q_rope, c_kv, k_rope = mla.queries_and_row(
            x, pos, self._leaf(prefix), self.config)
        if cache is not None and hasattr(cache, "block_tables"):
            return self._absorbed(q_nope, q_rope, c_kv, k_rope, prefix,
                                  cache)
        past = 0
        if cache is not None:
            past = cache[0].shape[1]
            c_kv = jnp.concatenate([cache[0]._value.astype(c_kv.dtype),
                                    c_kv], axis=1)
            k_rope = jnp.concatenate([cache[1]._value.astype(k_rope.dtype),
                                      k_rope], axis=1)
            cache = (Tensor(c_kv), Tensor(k_rope))
        return mla.expanded(q_nope, q_rope, c_kv, k_rope,
                            self._leaf(prefix), self.config, past), cache

    # -- the model ------------------------------------------------------------
    def forward(self, input_ids, position_ids=None, caches=None,
                valid=None):
        """Logits ``[B, T, vocabulary]`` of ids ``[B, T]``; with `caches`
        (a `PagedCacheView` in a list, or a (rows, rotary keys) pair for
        each attention sublayer) also the caches after the call. `valid`
        ``[B, T]`` bool keeps padding out of the expert blocks' counters
        (the engine's programs pass it; the logits do not depend on it)."""
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
        new_caches, counters = [], 0
        first, held = cfg.held
        for i in range(cfg.num_layers):
            p = f"model.layers.{i}."
            w = lambda leaf: self._w(p + leaf)
            cache = lambda j: (view if paged else None if caches is None
                               else caches[2 * i + j])
            a, c0 = self._attention(
                _rms(x, w("input_layernorm.0.weight"), cfg.rms_norm_eps),
                pos, p + "self_attn.0.", cache(0))
            if paged:
                view = c0
            x = x + a
            u = _rms(x, w("post_attention_layernorm.0.weight"),
                     cfg.rms_norm_eps)
            # the shortcut: the expert block reads the FIRST sublayer's
            # activations and nothing below reads `m` before the layer's end
            with jax.named_scope("held_experts"):
                m, counted = held_expert_block(
                    u.reshape(b * t, -1), w("mlp.router.classifier.weight"),
                    self.router_bias(i)._value,
                    w("mlp.experts.gate_proj.weight"),
                    w("mlp.experts.up_proj.weight"),
                    w("mlp.experts.down_proj.weight"),
                    topk=cfg.moe_topk, real_experts=cfg.n_routed_experts,
                    scaling=cfg.routed_scaling_factor, first_held=first,
                    valid=jnp.reshape(valid, (b * t,)))
            counters = counters + counted
            x = x + _swiglu(u, w("mlps.0.gate_proj.weight"),
                            w("mlps.0.up_proj.weight"),
                            w("mlps.0.down_proj.weight"))
            a, c1 = self._attention(
                _rms(x, w("input_layernorm.1.weight"), cfg.rms_norm_eps),
                pos, p + "self_attn.1.", cache(1))
            if paged:
                view = c1
            x = x + a
            x = x + _swiglu(
                _rms(x, w("post_attention_layernorm.1.weight"),
                     cfg.rms_norm_eps),
                w("mlps.1.gate_proj.weight"), w("mlps.1.up_proj.weight"),
                w("mlps.1.down_proj.weight")) \
                + m.reshape(b, t, -1).astype(x.dtype)
            new_caches += [c0, c1]
        x = _rms(x, self._w("model.norm.weight"), cfg.rms_norm_eps)
        with jax.named_scope("lm_head"):
            logits = Tensor(x @ self._w("lm_head.weight"))
        self._counters = counters
        if caches is None:
            return logits
        return logits, ([view] if paged else new_caches)

    def generate(self, input_ids, max_new_tokens=32, do_sample=False):
        """Greedy continuation, a token at a time through the row caches
        (the engine's degraded-mode fallback; no compiled loop)."""
        if do_sample:
            raise ValueError("LongCatFlashForCausalLM.generate is greedy")
        ids = jnp.asarray(getattr(input_ids, "_value", input_ids))
        logits, caches = self(ids, caches=self.gen_caches(ids.shape[0]))
        out = []
        for _ in range(int(max_new_tokens)):
            nxt = jnp.argmax(logits._value[:, -1], -1).astype(ids.dtype)
            out.append(nxt)
            logits, caches = self(nxt[:, None], caches=caches)
        return Tensor(jnp.stack(out, axis=1))
