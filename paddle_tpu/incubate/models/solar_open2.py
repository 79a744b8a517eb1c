"""Solar-Open2 (decoder-only: gated delta-rule linear attention and gated
softmax attention without positions in one stack, sparse experts beside a
shared one), for serving.

Source: `config.json` (`model_type` `solar_open2`) of
huggingface.co/upstage/Solar-Open2-250B; the layer equations are written
down in `benchmark/reference/solar_open2.py`, each guess beside them. What
differs from the models beside this file:

  * a layer is ``h = h + op(RMS(h)); h = h + ffn(RMS(h))`` and `op` is of
    TWO KINDS (`layer_types`): KDA, a GATED DELTA RULE with a decay a
    channel (`kernels/kda.py`), or softmax ATTENTION with fewer key/value
    heads than query heads, NO positions, and an elementwise sigmoid gate
    from the layer's input on its output (scope `gated_attention`);
  * a KDA layer keeps TWO things of a sequence, of different shapes AND
    types: the last ``short_conv_kernel_size - 1`` inputs of the q, k and
    v convolutions (the model's dtype) and a MATRIX STATE ``[heads, D, D]``
    in float32 that every token updates. `cache_spec()` states them as
    the two parts of the per-slot state beside the paged pool of the
    attention layers (serving/cache.py `CacheSpec`): a prefill hands both
    back as they stand after the prompt's true length (the chunked scan,
    the bucket's padding masked), a decode launch moves the active slots'
    one token on where they lie;
  * every layer's `ffn` is an expert block TOLD which experts it holds
    (`incubate/distributed/models/moe/held_experts.py`: sigmoid scores,
    top `num_experts_per_tok`, weights normalised) plus a shared expert
    that every token takes;
  * RMSNorm, no bias but `dt_bias`, an untied head.

Serving only: no loss, no gradient path is kept. `LLMEngine` reads of the
class what it reads of `Lfm2MoeForCausalLM`: `cache_spec()`,
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
from ...kernels import kda
from .lfm2_moe import grouped_causal_attention
from .mla import rms as _rms

__all__ = ["SolarOpen2Config", "SolarOpen2ForCausalLM"]

KDA, ATTENTION = "linear_attention", "full_attention"
L2_EPSILON = 1e-6


@dataclass
class SolarOpen2Config:
    vocab_size: int = 196608
    hidden_size: int = 4096
    moe_intermediate_size: int = 1280
    num_hidden_layers: int = 48
    layer_types: tuple = field(default_factory=lambda: tuple(
        ATTENTION if i % 4 == 0 else KDA for i in range(48)))
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    use_gqa_gate: bool = True
    linear_num_heads: int = 64
    linear_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_allow_neg_eigval: bool = True
    n_routed_experts: int = 320
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576
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
        if set(self.layer_types) - {KDA, ATTENTION}:
            raise ValueError(f"layer_types {set(self.layer_types)}: "
                             f"{KDA!r} or {ATTENTION!r}")

    @property
    def held(self):
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def linear_width(self):
        return self.linear_num_heads * self.linear_head_dim

    @property
    def state_parts(self):
        """What a KDA layer keeps of a sequence (`CacheSpec`'s
        `state_parts`): the convolutions' last inputs, q's, k's and v's
        side by side at a position and the positions side by side in ONE
        row (whole lane tiles a slot), in the model's dtype; and the
        delta rule's matrix a head, float32 whatever the model's."""
        taps = self.short_conv_kernel_size - 1
        return (("conv", (taps * 3 * self.linear_width,), None),
                ("delta", (self.linear_num_heads, self.linear_head_dim,
                           self.linear_head_dim), jnp.float32))


def param_shapes(cfg):
    """{name: shape} of the parameters, in the order the forward pass
    meets them (`benchmark/reference/solar_open2.py` states the same).
    Matrices are stored [in, out]; the experts stacked; a convolution's
    taps [channels, taps]."""
    d, hd = cfg.hidden_size, cfg.head_dim
    h, kh = cfg.num_attention_heads, cfg.num_key_value_heads
    lw, ld, lh = cfg.linear_width, cfg.linear_head_dim, cfg.linear_num_heads
    fe, held = cfg.moe_intermediate_size, cfg.held[1]
    shapes = {"model.embed_tokens.weight": (cfg.vocab_size, d)}
    for i, kind in enumerate(cfg.layer_types):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        shapes[p + "input_layernorm.weight"] = (d,)
        if kind == KDA:
            for s in "qkv":
                shapes[a + f"{s}_proj.weight"] = (d, lw)
                shapes[a + f"{s}_conv1d.weight"] = (
                    lw, cfg.short_conv_kernel_size)
            shapes.update({
                a + "f_a_proj.weight": (d, ld),
                a + "f_b_proj.weight": (ld, lw),
                a + "A_log": (lh,), a + "dt_bias": (lw,),
                a + "b_proj.weight": (d, lh),
                a + "g_a_proj.weight": (d, ld),
                a + "g_b_proj.weight": (ld, lw),
                a + "o_norm.weight": (ld,),
                a + "o_proj.weight": (lw, d)})
        else:
            shapes.update({a + "q_proj.weight": (d, h * hd),
                           a + "k_proj.weight": (d, kh * hd),
                           a + "v_proj.weight": (d, kh * hd),
                           a + "g_proj.weight": (d, h * hd),
                           a + "o_proj.weight": (h * hd, d)})
        shapes[p + "post_attention_layernorm.weight"] = (d,)
        f = p + "mlp."
        shared = fe * cfg.n_shared_experts
        shapes.update({
            f + "gate.weight": (d, cfg.n_routed_experts),
            f + "experts.gate_proj.weight": (held, d, fe),
            f + "experts.up_proj.weight": (held, d, fe),
            f + "experts.down_proj.weight": (held, fe, d),
            f + "shared_experts.gate_proj.weight": (d, shared),
            f + "shared_experts.up_proj.weight": (d, shared),
            f + "shared_experts.down_proj.weight": (shared, d)})
    shapes["model.norm.weight"] = (d,)
    shapes["lm_head.weight"] = (d, cfg.vocab_size)
    return shapes


def _initial(name, shape, cfg, rng):
    """A parameter no weights were handed in for: norm scales 1, matrices
    N(0, `initializer_range`), and a KDA layer's `A_log` and `dt_bias` as
    the flash-linear-attention implementation draws them (A uniform in
    [1, 16]; dt log-uniform in [0.001, 0.1], stored through softplus'
    inverse), so that an unhanded model decays as a trained one does."""
    if name.endswith("A_log"):
        return np.log(rng.uniform(1.0, 16.0, shape))
    if name.endswith("dt_bias"):
        dt = np.exp(rng.uniform(math.log(1e-3), math.log(1e-1), shape))
        return dt + np.log(-np.expm1(-dt))
    if len(shape) == 1:
        return np.ones(shape)
    return rng.normal(0.0, cfg.initializer_range, shape)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPSILON)


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


class SolarOpen2ForCausalLM(Layer):
    """The whole model as one `Layer`: its parameters by the names of
    `param_shapes`, its forward in `jax.numpy`.

    `weights` ({name: array}) are taken as the parameters' values where
    given, so a chip-filling model is never initialised and then
    overwritten (both would not fit); otherwise each is drawn
    (`_initial`)."""

    # `LLMEngine` passes this model's weights to its programs as arguments
    serve_weights_as_arguments = True
    # what a forward through a cache leaves in `pop_serve_counters()`: the
    # expert blocks' counters, then the grouped products the forward ran
    # and those of them the tiled kernel ran
    serve_counter_names = COUNTERS + ("products", "kernel_products")

    def __init__(self, config: SolarOpen2Config, weights=None):
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
            else:
                value = jnp.asarray(_initial(name, shape, config, rng),
                                    jnp.float32)
            # kept under its dotted name: `named_parameters()` then
            # yields the reference's names as they are
            self._parameters[name] = Parameter(value, name=name)
        self._counters = None

    def _w(self, name):
        return self._parameters[name]._value

    # -- what the engine reads ------------------------------------------------
    def cache_spec(self):
        from ...serving.cache import CacheSpec
        cfg = self.config
        kinds = cfg.layer_types
        return CacheSpec.per_head(
            kinds.count(ATTENTION), cfg.num_key_value_heads, cfg.head_dim,
            query_heads=cfg.num_attention_heads,
            state_layers=kinds.count(KDA), state_parts=cfg.state_parts)

    def pop_serve_counters(self):
        """The counters of the forward just traced, summed over the
        layers (int32 [len(serve_counter_names)])."""
        counters, self._counters = self._counters, None
        return counters

    def gen_caches(self, batch_size, dtype=None):
        """Dense caches with no token in them, in the order a forward takes
        them: a (keys, values) pair for each attention layer, then the
        state's parts (zeros: what lies before a sequence) for each KDA
        layer."""
        spec = self.cache_spec()
        dtype = dtype or self._w("model.norm.weight").dtype
        return spec.empty_prefill(dtype, rows=batch_size)

    # -- the two kinds of `op` ------------------------------------------------
    def _kda_gates(self, u, p):
        """(g ``[..., H, D]`` float32 log decays, beta ``[..., H]``
        float32) of a KDA layer's input u ``[..., d]``."""
        cfg = self.config
        f32 = jnp.float32
        f = ((u @ self._w(p + "f_a_proj.weight"))
             @ self._w(p + "f_b_proj.weight")).astype(f32) \
            + self._w(p + "dt_bias").astype(f32)
        heads = u.shape[:-1] + (cfg.linear_num_heads, cfg.linear_head_dim)
        g = -jnp.exp(self._w(p + "A_log").astype(f32))[:, None] \
            * jax.nn.softplus(f.reshape(heads))
        beta = jax.nn.sigmoid((u @ self._w(p + "b_proj.weight")).astype(f32))
        return g, (2.0 if cfg.kda_allow_neg_eigval else 1.0) * beta

    def _kda_out(self, o, u, p):
        """The heads' results o ``[..., H, D]`` float32 normed a head,
        gated from the layer's input u and projected."""
        cfg = self.config
        gate = jax.nn.sigmoid(((u @ self._w(p + "g_a_proj.weight"))
                               @ self._w(p + "g_b_proj.weight"))
                              .astype(jnp.float32))
        o = _rms(o, self._w(p + "o_norm.weight"), cfg.rms_norm_eps) \
            * gate.reshape(o.shape)
        return o.reshape(u.shape[:-1] + (cfg.linear_width,)).astype(
            u.dtype) @ self._w(p + "o_proj.weight")

    def _qkv(self, u, p):
        return [u @ self._w(p + f"{s}_proj.weight") for s in "qkv"], \
            [self._w(p + f"{s}_conv1d.weight") for s in "qkv"]

    def _heads(self, q, k, v):
        """The convolved streams (float32) as the delta rule takes them:
        q, k a head of unit length, q scaled; activations, so in the
        model's type (the rule widens them where it uses them)."""
        cfg = self.config
        dtype = self._w("model.norm.weight").dtype
        shape = q.shape[:-1] + (cfg.linear_num_heads, cfg.linear_head_dim)
        q, k, v = (x.reshape(shape) for x in (q, k, v))
        return tuple(x.astype(dtype) for x in (
            _l2norm(q) / math.sqrt(cfg.linear_head_dim), _l2norm(k), v))

    def _kda(self, u, p, state, length):
        """A KDA layer over u ``[B, T, d]`` behind `state` (its two
        parts, `SolarOpen2Config.state_parts`); `length` ``[B]`` of u's
        positions are a prompt and the rest its bucket's padding, which
        moves neither part. Returns (out ``[B, T, d]``, the state after
        `length`)."""
        t = u.shape[1]
        streams, taps = self._qkv(u, p)
        (q, k, v), conv = kda.kda_short_conv(streams, taps, state[0], length)
        g, beta = self._kda_gates(u, p)
        valid = jnp.arange(t, dtype=jnp.int32)[None, :] < length[:, None]
        o, delta = kda.kda_chunk_scan(
            *self._heads(q, k, v), jnp.where(valid[..., None, None], g, 0.0),
            jnp.where(valid[..., None], beta, 0.0), state[1])
        return self._kda_out(o, u, p), (conv, delta)

    def _paged_kda(self, u, p, view):
        """One token a slot behind the slots' states where they lie."""
        layer = view.state_layer
        u = u[:, 0]
        streams, taps = self._qkv(u, p)
        (q, k, v), conv = kda.kda_short_conv_step(
            streams, taps, view.slot_state[0], layer, view.active)
        g, beta = self._kda_gates(u, p)
        o, delta = kda.kda_decode_step(
            *self._heads(q, k, v), g, beta, view.slot_state[1], layer,
            view.active)
        return self._kda_out(o, u, p)[:, None], view.updated(
            slot_state=(conv, delta))

    @jax.named_scope("gated_attention")
    def _attention(self, u, p, cache):
        cfg = self.config
        h, kh, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        b, t, _ = u.shape
        q = (u @ self._w(p + "q_proj.weight")).reshape(b, t, h, hd)
        k = (u @ self._w(p + "k_proj.weight")).reshape(b, t, kh, hd)
        v = (u @ self._w(p + "v_proj.weight")).reshape(b, t, kh, hd)
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
        o = o.reshape(b, t, h * hd)
        if cfg.use_gqa_gate:
            o = o * jax.nn.sigmoid(u @ self._w(p + "g_proj.weight"))
        return o @ self._w(p + "o_proj.weight"), cache

    # -- the model ------------------------------------------------------------
    def forward(self, input_ids, caches=None, valid=None):
        """Logits ``[B, T, vocabulary]`` of ids ``[B, T]``; with `caches`
        (a `PagedCacheView` in a list, or `gen_caches`' layout: a (keys,
        values) pair for each attention layer, then a KDA layer's state
        parts for each such layer) also the caches after the call. `valid`
        ``[B, T]`` bool marks the prompt inside its bucket (a prefix of
        each row): it keeps padding out of the expert blocks' counters,
        and the KDA layers' states are taken where it ends (the logits
        at valid positions do not depend on it)."""
        cfg = self.config
        ids = jnp.asarray(getattr(input_ids, "_value", input_ids))
        b, t = ids.shape
        paged = caches is not None and hasattr(caches[0], "block_tables")
        if paged:
            valid = caches[0].active[:, None] if valid is None else valid
        elif valid is None:
            valid = jnp.ones((b, t), bool)
        length = jnp.sum(valid, axis=1, dtype=jnp.int32)
        x = self._w("model.embed_tokens.weight")[ids]
        view = caches[0] if paged else None
        n_attn = cfg.layer_types.count(ATTENTION)
        handed = caches is not None
        if not handed:
            caches = self.gen_caches(b, x.dtype)
        pairs, states = [], []
        counters = jnp.zeros(len(COUNTERS), jnp.int32)
        products = np.zeros(2, np.int32)
        first, held = cfg.held
        for i, kind in enumerate(cfg.layer_types):
            p = f"model.layers.{i}."
            u = _rms(x, self._w(p + "input_layernorm.weight"),
                     cfg.rms_norm_eps)
            if kind == KDA and paged:
                a, view = self._paged_kda(u, p + "self_attn.", view)
            elif kind == KDA:
                state = tuple(part._value
                              for part in caches[n_attn + len(states)])
                a, state = self._kda(u, p + "self_attn.", state, length)
                states.append(tuple(Tensor(part) for part in state))
            elif paged:
                a, view = self._attention(u, p + "self_attn.", view)
            else:
                a, pair = self._attention(u, p + "self_attn.",
                                          caches[len(pairs)])
                pairs.append(pair)
            x = x + a
            u = _rms(x, self._w(p + "post_attention_layernorm.weight"),
                     cfg.rms_norm_eps)
            f = p + "mlp."
            with jax.named_scope("held_experts"):
                m, counted = held_expert_block(
                    u.reshape(b * t, -1), self._w(f + "gate.weight"), None,
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
            with jax.named_scope("shared_expert"):
                shared = _swiglu(
                    u, self._w(f + "shared_experts.gate_proj.weight"),
                    self._w(f + "shared_experts.up_proj.weight"),
                    self._w(f + "shared_experts.down_proj.weight"))
            x = x + m.reshape(b, t, -1).astype(x.dtype) + shared
        x = _rms(x, self._w("model.norm.weight"), cfg.rms_norm_eps)
        with jax.named_scope("lm_head"):
            logits = Tensor(x @ self._w("lm_head.weight"))
        self._counters = jnp.concatenate([counters, jnp.asarray(products)])
        if not handed:
            return logits
        return logits, ([view] if paged else pairs + states)

    def generate(self, input_ids, max_new_tokens=32, do_sample=False):
        """Greedy continuation, a token at a time through the dense caches
        (the engine's degraded-mode fallback; no compiled loop)."""
        if do_sample:
            raise ValueError("SolarOpen2ForCausalLM.generate is greedy")
        ids = jnp.asarray(getattr(input_ids, "_value", input_ids))
        logits, caches = self(ids, caches=self.gen_caches(ids.shape[0]))
        out = []
        for _ in range(int(max_new_tokens)):
            nxt = jnp.argmax(logits._value[:, -1], -1).astype(ids.dtype)
            out.append(nxt)
            logits, caches = self(nxt[:, None], caches=caches)
        return Tensor(jnp.stack(out, axis=1))
