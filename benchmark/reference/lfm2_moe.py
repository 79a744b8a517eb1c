"""Plain LFM2-MoE reference: the forward pass in straightforward
`jax.numpy`, float32, matrix products at "highest". Serving only: no loss.

It follows `config.json` (`model_type` `lfm2_moe`) and
`modeling_lfm2_moe.py` of huggingface.co/LiquidAI/LFM2-8B-A1B. With T
tokens, d = `hidden_size`, RMS(x; g) = x / sqrt(mean(x^2) + `norm_eps`) * g,
no bias anywhere (`conv_bias` false):

  layer i: h = h + op_i(RMS(h; g_op));  h = h + ffn_i(RMS(h; g_ffn)).
  op of a `conv` layer (`layer_types`): [B | C | x] = u W_in (d -> 3d);
    z_t = B_t * x_t;  c_t = w_0 * z_(t-2) + w_1 * z_(t-1) + w_2 * z_t, a
    causal depthwise convolution of kernel `conv_L_cache` (3) with z zero
    before the sequence, written as three shifted products;
    op = (C_t * c_t) W_out.
  op of a `full_attention` layer: q = u W_q as `num_attention_heads` heads
    of d / heads; k = u W_k, v = u W_v as `num_key_value_heads` heads; RMS
    over each head of q and of k with one learned scale a projection;
    rotary positions over the whole head, halves rotated (`rotate_half`);
    query head i attends key/value head i // (heads / key-value heads);
    causal softmax at 1 / sqrt(head width); W_o. Every position, no cache.
  ffn of the first `num_dense_layers` layers: (silu(u W_1) * (u W_3)) W_2.
  ffn of every other layer: s = sigmoid(u W_r) over `num_experts`, in
    float32 in EVERY `precision`; the top `num_experts_per_tok` of s + b
    chosen (b the `expert_bias`, for the choice only); a chosen e weighs
    `routed_scaling_factor` * s_e / (sum of the chosen s + 1e-6)
    (`norm_topk_prob`); the sum over the chosen e of w_e E_e(u), E_e a
    SwiGLU of `moe_intermediate_size`. Every expert runs over every
    token, masked by the choice, an expert at a time; the choice is made
    HERE, from `u` itself.
  model: embedding, the layers, RMS (`embedding_norm`), logits through the
    embedding's own matrix (tied).

Departures from the source, each also under the configuration file's
`assumed`: the tied head; the head width d / heads; rotary halves rotated,
no scaling; the RMS form and the per-head q/k norms; the router's epsilon
1e-6; `expert_bias` zeros unless `params` carries the leaf (a test hands
one in). It imports nothing of the program. `precision`: see `common`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import F32, rounder

ROUTER_EPSILON = 1e-6


def bias_name(layer):
    return f"model.layers.{layer}.feed_forward.expert_bias"


def param_shapes(cfg):
    """{name: shape}, in the order the forward pass meets them. Matrices
    are stored [in, out]; the experts stacked."""
    d = cfg["hidden_size"]
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    ff, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    experts = cfg["num_experts"]
    shapes = {"model.embed_tokens.weight": (cfg["vocab_size"], d)}
    for i, kind in enumerate(cfg["layer_types"]):
        p = f"model.layers.{i}."
        shapes[p + "operator_norm.weight"] = (d,)
        if kind == "conv":
            shapes.update({
                p + "conv.in_proj.weight": (d, 3 * d),
                p + "conv.conv.weight": (d, cfg["conv_L_cache"]),
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
        if i < cfg["num_dense_layers"]:
            shapes.update({f + "w1.weight": (d, ff), f + "w3.weight": (d, ff),
                           f + "w2.weight": (ff, d)})
        else:
            shapes.update({f + "gate.weight": (d, experts),
                           f + "experts.w1.weight": (experts, d, fe),
                           f + "experts.w3.weight": (experts, d, fe),
                           f + "experts.w2.weight": (experts, fe, d)})
    shapes["model.embedding_norm.weight"] = (d,)
    return shapes


def num_params(cfg):
    return sum(math.prod(s) for s in param_shapes(cfg).values())


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _rope(x, theta):
    """x [rows, T, heads, r]: position t turns each pair (i, i + r/2) by
    t * theta^(-2i / r) (`rotate_half`)."""
    t, r = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=F32) / r))
    ang = (jnp.arange(t, dtype=F32)[:, None] * inv[None, :])[None, :, None]
    a, b = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def short_conv(u, w, mm):
    """The gated short convolution over u [rows, T, d]; `w(leaf)` widens a
    leaf of this layer. Three shifted products, z zero before the
    sequence."""
    t, d = u.shape[1], u.shape[2]
    gates = mm(u, w("in_proj.weight"))
    b, c, x = gates[..., :d], gates[..., d:2 * d], gates[..., 2 * d:]
    z = b * x
    taps = w("conv.weight")                                   # [d, L]
    lags = taps.shape[1]
    padded = jnp.pad(z, ((0, 0), (lags - 1, 0), (0, 0)))
    conv = sum(taps[:, j] * padded[:, j:j + t] for j in range(lags))
    return mm(c * conv, w("out_proj.weight"))


def attention(u, w, cfg, mm, rnd):
    """Grouped-query attention over u [rows, T, d], every position."""
    rows, t, d = u.shape
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, group = d // h, h // kh
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    q = mm(u, w("q_proj.weight")).reshape(rows, t, h, hd)
    k = mm(u, w("k_proj.weight")).reshape(rows, t, kh, hd)
    v = mm(u, w("v_proj.weight")).reshape(rows, t, kh, hd)
    q = _rope(_rms(q, w("q_layernorm.weight"), eps), theta)
    k = _rope(_rms(k, w("k_layernorm.weight"), eps), theta)
    # query head i = (its key/value head i // group, i % group)
    q = q.reshape(rows, t, kh, group, hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.einsum("bqkgd,btkd->bkgqt", rnd(q), rnd(k)) / math.sqrt(hd)
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
    o = jnp.einsum("bkgqt,btkd->bqkgd", rnd(p), rnd(v))
    return mm(o.reshape(rows, t, h * hd), w("out_proj.weight"))


def _swiglu(x, w1, w3, w2, mm):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def expert_block(u, params, layer, cfg, mm):
    """The routed sum over u [rows, T, d]. The choice is made here, in
    float32, from `u` itself; every expert over every token, masked."""
    p = f"model.layers.{layer}.feed_forward."
    scores = jax.nn.sigmoid(jnp.matmul(u, params[p + "gate.weight"]
                                       .astype(F32)))
    bias = params.get(bias_name(layer))
    ranked = scores if bias is None or not cfg["use_expert_bias"] \
        else scores + bias.astype(F32)
    _, chosen = jax.lax.top_k(ranked, cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, -1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True)
                             + ROUTER_EPSILON)
    weights = cfg["routed_scaling_factor"] * weights
    out = jnp.zeros_like(u)
    for e in range(cfg["num_experts"]):
        mine = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)
        leaf = lambda name: params[p + f"experts.{name}.weight"][e] \
            .astype(F32)
        out = out + mine[..., None] * _swiglu(u, leaf("w1"), leaf("w3"),
                                              leaf("w2"), mm)
    return out


def forward(params, ids, cfg, precision="float32"):
    """Logits [rows, seq, vocabulary] of token ids [rows, seq]. A layer at
    a time, each leaf widened to float32 where it is used and the stacked
    experts an expert at a time, so that weights served in bfloat16 are
    never held twice."""
    rnd = rounder(precision)
    mm = lambda a, w: jnp.matmul(rnd(a), rnd(w))
    f32 = lambda name: params[name].astype(F32)
    eps = cfg["norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = f32("model.embed_tokens.weight")[ids]
        for i, kind in enumerate(cfg["layer_types"]):
            p = f"model.layers.{i}."
            u = _rms(x, f32(p + "operator_norm.weight"), eps)
            if kind == "conv":
                x = x + short_conv(u, lambda leaf: f32(p + "conv." + leaf),
                                   mm)
            else:
                x = x + attention(
                    u, lambda leaf: f32(p + "self_attn." + leaf), cfg, mm,
                    rnd)
            u = _rms(x, f32(p + "ffn_norm.weight"), eps)
            f = p + "feed_forward."
            if i < cfg["num_dense_layers"]:
                x = x + _swiglu(u, f32(f + "w1.weight"), f32(f + "w3.weight"),
                                f32(f + "w2.weight"), mm)
            else:
                x = x + expert_block(u, params, i, cfg, mm)
        x = _rms(x, f32("model.embedding_norm.weight"), eps)
        return mm(x, f32("model.embed_tokens.weight").T)
