"""Plain MiMo-V2-Flash reference: the forward pass in straightforward
`jax.numpy`, float32, matrix products at "highest". Serving only: no loss.

It follows `config.json` (`model_type` `mimo_v2_flash`) of
huggingface.co/XiaomiMiMo/MiMo-V2-Flash. With T tokens, d = `hidden_size`,
RMS(x; g) = x / sqrt(mean(x^2) + `layernorm_epsilon`) * g, no bias
anywhere (`attention_bias` false):

  layer i: h = x + attn_i(RMS(x; g_in));  y = h + ffn_i(RMS(h; g_post)).
  attn, of the kind `layer_types[i]` says (the source's
    `hybrid_layer_pattern`: 0 "full_attention", 1 "sliding_attention"):
    q = u W_q as `num_attention_heads` heads of `head_dim`; k = u W_k as
    KH heads of `head_dim`, v = `attention_value_scale` * (u W_v) as KH
    heads of `v_head_dim`, KH = `num_key_value_heads` in a full layer and
    `swa_num_key_value_heads` in a window layer; rotary positions over the
    FIRST round(`partial_rotary_factor` * `head_dim`) values of each q and
    k head, halves rotated, base `rope_theta` (full) or `swa_rope_theta`
    (window), the other values as they are; query head i attends
    key/value head i // (heads / KH); scores q . k / sqrt(`head_dim`);
    causal; in a window layer position i sees j with
    i - `sliding_window` < j <= i, and one learned scalar s_h a query head
    (`add_swa_attention_sink_bias`) joins the denominator and adds no
    value: p_ij = exp(a_ij) / (exp(s_h) + sum_j exp(a_ij)); W_o over the
    heads' `v_head_dim`-wide results. Every position, no cache; computed
    a block of queries at a time (`QUERY_BLOCK`), a window layer's block
    against the keys its band reaches, so that 8,192 positions fit.
  ffn of the first `first_k_dense_replace` layers (the leading zeros of
    the source's `moe_layer_freq`): (silu(u W_g) * (u W_u)) W_d.
  ffn of every other layer: s = sigmoid(u W_r) over all the published
    experts, in float32 in EVERY `precision`; the top
    `num_experts_per_tok` of s + b chosen (`noaux_tc`, `n_group` 1: no
    group limit; b the `e_score_correction_bias`, for the choice only); a
    chosen e weighs `routed_scaling_factor` (null: 1) * s_e / (the sum of
    the chosen s + 1e-20) (`norm_topk_prob`); no shared expert; the sum
    over the chosen HELD e of w_e E_e(u), E_e a SwiGLU of
    `moe_intermediate_size`. A chosen expert that is not held adds nothing
    (a chip's share). Every held expert runs over every token, masked by
    the choice, an expert at a time; the choice is made HERE, from `u`.
  model: embedding, the layers, RMS (`norm`), an untied head.

Departures from the source and guesses, each also under the configuration
file's `assumed`: rotary halves rotated within the rotary part, no
scaling; the value scale applied to v; the sink as a score in the
denominator; the RMS form; the router's 1e-20; `e_score_correction_bias`
zeros unless `params` carries the leaf (a test hands one in); the three
MTP modules left out.

The router's width is the PUBLISHED count (`published.n_routed_experts`
where the file cuts the experts to a share, else `n_routed_experts`);
`n_routed_experts` is what is held, from id `experts_held_from` (0 where
the file has none). It imports nothing of the program. `precision`: see
`common`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import F32, rounder

QUERY_BLOCK = 512        # queries whose scores are held at once
ROUTER_EPSILON = 1e-20
WINDOW = "sliding_attention"


def _published_experts(cfg):
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def bias_name(layer):
    return f"model.layers.{layer}.mlp.gate.e_score_correction_bias"


def _kv_heads(cfg, kind):
    return cfg["swa_num_key_value_heads"] if kind == WINDOW \
        else cfg["num_key_value_heads"]


def _has_sink(cfg, kind):
    return cfg["add_swa_attention_sink_bias"] if kind == WINDOW \
        else cfg["add_full_attention_sink_bias"]


def param_shapes(cfg):
    """{name: shape}, in the order the forward pass meets them. Matrices
    are stored [in, out]; the experts stacked."""
    d, dk, dv = cfg["hidden_size"], cfg["head_dim"], cfg["v_head_dim"]
    h = cfg["num_attention_heads"]
    ff, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held = cfg["n_routed_experts"]
    shapes = {"model.embed_tokens.weight": (cfg["vocab_size"], d)}
    for i, kind in enumerate(cfg["layer_types"]):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        kh = _kv_heads(cfg, kind)
        shapes[p + "input_layernorm.weight"] = (d,)
        shapes.update({a + "q_proj.weight": (d, h * dk),
                       a + "k_proj.weight": (d, kh * dk),
                       a + "v_proj.weight": (d, kh * dv)})
        if _has_sink(cfg, kind):
            shapes[a + "attention_sink_bias"] = (h,)
        shapes[a + "o_proj.weight"] = (h * dv, d)
        shapes[p + "post_attention_layernorm.weight"] = (d,)
        f = p + "mlp."
        if i < cfg["first_k_dense_replace"]:
            shapes.update({f + "gate_proj.weight": (d, ff),
                           f + "up_proj.weight": (d, ff),
                           f + "down_proj.weight": (ff, d)})
        else:
            shapes.update({
                f + "gate.weight": (d, _published_experts(cfg)),
                f + "experts.gate_proj.weight": (held, d, fe),
                f + "experts.up_proj.weight": (held, d, fe),
                f + "experts.down_proj.weight": (held, fe, d)})
    shapes["model.norm.weight"] = (d,)
    shapes["lm_head.weight"] = (d, cfg["vocab_size"])
    return shapes


def num_params(cfg):
    return sum(math.prod(s) for s in param_shapes(cfg).values())


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def rotary_dim(cfg):
    return round(cfg["partial_rotary_factor"] * cfg["head_dim"])


def _rope_part(x, theta, r):
    """x [rows, T, heads, D]: position t turns each pair (i, i + r/2) of
    the FIRST r values by t * theta^(-2i / r) (`rotate_half`); the values
    from r on pass as they are."""
    t = x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=F32) / r))
    ang = (jnp.arange(t, dtype=F32)[:, None] * inv[None, :])[None, :, None]
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang),
                            x[..., r:]], -1)


def attention(u, w, cfg, kind, mm, rnd):
    """One layer's attention over u [rows, T, d], every position, a block
    of queries at a time; `w(leaf)` widens a leaf of this layer."""
    rows, t, _ = u.shape
    h, kh = cfg["num_attention_heads"], _kv_heads(cfg, kind)
    dk, dv, group = cfg["head_dim"], cfg["v_head_dim"], h // kh
    windowed = kind == WINDOW
    theta = cfg["swa_rope_theta"] if windowed else cfg["rope_theta"]
    r = rotary_dim(cfg)
    q = mm(u, w("q_proj.weight")).reshape(rows, t, h, dk)
    k = mm(u, w("k_proj.weight")).reshape(rows, t, kh, dk)
    v = cfg["attention_value_scale"] \
        * mm(u, w("v_proj.weight")).reshape(rows, t, kh, dv)
    q, k = _rope_part(q, theta, r), _rope_part(k, theta, r)
    # query head i = (its key/value head i // group, i % group)
    q = q.reshape(rows, t, kh, group, dk)
    sink = w("attention_sink_bias").reshape(kh, group)[None, :, :, None] \
        if _has_sink(cfg, kind) else None
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    # the keys a block of queries may see: a window layer's band reaches
    # `sliding_window` - 1 positions before the block's first query
    # (zeros stand before the sequence, masked), a full layer's all
    reach = min(cfg["sliding_window"] - 1, t) if windowed else 0
    span = block + reach if windowed else t
    if windowed:
        k = jnp.pad(k, ((0, 0), (reach, 0), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (reach, 0), (0, 0), (0, 0)))

    def one(first):
        """Queries first .. first + block - 1."""
        qb = jax.lax.dynamic_slice_in_dim(q, first, block, axis=1)
        if windowed:
            kb = jax.lax.dynamic_slice_in_dim(k, first, span, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, first, span, axis=1)
            at = first - reach + jnp.arange(span)      # the keys' positions
        else:
            kb, vb, at = k, v, jnp.arange(span)
        i = first + jnp.arange(block)[:, None]
        keep = (at[None, :] <= i) & (at[None, :] >= 0)
        if windowed:
            keep = keep & (i - at[None, :] < cfg["sliding_window"])
        s = jnp.einsum("bqkgd,btkd->bkgqt", rnd(qb), rnd(kb)) \
            / math.sqrt(dk)
        s = jnp.where(keep, s, -jnp.inf)
        top = jnp.max(s, -1, keepdims=True)
        if sink is not None:
            top = jnp.maximum(top, sink[..., None])
        e = jnp.exp(s - top)
        under = jnp.sum(e, -1, keepdims=True)
        if sink is not None:
            under = under + jnp.exp(sink[..., None] - top)
        o = jnp.einsum("bkgqt,btkd->bqkgd", rnd(e / under), rnd(vb))
        return o.reshape(rows, block, h * dv)

    firsts = jnp.arange(0, t, block)
    o = jax.lax.map(one, firsts)                # [blocks, rows, block, .]
    o = jnp.moveaxis(o, 0, 1).reshape(rows, t, h * dv)
    return mm(o, w("o_proj.weight"))


def _swiglu(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def expert_block(u, params, layer, cfg, mm):
    """The held experts' part of the routed sum over u [rows, T, d]. The
    choice is made here, in float32, from `u` itself, over ALL the
    published experts; every held expert over every token, masked."""
    p = f"model.layers.{layer}.mlp."
    held, first = cfg["n_routed_experts"], cfg.get("experts_held_from", 0)
    scores = jax.nn.sigmoid(jnp.matmul(u, params[p + "gate.weight"]
                                       .astype(F32)))
    bias = params.get(bias_name(layer))
    ranked = scores if bias is None else scores + bias.astype(F32)
    _, chosen = jax.lax.top_k(ranked, cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, -1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True)
                             + ROUTER_EPSILON)
    scaling = cfg.get("routed_scaling_factor")
    weights = (1.0 if scaling is None else scaling) * weights
    out = jnp.zeros_like(u)
    for e in range(held):
        mine = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        leaf = lambda name: params[p + f"experts.{name}.weight"][e] \
            .astype(F32)
        out = out + mine[..., None] * _swiglu(
            u, leaf("gate_proj"), leaf("up_proj"), leaf("down_proj"), mm)
    return out


def forward(params, ids, cfg, precision="float32"):
    """Logits [rows, seq, vocabulary] of token ids [rows, seq]. A layer at
    a time, each leaf widened to float32 where it is used and the stacked
    experts an expert at a time, so that weights served in bfloat16 are
    never held twice."""
    rnd = rounder(precision)
    mm = lambda a, w: jnp.matmul(rnd(a), rnd(w))
    f32 = lambda name: params[name].astype(F32)
    eps = cfg["layernorm_epsilon"]
    with jax.default_matmul_precision("highest"):
        x = f32("model.embed_tokens.weight")[ids]
        for i, kind in enumerate(cfg["layer_types"]):
            p = f"model.layers.{i}."
            u = _rms(x, f32(p + "input_layernorm.weight"), eps)
            x = x + attention(
                u, lambda leaf: f32(p + "self_attn." + leaf), cfg, kind, mm,
                rnd)
            u = _rms(x, f32(p + "post_attention_layernorm.weight"), eps)
            f = p + "mlp."
            if i < cfg["first_k_dense_replace"]:
                x = x + _swiglu(u, f32(f + "gate_proj.weight"),
                                f32(f + "up_proj.weight"),
                                f32(f + "down_proj.weight"), mm)
            else:
                x = x + expert_block(u, params, i, cfg, mm)
        x = _rms(x, f32("model.norm.weight"), eps)
        return mm(x, f32("lm_head.weight"))
