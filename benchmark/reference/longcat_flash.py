"""Plain LongCat-Flash reference: the forward pass in straightforward
`jax.numpy`, float32, matrix products at "highest". Serving only: no loss.

It follows `config.json` and `modeling_longcat_flash.py` of
huggingface.co/meituan-longcat/LongCat-Flash-Chat and the LongCat-Flash
technical report (arXiv:2509.01322). With T tokens, d = `hidden_size`,
RMS(x; g) = x / sqrt(mean(x^2) + `rms_norm_eps`) * g:

  attention sublayer (multi-head latent attention, no biases):
    c_q = RMS(x W_qa; g_q);  q = (c_q W_qb) * sqrt(d / q_lora_rank),
    reshaped [T, heads, nope + rope];
    [c | k_r] = x W_kva (kv_lora_rank + rope columns);
    c_kv = RMS(c; g_kv) * sqrt(d / kv_lora_rank);  k_r is not scaled;
    rotary positions on q's last `rope` values and on k_r, which all heads
    share;  [k_nope | v] = c_kv W_kvb reshaped [T, heads, nope + v];
    scores (q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope), causal
    softmax, o = sum p v, out = concat(o) W_o.
    EXPANDED at every position: no absorption, no cache.
  dense FFN: (silu(u W_g) * (u W_u)) W_d.
  expert block MoE(u): s = softmax(u W_r) over all the real and identity
    experts, in float32 in EVERY `precision`; the top `moe_topk` of s + b
    are chosen (b the `e_score_correction_bias`, for the choice only);
    a chosen e weighs `routed_scaling_factor` * s_e, not renormalised;
    MoE(u) = sum over chosen held real e of w_e E_e(u) + sum over chosen
    identity e of w_e u, E_e a SwiGLU of `expert_ffn_hidden_size`. A
    chosen real expert that is not held adds nothing (a chip's share).
    Every held expert runs over every token, masked by the choice, an
    expert at a time.
  layer: h1 = x + MLA0(RMS(x));  u = RMS(h1);  m = MoE(u);
    h2 = h1 + FFN0(u);  h3 = h2 + MLA1(RMS(h2));
    h4 = h3 + FFN1(RMS(h3)) + m   (the shortcut: m joins at the end).
  model: embedding, the layers, RMS, an untied head.

Departures from the source, each also under the configuration file's
`assumed`: rotary pairs interleaved (2i, 2i+1) with no scaling; the scale
1/sqrt(nope + rope); weights not renormalised over the chosen; an untied
head; `e_score_correction_bias` zeros unless `params` carries the leaf
(the source registers it as a zeros buffer; a test hands one in).

The router's width is the PUBLISHED count (`published.n_routed_experts`
where the file cuts the experts to a share, else `n_routed_experts`) plus
`zero_expert_num`; `n_routed_experts` is what is held, from id
`experts_held_from` (0 where the file has none). It imports nothing of the
program. `precision`: see `common`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import F32, rounder

HEADS_AT_A_TIME = 16     # of a sublayer's scores held at once


def _real_experts(cfg):
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def bias_name(layer):
    return f"model.layers.{layer}.mlp.router.e_score_correction_bias"


def param_shapes(cfg):
    """{name: shape}, in the order the forward pass meets them."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    ff, fe = cfg["ffn_hidden_size"], cfg["expert_ffn_hidden_size"]
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    held = cfg["n_routed_experts"]
    ranked = _real_experts(cfg) + cfg["zero_expert_num"]
    shapes = {"model.embed_tokens.weight": (cfg["vocab_size"], d)}
    for i in range(cfg["num_layers"]):
        p = f"model.layers.{i}."
        for j in (0, 1):
            a = f"{p}self_attn.{j}."
            shapes.update({
                f"{p}input_layernorm.{j}.weight": (d,),
                a + "q_a_proj.weight": (d, ql),
                a + "q_a_layernorm.weight": (ql,),
                a + "q_b_proj.weight": (ql, h * (nope + rope)),
                a + "kv_a_proj_with_mqa.weight": (d, kl + rope),
                a + "kv_a_layernorm.weight": (kl,),
                a + "kv_b_proj.weight": (kl, h * (nope + vd)),
                a + "o_proj.weight": (h * vd, d),
                f"{p}post_attention_layernorm.{j}.weight": (d,),
            })
            if j == 0:
                shapes.update({
                    p + "mlp.router.classifier.weight": (d, ranked),
                    p + "mlp.experts.gate_proj.weight": (held, d, fe),
                    p + "mlp.experts.up_proj.weight": (held, d, fe),
                    p + "mlp.experts.down_proj.weight": (held, fe, d),
                })
            m = f"{p}mlps.{j}."
            shapes.update({m + "gate_proj.weight": (d, ff),
                           m + "up_proj.weight": (d, ff),
                           m + "down_proj.weight": (ff, d)})
    shapes.update({"model.norm.weight": (d,),
                   "lm_head.weight": (d, cfg["vocab_size"])})
    return shapes


def num_params(cfg):
    return sum(math.prod(s) for s in param_shapes(cfg).values())


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _rope(x, theta):
    """x [rows, T, ..., r]: position t turns each pair (2i, 2i+1) by
    t * theta^(-2i / r)."""
    t, r = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=F32) / r))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]       # [T, r/2]
    ang = ang.reshape((1, t) + (1,) * (x.ndim - 3) + (r // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                        a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)
    return turned.reshape(x.shape)


def _attention(x, w, cfg, mm, rnd):
    """One attention sublayer over x [rows, T, d]; `w(leaf)` widens a leaf
    of this sublayer."""
    rows, t, d = x.shape
    h = cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    kl, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    c_q = _rms(mm(x, w("q_a_proj.weight")), w("q_a_layernorm.weight"), eps)
    q = mm(c_q, w("q_b_proj.weight"))
    if cfg["mla_scale_q_lora"]:
        q = q * math.sqrt(d / cfg["q_lora_rank"])
    q = q.reshape(rows, t, h, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], cfg["rope_theta"])
    ckr = mm(x, w("kv_a_proj_with_mqa.weight"))
    c_kv = _rms(ckr[..., :kl], w("kv_a_layernorm.weight"), eps)
    if cfg["mla_scale_kv_lora"]:
        c_kv = c_kv * math.sqrt(d / kl)
    k_rope = _rope(ckr[..., kl:], cfg["rope_theta"])           # [rows, T, r]
    kv = mm(c_kv, w("kv_b_proj.weight")).reshape(rows, t, h, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    causal = jnp.tril(jnp.ones((t, t), bool))
    outs = []
    for at in range(0, h, HEADS_AT_A_TIME):
        some = slice(at, at + HEADS_AT_A_TIME)
        s = (jnp.einsum("bqhd,bkhd->bhqk", rnd(q_nope[:, :, some]),
                        rnd(k_nope[:, :, some]))
             + jnp.einsum("bqhd,bkd->bhqk", rnd(q_rope[:, :, some]),
                          rnd(k_rope))) / math.sqrt(nope + rope)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", rnd(p),
                               rnd(v[:, :, some])))
    o = jnp.concatenate(outs, axis=2).reshape(rows, t, h * vd)
    return mm(o, w("o_proj.weight"))


def _swiglu(x, gate_w, up_w, down_w, mm):
    return mm(jax.nn.silu(mm(x, gate_w)) * mm(x, up_w), down_w)


def expert_block(u, params, layer, cfg, mm):
    """MoE(u) of u [rows, T, d]: the held experts' part, and the identity
    experts'. The choice is made here, in float32, from `u` itself."""
    p = f"model.layers.{layer}.mlp."
    real, held = _real_experts(cfg), cfg["n_routed_experts"]
    first = cfg.get("experts_held_from", 0)
    router = params[p + "router.classifier.weight"].astype(F32)
    scores = jax.nn.softmax(jnp.matmul(u, router), -1)
    bias = params.get(bias_name(layer))
    ranked = scores if bias is None else scores + bias.astype(F32)
    _, chosen = jax.lax.top_k(ranked, cfg["moe_topk"])
    weights = cfg["routed_scaling_factor"] \
        * jnp.take_along_axis(scores, chosen, -1)
    out = jnp.sum(jnp.where(chosen >= real, weights, 0.0), -1)[..., None] * u
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
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = f32("model.embed_tokens.weight")[ids]
        for i in range(cfg["num_layers"]):
            p = f"model.layers.{i}."
            attn = lambda j, y: _attention(
                y, lambda leaf: f32(f"{p}self_attn.{j}.{leaf}"), cfg, mm,
                rnd)
            ffn = lambda j, y: _swiglu(
                y, f32(f"{p}mlps.{j}.gate_proj.weight"),
                f32(f"{p}mlps.{j}.up_proj.weight"),
                f32(f"{p}mlps.{j}.down_proj.weight"), mm)
            h1 = x + attn(0, _rms(x, f32(p + "input_layernorm.0.weight"),
                                  eps))
            u = _rms(h1, f32(p + "post_attention_layernorm.0.weight"), eps)
            m = expert_block(u, params, i, cfg, mm)
            h2 = h1 + ffn(0, u)
            h3 = h2 + attn(1, _rms(h2, f32(p + "input_layernorm.1.weight"),
                                   eps))
            x = h3 + ffn(1, _rms(
                h3, f32(p + "post_attention_layernorm.1.weight"), eps)) + m
        x = _rms(x, f32("model.norm.weight"), eps)
        return mm(x, f32("lm_head.weight"))
