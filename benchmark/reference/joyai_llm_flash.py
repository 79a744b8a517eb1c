"""Plain JoyAI-LLM-Flash reference: forward, the two-term loss and its
gradients in straightforward `jax.numpy`, float32, matrix products at
"highest". No kernel, no cache, no grouping.

It follows `config.json` of huggingface.co/jdopensource/JoyAI-LLM-Flash
(`model_type` `joyai_llm_flash`, key for key the DeepSeek-V3 family's), the
layer equations of DeepSeek-V3 (arXiv:2412.19437 sections 2.1-2.2) and the
family's `modeling_deepseek.py` / `deepseek_mtp.py`. With T tokens, d =
`hidden_size`, RMS(x; g) = x / sqrt(mean(x^2) + `rms_norm_eps`) * g, no
bias anywhere:

  attention (multi-head latent attention), EXPANDED at every position:
    c_q = RMS(x W_qa; g_q);  q = c_q W_qb, reshaped [T, heads, nope + rope];
    [c | k_r] = x W_kva (kv_lora_rank + rope columns);  c_kv = RMS(c; g_kv);
    rotary positions (pairs (2i, 2i+1), theta `rope_theta`, no scaling) on
    q's last `rope` values and on k_r, which all heads share;
    [k_nope | v] = c_kv W_kvb reshaped [T, heads, nope + v];
    scores (q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope), causal
    softmax, o = sum p v, out = concat(o) W_o. A head at a time.
  dense FFN (the `first_k_dense_replace` leading layers):
    (silu(u W_g) * (u W_u)) W_d, width `intermediate_size`.
  expert layer MoE(u): s = sigmoid(u W_r) over ALL the published experts,
    in float32 in EVERY `precision`; the top `num_experts_per_tok` of s + b
    are chosen (b the `e_score_correction_bias`, for the choice only; zeros
    unless `params` carries the leaf); a chosen e weighs
    `routed_scaling_factor` * s_e / (sum of the chosen s + 1e-20), the sum
    over ALL the chosen, held here or not;
    MoE(u) = sum over chosen HELD e of w_e E_e(u) + E_shared(u), every E a
    SwiGLU of `moe_intermediate_size` (the shared one `n_shared_experts`
    times as wide). A chosen expert that is not held adds nothing (a
    chip's share). Every held expert runs over every token, masked by the
    choice, an expert at a time. b has no gradient.
  layer: h = x + MLA(RMS(x; g_1));  y = h + F(RMS(h; g_2)).
  model: embedding, the layers, h_main = RMS(.; g_f), logits = h_main
    W_head (untied).
  MTP module (layer `num_hidden_layers`; arXiv:2412.19437 eq. 21-24), with
    t_{i+1} = labels[i]:  z_i = [RMS(Emb(t_{i+1}); g_e) | RMS(h_main,i;
    g_h)] W_eh, one more expert layer over z, logits' = RMS(.; g_s) W_head
    with the SAME Emb and W_head; it predicts t_{i+2} = labels[i + 1] at
    every position of a row but the last.
  loss: CE(logits, t_{i+1}) + `mtp_loss_weight` * CE(logits', t_{i+2}),
    each a mean over its own positions.

Departures and assumptions are the configuration file's `assumed`. The
router's width is the PUBLISHED count (`published.n_routed_experts` where
the file cuts the experts to a share, else `n_routed_experts`);
`n_routed_experts` is what is held, from id `experts_held_from`. It
imports nothing of the program. `precision`: see `common` (both operands of
every matrix product rounded, the router's excepted).

So that it fits beside its own AdamW step on the chip it checks
(`correct.reference_train`: 20 B a parameter before any activation), a
layer, a head of its attention, an expert and a head-and-loss pass are
each made again in the backward pass (`jax.checkpoint`), the loss works in
blocks of rows under ONE gradient, and one head's scores are all of
attention that is held at a time.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .common import F32, rounder


def _ranked_experts(cfg):
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def bias_name(layer):
    return f"model.layers.{layer}.mlp.gate.e_score_correction_bias"


def _attention_shapes(cfg, p):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    a = p + "self_attn."
    return {
        p + "input_layernorm.weight": (d,),
        a + "q_a_proj.weight": (d, ql),
        a + "q_a_layernorm.weight": (ql,),
        a + "q_b_proj.weight": (ql, h * (nope + rope)),
        a + "kv_a_proj_with_mqa.weight": (d, kl + rope),
        a + "kv_a_layernorm.weight": (kl,),
        a + "kv_b_proj.weight": (kl, h * (nope + vd)),
        a + "o_proj.weight": (h * vd, d),
        p + "post_attention_layernorm.weight": (d,),
    }


def _expert_layer_shapes(cfg, p):
    d, fe = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held, fs = cfg["n_routed_experts"], fe * cfg["n_shared_experts"]
    m = p + "mlp."
    return {
        m + "gate.weight": (d, _ranked_experts(cfg)),
        m + "experts.gate_proj.weight": (held, d, fe),
        m + "experts.up_proj.weight": (held, d, fe),
        m + "experts.down_proj.weight": (held, fe, d),
        m + "shared_experts.gate_proj.weight": (d, fs),
        m + "shared_experts.up_proj.weight": (d, fs),
        m + "shared_experts.down_proj.weight": (fs, d),
    }


def param_shapes(cfg):
    """{name: shape}, in the order the forward pass meets them; the MTP
    module is layer `num_hidden_layers`."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    layers = cfg["num_hidden_layers"]
    shapes = {"model.embed_tokens.weight": (cfg["vocab_size"], d)}
    for i in range(layers):
        p = f"model.layers.{i}."
        shapes.update(_attention_shapes(cfg, p))
        if i < cfg["first_k_dense_replace"]:
            shapes.update({p + "mlp.gate_proj.weight": (d, ff),
                           p + "mlp.up_proj.weight": (d, ff),
                           p + "mlp.down_proj.weight": (ff, d)})
        else:
            shapes.update(_expert_layer_shapes(cfg, p))
    shapes.update({"model.norm.weight": (d,),
                   "lm_head.weight": (d, cfg["vocab_size"])})
    p = f"model.layers.{layers}."
    shapes.update({p + "enorm.weight": (d,), p + "hnorm.weight": (d,),
                   p + "eh_proj.weight": (2 * d, d)})
    shapes.update(_attention_shapes(cfg, p))
    shapes.update(_expert_layer_shapes(cfg, p))
    shapes[p + "shared_head.norm.weight"] = (d,)
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
    """Latent attention over x [rows, T, d]; `w(leaf)` is a float32 leaf of
    this layer's `self_attn`."""
    rows, t, _ = x.shape
    h = cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    kl, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    c_q = _rms(mm(x, w("q_a_proj.weight")), w("q_a_layernorm.weight"), eps)
    q = mm(c_q, w("q_b_proj.weight")).reshape(rows, t, h, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], cfg["rope_theta"])
    ckr = mm(x, w("kv_a_proj_with_mqa.weight"))
    c_kv = _rms(ckr[..., :kl], w("kv_a_layernorm.weight"), eps)
    k_rope = _rope(ckr[..., kl:], cfg["rope_theta"])           # [rows, T, r]
    kv = mm(c_kv, w("kv_b_proj.weight")).reshape(rows, t, h, nope + vd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scale = 1.0 / math.sqrt(nope + rope)

    @jax.checkpoint
    def one_head(of_head):
        qn, qr, kn, v = of_head                                # [rows, T, .]
        s = (jnp.einsum("bqd,bkd->bqk", rnd(qn), rnd(kn))
             + jnp.einsum("bqd,bkd->bqk", rnd(qr), rnd(k_rope))) * scale
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        return jnp.einsum("bqk,bkd->bqd", rnd(p), rnd(v))

    by_head = lambda a: jnp.moveaxis(a, 2, 0)                  # heads first
    o = jax.lax.map(one_head, (by_head(q_nope), by_head(q_rope),
                               by_head(kv[..., :nope]),
                               by_head(kv[..., nope:])))
    o = jnp.moveaxis(o, 0, 2).reshape(rows, t, h * vd)
    return mm(o, w("o_proj.weight"))


def _swiglu(x, gate_w, up_w, down_w, mm):
    return mm(jax.nn.silu(mm(x, gate_w)) * mm(x, up_w), down_w)


def expert_layer(u, w, bias, cfg, mm):
    """MoE(u) of u [rows, T, d]: the held experts' part and the shared
    expert's. The choice is made here, in float32, from `u` itself;
    `w(leaf)` is a float32 leaf of this layer's `mlp`."""
    first = cfg.get("experts_held_from", 0)
    scores = jax.nn.sigmoid(jnp.matmul(u, w("gate.weight")))
    ranked = scores if bias is None else scores + bias.astype(F32)
    _, chosen = jax.lax.top_k(ranked, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, -1)
    weights = cfg["routed_scaling_factor"] * picked \
        / (jnp.sum(picked, -1, keepdims=True) + 1e-20)

    @jax.checkpoint
    def one_expert(e, gate_w, up_w, down_w):
        mine = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        return mine[..., None] * _swiglu(u, gate_w, up_w, down_w, mm)

    def add_expert(out, of_expert):
        return out + one_expert(*of_expert), None

    held = cfg["n_routed_experts"]
    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(u), (
        jnp.arange(held), w("experts.gate_proj.weight"),
        w("experts.up_proj.weight"), w("experts.down_proj.weight")))
    return out + _swiglu(u, w("shared_experts.gate_proj.weight"),
                         w("shared_experts.up_proj.weight"),
                         w("shared_experts.down_proj.weight"), mm)


def _layer(x, params, layer, cfg, mm, rnd):
    """One block over x [rows, T, d], made again in the backward pass."""
    p = f"model.layers.{layer}."
    eps = cfg["rms_norm_eps"]

    @jax.checkpoint
    def block(x, leaves, bias):
        w = lambda leaf: leaves[p + leaf].astype(F32)
        h = x + _attention(_rms(x, w("input_layernorm.weight"), eps),
                           lambda leaf: w("self_attn." + leaf), cfg, mm, rnd)
        u = _rms(h, w("post_attention_layernorm.weight"), eps)
        mlp = lambda leaf: w("mlp." + leaf)
        if layer < cfg["first_k_dense_replace"]:
            return h + _swiglu(u, mlp("gate_proj.weight"),
                               mlp("up_proj.weight"),
                               mlp("down_proj.weight"), mm)
        return h + expert_layer(u, mlp, bias, cfg, mm)

    leaves = {k: v for k, v in params.items()
              if k.startswith(p) and k != bias_name(layer)}
    return block(x, leaves, params.get(bias_name(layer)))


def _trunk(params, ids, cfg, mm, rnd):
    """The main model up to its last block: x [rows, T, d] (before g_f)."""
    x = params["model.embed_tokens.weight"].astype(F32)[ids]
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, params, i, cfg, mm, rnd)
    return x


def forward(params, ids, cfg, precision="float32"):
    """The main model's logits [rows, seq, vocabulary] of ids [rows,
    seq]."""
    rnd = rounder(precision)
    mm = lambda a, w: jnp.matmul(rnd(a), rnd(w))
    with jax.default_matmul_precision("highest"):
        x = _rms(_trunk(params, ids, cfg, mm, rnd),
                 params["model.norm.weight"].astype(F32),
                 cfg["rms_norm_eps"])
        return mm(x, params["lm_head.weight"].astype(F32))


def loss_sums(params, ids, labels, cfg, precision="float32"):
    """(summed cross entropy of the main head against labels, summed cross
    entropy of the MTP head against the labels one further on, over every
    position of a row but its last) of the rows given."""
    rnd = rounder(precision)
    mm = lambda a, w: jnp.matmul(rnd(a), rnd(w))
    eps = cfg["rms_norm_eps"]
    f32 = lambda name: params[name].astype(F32)

    @jax.checkpoint
    def head_sum(h, norm_w, head_w, targets, counted):
        logp = jax.nn.log_softmax(mm(_rms(h, norm_w, eps), head_w), -1)
        nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
        return jnp.sum(jnp.where(counted, nll, 0.0))

    with jax.default_matmul_precision("highest"):
        x = _trunk(params, ids, cfg, mm, rnd)
        main = head_sum(x, f32("model.norm.weight"), f32("lm_head.weight"),
                        labels, jnp.ones(labels.shape, bool))
        layer = cfg["num_hidden_layers"]
        p = f"model.layers.{layer}."
        h = _rms(x, f32("model.norm.weight"), eps)
        z = mm(jnp.concatenate([
            _rms(f32("model.embed_tokens.weight")[labels],
                 f32(p + "enorm.weight"), eps),
            _rms(h, f32(p + "hnorm.weight"), eps)], -1),
            f32(p + "eh_proj.weight"))
        y = _layer(z, params, layer, cfg, mm, rnd)
        t = labels.shape[1]
        mtp = head_sum(y, f32(p + "shared_head.norm.weight"),
                       f32("lm_head.weight"), jnp.roll(labels, -1, axis=1),
                       jnp.broadcast_to(jnp.arange(t) < t - 1, labels.shape))
    return main, mtp


def loss_terms(params, ids, labels, cfg, precision="float32"):
    """(main term, MTP term), each the mean over its own positions."""
    rows, seq = ids.shape
    main, mtp = loss_sums(params, ids, labels, cfg, precision)
    return main / (rows * seq), mtp / (rows * (seq - 1))


def loss_and_grads(params, ids, labels, cfg, precision="float32",
                   rows_per_block=None):
    """``main term + mtp_loss_weight * MTP term`` over the batch and its
    gradients, in blocks of rows so that one block's activations are all
    that is held at a time."""
    rows, seq = ids.shape
    block = rows_per_block or rows
    if rows % block:
        raise ValueError(f"{rows} rows do not split into blocks of {block}")
    weight = cfg["mtp_loss_weight"]

    def loss(p):
        @jax.checkpoint
        def block_loss(ids_, labels_):
            main, mtp = loss_sums(p, ids_, labels_, cfg, precision)
            return main / (rows * seq) + weight * mtp / (rows * (seq - 1))

        # ONE gradient over the sum of the blocks' losses: the backward
        # pass adds each block's gradient into the running one. A gradient
        # of its own for every block, added afterwards, compiles to 1.4 GB
        # more at the cell's size (PERF.md section 4)
        total, _ = jax.lax.scan(
            lambda acc, of_block: (acc + block_loss(*of_block), None),
            jnp.zeros((), F32), (ids.reshape(-1, block, seq),
                                 labels.reshape(-1, block, seq)))
        return total

    return jax.value_and_grad(loss)(params)
