"""Plain Solar-Open2 reference: the forward pass in straightforward
`jax.numpy`, float32, matrix products at "highest". Serving only: no loss.

It follows `config.json` (`model_type` `solar_open2`) of
huggingface.co/upstage/Solar-Open2-250B. With T tokens, d = `hidden_size`,
RMS(x; w) = x / sqrt(mean(x^2) + `rms_norm_eps`) * w, no bias anywhere but
`dt_bias`:

  layer i: h = x + attn_i(RMS(x; w_in));  y = h + ffn_i(RMS(h; w_post)).
  attn of the kind `layer_types[i]` says (the source's `gqa_layers`: the
    layers it lists are "full_attention", every other "linear_attention"):

    "full_attention": q = u W_q as `num_attention_heads` heads of
    `head_dim`, k, v = u W_k, u W_v as `num_key_value_heads` heads; NO
    positions (`use_rope` false); query head i attends key/value head
    i // (heads / KH); scores q . k / sqrt(`head_dim`), causal softmax;
    `use_gqa_gate`: (attn * sigmoid(u W_g)) W_o, W_g [d, heads * head_dim],
    elementwise, from the layer's own input. A block of queries at a time
    (`QUERY_BLOCK`), so that 17,408 positions fit.

    "linear_attention" (KDA: `linear_attn_config`, H = `num_heads` heads
    of D = `head_dim` for keys and values, `num_kv_heads` null = H): q~,
    k~, v~ = u W_q, u W_k, u W_v ([d, H D] each); each through a depthwise
    causal convolution of `short_conv_kernel_size` taps over time (zeros
    before the sequence) and SiLU; a head's q = l2norm(q') / sqrt(D),
    k = l2norm(k'), v = v' (l2norm(x) = x / sqrt(sum x^2 + 1e-6)); the
    decay a CHANNEL g_t = -exp(A_log_h) * softplus(u W_fa W_fb + dt_bias)
    in R^{H x D}, a_t = exp(g_t); beta_t = 2 * sigmoid(u W_b) in R^H (the
    2 is `kda_allow_neg_eigval`); the state S in R^{D x D} a head, zeros
    before the sequence, TOKEN BY TOKEN (`lax.scan` over positions: no
    chunk, no kernel):
        S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t
    (heads are independent: `HEAD_BLOCK` of them at a time, projections
    and all) and y = (RMS_head(o_t; w_o_norm) * sigmoid(u W_ga W_gb)) W_o. The
    recurrence is float32 in EVERY `precision` (the configuration states
    the state's type beside the products'): `precision` rounds the
    operands of the projections around it.
  ffn of every layer (`first_k_dense_replace` 0; `intermediate_size` is
    read by nothing): s = sigmoid(u W_r) over all the published experts,
    in float32 in EVERY `precision`; the top `num_experts_per_tok` of s + b
    chosen (b the `e_score_correction_bias`, zeros, for the choice only);
    a chosen e weighs `routed_scaling_factor` * s_e / (the sum of the
    chosen s + 1e-20) (`norm_topk_prob`); the sum over the chosen HELD e
    of w_e E_e(u), E_e a SwiGLU of `moe_intermediate_size`, plus
    `n_shared_experts` shared SwiGLU of the same width that every token
    takes. A chosen expert that is not held adds nothing (a chip's share).
    Every held expert runs over every token, masked by the choice, an
    expert at a time; the choice is made HERE, from `u`.
  model: embedding, the layers, RMS (`norm`), an untied head.

Departures from the source and guesses, each also under the configuration
file's `assumed`: `kda_use_full_proj` false read as the low-rank pairs W_fa
W_fb and W_ga W_gb of rank `head_dim` (the Kimi Linear / flash-linear-
attention `KimiDeltaAttention` convention, arXiv:2510.26692); no bias but
`dt_bias`; the l2norm's 1e-6; q scaled by 1 / sqrt(D); the gate of the
GQA layers elementwise from the layer's input, no q/k norm there; sigmoid
router scores with a zero correction bias and the router's 1e-20.

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

QUERY_BLOCK = 128        # queries whose scores are held at once
HEAD_BLOCK = 8           # KDA heads whose recurrence runs at once
ROUTER_EPSILON = 1e-20
L2_EPSILON = 1e-6
LINEAR = "linear_attention"


def _published_experts(cfg):
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def bias_name(layer):
    return f"model.layers.{layer}.mlp.gate.e_score_correction_bias"


def _linear(cfg):
    """(heads, head width, taps) of a KDA layer."""
    lin = cfg["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]


def param_shapes(cfg):
    """{name: shape}, in the order the forward pass meets them. Matrices
    are stored [in, out]; the experts stacked; a convolution's taps
    [channels, taps], the newest input's tap last."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    lh, ld, taps = _linear(cfg)
    fe, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    shapes = {"model.embed_tokens.weight": (cfg["vocab_size"], d)}
    for i, kind in enumerate(cfg["layer_types"]):
        p = f"model.layers.{i}."
        a = p + "self_attn."
        shapes[p + "input_layernorm.weight"] = (d,)
        if kind == LINEAR:
            for s in "qkv":
                shapes[a + f"{s}_proj.weight"] = (d, lh * ld)
                shapes[a + f"{s}_conv1d.weight"] = (lh * ld, taps)
            shapes.update({
                a + "f_a_proj.weight": (d, ld),
                a + "f_b_proj.weight": (ld, lh * ld),
                a + "A_log": (lh,), a + "dt_bias": (lh * ld,),
                a + "b_proj.weight": (d, lh),
                a + "g_a_proj.weight": (d, ld),
                a + "g_b_proj.weight": (ld, lh * ld),
                a + "o_norm.weight": (ld,),
                a + "o_proj.weight": (lh * ld, d)})
        else:
            shapes.update({a + "q_proj.weight": (d, h * hd),
                           a + "k_proj.weight": (d, kh * hd),
                           a + "v_proj.weight": (d, kh * hd),
                           a + "g_proj.weight": (d, h * hd),
                           a + "o_proj.weight": (h * hd, d)})
        shapes[p + "post_attention_layernorm.weight"] = (d,)
        f = p + "mlp."
        shapes.update({
            f + "gate.weight": (d, _published_experts(cfg)),
            f + "experts.gate_proj.weight": (held, d, fe),
            f + "experts.up_proj.weight": (held, d, fe),
            f + "experts.down_proj.weight": (held, fe, d)})
        shared = fe * cfg["n_shared_experts"]
        shapes.update({f + "shared_experts.gate_proj.weight": (d, shared),
                       f + "shared_experts.up_proj.weight": (d, shared),
                       f + "shared_experts.down_proj.weight": (shared, d)})
    shapes["model.norm.weight"] = (d,)
    shapes["lm_head.weight"] = (d, cfg["vocab_size"])
    return shapes


def num_params(cfg):
    return sum(math.prod(s) for s in param_shapes(cfg).values())


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPSILON)


def gated_attention(u, w, cfg, mm, rnd):
    """A "full_attention" layer over u [rows, T, d], every position, a
    block of queries at a time; `w(leaf)` widens a leaf of this layer."""
    rows, t, _ = u.shape
    h, kh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    group = h // kh
    q = mm(u, w("q_proj.weight")).reshape(rows, t, kh, group, hd)
    k = mm(u, w("k_proj.weight")).reshape(rows, t, kh, hd)
    v = mm(u, w("v_proj.weight")).reshape(rows, t, kh, hd)
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    at = jnp.arange(t)

    def one(first):
        """Queries first .. first + block - 1."""
        qb = jax.lax.dynamic_slice_in_dim(q, first, block, axis=1)
        keep = at[None, :] <= first + jnp.arange(block)[:, None]
        s = jnp.einsum("bqkgd,btkd->bkgqt", rnd(qb), rnd(k)) / math.sqrt(hd)
        prob = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bkgqt,btkd->bqkgd", rnd(prob), rnd(v))
        return o.reshape(rows, block, h * hd)

    o = jax.lax.map(one, jnp.arange(0, t, block))  # [blocks, rows, block, .]
    o = jnp.moveaxis(o, 0, 1).reshape(rows, t, h * hd)
    return mm(o * jax.nn.sigmoid(mm(u, w("g_proj.weight"))),
              w("o_proj.weight"))


def short_conv(x, taps):
    """Depthwise causal convolution of x [rows, T, C] under taps [C, K]
    (the newest input's tap last; zeros before the sequence), then SiLU."""
    k = taps.shape[1]
    t = x.shape[1]
    xx = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(taps[:, j] * xx[:, j:j + t] for j in range(k)))


def delta_rule(q, k, v, g, beta, state=None):
    """The recurrence, token by token. q, k, g [rows, T, H, D], v [rows,
    T, H, Dv], beta [rows, T, H]; `state` [rows, H, D, Dv] (zeros where
    None). Returns (o [rows, T, H, Dv], the state after the last token)."""
    rows, _, h, d = q.shape
    if state is None:
        state = jnp.zeros((rows, h, d, v.shape[-1]), F32)

    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[..., None] * s
        u = b_t[..., None] * (v_t - jnp.sum(s * k_t[..., None], -2))
        s = s + k_t[..., None] * u[..., None, :]
        return s, jnp.sum(s * q_t[..., None], -2)

    state, o = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def kda_gates(u, w, cfg, mm):
    """(g [rows, T, H, D] log decays, beta [rows, T, H]) of a KDA layer's
    input u."""
    rows, t, _ = u.shape
    lh, ld, _ = _linear(cfg)
    f = mm(mm(u, w("f_a_proj.weight")), w("f_b_proj.weight")) + w("dt_bias")
    g = -jnp.exp(w("A_log"))[:, None] \
        * jax.nn.softplus(f.reshape(rows, t, lh, ld))
    beta = jax.nn.sigmoid(mm(u, w("b_proj.weight")))
    if cfg["kda_allow_neg_eigval"]:
        beta = 2.0 * beta
    return g, beta


def linear_attention(u, w, cfg, mm, rnd):
    """A "linear_attention" (KDA) layer over u [rows, T, d]. Heads are
    independent, so the projections, the convolutions, the decays and the
    recurrence run `HEAD_BLOCK` heads at a time (every product's operands
    rounded WHOLE first, as `mm` rounds them): what is held of q, k, v, g
    and o is a block's, and 17,408 positions fit beside the control's
    second pass."""
    rows, t, _ = u.shape
    lh, ld, _ = _linear(cfg)
    block = HEAD_BLOCK if lh % HEAD_BLOCK == 0 else lh
    blocks = lh // block

    def columns(x):
        """[..., H D] -> [blocks, ..., block D]: a block of heads first."""
        return jnp.moveaxis(x.reshape(x.shape[:-1] + (blocks, block * ld)),
                            -2, 0)

    ru = rnd(u)
    decay_in = rnd(mm(u, w("f_a_proj.weight")))
    beta = jax.nn.sigmoid(mm(u, w("b_proj.weight")))
    if cfg["kda_allow_neg_eigval"]:
        beta = 2.0 * beta

    def heads(x):
        proj, taps, f_b, dt_bias, a_log, beta_b = x
        q, k, v = (short_conv(jnp.matmul(ru, proj[i]), taps[i])
                   .reshape(rows, t, block, ld) for i in range(3))
        f = jnp.matmul(decay_in, f_b) + dt_bias
        g = -jnp.exp(a_log)[:, None] \
            * jax.nn.softplus(f.reshape(rows, t, block, ld))
        return delta_rule(_l2norm(q) / math.sqrt(ld), _l2norm(k), v, g,
                          beta_b)[0]

    parts = (
        jnp.stack([columns(rnd(w(f"{s}_proj.weight"))) for s in "qkv"], 1),
        jnp.stack([columns(w(f"{s}_conv1d.weight").T).swapaxes(-1, -2)
                   for s in "qkv"], 1),
        columns(rnd(w("f_b_proj.weight"))), columns(w("dt_bias")),
        w("A_log").reshape(blocks, block),
        jnp.moveaxis(beta.reshape(rows, t, blocks, block), 2, 0))
    # a block after the other in the trace itself (a `lax.map` over the
    # blocks held 18 GB at 17,408 positions on the chip's compiler)
    o = jnp.concatenate([heads(tuple(part[i] for part in parts))
                         for i in range(blocks)], axis=2)
    gate = jax.nn.sigmoid(mm(mm(u, w("g_a_proj.weight")),
                             w("g_b_proj.weight")))
    o = _rms(o, w("o_norm.weight"), cfg["rms_norm_eps"]) \
        * gate.reshape(rows, t, lh, ld)
    return mm(o.reshape(rows, t, lh * ld), w("o_proj.weight"))


def _swiglu(x, gate, up, down, mm):
    return mm(jax.nn.silu(mm(x, gate)) * mm(x, up), down)


def routed_sum(u, params, layer, cfg, mm, held=None, first=None):
    """The part of the routed sum over u [rows, T, d] that experts `first`
    .. `first + held - 1` give (the file's own share where not said). The
    choice is made here, in float32, from `u` itself, over ALL the
    published experts; every held expert over every token, masked."""
    p = f"model.layers.{layer}.mlp."
    held = cfg["n_routed_experts"] if held is None else held
    first = cfg.get("experts_held_from", 0) if first is None else first
    scores = jax.nn.sigmoid(jnp.matmul(u, params[p + "gate.weight"]
                                       .astype(F32)))
    bias = params.get(bias_name(layer))
    ranked = scores if bias is None else scores + bias.astype(F32)
    _, chosen = jax.lax.top_k(ranked, cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, -1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, -1, keepdims=True)
                             + ROUTER_EPSILON)
    weights = cfg["routed_scaling_factor"] * weights
    out = jnp.zeros_like(u)
    for e in range(held):
        mine = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), -1)
        leaf = lambda name: params[p + f"experts.{name}.weight"][e] \
            .astype(F32)
        out = out + mine[..., None] * _swiglu(
            u, leaf("gate_proj"), leaf("up_proj"), leaf("down_proj"), mm)
    return out


def shared_expert(u, params, layer, mm):
    """What every token takes, whatever the router chose."""
    leaf = lambda name: params[
        f"model.layers.{layer}.mlp.shared_experts.{name}.weight"].astype(F32)
    return _swiglu(u, leaf("gate_proj"), leaf("up_proj"), leaf("down_proj"),
                   mm)


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
        for i, kind in enumerate(cfg["layer_types"]):
            p = f"model.layers.{i}."
            w = lambda leaf, a=p + "self_attn.": f32(a + leaf)
            u = _rms(x, f32(p + "input_layernorm.weight"), eps)
            x = x + (linear_attention(u, w, cfg, mm, rnd) if kind == LINEAR
                     else gated_attention(u, w, cfg, mm, rnd))
            u = _rms(x, f32(p + "post_attention_layernorm.weight"), eps)
            x = x + routed_sum(u, params, i, cfg, mm) \
                + shared_expert(u, params, i, mm)
        x = _rms(x, f32("model.norm.weight"), eps)
        return mm(x, f32("lm_head.weight"))
