"""A plain reference of a model that ROUTES its tokens to experts, for the
tests and the chip readings that show what a serve check can tell apart
when the model makes a discrete choice. It keeps the contract of
`benchmark/reference/__init__.py` and shares only
`benchmark.reference.common`.

A decoder with the key names of a newer `config.json`: RMSNorm, causal
attention (heads of `head_dim`, one fused projection, no positions: the
builder's choice, the attention is not what is studied), no biases, an
untied head; `first_k_dense_replace` leading layers with a dense gated
SiLU MLP, then expert layers as DeepSeek-V3's technical report spells
them out (arXiv:2412.19437, section 2.1.2: sigmoid scores, the bias of
its auxiliary-loss-free balancing, one group):

    s = sigmoid(x W_r)                     float32 in every `precision`
    chosen = top-k of (s + b)              b is used for the choice only
    g = s[chosen] / sum(s[chosen])         (`norm_topk_prob`)
    y = shared(x) + routed_scaling_factor * sum_e g_e expert_e(x)

Every expert is computed on every token and masked by its weight, an
expert at a time (a scan over the stacked expert leaves, each widened
where it is used). The router runs in float32 whatever `precision` says,
as the programs that serve such models keep it, and takes its input from
this forward pass's own activations: a discrete choice is never handed in
from outside (see the contract)."""
import math

import jax
import jax.numpy as jnp

from benchmark.reference.common import F32, rounder


def param_shapes(cfg):
    h, m = cfg["hidden_size"], cfg["moe_intermediate_size"]
    experts, width = cfg["n_routed_experts"], \
        cfg["num_attention_heads"] * cfg["head_dim"]
    shapes = {"embed.weight": (cfg["vocab_size"], h)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        shapes.update({
            p + "attn_norm.weight": (h,), p + "qkv.weight": (h, 3 * width),
            p + "o.weight": (width, h), p + "mlp_norm.weight": (h,)})
        if i < cfg["first_k_dense_replace"]:
            ff = cfg["intermediate_size"]
            shapes.update({p + "gate_up.weight": (h, 2 * ff),
                           p + "down.weight": (ff, h)})
            continue
        shared = m * cfg["n_shared_experts"]
        shapes.update({
            p + "router.weight": (h, experts), p + "router.bias": (experts,),
            p + "experts.gate_up.weight": (experts, h, 2 * m),
            p + "experts.down.weight": (experts, m, h),
            p + "shared.gate_up.weight": (h, 2 * shared),
            p + "shared.down.weight": (shared, h)})
    shapes.update({"norm.weight": (h,), "head.weight": (h, cfg["vocab_size"])})
    return shapes


def num_params(cfg):
    return sum(math.prod(s) for s in param_shapes(cfg).values())


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _gated(x, gate_up, down, mm):
    gate, up = jnp.split(mm(x, gate_up), 2, -1)
    return mm(jax.nn.silu(gate) * up, down)


def route(x, w_router, bias, cfg):
    """(weights [..., experts], zero off the chosen; chosen [..., experts]
    bool). Float32, products at "highest"."""
    scores = jax.nn.sigmoid(jnp.matmul(x, w_router))
    _, picked = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    chosen = jnp.any(jax.nn.one_hot(picked, scores.shape[-1], dtype=bool), -2)
    gates = jnp.where(chosen, scores, 0.0)
    if cfg["norm_topk_prob"]:
        gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    return gates * cfg["routed_scaling_factor"], chosen


def _experts(x, gates, gate_up, down, mm):
    """sum_e gates[..., e] * expert_e(x), an expert at a time."""
    def add(total, expert):
        w_in, w_out, g = expert
        y = _gated(x, w_in.astype(F32), w_out.astype(F32), mm)
        return total + g[..., None] * y, None
    total, _ = jax.lax.scan(add, jnp.zeros_like(x),
                            (gate_up, down, jnp.moveaxis(gates, -1, 0)))
    return total


def forward_and_choices(params, ids, cfg, precision="float32"):
    """(logits [rows, seq, vocabulary], chosen [expert layers, rows, seq,
    experts] bool: which experts each position was routed to)."""
    rnd = rounder(precision)
    f32 = lambda name: params[name].astype(F32)
    mm = lambda a, w: jnp.matmul(rnd(a), rnd(w))
    rows, n = ids.shape
    heads, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    choices = []
    with jax.default_matmul_precision("highest"):
        x = f32("embed.weight")[ids]
        for i in range(cfg["num_hidden_layers"]):
            p = f"layers.{i}."
            a = _rms(x, f32(p + "attn_norm.weight"), eps)
            q, k, v = jnp.moveaxis(mm(a, f32(p + "qkv.weight")).reshape(
                rows, n, 3, heads, -1), 2, 0)
            s = jnp.einsum("bqhd,bkhd->bhqk", rnd(q), rnd(k)) \
                / math.sqrt(q.shape[-1])
            s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
            o = jnp.einsum("bhqk,bkhd->bqhd", rnd(jax.nn.softmax(s, -1)),
                           rnd(v))
            x = x + mm(o.reshape(rows, n, -1), f32(p + "o.weight"))
            a = _rms(x, f32(p + "mlp_norm.weight"), eps)
            if i < cfg["first_k_dense_replace"]:
                x = x + _gated(a, f32(p + "gate_up.weight"),
                               f32(p + "down.weight"), mm)
                continue
            gates, chosen = route(a, f32(p + "router.weight"),
                                  f32(p + "router.bias"), cfg)
            choices.append(chosen)
            x = x + _gated(a, f32(p + "shared.gate_up.weight"),
                           f32(p + "shared.down.weight"), mm) \
                + _experts(a, gates, params[p + "experts.gate_up.weight"],
                           params[p + "experts.down.weight"], mm)
        logits = mm(_rms(x, f32("norm.weight"), eps), f32("head.weight"))
    return logits, jnp.stack(choices)


def forward(params, ids, cfg, precision="float32"):
    return forward_and_choices(params, ids, cfg, precision)[0]
