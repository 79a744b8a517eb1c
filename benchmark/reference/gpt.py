"""Plain GPT-2 / GPT-3 reference: forward, loss, gradients and AdamW in
straightforward `jax.numpy`, float32, matrix products at "highest".

It follows Radford et al. 2019 (GPT-2) as the published `config.json` of
`gpt2` spells it out: learned positions, pre-LayerNorm blocks, one fused
q/k/v projection, tanh-approximated GELU, logits through the transposed
token embedding. No kernel, no cache, no batching tricks. It imports
nothing of the program and is handed nothing the program made: weights and
batches come from the benchmark's own seed functions.

`precision` selects the arithmetic of the matrix products:
  "float32"  the reference itself;
  "bfloat16" / "fp8"  the control, the precision a later PR would be
             tempted by: both operands of every matrix product are rounded
             (fp8: e4m3 with a per-tensor scale forward, e5m2 backward, as
             fp8 training recipes do) and the product is accumulated in
             float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def param_shapes(cfg):
    """{name: shape}, in the order the forward pass meets them. `cfg` is
    the configuration file's `model` group."""
    h, ff = cfg["n_embd"], cfg["n_inner"]
    shapes = {"wte.weight": (cfg["vocab_size"], h),
              "wpe.weight": (cfg["n_positions"], h)}
    for i in range(cfg["n_layer"]):
        p = f"h.{i}."
        shapes.update({
            p + "ln_1.weight": (h,), p + "ln_1.bias": (h,),
            p + "attn.qkv_proj.weight": (h, 3 * h),
            p + "attn.qkv_proj.bias": (3 * h,),
            p + "attn.out_proj.weight": (h, h),
            p + "attn.out_proj.bias": (h,),
            p + "ln_2.weight": (h,), p + "ln_2.bias": (h,),
            p + "mlp.fc_in.weight": (h, ff), p + "mlp.fc_in.bias": (ff,),
            p + "mlp.fc_out.weight": (ff, h), p + "mlp.fc_out.bias": (h,),
        })
    shapes.update({"ln_f.weight": (h,), "ln_f.bias": (h,)})
    return shapes


def num_params(cfg):
    return sum(math.prod(s) for s in param_shapes(cfg).values())


# ---------------------------------------------------------------------------
# arithmetic of a matrix product, by precision
# ---------------------------------------------------------------------------

def _fake_quant(x, dtype):
    """Round to an 8-bit float with one scale for the whole tensor."""
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(F32) * scale


@jax.custom_vjp
def _fp8(x):
    return _fake_quant(x, jnp.float8_e4m3fn)


_fp8.defvjp(lambda x: (_fp8(x), None),
            lambda _, g: (_fake_quant(g, jnp.float8_e5m2),))


def _rounder(precision):
    if precision == "float32":
        return lambda x: x
    if precision == "bfloat16":
        return lambda x: x.astype(jnp.bfloat16).astype(F32)
    if precision == "fp8":
        return _fp8
    raise ValueError(f"unknown precision {precision!r}")


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, cfg, rnd):
    b, n, h = x.shape
    heads = cfg["n_head"]
    d = h // heads
    mm = lambda a, w: jnp.matmul(rnd(a), rnd(w))
    a = _layer_norm(x, p["ln_1.weight"], p["ln_1.bias"],
                    cfg["layer_norm_epsilon"])
    qkv = mm(a, p["attn.qkv_proj.weight"]) + p["attn.qkv_proj.bias"]
    q, k, v = jnp.moveaxis(qkv.reshape(b, n, 3, heads, d), 2, 0)
    s = jnp.einsum("bqhd,bkhd->bhqk", rnd(q), rnd(k)) / math.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", rnd(jax.nn.softmax(s, -1)), rnd(v))
    x = x + mm(o.reshape(b, n, h), p["attn.out_proj.weight"]) \
        + p["attn.out_proj.bias"]
    a = _layer_norm(x, p["ln_2.weight"], p["ln_2.bias"],
                    cfg["layer_norm_epsilon"])
    a = _gelu_tanh(mm(a, p["mlp.fc_in.weight"]) + p["mlp.fc_in.bias"])
    return x + mm(a, p["mlp.fc_out.weight"]) + p["mlp.fc_out.bias"]


LAYER_LEAVES = ("ln_1.weight", "ln_1.bias", "attn.qkv_proj.weight",
                "attn.qkv_proj.bias", "attn.out_proj.weight",
                "attn.out_proj.bias", "ln_2.weight", "ln_2.bias",
                "mlp.fc_in.weight", "mlp.fc_in.bias", "mlp.fc_out.weight",
                "mlp.fc_out.bias")


def forward(params, ids, cfg, precision="float32"):
    """Logits [batch, seq, vocab] of token ids [batch, seq]."""
    rnd = _rounder(precision)
    params = {k: v.astype(F32) for k, v in params.items()}
    n = ids.shape[1]
    # the layers are alike: stack their leaves and scan, so that the
    # compiler sees one block, and keep one layer's activations at a time
    # in the backward pass
    layers = {leaf: jnp.stack([params[f"h.{i}.{leaf}"]
                               for i in range(cfg["n_layer"])])
              for leaf in LAYER_LEAVES}
    block = jax.checkpoint(
        lambda x, layer: (_block(x, layer, cfg, rnd), None))
    with jax.default_matmul_precision("highest"):
        x = params["wte.weight"][ids] + params["wpe.weight"][:n][None]
        x, _ = jax.lax.scan(block, x, layers)
        x = _layer_norm(x, params["ln_f.weight"], params["ln_f.bias"],
                        cfg["layer_norm_epsilon"])
        return jnp.matmul(rnd(x), rnd(params["wte.weight"]).T)


def loss_sum(params, ids, labels, cfg, precision="float32"):
    """Summed next-token cross entropy of the rows given (the caller
    divides by the whole batch's token count)."""
    logp = jax.nn.log_softmax(forward(params, ids, cfg, precision), -1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[..., None], -1))


def loss_and_grads(params, ids, labels, cfg, precision="float32",
                   rows_per_block=None):
    """Mean loss over the batch and its gradients, in blocks of rows so
    that one block's logits are all that is held at a time."""
    rows, seq = ids.shape
    block = rows_per_block or rows
    if rows % block:
        raise ValueError(f"{rows} rows do not split into blocks of {block}")
    vg = jax.value_and_grad(loss_sum)

    def add_block(carry, rows_of):
        l, g = vg(params, rows_of[0], rows_of[1], cfg, precision)
        return (carry[0] + l, jax.tree.map(jnp.add, carry[1], g)), None

    zero = (jnp.zeros((), F32),
            jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params))
    (total, grads), _ = jax.lax.scan(
        add_block, zero, (ids.reshape(-1, block, seq),
                          labels.reshape(-1, block, seq)))
    count = float(ids.size)
    return total / count, jax.tree.map(lambda g: g / count, grads)


def adamw_step(params, m1, m2, grads, step, opt):
    """Loshchilov & Hutter's AdamW as the configuration's `optimizer`
    group states it: decay applied to every parameter before the update."""
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["epsilon"]
    lr, wd = opt["learning_rate"], opt["weight_decay"]
    t = jnp.asarray(step, F32)
    lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
    new_p, new_m1, new_m2 = {}, {}, {}
    for k, p in params.items():
        g = grads[k]
        new_m1[k] = b1 * m1[k] + (1 - b1) * g
        new_m2[k] = b2 * m2[k] + (1 - b2) * g * g
        new_p[k] = p * (1 - lr * wd) \
            - lr_t * new_m1[k] / (jnp.sqrt(new_m2[k]) + eps)
    return new_p, new_m1, new_m2


def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(F32))))
            for k, v in tree.items()}
