"""Multi-head latent attention (MLA), the part every model with it shares.

DeepSeek-V2's attention (arXiv:2405.04434 section 2.1), as `longcat_flash.py`
serves it and `joyai_llm_flash.py` trains it: queries through a low-rank
bottleneck, keys and values expanded from ONE compressed row a token
(`kv_lora_rank` values) plus ONE rotary key (`qk_rope_head_dim` values) that
all heads share. Plain `jax.numpy` over arrays; `w(leaf)` hands a sublayer's
weight by the leaf's name (`q_a_proj.weight`, ...), stored [in, out]; `cfg`
is the model's configuration, read for `num_attention_heads`, the five MLA
sizes, `rms_norm_eps`, `rope_theta` and, where the model has them,
LongCat's `mla_scale_q_lora` / `mla_scale_kv_lora` (absent: no factor).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = ["rms", "rotate", "queries_and_row", "expanded", "causal_train"]


def rms(x, g, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                            + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def rotate(x, pos, theta):
    """Rotary positions over the last axis of x ``[..., T, (heads,) r]``,
    pairs interleaved (2i, 2i+1); pos ``[..., T]`` int."""
    r = x.shape[-1]
    freq = jnp.float32(theta) ** (-jnp.arange(0, r, 2, dtype=jnp.float32)
                                  / r)
    ang = pos.astype(jnp.float32)[..., None] * freq          # [..., T, r/2]
    if x.ndim == ang.ndim + 1:                               # a heads axis
        ang = ang[..., None, :]
    x32 = x.astype(jnp.float32)
    even, odd = x32[..., 0::2], x32[..., 1::2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape).astype(x.dtype)


def queries_and_row(x, pos, w, cfg):
    """x ``[B, T, d]`` -> q_nope ``[B, T, H, nope]``, q_rope (rotated)
    ``[B, T, H, rope]``, the row's parts c_kv ``[B, T, kv_lora]``
    (normed and scaled) and k_rope ``[B, T, rope]`` (rotated)."""
    h, nope, rope = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim)
    b, t, d = x.shape
    c_q = rms(x @ w("q_a_proj.weight"), w("q_a_layernorm.weight"),
              cfg.rms_norm_eps)
    q = c_q @ w("q_b_proj.weight")
    if getattr(cfg, "mla_scale_q_lora", False):
        q = q * jnp.asarray(math.sqrt(d / cfg.q_lora_rank), q.dtype)
    q = q.reshape(b, t, h, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    ckr = x @ w("kv_a_proj_with_mqa.weight")
    c_kv = rms(ckr[..., :cfg.kv_lora_rank],
               w("kv_a_layernorm.weight"), cfg.rms_norm_eps)
    if getattr(cfg, "mla_scale_kv_lora", False):
        c_kv = c_kv * jnp.asarray(math.sqrt(d / cfg.kv_lora_rank),
                                  c_kv.dtype)
    k_rope = rotate(ckr[..., cfg.kv_lora_rank:], pos, cfg.rope_theta)
    return q_nope, rotate(q_rope, pos, cfg.rope_theta), c_kv, k_rope


def expanded(q_nope, q_rope, c_kv, k_rope, w, cfg, past):
    """Causal attention with every head's keys and values expanded from
    the rows, scores as one ``[B, H, T, total]`` array (prefill, the eager
    path, and the training path off the flash kernel). `past` rows precede
    the call's own."""
    h, nope, vd = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                   cfg.v_head_dim)
    b, t = q_nope.shape[:2]
    total = c_kv.shape[1]
    kv = (c_kv @ w("kv_b_proj.weight")).reshape(b, total, h, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = 1.0 / math.sqrt(nope + cfg.qk_rope_head_dim)
    s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope,
                      preferred_element_type=jnp.float32)) * scale
    keep = (jnp.arange(total)[None, :]
            <= past + jnp.arange(t)[:, None])
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                   preferred_element_type=jnp.float32).astype(v.dtype)
    return o.reshape(b, t, h * vd) @ w("o_proj.weight")


def causal_train(q_nope, q_rope, c_kv, k_rope, w, cfg):
    """Causal self-attention over the call's own rows, for training: through
    the flash kernel where it takes the shapes (a key is its own half
    beside the shared rotary key, `nope + rope` wide; a value `v_head_dim`),
    else `expanded`. A TPU that refuses a long sequence for its head widths
    is counted by the kernel module (`width_fallbacks`)."""
    from ...kernels import flash_attention as fa
    h, nope, rope, vd = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
    b, t = q_nope.shape[:2]
    shape = lambda width: jax.ShapeDtypeStruct((b, t, h, width),
                                               q_nope.dtype)
    if not fa.is_eligible(shape(nope + rope), shape(nope + rope), shape(vd),
                          None, 0.0, is_causal=True):
        return expanded(q_nope, q_rope, c_kv, k_rope, w, cfg, 0)
    kv = (c_kv @ w("kv_b_proj.weight")).reshape(b, t, h, nope + vd)
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_rope[:, :, None, :], (b, t, h, rope))], -1)
    with jax.named_scope("mla_flash_attention"):
        o = fa.flash_attention_bnhd(q, k, kv[..., nope:], True,
                                    1.0 / math.sqrt(nope + rope))
    return o.reshape(b, t, h * vd) @ w("o_proj.weight")
