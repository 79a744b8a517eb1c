"""Sequence/context parallelism over the "sep" mesh axis.

The reference snapshot has NO sequence parallelism (SURVEY.md §5: grep for
sequence_parallel / ring attention / context parallel / Ulysses over
paddle/ and python/ returns nothing) — this subsystem is designed TPU-first
from scratch rather than translated:

- **Ring attention** (`ring_attention`): K/V blocks rotate around the sep
  axis with `jax.lax.ppermute` (ICI neighbour exchange); each step folds one
  K/V block into a blockwise online-softmax accumulator (the same recipe as
  the Pallas flash kernel in paddle_tpu/kernels/flash_attention.py), so the
  full [N, N] score matrix never exists and each chip only ever holds
  seq/sep_degree keys. Comm is neighbour-only ⇒ rides ICI links.
- **Ulysses attention** (`ulysses_attention`): `jax.lax.all_to_all` swaps the
  sharded axis from sequence to heads, runs dense local attention over the
  full sequence for heads/sep_degree heads, and swaps back. Cheaper compute
  than ring when heads % sep == 0 and the all-to-all fits ICI.

Both are *axis-name aware* in the style of mp_ops: they must run inside a
shard_map/SPMD trace that binds the sep axis, with q/k/v sharded along the
sequence dimension (paddle layout [batch, seq, heads, head_dim]). Gradients
flow through ppermute/all_to_all natively via jax AD.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .mp_ops import in_spmd_axis

__all__ = ["ring_attention", "ulysses_attention", "sep_attention", "SEP_AXIS"]

SEP_AXIS = "sep"

_NEG_INF = -1e30


def _block_fold(q, k, v, scale, m, l, o, q_pos, k_pos, causal):
    """Fold one K/V block into the online-softmax accumulator.

    q: [B, H, n, D]; k, v: [B, H, mblk, D]; m, l: [B, H, n, 1]; o like q (f32).
    q_pos: [n] global query positions; k_pos: [mblk] global key positions.
    """
    scores = jnp.einsum("bhnd,bhmd->bhnm", q, k) * scale
    scores = scores.astype(jnp.float32)
    if causal:
        allowed = q_pos[:, None] >= k_pos[None, :]          # [n, mblk]
        scores = jnp.where(allowed[None, None], scores, _NEG_INF)
    blk_max = jnp.max(scores, axis=-1, keepdims=True)        # [B,H,n,1]
    new_m = jnp.maximum(m, blk_max)
    # guard: a fully-masked block keeps new_m == m (both may be -inf-ish)
    p = jnp.exp(scores - new_m)                              # [B,H,n,mblk]
    corr = jnp.exp(m - new_m)                                # [B,H,n,1]
    new_l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("bhnm,bhmd->bhnd", p, v.astype(jnp.float32))
    new_o = o * corr + pv
    return new_m, new_l, new_o


def ring_attention(q, k, v, axis_name=SEP_AXIS, causal=False, scale=None):
    """Blockwise ring attention across a sequence-sharded sep axis.

    q/k/v: shard-local [B, n, H, D] where the global sequence N = n * sep and
    device i along `axis_name` holds contiguous positions [i*n, (i+1)*n).
    Returns shard-local [B, n, H, D].
    """
    s = jax.lax.axis_size(axis_name)
    i = jax.lax.axis_index(axis_name)
    b, n, h, d = q.shape
    mblk = k.shape[1]                # kv shard length (> n with caches)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    qt = jnp.swapaxes(q, 1, 2)                               # [B,H,n,D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    m = jnp.full((b, h, n, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, n, 1), jnp.float32)
    o = jnp.zeros((b, h, n, d), jnp.float32)
    # bottom-right causal alignment: with M = mblk*s total keys and N = n*s
    # queries, query j sits at absolute position j + (M - N), matching
    # _plain_attention's kv-cache convention
    q_pos = i * n + jnp.arange(n) + (mblk - n) * s

    perm = [(j, (j + 1) % s) for j in range(s)]
    kv = (kt, vt)
    # static python loop: s is a mesh constant, trace unrolls s ring steps;
    # XLA overlaps each ppermute with the previous step's einsums
    for t in range(s):
        kv_idx = (i - t) % s
        k_pos = kv_idx * mblk + jnp.arange(mblk)
        m, l, o = _block_fold(qt, kv[0], kv[1], scale, m, l, o,
                              q_pos, k_pos, causal)
        if t + 1 < s:
            kv = jax.lax.ppermute(kv, axis_name, perm)
    out = o / jnp.maximum(l, 1e-30)
    return jnp.swapaxes(out.astype(q.dtype), 1, 2)           # [B,n,H,D]


def ulysses_attention(q, k, v, axis_name=SEP_AXIS, causal=False, scale=None,
                      attn_fn=None):
    """DeepSpeed-Ulysses style: all-to-all seq<->heads, dense local attention.

    q/k/v: shard-local [B, n, H, D] with H % sep_degree == 0. Two all-to-alls
    per tensor (in + out) replace the ring's (sep-1) ppermute rounds.
    """
    s = jax.lax.axis_size(axis_name)
    b, n, h, d = q.shape
    if h % s != 0:
        raise ValueError(f"ulysses needs heads ({h}) divisible by sep ({s})")

    def seq2head(x):
        # [B, n, H, D] -> [B, n*s, H/s, D]: split heads, concat sequence
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def head2seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qg, kg, vg = seq2head(q), seq2head(k), seq2head(v)
    if attn_fn is None:
        from ....nn.functional.attention import _plain_attention
        if scale is None:
            scale = 1.0 / (d ** 0.5)
        out = _plain_attention(qg, kg, vg, None, causal, scale)
    else:
        out = attn_fn(qg, kg, vg, causal)
    return head2seq(out)


def sep_attention(q, k, v, causal=False, scale=None, mode="ring",
                  axis_name=SEP_AXIS):
    """Dispatch helper: ring or ulysses when inside an SPMD trace binding the
    sep axis; dense fallback otherwise (so model code is mode-agnostic)."""
    if mode not in ("ring", "ulysses"):
        raise ValueError(f"unknown sep attention mode {mode!r}; "
                         "expected 'ring' or 'ulysses'")
    if in_spmd_axis(axis_name):
        if mode == "ulysses":
            return ulysses_attention(q, k, v, axis_name, causal, scale)
        return ring_attention(q, k, v, axis_name, causal, scale)
    from ....nn.functional.attention import _plain_attention
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    return _plain_attention(q, k, v, None, causal, scale)
