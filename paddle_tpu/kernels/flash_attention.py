"""Flash attention (Pallas, TPU).

Reference analog: fluid/operators/fused/fused_attention_op.cu + fmha_ref.h —
the reference's fused MHA. TPU-native design: blockwise online-softmax
attention in VMEM (Rabe&Staats / FlashAttention recipe), one grid cell per
(batch*head, q_block); K/V stream through VMEM blocks so the N×N score matrix
never hits HBM.

Forward and backward both run as Pallas kernels (FlashAttention-2
decomposition: forward saves the per-row logsumexp; backward is two kernels —
dQ gridded over q blocks, dK/dV gridded over k blocks — so no atomics and no
N x N materialization anywhere). Measured v5e, GPT-2 bench shape (b16 h12
n1024 d64): fwd 0.93ms vs XLA 2.03ms; fwd+bwd 3.7ms vs XLA 5.7ms.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

try:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    _HAS_PALLAS = True
except Exception:  # pragma: no cover
    _HAS_PALLAS = False

from ._common import ZERO as _SHARED_ZERO, on_tpu as _on_tpu

__all__ = ["flash_attention_bnhd", "is_eligible", "width_fallbacks"]

_NEG_INF = -1e30


# with the Pallas backward and 512-wide blocks the flash path beats XLA's
# fused attention from seq 1024 up (v5e, GPT-2 shape: 3.7ms vs 5.7ms
# fwd+bwd); below that the kernel launch overhead loses to XLA's N^2 path
FLASH_MIN_SEQ = 1024


def _auto_blocks(n, m):
    """512-wide tiles win on v5e (VMEM-resident [512,512] f32 score tile
    saturates the MXU; 128-wide tiles leave it 3x underutilized). The block
    must DIVIDE the sequence length — the pallas grids floor-divide, so a
    non-dividing block would silently drop the tail rows/keys."""
    def largest_dividing(seq):
        for cand in (512, 256, 128):
            if seq % cand == 0:
                return cand
        return min(seq, 128)
    bq = largest_dividing(n)
    bk = largest_dividing(m)
    # causal diagonal trimming requires block_q % block_k == 0
    if bq % bk:
        bk = math.gcd(bq, bk)
    return bq, bk


def is_eligible(q, k, v, mask, dropout_p, is_causal=False):
    """Flash path requires: TPU, no explicit mask (causal flag ok), no dropout,
    block-friendly seq lengths and head_dim, and long-enough sequences that
    blockwise streaming beats XLA's fused N^2 attention."""
    if not _HAS_PALLAS or not _on_tpu():
        return False
    if mask is not None or dropout_p:
        return False
    if q.ndim != 4:
        return False
    b, n, h, d = q.shape
    m = k.shape[1]
    if not _width_ok(q, k, v, is_causal):
        return False
    if is_causal and n != m:
        # kv-cache decode/prefill shapes (m > n) use bottom-right causal
        # alignment; this kernel's causal masking is top-left (n == m) only.
        # Non-causal cross-attention has no mask, so any n/m is fine.
        return False
    if n % 128 != 0 or m % 128 != 0:
        return False
    from ..framework.flags import FLAGS
    if not FLAGS.use_flash_attention:
        return False
    if max(n, m) < FLASH_MIN_SEQ:
        return False
    return True


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal, scale,
                block_q, block_k, seq_k):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale          # [block_q, d]

    def body(start_k, carry):
        o_acc, m_acc, l_acc = carry
        k_blk = k_ref[0, pl.ds(start_k * block_k, block_k), :] \
            .astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(start_k * block_k, block_k), :] \
            .astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = start_k * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, jnp.float32(_NEG_INF))
        m_new = jnp.maximum(m_acc, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_acc - m_new)
        l_new = alpha * l_acc + jnp.sum(p, axis=1)
        o_new = o_acc * alpha[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return o_new, m_new, l_new

    num_k_blocks = seq_k // block_k
    if causal:
        # only iterate K blocks up to (and including) the diagonal;
        # block_q % block_k == 0 keeps this pure integer-multiply on the
        # traced program id (no traced floor-div)
        assert block_q % block_k == 0
        last = (qi + 1) * (block_q // block_k)
        upper = jnp.minimum(last, num_k_blocks)
    else:
        upper = num_k_blocks

    d = v_ref.shape[-1]                       # the accumulator is v's width
    o0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    # i32 loop bounds: x64 mode would otherwise make an i64 counter, which
    # Mosaic cannot legalize
    o_acc, m_acc, l_acc = jax.lax.fori_loop(
        jnp.int32(0), jnp.asarray(upper, jnp.int32), body, (o0, m0, l0))
    l_safe = jnp.maximum(l_acc, jnp.float32(1e-30))
    o_ref[0] = (o_acc / l_safe[:, None]).astype(o_ref.dtype)
    # logsumexp per row, needed by the Pallas backward ([bq, 1] tile: TPU
    # blocks must be >= 2-D)
    lse_ref[0] = (m_acc + jnp.log(l_safe))[:, None]


def _flash_fwd(q, k, v, causal, scale, block_q=None, block_k=None,
               interpret=False):
    """q,k: [B, N, H, D], v: [B, N, H, Dv] — the kernel per (b*h, q_block).

    Returns (out [B,N,H,Dv], lse [B*H, N] float32)."""
    b, n, h, d = q.shape
    m, dv = k.shape[1], v.shape[-1]
    if block_q is None or block_k is None:
        block_q, block_k = _auto_blocks(n, m)
    # fold batch & heads, move seq to the row dim: [B*H, N, D]
    qf = jnp.swapaxes(q, 1, 2).reshape(b * h, n, d)
    kf = jnp.swapaxes(k, 1, 2).reshape(b * h, m, d)
    vf = jnp.swapaxes(v, 1, 2).reshape(b * h, m, dv)

    grid = (b * h, n // block_q)
    kernel = functools.partial(_fwd_kernel, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k, seq_k=m)
    # index maps must emit i32 (see kernels/_common.py)
    zero = _SHARED_ZERO
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, zero)),
            pl.BlockSpec((1, m, d), lambda bh, qi: (bh, zero, zero)),
            pl.BlockSpec((1, m, dv), lambda bh, qi: (bh, zero, zero)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda bh, qi: (bh, qi, zero)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi: (bh, qi, zero)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, n, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, n, 1), jnp.float32),
        ],
        interpret=interpret, compiler_params=_vmem(m, d, dv),
        name="flash_attention_fwd",
    )(qf, kf, vf)
    return out.reshape(b, h, n, dv).swapaxes(1, 2), lse


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               causal, scale, block_q, block_k, seq_k):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)                   # [bq, d]
    do = do_ref[0].astype(jnp.float32)                 # [bq, d]
    lse = lse_ref[0]                                   # [bq, 1]
    delta = delta_ref[0]                               # [bq, 1]

    def body(ki, dq_acc):
        k_blk = k_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, jnp.float32(_NEG_INF))
        p = jnp.exp(s - lse)                           # normalized probs
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq_acc + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    num_k_blocks = seq_k // block_k
    if causal:
        assert block_q % block_k == 0
        upper = jnp.minimum((qi + 1) * (block_q // block_k), num_k_blocks)
    else:
        upper = num_k_blocks
    dq0 = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    dq = jax.lax.fori_loop(jnp.int32(0), jnp.asarray(upper, jnp.int32),
                           body, dq0)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, causal, scale, block_q, block_k, seq_q):
    ki = pl.program_id(1)
    k_blk = k_ref[0].astype(jnp.float32)               # [bk, d]
    v_blk = v_ref[0].astype(jnp.float32)               # [bk, d]

    num_q_blocks = seq_q // block_q
    if causal:
        # only q blocks at/after this k block's diagonal contribute; loop a
        # traced COUNT from a static 0 with a shifted induction variable.
        # lax.div, not //: Mosaic's floor_divide lowering recurses through
        # convert_element_type under x64
        assert block_q % block_k == 0
        first = jax.lax.div(ki * jnp.int32(block_k), jnp.int32(block_q))
    else:
        first = 0

    def body(j, carry):
        qi = j + first
        dk_acc, dv_acc = carry
        q = q_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qi * block_q, block_q), :]    # [bq, 1]
        delta = delta_ref[0, pl.ds(qi * block_q, block_q), :]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, jnp.float32(_NEG_INF))
        p = jnp.exp(s - lse)                           # [bq, bk]
        dv_new = dv_acc + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # p^T @ do
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_new = dk_acc + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # ds^T @ q
        return dk_new, dv_new

    d, dv = k_blk.shape[-1], v_blk.shape[-1]
    init = (jnp.zeros((block_k, d), jnp.float32),
            jnp.zeros((block_k, dv), jnp.float32))
    count = jnp.asarray(num_q_blocks - first, jnp.int32)
    dk, dv = jax.lax.fori_loop(jnp.int32(0), count, body, init)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, g, causal, scale,
               block_q=None, block_k=None, interpret=False):
    """Pallas flash backward: dQ via one kernel over q blocks, dK/dV via one
    kernel over k blocks — FlashAttention-2 decomposition, no atomics, no
    N x N materialization."""
    b, n, h, d = q.shape
    m, dv = k.shape[1], v.shape[-1]
    if block_q is None or block_k is None:
        block_q, block_k = _auto_blocks(n, m)
    qf = jnp.swapaxes(q, 1, 2).reshape(b * h, n, d)
    kf = jnp.swapaxes(k, 1, 2).reshape(b * h, m, d)
    vf = jnp.swapaxes(v, 1, 2).reshape(b * h, m, dv)
    of = jnp.swapaxes(out, 1, 2).reshape(b * h, n, dv)
    gf = jnp.swapaxes(g, 1, 2).reshape(b * h, n, dv)
    # rescale q once here so fwd/bwd agree on s = (q*scale) @ k^T
    delta = jnp.sum(of.astype(jnp.float32) * gf.astype(jnp.float32),
                    axis=-1, keepdims=True)             # [bh, n, 1]
    zero = _SHARED_ZERO

    dq_kernel = functools.partial(_dq_kernel, causal=causal, scale=scale,
                                  block_q=block_q, block_k=block_k, seq_k=m)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b * h, n // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, zero)),
            pl.BlockSpec((1, m, d), lambda bh, qi: (bh, zero, zero)),
            pl.BlockSpec((1, m, dv), lambda bh, qi: (bh, zero, zero)),
            pl.BlockSpec((1, block_q, dv), lambda bh, qi: (bh, qi, zero)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi: (bh, qi, zero)),
            pl.BlockSpec((1, block_q, 1), lambda bh, qi: (bh, qi, zero)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda bh, qi: (bh, qi, zero)),
        out_shape=jax.ShapeDtypeStruct((b * h, n, d), q.dtype),
        interpret=interpret, compiler_params=_vmem(m, d, dv),
        name="flash_attention_dq",
    )(qf, kf, vf, gf, lse, delta)

    dkv_kernel = functools.partial(_dkv_kernel, causal=causal, scale=scale,
                                   block_q=block_q, block_k=block_k, seq_q=n)
    dk, dw = pl.pallas_call(
        dkv_kernel,
        grid=(b * h, m // block_k),
        in_specs=[
            pl.BlockSpec((1, n, d), lambda bh, ki: (bh, zero, zero)),
            pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, zero)),
            pl.BlockSpec((1, block_k, dv), lambda bh, ki: (bh, ki, zero)),
            pl.BlockSpec((1, n, dv), lambda bh, ki: (bh, zero, zero)),
            pl.BlockSpec((1, n, 1), lambda bh, ki: (bh, zero, zero)),
            pl.BlockSpec((1, n, 1), lambda bh, ki: (bh, zero, zero)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, ki: (bh, ki, zero)),
            pl.BlockSpec((1, block_k, dv), lambda bh, ki: (bh, ki, zero)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, m, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, m, dv), v.dtype),
        ],
        interpret=interpret, compiler_params=_vmem(n, d, dv, True),
        name="flash_attention_dkv",
    )(qf, kf, vf, gf, lse, delta)

    def unfold(t, nn):
        return t.reshape(b, h, nn, t.shape[-1]).swapaxes(1, 2)

    return unfold(dq, n), unfold(dk, m), unfold(dw, m)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_bnhd(q, k, v, causal=False, scale=None):
    """Flash attention over [batch, seq, heads, head_dim] tensors."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _flash_fwd(q, k, v, causal, scale)[0]


def _fa_fwd(q, k, v, causal, scale):
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    out, lse = _flash_fwd(q, k, v, causal, scale)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, scale, res, g):
    q, k, v, out, lse = res
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _flash_bwd(q, k, v, out, lse, g, causal, scale)


flash_attention_bnhd.defvjp(_fa_fwd, _fa_bwd)


# -- head widths ------------------------------------------------------------
# Kept BELOW the kernels: a Mosaic kernel's lowering carries the line of
# every operation of its body, so a line added above them changes the
# compiled bytes of every model's step.

# (q and k width, v and o width) the kernels take: one width for all four,
# or latent attention's 192-wide q and k (128 + 64 rotary) over a 128-wide v
_WIDTHS = frozenset({(64, 64), (128, 128), (256, 256), (192, 128)})

# causal self-attentions of >= FLASH_MIN_SEQ tokens that a TPU sent to XLA's
# N^2 attention for their head widths alone, counted where they are traced
_width_fallbacks = [0]


def width_fallbacks():
    """How many attention calls this process has traced ON A TPU that met
    every condition of the flash path but the head widths (`_WIDTHS`) and so
    took XLA's N^2 attention. `TrainStepStats` reports it: 0, or a model
    has lost its kernel unseen."""
    return _width_fallbacks[0]


def _width_ok(q, k, v, is_causal):
    """Whether the kernels take these head widths; a refusal that sends a
    long causal self-attention to the N^2 path is counted."""
    n, d = q.shape[1], q.shape[-1]
    if k.shape[-1] == d and (d, v.shape[-1]) in _WIDTHS:
        return True
    if is_causal and k.shape[1] == n and n % 128 == 0 \
            and n >= FLASH_MIN_SEQ:
        _width_fallbacks[0] += 1
    return False


# a call keeps one head's whole K and V (forward, dQ) or Q, dO and the two
# row statistics (dK/dV) in VMEM, twice for the pipeline; a statistic's
# [rows, 1] block is padded to 128 lanes. Past this many bytes the
# compiler's default scoped limit (16 MB of the core's 128) is raised
_VMEM_DEFAULT_FITS = 8 << 20


def _vmem(rows, d, dv, with_stats=False):
    """`compiler_params` of a call that keeps `rows` rows of a `d`-wide
    and a `dv`-wide bf16 operand resident: None (the compiler's own limit,
    and the lowering every model had before) while they fit it."""
    lanes = lambda w: -(-w // 128) * 128
    held = 2 * rows * (2 * lanes(d) + 2 * lanes(dv)
                       + (2 * 128 * 4 if with_stats else 0))
    if held <= _VMEM_DEFAULT_FITS:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=min(4 * held, 100 << 20))


# -- a causal BAND (a window layer's prefill), forward only ------------------
# Below the kernels above, as the head widths are: their lowered bytes carry
# their lines' numbers (this section's import too).

import numpy as np  # noqa: E402

__all__ += ["flash_band_attention_bnhd", "is_band_eligible"]


def _band_block(window):
    """The block both the queries and the keys go by: whole 128-row tiles
    that hold ``window - 1`` positions, so that a block of queries sees
    its own block of keys and the one before it and no other."""
    return max(128, -(-(int(window) - 1) // 128) * 128)


def is_band_eligible(q, k, v, window):
    """Can `flash_band_attention_bnhd` run compiled here: a TPU, the flag,
    self-attention of whole blocks (`_band_block`) and at least
    `FLASH_MIN_SEQ` positions, head widths the kernels take, whole groups
    of query heads a key/value head."""
    if not _HAS_PALLAS or not _on_tpu() or q.ndim != 4:
        return False
    n, h, d = q.shape[1:]
    if k.shape[1] != n or n % _band_block(window) or n < FLASH_MIN_SEQ:
        return False
    if k.shape[-1] != d or (d, v.shape[-1]) not in _WIDTHS \
            or h % k.shape[2]:
        return False
    from ..framework.flags import FLAGS
    return bool(FLAGS.use_flash_attention)


def _band_kernel(q_ref, kp_ref, kc_ref, vp_ref, vc_ref, *rest, scale, block,
                 window):
    """One block of queries of one head against its own block of keys
    (`kc`, `vc`) and the block before it (`kp`, `vp`): every key a query
    of a band of `window` sees lies in those two, and no other block is
    copied or multiplied. Scores are exact products of the operands as
    they are (bf16: one pass) accumulated in float32 and scaled after;
    the softmax and p . v are float32. With a sink (`rest` then starts
    with its [1, 1] block) a head's learned score joins the denominator
    and adds no value."""
    sink_ref = rest[0] if len(rest) == 2 else None
    o_ref = rest[-1]
    qi = pl.program_id(1)
    q = q_ref[0]
    dims = (((1,), (1,)), ((), ()))
    row = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    neg = jnp.float32(_NEG_INF)

    def scores(k_ref, keep):
        s = jax.lax.dot_general(q, k_ref[0], dims,
                                preferred_element_type=jnp.float32)
        return jnp.where(keep, s * jnp.float32(scale), neg)

    # the block before: key c lies block - c + r positions behind query r;
    # the first block of queries has none before it (its index map reads
    # block 0 again)
    keep_p = (row - col + jnp.int32(block) < jnp.int32(window)) \
        & (qi > jnp.int32(0))
    keep_c = (col <= row) & (row - col < jnp.int32(window))
    s_p, s_c = scores(kp_ref, keep_p), scores(kc_ref, keep_c)
    m = jnp.maximum(jnp.max(s_p, axis=1, keepdims=True),
                    jnp.max(s_c, axis=1, keepdims=True))
    if sink_ref is not None:
        m = jnp.maximum(m, sink_ref[0])
    p_p = jnp.where(keep_p, jnp.exp(s_p - m), jnp.float32(0.0))
    p_c = jnp.where(keep_c, jnp.exp(s_c - m), jnp.float32(0.0))
    l = jnp.sum(p_p, axis=1, keepdims=True) \
        + jnp.sum(p_c, axis=1, keepdims=True)
    if sink_ref is not None:
        l = l + jnp.exp(sink_ref[0] - m)
    pv = (((1,), (0,)), ((), ()))
    o = jax.lax.dot_general(p_p, vp_ref[0].astype(jnp.float32), pv,
                            preferred_element_type=jnp.float32) \
        + jax.lax.dot_general(p_c, vc_ref[0].astype(jnp.float32), pv,
                              preferred_element_type=jnp.float32)
    o_ref[0] = (o / jnp.maximum(l, jnp.float32(1e-30))).astype(o_ref.dtype)


def flash_band_attention_bnhd(q, k, v, window, sink=None, scale=None,
                              interpret=False):
    """Causal attention inside a band: query i attends key j with
    ``i - window < j <= i``. q ``[B, N, H, D]``; k ``[B, N, KH, D]`` and
    v ``[B, N, KH, Dv]`` with KH dividing H (query head i reads key/value
    head ``i // (H // KH)`` through the index map: no head is repeated in
    memory); `sink` ``[H]`` float32 or None. Forward only (serving). A
    grid cell per (batch * head, block of queries) copies TWO blocks of
    keys and values, whatever N: the blocks outside the band are skipped,
    not masked. Returns ``[B, N, H, Dv]``."""
    b, n, h, d = q.shape
    kh, dv = k.shape[2], v.shape[-1]
    group = h // kh
    block = _band_block(window)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    qf = jnp.swapaxes(q, 1, 2).reshape(b * h, n, d)
    kf = jnp.swapaxes(k, 1, 2).reshape(b * kh, n, d)
    vf = jnp.swapaxes(v, 1, 2).reshape(b * kh, n, dv)
    zero = _SHARED_ZERO
    # (numpy scalars: an index map may capture no array)
    one, grp = np.int32(1), np.int32(group)

    def own(bh, qi):
        return (bh, qi, zero)

    # the key/value head of query head bh: batch-major on both sides, so
    # bh // group is (batch, head // group) folded
    def before(bh, qi):
        return (jax.lax.div(bh, grp), jnp.maximum(qi - one, zero), zero)

    def current(bh, qi):
        return (jax.lax.div(bh, grp), qi, zero)

    in_specs = [pl.BlockSpec((1, block, d), own),
                pl.BlockSpec((1, block, d), before),
                pl.BlockSpec((1, block, d), current),
                pl.BlockSpec((1, block, dv), before),
                pl.BlockSpec((1, block, dv), current)]
    operands = [qf, kf, kf, vf, vf]
    if sink is not None:
        in_specs.append(pl.BlockSpec((1, 1, 1),
                                     lambda bh, qi: (bh, zero, zero)))
        operands.append(jnp.tile(sink.astype(jnp.float32), b)[:, None, None])
    out = pl.pallas_call(
        functools.partial(_band_kernel, scale=scale, block=block,
                          window=int(window)),
        grid=(b * h, n // block),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block, dv), own),
        out_shape=jax.ShapeDtypeStruct((b * h, n, dv), q.dtype),
        interpret=interpret,
        name="flash_band_attention",
    )(*operands)
    return out.reshape(b, h, n, dv).swapaxes(1, 2)
