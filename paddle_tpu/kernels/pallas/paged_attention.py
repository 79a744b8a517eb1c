"""Blockwise paged decode attention over the block-pool KV cache.

The serving decode step used to gather every slot's paged KV history into
a dense ``[S, T, H, D]`` context per layer (nn/functional/attention.py)
— the main obstacle between the 0.178 ms/step CPU proxy and the 0.08 ms
TPU target. This module is the FlashAttention-style fix specialized to
PagedAttention's memory model: stream the pool's KV blocks through the
block table with ONLINE (streaming) softmax, fp32 accumulators, one block
resident at a time — the dense context never exists.

Both read ONE pool layout, ``[L, num_blocks, bs, H*D]`` (serving/cache.py),
at a layer index, and only ever split GATHERED rows into heads: the pool
is never reshaped or sliced per layer, so a program that donates it
updates and reads it where it lies. Two implementations with identical
semantics:

  * `pallas_paged_attention` — the TPU kernel. Grid ``(S, M)``; the
    block table and (effective) lengths ride as scalar-prefetch
    arguments, so each grid cell's BlockSpec index map picks its pool
    block ``(layer, tables[s, j])`` of the stacked pool directly — the
    DMA engine walks the page table, the kernel body only ever sees one
    ``[bs, H*D]`` block in VMEM (a token a sublane, its heads side by
    side on the lanes) and reduces per head inside it.
    int8 pools dequantize inside the load (`q * scale / 127`), so the
    fp values exist only in VMEM. Length masking keeps the null-block
    branch-free contract: padded/inactive table entries read block 0 and
    their scores are masked, never branched on. Runs under
    ``interpret=True`` on CPU for the fused-vs-reference parity tests.
  * `blockwise_paged_attention` — pure-JAX `lax.scan` over block chunks
    with the same online-softmax recurrence. This is the CPU/parity
    fallback AND a standalone win: it replaces the dense gather's
    ``[S, T, H, D]`` materialization with cache-resident chunks, so it
    beats the gather on the serve CPU legs from seq ~1k up
    (tools/perf_smoke.py leg j guards the floor).

Numerics: scores, the softmax recurrence, and the output accumulator are
fp32 regardless of the query/pool dtype; only the final output casts back
to the query dtype. Masked positions contribute exactly zero probability
(explicit `where`, not just a large negative score).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

try:
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    _HAS_PALLAS = True
except Exception:  # pragma: no cover
    _HAS_PALLAS = False

from .._common import ZERO as _ZERO, on_tpu as _on_tpu
from ...quantization.kv_cache import QMAX as _QMAX, dequantize as _dequant

__all__ = ["blockwise_paged_attention", "pallas_paged_attention",
           "is_eligible"]

_NEG_INF = -1e30

# blockwise scan chunking: gather KV per scan step in chunks targeting
# this many BYTES per pool side (multiple pool blocks per step when
# block_size is small) — big enough to amortize the scan-iteration
# overhead, small enough to stay cache-resident instead of
# re-materializing the dense context. Tokens are capped so tiny-head
# shapes don't degenerate into one dense chunk
_CHUNK_TARGET_BYTES = 256 * 1024
_CHUNK_TOKENS_MAX = 512


# Largest [block_size, H*D] pool block (in elements, the row padded to whole
# 128-lane tiles) the v5e compiler accepted for every pool dtype: the kernel
# keeps K and V double-buffered plus their fp32 copies and products in
# VMEM, and a 2x larger int8 block ran out of it.
# tests/test_tpu_compile.py compiles both sides of this bound.
_MAX_BLOCK_ELEMS = 256 * 1024


def is_eligible(num_heads, head_dim, block_size):
    """Can the Pallas kernel run compiled (non-interpret) here?
    Returns (ok, why) — `why` is the attribution detail for the
    `kernel.fallback` flight-recorder event when not."""
    if not _HAS_PALLAS:
        return False, "no_pallas"
    if not _on_tpu():
        return False, "not_on_tpu"
    if None in (num_heads, head_dim, block_size):
        return False, "shape_unknown"
    padded = block_size * -(-num_heads * head_dim // 128) * 128
    if padded > _MAX_BLOCK_ELEMS:
        return False, "block_exceeds_vmem"
    return True, None


# ---------------------------------------------------------------------------
# pure-JAX blockwise reference path (lax.scan over block chunks)
# ---------------------------------------------------------------------------

def blockwise_paged_attention(q, k_pools, v_pools, layer, block_tables,
                              lens, block_size, k_scales=None, v_scales=None,
                              chunk_blocks=None):
    """Online-softmax paged attention, one KV chunk at a time.

    q: ``[S, H, D]`` this step's queries; k_pools/v_pools:
    ``[L, num_blocks, bs, H*D]`` (fp, or int8 with `k_scales`/`v_scales`
    ``[L, num_blocks, H]``) and `layer` the one to read; block_tables:
    ``[S, M]`` int32; lens: ``[S]`` int32 EFFECTIVE lengths (position p
    attends iff p <= lens[s]; inactive slots pass 0). Returns
    ``[S, H, D]`` in q's dtype. Each scan step gathers
    ``pool[layer, block ids]`` and splits the GATHERED rows into heads:
    neither a layer of the pool nor the pool in another shape is ever a
    value, so the program reads a donated pool where it lies.
    """
    s, h, d = q.shape
    m = block_tables.shape[1]
    bs = int(block_size)
    quant = k_scales is not None
    if chunk_blocks is None:
        per_token = h * d * jnp.dtype(jnp.float32).itemsize
        tokens = min(max(_CHUNK_TARGET_BYTES // per_token, bs),
                     _CHUNK_TOKENS_MAX)
        chunk_blocks = max(1, int(tokens) // bs)
    chunk_blocks = min(int(chunk_blocks), m)
    n_chunks = -(-m // chunk_blocks)
    pad = n_chunks * chunk_blocks - m
    tables = block_tables
    if pad:
        # padded entries read the null block; their positions exceed
        # every possible length, so the mask kills them
        tables = jnp.pad(tables, ((0, 0), (0, pad)))
    # [n_chunks, S, C]: scan consumes chunks along the leading axis
    tabs = jnp.swapaxes(
        tables.reshape(s, n_chunks, chunk_blocks), 0, 1)
    q32 = q.astype(jnp.float32) * (1.0 / math.sqrt(d))
    t_chunk = chunk_blocks * bs
    offs = jnp.arange(t_chunk, dtype=jnp.int32)

    def step(carry, xs):
        acc, mx, l = carry
        ci, bids = xs                                   # [], [S, C]
        kc = k_pools[layer, bids]                       # [S, C, bs, H*D]
        vc = v_pools[layer, bids]
        if quant:
            split = (s, chunk_blocks, bs, h, d)
            kc = _dequant(kc.reshape(split), k_scales[layer, bids])
            vc = _dequant(vc.reshape(split), v_scales[layer, bids])
        else:
            kc = kc.astype(jnp.float32)
            vc = vc.astype(jnp.float32)
        kc = kc.reshape(s, t_chunk, h, d)
        vc = vc.reshape(s, t_chunk, h, d)
        scores = jnp.einsum("shd,sthd->sht", q32, kc)
        pos = ci * t_chunk + offs
        valid = pos[None, :] <= lens[:, None]           # [S, t]
        scores = jnp.where(valid[:, None, :], scores,
                           jnp.float32(_NEG_INF))
        m_new = jnp.maximum(mx, jnp.max(scores, axis=-1))
        # explicit zero for masked slots: a fully-masked chunk must not
        # leak exp(NEG - NEG) == 1 into the row sums
        p = jnp.where(valid[:, None, :],
                      jnp.exp(scores - m_new[..., None]), 0.0)
        alpha = jnp.exp(mx - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] \
            + jnp.einsum("sht,sthd->shd", p, vc)
        return (acc, m_new, l), None

    acc0 = jnp.zeros((s, h, d), jnp.float32)
    m0 = jnp.full((s, h), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((s, h), jnp.float32)
    (acc, _, l), _ = jax.lax.scan(
        step, (acc0, m0, l0),
        (jnp.arange(n_chunks, dtype=jnp.int32), tabs))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas TPU kernel: one grid cell per (slot, table entry), all heads at once
# ---------------------------------------------------------------------------

def _decode_kernel(tab_ref, lens_ref, q_ref, seg_ref, k_ref, v_ref, *rest,
                   block_size, quantized):
    if quantized:
        ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
    s = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # A pool block is [bs, H*D]: a token a sublane, its heads side by side
    # on the lanes. `seg` [H*D, H] is 1 where a lane belongs to a head, so
    # two small matrix products (exact: float32 contraction, and a lane
    # belongs to one head) take a row to per-head values and back; the
    # softmax state lives on the lanes, every head's value repeated over
    # its D lanes, so nothing below reshapes or relayouts.
    seg = seg_ref[...]
    exact = dict(precision=jax.lax.Precision.HIGHEST,
                 preferred_element_type=jnp.float32)

    def per_head(x):                                   # [bs, H*D] -> [bs, H]
        return jnp.dot(x, seg, **exact)

    def per_lane(x):                                   # [bs, H] -> [bs, H*D]
        return jax.lax.dot_general(x, seg, (((1,), (1,)), ((), ())), **exact)

    k = k_ref[...].astype(jnp.float32)                 # [bs, H*D]
    v = v_ref[...].astype(jnp.float32)
    if quantized:
        # dequant fused into the block load: fp K/V exist only in VMEM
        heads = (k.shape[0], seg.shape[1])
        k = k * per_lane(jnp.broadcast_to(ks_ref[...] * (1.0 / _QMAX), heads))
        v = v * per_lane(jnp.broadcast_to(vs_ref[...] * (1.0 / _QMAX), heads))
    scores = per_lane(per_head(k * q_ref[...]))        # q is pre-scaled
    pos = j * jnp.int32(block_size) + jax.lax.broadcasted_iota(
        jnp.int32, scores.shape, 0)
    valid = pos <= lens_ref[s]
    scores = jnp.where(valid, scores, jnp.float32(_NEG_INF))
    m_prev = m_ref[...]                                # [1, H*D]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=0, keepdims=True))
    p = jnp.where(valid, jnp.exp(scores - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=0, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha \
        + jnp.sum(p * v, axis=0, keepdims=True)
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def pallas_paged_attention(q, k_pools, v_pools, layer, block_tables, lens,
                           block_size, k_scales=None, v_scales=None,
                           interpret=False):
    """The Pallas kernel: same contract as `blockwise_paged_attention`.
    `interpret=True` runs the kernel through the Pallas interpreter on
    any backend (the CPU parity path)."""
    s, h, d = q.shape
    hd = h * d
    bs = int(block_size)
    m = block_tables.shape[1]
    quant = k_scales is not None
    zero = _ZERO
    layer = np.int32(layer)      # index maps emit i32 (kernels/_common.py)
    qf = (q.astype(jnp.float32) * (1.0 / math.sqrt(d))).reshape(s, 1, hd)
    seg = (jnp.arange(hd, dtype=jnp.int32)[:, None] // d
           == jnp.arange(h, dtype=jnp.int32)[None, :]).astype(jnp.float32)
    tables = block_tables.astype(jnp.int32)
    lens32 = lens.astype(jnp.int32)

    # The pool is read where it lies: a block is one [bs, H*D] row group
    # of the STACKED pool, picked by (layer, table entry) in the index map
    # — the block table IS the page table the DMA walks. Index maps
    # receive (grid ids..., scalar-prefetch refs). Every block's last two
    # dimensions equal its array's, the shape the TPU lowering accepts
    # whatever H and D are.
    pool_spec = pl.BlockSpec(
        (None, None, bs, hd),
        lambda si, j, t, l: (layer, t[si, j], zero, zero))
    slot_spec = pl.BlockSpec((None, 1, hd),
                             lambda si, j, t, l: (si, zero, zero))
    seg_spec = pl.BlockSpec((hd, h), lambda si, j, t, l: (zero, zero))
    in_specs = [slot_spec, seg_spec, pool_spec, pool_spec]
    args = [tables, lens32, qf, seg, k_pools, v_pools]
    if quant:
        spec = pl.BlockSpec(
            (None, None, 1, h),
            lambda si, j, t, l: (layer, t[si, j], zero, zero))
        in_specs += [spec, spec]
        args += [k_scales[:, :, None], v_scales[:, :, None]]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, m),
        in_specs=in_specs,
        out_specs=slot_spec,
        scratch_shapes=[pltpu.VMEM((1, hd), jnp.float32)] * 3)
    kernel = functools.partial(_decode_kernel, block_size=bs,
                               quantized=quant)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, 1, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_decode_attention")(*args)
    return out.reshape(s, h, d)
