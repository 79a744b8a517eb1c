"""int8 KV-cache quantization for the paged serving pool.

Reference analog: the slim post-training quantization passes
(fake_quantize_abs_max family) applied to the serving KV cache — the
reference never quantizes its `fused_multi_transformer` cache buffers;
this is the TPU-native capacity lever the paper's PHI fused-kernel layer
pairs with raw-speed kernels: int8 KV halves the bytes every cached token
costs, so the same pool admits ~2x the streams before the scheduler's
watermark starts refusing (`kv_exhausted`).

Granularity: ONE fp32 scale per (layer, pool block, head) — `[L, num_blocks,
H]` beside each `[L, num_blocks, block_size, H*D]` int8 pool (serving/cache.py:
a layer's heads side by side in one row). Both write paths below address the
STACKED pool and its side-table at `(layer, block, offset)`, so a donated pool
is updated where it lies and no layer of it is ever replaced. Per-block-per-head
is the natural write granularity of the paged cache (prefill lands whole
blocks; decode appends into exactly one block per slot per step) and
keeps the scale side-table negligible (H floats per block vs bs*H*D
bytes of payload).

Write paths:

  * `quantize_scatter` — bulk prompt insertion (serving/cache.py
    `scatter_prefill`): per-block scales are scatter-maxed from the
    written tokens' per-head amax, then every token quantizes under its
    block's scale. Fresh blocks reset their scale first so a previous
    tenant's amax never inflates the new tenant's quantization step.
  * `quantize_block_write` — the decode step's single-token append: the
    slot's write block is read back, dequantized, the new token inserted,
    entries beyond the (post-write) length zeroed (stale garbage must not
    inflate the block scale), and the block re-quantized under the
    updated per-head amax. When the scale did not grow this round-trip is
    exact (the stored int8 levels re-quantize to themselves), so error
    only accrues on the rare amax-raising writes.

Dequantization (`value = int8 * scale / 127`) is fused into the blockwise
attention loop's chunk gathers (kernels/pallas/paged_attention.py): the
fp values exist only inside the loop body's chunk, never as a
materialized pool. (The Pallas kernel reads fp pools; an int8 pool
resolves to the loop.)

Everything here is shape-static pure jnp: the compiled decode/prefill
programs stay ONE executable per engine, int8 or not.
"""
from __future__ import annotations

import jax.numpy as jnp

__all__ = ["QMAX", "SCALE_EPS", "quantize_block_write", "quantize_scatter",
           "dequantize"]

# symmetric int8: levels in [-127, 127] (the -128 level is unused so the
# grid is symmetric and dequant is a pure multiply)
QMAX = 127.0
# floor for stored scales: an all-zero block must not divide by zero
SCALE_EPS = 1e-8


def dequantize(values, scales):
    """int8 `values` `[..., bs, H, D]` under per-head `scales` `[..., H]`
    back to fp32 (`q * scale / 127`)."""
    return values.astype(jnp.float32) \
        * (scales * (1.0 / QMAX))[..., None, :, None]


def quantize_block_write(pools, scales, layer, new_vec, write_block,
                         write_off):
    """Append one token per slot into its int8 block of `layer`,
    re-quantizing the block under the updated per-block-per-head scale.

    pools: ``[L, num_blocks, bs, H*D]`` int8; scales: ``[L, num_blocks,
    H]`` fp32; new_vec: ``[S, H, D]`` fp; write_block/write_off: ``[S]``
    int32 (inactive slots all target the null block — duplicate writes
    there are fine, its content is never unmasked).

    Returns (pools, scales). Traceable and shape-static.
    """
    s, h, d = new_vec.shape
    bs = pools.shape[2]
    rows = jnp.arange(s, dtype=jnp.int32)
    blk = dequantize(pools[layer, write_block].reshape(s, bs, h, d),
                     scales[layer, write_block])             # [S, bs, H, D]
    blk = blk.at[rows, write_off].set(new_vec.astype(jnp.float32))
    # offsets past the write position are stale (a freed block's previous
    # tenant, or prefill padding): zero them so they never inflate the
    # block scale — attention masks them by length, so their VALUE is
    # already dead, but their magnitude would still cost precision here
    live = jnp.arange(bs, dtype=jnp.int32)[None, :] <= write_off[:, None]
    blk = jnp.where(live[:, :, None, None], blk, 0.0)
    new_sc = jnp.maximum(jnp.max(jnp.abs(blk), axis=(1, 3)), SCALE_EPS)
    q = jnp.clip(jnp.round(blk * (QMAX / new_sc)[:, None, :, None]),
                 -QMAX, QMAX).astype(pools.dtype)
    return (pools.at[layer, write_block].set(q.reshape(s, bs, h * d)),
            scales.at[layer, write_block].set(new_sc))


def quantize_scatter(pools, scales, layer, tok_vals, blocks, offs,
                     block_row, length):
    """Bulk-quantize a prefilled prompt's per-token K or V into `layer` of
    the int8 pools (the quantized leg of serving/cache.py
    `scatter_prefill`).

    pools: ``[L, num_blocks, bs, H*D]`` int8; scales: ``[L, num_blocks,
    H]`` fp32; tok_vals: ``[T, H, D]`` fp (right-padded to the prefill bucket);
    blocks/offs: ``[T]`` int32 per-token targets (padded tokens route to
    the null block); block_row: ``[max_blocks]`` int32 — the sequence's
    block table, used to RESET the touched blocks' scales before the
    scatter-max (a freed block keeps its previous tenant's scale
    otherwise); length: scalar int32 true prompt length.

    Returns (pools, scales).
    """
    t = tok_vals.shape[0]
    vals = tok_vals.astype(jnp.float32)
    amax = jnp.max(jnp.abs(vals), axis=-1)                    # [T, H]
    # floor real tokens' amax so the STORED scale is the one quantization
    # divides by (an unfloored stored scale would dequantize sub-epsilon
    # blocks inconsistently); padded tokens contribute nothing
    amax = jnp.where((jnp.arange(t, dtype=jnp.int32)
                      < length)[:, None],
                     jnp.maximum(amax, SCALE_EPS), 0.0)
    scales = scales.at[layer, block_row].set(0.0)
    scales = scales.at[layer, blocks].max(amax)
    sc_t = jnp.maximum(scales[layer, blocks], SCALE_EPS)      # [T, H]
    q = jnp.clip(jnp.round(vals * (QMAX / sc_t)[..., None]),
                 -QMAX, QMAX).astype(pools.dtype)
    return pools.at[layer, blocks, offs].set(q.reshape(t, -1)), scales
