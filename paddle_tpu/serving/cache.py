"""Paged KV cache: block-pool attention memory for continuous batching.

Reference analog: the reference serves through `fused_multi_transformer`'s
dense per-request `[B, max_len, H, D]` cache buffers behind
`AnalysisPredictor` (inference/api/analysis_predictor.h:95). Dense buffers
reserve `max_len` for EVERY sequence, so a 16-token chat and a 2k-token
document cost the same HBM and a new request of a different length means a
new buffer (and on TPU a new compiled shape). This module is the
PagedAttention memory model (vLLM, SOSP'23) rebuilt TPU-native:

  * ONE preallocated block pool, shape
    ``[L, num_blocks, block_size, H*D]`` (a layer's heads side by side in
    one row, so a block is whole (sublane, lane) tiles and the device
    keeps the array row-major: a donated pool is written where it lies)
    — total KV memory is fixed at engine construction, independent of
    how many sequences share it;
  * each sequence owns an ordered list of block ids (its *block table*);
    token position ``p`` of a sequence lives at
    ``(table[p // block_size], p % block_size)``;
  * admission / growth / eviction / preemption are *host-side edits of
    integer tables* — no cache copy, no reshape, no recompile. The
    compiled decode step (serving/engine.py) only ever sees the fixed
    ``[S, max_blocks]`` int32 table and the fixed pools, so sequences of
    wildly different lengths batch into one executable with zero
    retraces.

Block 0 is reserved as the *null block*: inactive batch slots and padded
table entries point at it, so in-graph gathers/scatters never need a
branch — garbage goes to (and comes from) block 0 and is masked out of
the attention softmax. How much of a table a decode step READS is the
attention variant's affair: the blockwise loop
(kernels/pallas/paged_attention.py) stops at the batch's longest context
and, with enough slots, leaves the shorter half of them out of the
chunks only longer ones reach, so those entries are never gathered;
entries it does read that lie past a slot's own length, and the one
chunk an inactive slot is read through, still come from the table (the
null block for padding) and are masked. The Pallas kernel and the dense
reference read every entry and mask.

The device side of the design lives in
`nn/functional/attention.py::paged_decode_attention` (gather-by-block-table
attention) and `scatter_prefill` below (bulk prompt-KV insertion); the
policy side (who gets blocks, who is evicted) lives in
serving/scheduler.py.
"""
from __future__ import annotations

from collections import deque

import jax
import jax.numpy as jnp

__all__ = ["BlockAllocator", "PagedKVCache", "PagedCacheView",
           "scatter_prefill", "NULL_BLOCK", "pool_bytes_per_block",
           "num_blocks_for_bytes"]

# block id 0 is never allocated: it is the write/read target for inactive
# slots and out-of-range table entries (see module docstring)
NULL_BLOCK = 0


class BlockAllocator:
    """Host-side refcounted free-list allocator over the pool's block ids.

    Pure bookkeeping — no device state. O(1) allocate/free; the free
    count is the scheduler's admission-watermark signal.

    PR 17 makes ownership refcounted for shared-prefix KV reuse: a block
    aliased into several sequences' tables (serving/tenancy.py
    PrefixCache) carries one reference per owner, `free` is a decref
    that returns the block to the free list only at zero, and the free
    list holds exactly the refcount-zero blocks — so `num_free` counts
    every shared block ONCE by construction and the watermark/admission
    math needs no aliasing-aware correction. Exclusive ownership (every
    pre-PR 17 caller) behaves exactly as before: allocate hands out a
    block at refcount 1 and the first free releases it.
    """

    def __init__(self, num_blocks):
        if num_blocks < 2:
            raise ValueError(
                f"need >= 2 blocks (one is the reserved null block), got "
                f"{num_blocks}")
        self.num_blocks = int(num_blocks)
        # block 0 reserved; 1..num_blocks-1 allocatable
        self._free = deque(range(1, self.num_blocks))
        self._refs = {}          # block id -> refcount (allocated only)

    @property
    def num_free(self):
        return len(self._free)

    @property
    def capacity(self):
        """Allocatable blocks (pool minus the null block)."""
        return self.num_blocks - 1

    @property
    def num_shared(self):
        """Allocated blocks with more than one owner (prefix aliases)."""
        return sum(1 for rc in self._refs.values() if rc > 1)

    def refcount(self, block):
        """Live owners of `block` (0 when free/never allocated) — the
        engine's copy-on-write trigger reads this before every write
        that would land in a possibly-shared block."""
        return self._refs.get(block, 0)

    def allocate(self, n):
        """Pop `n` block ids (each at refcount 1), or None (allocating
        nothing) when fewer than `n` are free — admission is
        all-or-nothing."""
        if n > len(self._free):
            return None
        out = [self._free.popleft() for _ in range(n)]
        for b in out:
            self._refs[b] = 1
        return out

    def incref(self, block):
        """Add an owner to an ALLOCATED block (prefix-cache aliasing:
        a new sequence's table points at an existing block's KV)."""
        if block == NULL_BLOCK:
            raise ValueError("attempt to share the reserved null block")
        rc = self._refs.get(block)
        if rc is None:
            raise ValueError(
                f"incref of free/unallocated block {block}")
        self._refs[block] = rc + 1

    def free(self, blocks):
        """Drop one owner per listed block; a block rejoins the free
        list only when its LAST owner lets go (shared prefix blocks
        survive any one sequence's eviction)."""
        for b in blocks:
            if b == NULL_BLOCK:
                raise ValueError("attempt to free the reserved null block")
            rc = self._refs.get(b)
            if rc is None:
                raise ValueError(f"free of unallocated block {b}")
            if rc == 1:
                del self._refs[b]
                self._free.append(b)
            else:
                self._refs[b] = rc - 1


class PagedCacheView:
    """The paged cache as one layer sees it from INSIDE the compiled
    decode step: the STACKED pools ``[L, num_blocks, block_size, H*D]``,
    the index of the layer that reads and writes them next, plus the
    batch's block tables / lengths / active mask (jnp arrays or tracers).
    `GPTAttention` detects this view by its `block_tables` attribute and
    routes to the paged decode path. The model threads ONE view through
    its layers: each writes its token at ``(layer, block, offset)`` of the
    stacked pool and `updated()` hands the written pools to the next
    layer, so no per-layer slice of a pool is ever a value of the program
    and the view the last layer returns holds the step's pools.

    int8 mode carries the per-block-per-head scale side-tables
    (`k_scales`/`v_scales`, quantization/kv_cache.py); `kernel` pins the
    attention variant the owning engine resolved at construction
    (nn/functional/attention.resolve_paged_kernel), so a mid-run flag
    flip never re-keys a live engine's compiled decode step."""

    __slots__ = ("k_pools", "v_pools", "layer", "block_tables", "seq_lens",
                 "active", "block_size", "k_scales", "v_scales", "kernel")

    def __init__(self, k_pools, v_pools, layer, block_tables, seq_lens,
                 active, block_size, k_scales=None, v_scales=None,
                 kernel=None):
        self.k_pools = k_pools
        self.v_pools = v_pools
        self.layer = int(layer)
        self.block_tables = block_tables
        self.seq_lens = seq_lens
        self.active = active
        self.block_size = int(block_size)
        self.k_scales = k_scales
        self.v_scales = v_scales
        self.kernel = kernel

    def updated(self, k_pools, v_pools, k_scales=None, v_scales=None):
        """The view for the NEXT layer, over the pools this one wrote."""
        return PagedCacheView(k_pools, v_pools, self.layer + 1,
                              self.block_tables, self.seq_lens, self.active,
                              self.block_size, k_scales=k_scales,
                              v_scales=v_scales, kernel=self.kernel)


def _is_int8(dtype):
    return dtype in ("int8", jnp.int8) or jnp.dtype(dtype) == jnp.int8


class PagedKVCache:
    """The device pools + the allocator, sized once at engine start.

    Pools are stacked over layers — ``[L, num_blocks, block_size, H*D]``
    — so the compiled decode/prefill programs donate exactly two buffers
    regardless of depth, and every program updates them in place: the two
    minor dimensions are whole tiles (16 bf16 sublanes, H*D lanes), so
    the device layout is the row-major one that scatters and gathers by
    block id work on. Sizing policy (blocks per context length, the
    admission budget) lives in ONE place: serving/scheduler.py.

    ``dtype=jnp.int8`` turns on the quantized KV mode
    (quantization/kv_cache.py): int8 pools plus fp32 per-block-per-head
    scale side-tables ``[L, num_blocks, H]`` (`k_scales`/`v_scales`) —
    each cached token costs 1 byte per element instead of 4, so the same
    HBM watermark admits ~2x the streams before `kv_exhausted`.
    """

    def __init__(self, num_layers, num_heads, head_dim, num_blocks,
                 block_size, dtype=jnp.float32):
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.quantized = _is_int8(dtype)
        self.dtype = jnp.int8 if self.quantized else dtype
        shape = (self.num_layers, self.num_blocks, self.block_size,
                 self.num_heads * self.head_dim)
        self.k_pools = jnp.zeros(shape, self.dtype)
        self.v_pools = jnp.zeros(shape, self.dtype)
        if self.quantized:
            sshape = (self.num_layers, self.num_blocks, self.num_heads)
            self.k_scales = jnp.zeros(sshape, jnp.float32)
            self.v_scales = jnp.zeros(sshape, jnp.float32)
        else:
            self.k_scales = None
            self.v_scales = None
        self.allocator = BlockAllocator(self.num_blocks)


def pool_bytes_per_block(num_layers, num_heads, head_dim, block_size,
                         dtype=jnp.float32):
    """Device bytes ONE pool block costs across k+v (and the int8 scale
    side-tables) over every layer — the unit of the serving capacity
    math: `pool bytes = num_blocks * pool_bytes_per_block(...)`."""
    if _is_int8(dtype):
        payload = block_size * num_heads * head_dim        # 1 byte/elem
        scales = num_heads * 4
        return 2 * num_layers * (payload + scales)
    itemsize = jnp.dtype(dtype).itemsize
    return 2 * num_layers * block_size * num_heads * head_dim * itemsize


def num_blocks_for_bytes(budget_bytes, num_layers, num_heads, head_dim,
                         block_size, dtype=jnp.float32):
    """Blocks a byte budget buys (>= 2: the null block + one real one).
    The int8 capacity win reads directly off this: the same budget buys
    ~4x the fp32 blocks (~2x bf16), so the watermark admits ~2-4x the
    concurrent streams before `kv_exhausted` refusals begin."""
    per = pool_bytes_per_block(num_layers, num_heads, head_dim,
                               block_size, dtype)
    return max(2, int(budget_bytes) // per)


@jax.named_scope("scatter_prefill")
def scatter_prefill(k_pools, v_pools, k_layers, v_layers, block_row,
                    length, block_size, k_scales=None, v_scales=None):
    """Bulk-insert a prefilled prompt's K/V into the pools.

    k_layers/v_layers: ``[L, T_bucket, H, D]`` — the per-layer prompt KV
    computed by the bucketed prefill program (right-padded to the bucket).
    block_row: ``[max_blocks]`` int32 — the sequence's block table.
    length: scalar int32 — true prompt length; padded positions are
    routed to the null block (their values are garbage by construction
    and never read: gather masks by `seq_lens`).

    With int8 pools, pass the scale side-tables (``[L, num_blocks, H]``):
    each layer's tokens quantize under freshly computed per-block-per-head
    scales (quantization/kv_cache.py `quantize_scatter`) and the call
    returns ``(k_pools, v_pools, k_scales, v_scales)``.

    Every write is a scatter of flat ``[T, H*D]`` rows at ``(layer, block,
    offset)`` of the stacked ``[L, num_blocks, block_size, H*D]`` pools,
    so a donated pool is updated where it lies. Traceable (runs inside
    the jitted prefill program). Returns the updated pools.
    """
    t_bucket = k_layers.shape[1]
    pidx = jnp.arange(t_bucket, dtype=jnp.int32)
    blocks = jnp.where(pidx < length,
                       block_row[pidx // block_size],
                       jnp.asarray(NULL_BLOCK, jnp.int32))
    offs = pidx % block_size
    num_layers = k_layers.shape[0]
    if k_scales is not None:
        from ..quantization.kv_cache import quantize_scatter
        for layer in range(num_layers):
            k_pools, k_scales = quantize_scatter(
                k_pools, k_scales, layer, k_layers[layer], blocks, offs,
                block_row, length)
            v_pools, v_scales = quantize_scatter(
                v_pools, v_scales, layer, v_layers[layer], blocks, offs,
                block_row, length)
        return k_pools, v_pools, k_scales, v_scales
    k_rows = k_layers.reshape(num_layers, t_bucket, -1).astype(k_pools.dtype)
    v_rows = v_layers.reshape(num_layers, t_bucket, -1).astype(v_pools.dtype)
    for layer in range(num_layers):
        k_pools = k_pools.at[layer, blocks, offs].set(k_rows[layer])
        v_pools = v_pools.at[layer, blocks, offs].set(v_rows[layer])
    return k_pools, v_pools
