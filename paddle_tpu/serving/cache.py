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
null block for padding) and are masked. The Pallas kernel copies only
the pages up to each slot's newest token (one page for an inactive
slot); the dense reference reads every entry and masks.

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

__all__ = ["BlockAllocator", "CacheSpec", "PagedKVCache", "PagedCacheView",
           "scatter_prefill", "scatter_window_prefill", "ring_block",
           "ring_blocks",
           "NULL_BLOCK", "pool_bytes_per_block", "num_blocks_for_bytes"]

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


class CacheSpec:
    """What a model caches for a token, as the model itself describes it
    (`model.cache_spec()`); the manager builds the pools from it and the
    engine asks the model nothing else about its attention.

    kind: ``"kv"``, a key and a value for each of `num_heads` heads (a K
    pool and a V pool of ``num_heads * head_dim`` values a row), or
    ``"latent"``, ONE row that every head shares (a compressed key/value
    and the rotated shared key; attention reads it absorbed).
    num_layers: the cached attention sublayers (a layer may hold several).
    parts: the two tensors a sublayer hands back for a prefilled token,
    as trailing shapes: ``((H, D), (H, D))``, or ``((latent,), (rope,))``.
    widths: a row's width in each pool: two under ``"kv"``; ONE under
    ``"latent"``, a pool whose rows hold both parts side by side, padded
    to that width (whole 128-lane tiles; a pool for each part read 1.8 x
    slower on the chip, PERF.md section 4). The programs' second pool is
    then empty.
    num_heads / head_dim: what the blockwise loop plans its chunks from
    (`_blockwise_plan`): a latent row is one shared head.
    chunk_tokens / min_width_slots: the loop's chunk and its narrowest
    width, where the plan's own are not wanted (`loop_plan(block_size)`
    is what the loop and its counter are both given).
    query_heads: the heads that ASK, where they are more than the
    `num_heads` a ``"kv"`` row holds (grouped queries: query head i reads
    key/value head ``i // (query_heads // num_heads)``); `num_heads`
    where not given.
    state_layers / state_parts: a SECOND kind of state beside the paged
    pool, for layers that are not attention and still keep something of
    the past (a short convolution's last inputs; a delta rule's matrix a
    head): each of `state_layers` layers keeps, a slot, ONE fixed block of
    EVERY part that `state_parts` names, ``(name, trailing shape, dtype)``
    each (dtype None: the model's), not paged: an array a part, ``[state_
    layers, slots] + shape`` (`PagedKVCache.slot_state`, a tuple in the
    parts' order), threaded through the layers by `PagedCacheView` as the
    pools are. Parts differ in shape AND type (bfloat16 inputs beside a
    float32 matrix 28 x their size); one part is the one-entry case of
    the same path. 0 layers: the model keeps none, no buffer exists and
    no program has an argument for it.

    THE RULE a slot's state is kept by (tests/test_lfm2_moe.py and
    tests/test_solar_open2.py hold it): a PREFILL WRITES every part of
    its slot's state WHOLE, as it stands after the prompt's true `length`
    (not its bucket's end; what lies before the sequence is zeros, so
    prompts shorter than the state need no special case), so a reused
    slot needs no clearing and an evicted request's resume, which is a
    re-prefill of prompt + generated tokens, restores the state by
    computing it; a DECODE launch moves the state of ACTIVE slots only
    one token on, where it lies. What a state cannot do yet the engine
    refuses by name at construction: reuse of a prefix (no state exists
    at a prefix's boundary), adapters, an int8 pool.

    window_layers / window / window_parts: a THIRD kind, for attention
    layers that see only the last `window` tokens (position i attends j
    with ``i - window < j <= i``). Held in the paged pool such a layer
    would keep every token of a sequence for nothing, so its keys and
    values live in an allocation of their own: a RING OF BLOCKS A SLOT,
    ``ring_blocks(block_size) = ceil(window / block_size) + 1`` blocks
    (the window and the block the newest token is filling), two pools
    ``[window_layers, 1 + slots * ring, block_size, H * D]`` at the
    rows of `window_parts` (``((H, Dk), (H, Dv))``: their own head count
    and widths), block 0 the null block. Position p of slot s lies in
    block ``1 + s * ring + (p // block_size) % ring`` at row
    ``p % block_size``: no table, no allocator, nothing the scheduler
    counts; what it costs is ``slots * ring * block_size`` rows a layer
    whatever the contexts. Under ``"kv"`` the two `parts` may differ in
    width too (a key wider than its value).

    THE RULE the rings are kept by (tests/test_mimo_v2_flash.py holds
    it): a PREFILL WRITES the last `window` tokens before the prompt's
    true `length` (not its bucket's end; all of a shorter prompt) into
    its slot's ring, which is every position the slot's first decode
    launch may read, so a reused slot needs no clearing; a DECODE launch
    writes the new token of ACTIVE slots only (an inactive slot's goes
    to the null block) over the block that left the window longest ago,
    and reads the blocks from the window's oldest position to the
    newest, masked to the window; EVICTION frees nothing here (the ring
    is the slot's, not the request's) and a RESUME, which is a
    re-prefill of prompt + generated tokens, writes the ring anew. What
    a ring cannot do yet the engine refuses by name at construction:
    reuse of a prefix (a prefix hit skips the prefill that writes the
    ring, and no ring exists at a prefix's boundary), adapters, an int8
    pool.
    """

    __slots__ = ("kind", "num_layers", "parts", "widths", "num_heads",
                 "head_dim", "chunk_tokens", "min_width_slots",
                 "query_heads", "state_layers", "state_parts",
                 "window_layers", "window", "window_parts")

    def __init__(self, kind, num_layers, parts, widths, num_heads,
                 head_dim, chunk_tokens=None, min_width_slots=None,
                 query_heads=None, state_layers=0, state_parts=(),
                 window_layers=0, window=0, window_parts=()):
        if kind not in ("kv", "latent"):
            raise ValueError(f"unknown cache kind {kind!r}")
        if len(widths) != (2 if kind == "kv" else 1):
            raise ValueError(f"a {kind!r} cache of {len(widths)} pools")
        query_heads = int(query_heads or num_heads)
        if query_heads % int(num_heads):
            raise ValueError(f"{query_heads} query heads over {num_heads} "
                             "key/value heads: not whole groups")
        self.kind = kind
        self.num_layers = int(num_layers)
        self.parts = tuple(tuple(int(n) for n in p) for p in parts)
        self.widths = tuple(int(w) for w in widths)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.chunk_tokens = chunk_tokens
        self.min_width_slots = min_width_slots
        self.query_heads = query_heads
        self.state_layers = int(state_layers)
        self.state_parts = tuple(
            (str(name), tuple(int(n) for n in shape), dtype)
            for name, shape, dtype in state_parts)
        if bool(self.state_layers) != bool(self.state_parts):
            raise ValueError(
                f"{self.state_layers} state layers of "
                f"{len(self.state_parts)} parts: a layer that keeps a "
                "state names its parts, and parts need such a layer")
        self.window_layers = int(window_layers)
        self.window = int(window)
        self.window_parts = tuple(tuple(int(n) for n in p)
                                  for p in window_parts)
        if self.window_layers and (self.window < 1
                                   or len(self.window_parts) != 2):
            raise ValueError(
                f"{self.window_layers} window layers need a window and the "
                f"two parts of a token, got window {window!r} and parts "
                f"{window_parts!r}")

    def ring_blocks(self, block_size):
        """Blocks a slot's ring holds (`ring_blocks`)."""
        return ring_blocks(self.window, block_size)

    def loop_plan(self, block_size):
        """Keyword arguments of the blockwise loop's plan ({} for the
        plan's own)."""
        plan = {}
        if self.chunk_tokens:
            plan["chunk_blocks"] = max(
                1, int(self.chunk_tokens) // int(block_size))
        if self.min_width_slots:
            plan["min_width"] = int(self.min_width_slots)
        return plan

    @classmethod
    def per_head(cls, num_layers, num_heads, head_dim, value_dim=None,
                 **more):
        """A K and a V pool of all the heads' values side by side
        (`value_dim`: a value's width where it is not the key's)."""
        value_dim = head_dim if value_dim is None else value_dim
        return cls("kv", num_layers,
                   ((num_heads, head_dim), (num_heads, value_dim)),
                   (num_heads * head_dim, num_heads * value_dim), num_heads,
                   head_dim, **more)

    def state_arrays(self, leading, dtype):
        """One zeroed array a state part, `leading` dimensions in front
        of the part's own shape, in the part's dtype (`dtype` where the
        part names none)."""
        return tuple(jnp.zeros(tuple(leading) + shape, own or dtype)
                     for _, shape, own in self.state_parts)

    def empty_prefill(self, dtype, rows=1):
        """The caches a prefill hands the model: for each cached sublayer
        the two parts with no token in them yet, then the same for each
        window layer, then, for each layer that keeps a per-slot state,
        the tuple of that state's parts as they are before a sequence:
        zeros. The model hands back the same list after the prompt."""
        from ..framework.core import Tensor
        caches = []
        for parts, layers in ((self.parts, self.num_layers),
                              (self.window_parts, self.window_layers)):
            for _ in range(layers):
                first = Tensor(jnp.zeros((rows, 0) + parts[0], dtype))
                # parts of one shape share the one empty tensor
                caches.append((first, first if parts[1] == parts[0]
                               else Tensor(jnp.zeros((rows, 0) + parts[1],
                                                     dtype))))
        if self.state_layers:
            caches += [tuple(Tensor(part) for part in
                             self.state_arrays((rows,), dtype))] \
                * self.state_layers
        return caches


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
    flip never re-keys a live engine's compiled decode step.

    `slot_state` (a tuple of one ``[state_layers, slots, ...]`` array a
    state part, or None where the model keeps none) is the slots' state
    that is not paged, with `state_layer`, the index of the next layer
    that owns one: such a layer reads and writes its own index of every
    part and hands the view on through `updated(slot_state=...)`, as an
    attention layer hands on the pools.

    `window_pools` (the two ring pools of `CacheSpec`'s window layers,
    or None where the model has none) with `window_layer` and `window`
    (tokens) go the same way: a window layer reads and writes its own
    index of them and hands the view on through
    `updated(window_pools=...)`."""

    __slots__ = ("k_pools", "v_pools", "layer", "block_tables", "seq_lens",
                 "active", "block_size", "k_scales", "v_scales", "kernel",
                 "slot_state", "state_layer", "window_pools", "window_layer",
                 "window")

    def __init__(self, k_pools, v_pools, layer, block_tables, seq_lens,
                 active, block_size, k_scales=None, v_scales=None,
                 kernel=None, slot_state=None, state_layer=0,
                 window_pools=None, window_layer=0, window=0):
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
        self.slot_state = slot_state
        self.state_layer = int(state_layer)
        self.window_pools = window_pools
        self.window_layer = int(window_layer)
        self.window = int(window)

    def updated(self, k_pools=None, v_pools=None, k_scales=None,
                v_scales=None, slot_state=None, window_pools=None):
        """The view for the NEXT layer: over the pools an attention layer
        wrote, or, given `slot_state` or `window_pools`, over what a layer
        that keeps a state, or a window layer, wrote (the pools and their
        layer index as they were)."""
        if slot_state is not None or window_pools is not None:
            stated = slot_state is not None
            return PagedCacheView(
                self.k_pools, self.v_pools, self.layer, self.block_tables,
                self.seq_lens, self.active, self.block_size,
                k_scales=self.k_scales, v_scales=self.v_scales,
                kernel=self.kernel,
                slot_state=slot_state if stated else self.slot_state,
                state_layer=self.state_layer + stated,
                window_pools=self.window_pools if stated else window_pools,
                window_layer=self.window_layer + (not stated),
                window=self.window)
        return PagedCacheView(k_pools, v_pools, self.layer + 1,
                              self.block_tables, self.seq_lens, self.active,
                              self.block_size, k_scales=k_scales,
                              v_scales=v_scales, kernel=self.kernel,
                              slot_state=self.slot_state,
                              state_layer=self.state_layer,
                              window_pools=self.window_pools,
                              window_layer=self.window_layer,
                              window=self.window)


def _is_int8(dtype):
    return dtype in ("int8", jnp.int8) or jnp.dtype(dtype) == jnp.int8


class PagedKVCache:
    """The device pools + the allocator, sized once at engine start.

    Built from the model's own description (`CacheSpec`): per-head K and
    V pools, or a latent pool of one shared row a token. Pools are
    stacked over layers — ``[L, num_blocks, block_size, H*D]``
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

    A model whose `CacheSpec` describes a per-slot state gets it beside
    the pools: `slot_state`, one array ``[state_layers, num_slots] +
    shape`` a part, in the part's own dtype or, where it names none,
    `state_dtype` (the model's; the pool's where not given), zeros, not
    paged and never allocated from. One whose `CacheSpec` describes
    window layers gets their two ring pools (`window_pools`
    ``[window_layers, 1 + num_slots * ring, block_size, H * D]``, zeros,
    in the pool's dtype; never allocated from either: a slot's ring is
    its own). `buffers()` is every device buffer of the cache in the
    order the engine's programs take, donate and hand them back.
    """

    def __init__(self, spec, num_blocks, block_size, dtype=jnp.float32,
                 num_slots=0, state_dtype=None):
        self.spec = spec
        self.num_layers = spec.num_layers
        self.num_heads = spec.num_heads
        self.head_dim = spec.head_dim
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.quantized = _is_int8(dtype)
        self.dtype = jnp.int8 if self.quantized else dtype
        pools = [jnp.zeros((self.num_layers, self.num_blocks,
                            self.block_size, width), self.dtype)
                 for width in spec.widths]
        # the programs thread two donated pools: a description with one
        # width (a latent row, both parts side by side) leaves the second
        # empty, and no program reads or writes it
        self.k_pools = pools[0]
        self.v_pools = pools[1] if len(pools) > 1 \
            else jnp.zeros((0,), self.dtype)
        if self.quantized:
            sshape = (self.num_layers, self.num_blocks, self.num_heads)
            self.k_scales = jnp.zeros(sshape, jnp.float32)
            self.v_scales = jnp.zeros(sshape, jnp.float32)
        else:
            self.k_scales = None
            self.v_scales = None
        self.slot_state = None
        if spec.state_layers:
            self.slot_state = spec.state_arrays(
                (spec.state_layers, int(num_slots)),
                self.dtype if state_dtype is None else state_dtype)
        self.window_pools = None
        if spec.window_layers:
            blocks = 1 + int(num_slots) * spec.ring_blocks(self.block_size)
            self.window_pools = tuple(
                jnp.zeros((spec.window_layers, blocks, self.block_size,
                           heads * width), self.dtype)
                for heads, width in spec.window_parts)
        self.allocator = BlockAllocator(self.num_blocks)

    def buffers(self):
        """The cache's device buffers: the two pools, the int8 scale
        side-tables where the pool is quantized, the slots' state (an
        array a part) where the model keeps one, the window layers' two
        ring pools where it has such layers."""
        out = (self.k_pools, self.v_pools)
        if self.quantized:
            out += (self.k_scales, self.v_scales)
        if self.slot_state is not None:
            out += self.slot_state
        if self.window_pools is not None:
            out += self.window_pools
        return out

    def slot_state_bytes(self):
        """{part: bytes} of the slots' state ({} where the model keeps
        none)."""
        return {name: int(part.nbytes) for (name, _, _), part in
                zip(self.spec.state_parts, self.slot_state or ())}


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
    if not v_pools.size:
        # one pool (a latent row): both parts side by side in its rows,
        # zeros up to the pool's width
        fill = k_pools.shape[-1] - k_rows.shape[-1] - v_rows.shape[-1]
        k_rows = jnp.concatenate(
            [k_rows, v_rows, jnp.zeros(k_rows.shape[:2] + (fill,),
                                       k_rows.dtype)], axis=-1)
    for layer in range(num_layers):
        k_pools = k_pools.at[layer, blocks, offs].set(k_rows[layer])
        if v_pools.size:
            v_pools = v_pools.at[layer, blocks, offs].set(v_rows[layer])
    return k_pools, v_pools


def ring_blocks(window, block_size):
    """Blocks a slot's ring holds for a window of `window` tokens: the
    window and the block the newest token is filling."""
    return -(-int(window) // int(block_size)) + 1


def ring_block(slot, position, block_size, ring):
    """The block of a window layer's ring pool that holds `position` of
    `slot` (`CacheSpec`: block 0 is the null block, a slot's `ring`
    blocks follow each other, a position's block turns with
    ``position // block_size``). Arrays or tracers, int32."""
    return 1 + slot * ring + (position // block_size) % ring


@jax.named_scope("scatter_window_prefill")
def scatter_window_prefill(k_pools, v_pools, k_layers, v_layers, slot,
                           length, block_size, window):
    """A prefilled prompt's last `window` tokens into `slot`'s ring
    (`CacheSpec`'s rule: the tokens before the prompt's TRUE `length`,
    every position the slot's first decode launch may read).

    k_layers/v_layers: ``[L, T_bucket, H, D]`` of the window layers, as
    the bucketed prefill computed them; k_pools/v_pools: the ring pools
    ``[L, 1 + slots * ring, block_size, H*D]``. Only `window` rows a
    layer are moved (a slice that ends at `length`, not the bucket); rows
    of it that lie before the sequence or past its length go to the null
    block. Traceable; returns the written pools."""
    num_layers, t_bucket = k_layers.shape[:2]
    ring = ring_blocks(window, block_size)
    rows = min(int(window), t_bucket)
    first = jnp.clip(length - rows, 0, t_bucket - rows).astype(jnp.int32)
    pos = first + jnp.arange(rows, dtype=jnp.int32)
    blocks = jnp.where(pos < length,
                       ring_block(slot, pos, block_size, ring),
                       jnp.asarray(NULL_BLOCK, jnp.int32)).astype(jnp.int32)
    offs = pos % block_size

    def last(layers, pool):
        cut = jax.lax.dynamic_slice_in_dim(layers, first, rows, axis=1)
        return cut.reshape(num_layers, rows, -1).astype(pool.dtype)

    k_rows, v_rows = last(k_layers, k_pools), last(v_layers, v_pools)
    for layer in range(num_layers):
        k_pools = k_pools.at[layer, blocks, offs].set(k_rows[layer])
        v_pools = v_pools.at[layer, blocks, offs].set(v_rows[layer])
    return k_pools, v_pools
