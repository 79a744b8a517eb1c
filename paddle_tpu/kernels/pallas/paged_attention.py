"""Paged decode attention over the block-pool KV cache: streaming softmax
over the pages that hold tokens, the dense ``[S, T, H, D]`` context of a
gather by block table (nn/functional/attention.py, the oracle) never made.

What the file holds: a Pallas TPU kernel and a pure-JAX loop over ONE pool
layout, ``[L, num_blocks, bs, H*D]`` (serving/cache.py), read at a layer
index where it lies (the pool is never reshaped or sliced per layer, so a
program that donates it updates and reads it in place), each with the
numpy count of what it reads that the engine keeps, and both again over
a latent pool. `resolve_paged_kernel` (nn/functional/attention.py) chooses
between kernel and loop from platform, pool dtype and the row's shape;
`PERF.md` sections 5 and 6 have their chip readings.

  * `pallas_paged_attention` — the TPU kernel over a per-head fp pool.
    A grid step a slot; the pools stay in HBM, block tables, lengths and
    the layer index ride as scalar prefetch. For a slot it copies ONLY
    the pages up to the one that holds its newest token (`_slot_pages`;
    an inactive slot reads one page), `_group_pages` pages a step, K and
    V each by `make_async_copy` into one of two VMEM buffers, and the
    copies do not drain at a slot's end: the next slot's first group is
    in flight while this one's last is multiplied. The rows go to the
    matrix unit as they lie (``[tokens, H*D]``, heads side by side on
    the lanes): scores ``[H, tokens]`` are a block-diagonal query
    ``[H, H*D]`` (head h's query in its own D lanes) contracted with K
    over the lanes, the output ``[H, H*D]`` is p x V, of which head h
    keeps its own D lanes: H times the useful multiply-adds, and still
    bound by its copies. The running max, sum and accumulator of a slot
    stay on the chip from its first group to its last; only ``[S, H*D]``
    is written. Positions past a slot's length in its last page, and
    rows of a buffer no copy reached, are masked. `pallas_copied_pages`
    is the host's count of the pages it copies, from `_slot_pages` too.
    Runs under ``interpret=True`` on the CPU for the parity tests.
  * `blockwise_paged_attention` — a pure-JAX loop over block chunks with
    the same online-softmax recurrence, BOUNDED BY THE LENGTHS: the
    loop stops after the chunk that holds the longest slot's newest
    token, and with enough slots they are ordered longest first and a
    step reads its chunk only for the first half, quarter, ... of them
    when no other slot reaches that chunk (a trip count and a branch
    index the device reads; one program whatever the lengths). Table
    entries a step leaves out are not read at all; inside a step,
    positions past a slot's own length and the entries of an inactive
    slot's one chunk still read what the table names (the null block,
    once cleared) and are masked. What a latent pool, an int8 pool
    (dequantized inside the chunk gather: `q * scale / 127`), the CPU
    and a shape off the TPU's tiles run.
    `blockwise_streamed_entries` is the host's count
    of what that loop reads, from the same plan and step widths.
  * `blockwise_latent_attention`, `pallas_latent_attention` — that loop
    and that kernel over a pool whose token is ONE row every head shares
    (multi-head latent attention, absorbed): the kernel's copy pipeline
    is the per-head kernel's own (`_page_copies`), over one pool and not
    two; its multiply step has no block-diagonal query: scores are one
    product of all the heads' queries with the rows as they lie, the
    value the rows' first lanes.

Numerics: scores, the softmax recurrence, and the output accumulator are
fp32 regardless of the query/pool dtype; only the final output casts back
to the query dtype. In the kernel K and V enter the matrix unit in the
pool's dtype: two bf16 operands are one exact pass (products exact in
fp32), p goes into the second product as its three bf16 parts (all 24
bits), and any fp32 operand runs at `Precision.HIGHEST`. Masked positions
contribute exactly zero probability (explicit `where`, not just a large
negative score).
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

__all__ = ["blockwise_paged_attention", "blockwise_latent_attention",
           "blockwise_streamed_entries", "pallas_paged_attention",
           "pallas_latent_attention", "pallas_banded_attention",
           "pallas_copied_pages", "is_eligible"]

_NEG_INF = -1e30

# blockwise chunking: gather KV per loop step in chunks targeting this
# many BYTES per pool side and slot (multiple pool blocks per step when
# block_size is small) — big enough to amortize the loop-iteration
# overhead, small enough to stay cache-resident instead of
# re-materializing the dense context, and the grain of the loop's bound
# (it streams whole chunks). Tokens are capped so tiny-head shapes don't
# degenerate into one dense chunk
_CHUNK_TARGET_BYTES = 256 * 1024
_CHUNK_TOKENS_MAX = 512

# blockwise widths: a loop step reads its chunk for all the slots or for
# the first half, quarter, ... of them (ordered longest first), whichever
# is the narrowest that holds every slot with a token in that chunk; no
# width is under this many slots. Each width is one more traced copy of
# the step, and a narrow step pays the step's fixed cost for few bytes;
# PERF.md section 7 has the chip readings that chose it.
_MIN_WIDTH_SLOTS = 64


def is_eligible(num_heads, head_dim, block_size, kv_dtype=jnp.bfloat16):
    """Can the Pallas kernels run compiled (non-interpret) here, over a
    pool of `kv_dtype` whose row is ``num_heads * head_dim`` values (a
    latent row is ONE head)? Returns (ok, why) — `why` is the
    attribution detail for the `kernel.fallback` flight-recorder event
    when not. The shape's part is what the v5e compiler accepts of both
    kernels (tests/test_tpu_compile.py): they multiply the rows of a page
    as they lie, so a row is whole 128-lane tiles and a page whole
    sublane tiles of the pool's dtype, and two groups a side live in VMEM
    (`_group_pages`)."""
    if not _HAS_PALLAS:
        return False, "no_pallas"
    if not _on_tpu():
        return False, "not_on_tpu"
    if None in (num_heads, head_dim, block_size):
        return False, "shape_unknown"
    kv_dtype = jnp.dtype(kv_dtype)
    if not jnp.issubdtype(kv_dtype, jnp.floating):
        return False, "quantized_pool"
    row = num_heads * head_dim
    if row % 128:
        return False, "row_not_whole_lane_tiles"
    if block_size % _SUBLANE_TILE:
        return False, "block_not_whole_sublane_tiles"
    if not _group_pages(1, block_size, row, kv_dtype):
        return False, "block_exceeds_vmem"
    return True, None


# ---------------------------------------------------------------------------
# pure-JAX blockwise path: a length-bounded loop over block chunks
# ---------------------------------------------------------------------------

def _blockwise_plan(num_slots, table_entries, block_size, num_heads,
                    head_dim, chunk_blocks=None, min_width=None):
    """The static shape of the blockwise loop, from the call's shapes
    alone: ``(widths, chunk_blocks, n_chunks)``. `widths` are the slot
    counts a loop step can run at, widest first: all the slots, then
    halves (rounded up) while a half still holds `_MIN_WIDTH_SLOTS`: so
    one width, and a loop that only stops at the longest context, up
    to about twice that many slots."""
    if chunk_blocks is None:
        per_token = num_heads * head_dim * jnp.dtype(jnp.float32).itemsize
        tokens = min(max(_CHUNK_TARGET_BYTES // per_token, block_size),
                     _CHUNK_TOKENS_MAX)
        chunk_blocks = max(1, int(tokens) // block_size)
    chunk_blocks = min(int(chunk_blocks), table_entries)
    n_chunks = -(-table_entries // chunk_blocks)
    widths = [num_slots]
    # a narrower last width is a caller's to ask for, with a chunk whose
    # gather at that width is as large as one this chip has run
    while -(-widths[-1] // 2) >= (min_width or _MIN_WIDTH_SLOTS):
        widths.append(-(-widths[-1] // 2))
    return tuple(widths), chunk_blocks, n_chunks


def _step_widths(lens, widths, chunk_tokens, n_chunks, xp):
    """What each loop step runs at, in `xp` (jax.numpy inside the program,
    numpy for the host's count, so the two cannot drift). lens: ``[S]``,
    SORTED longest first where there is more than one width. Returns
    ``(trips, which)``: `trips` the chunks the loop streams (those that
    reach the longest slot's newest token, at least one), `which`
    ``[n_chunks]`` the index into `widths` of the narrowest width that
    holds every slot with a token in that chunk (slot r has one in
    chunk c iff ``lens[r] // chunk_tokens >= c``; the slots are sorted,
    so those are the first so many)."""
    last = lens.astype(xp.int32) // chunk_tokens        # [S] last chunk
    trips = xp.clip(last.max() + 1, 1, n_chunks)
    chunks = xp.arange(n_chunks, dtype=xp.int32)
    # chunk 0 is every slot's (an empty slot reads the null block there)
    need = xp.where(chunks == 0, lens.shape[0],
                    (last[None, :] >= chunks[:, None]).sum(axis=1))
    fits = xp.asarray(widths, dtype=xp.int32)[None, :] >= need[:, None]
    which = fits.sum(axis=1) - 1                        # widths descend
    return trips.astype(xp.int32), which.astype(xp.int32)


def blockwise_streamed_entries(lens, active, table_entries, block_size,
                               num_heads, head_dim, chunk_blocks=None,
                               min_width=None):
    """The host's count of one decode step's attention, in table entries
    summed over the slots: ``(streamed, held)``. `streamed` is what
    `blockwise_paged_attention`'s loop reads for these lengths (the same
    plan and the same step widths as the program's), `held` the entries
    that hold a token some slot attends to. Both are at most
    ``len(lens) * table_entries``, what a loop over the whole table reads.
    lens/active: numpy ``[S]``, as the engine keeps them."""
    active = np.asarray(active, bool)
    eff = np.where(active, np.asarray(lens, np.int64), 0)
    widths, chunk_blocks, n_chunks = _blockwise_plan(
        eff.shape[0], table_entries, block_size, num_heads, head_dim,
        chunk_blocks, min_width)
    trips, which = _step_widths(-np.sort(-eff), widths,
                                chunk_blocks * block_size, n_chunks, np)
    # the last chunk may reach past the table: those entries are fill
    entries = np.minimum(chunk_blocks, table_entries
                         - np.arange(n_chunks) * chunk_blocks)
    streamed = int((np.asarray(widths)[which] * entries)[:trips].sum())
    held = int((eff // block_size + 1)[active].sum())
    return streamed, held


def _blockwise_loop(q32, block_tables, lens, plan, block_size, value_width,
                    chunk, starts=None, sink=None):
    """The loop both blockwise attentions share: online softmax over the
    chunks that hold tokens, at the widths `_step_widths` picks.
    `starts` ``[S]`` int32 (a window's oldest position: positions before
    it are masked) and `sink` ``[H]`` float32 (a learned score a head
    that joins the softmax's denominator and adds no value: the
    recurrence starts from max = sink, sum = 1) where the caller has them.

    q32: ``[S, H, .]`` float32 scaled queries; plan: `_blockwise_plan`'s;
    ``chunk(w, bids, q)`` reads the chunk whose block ids are `bids`
    ``[w, C]`` for the first `w` slots (a static count; `q` the queries
    of ALL the slots in the loop's order, of which it takes ``q[:w]``)
    and returns ``(scores [w, H, t], weigh)``, `weigh(p)` being the chunk's values
    weighed by p ``[w, H, t]`` -> ``[w, H, value_width]``. Returns the
    attention output ``[S, H, value_width]`` float32, in the callers'
    slot order."""
    s, h = q32.shape[:2]
    m = block_tables.shape[1]
    widths, chunk_blocks, n_chunks = plan
    t_chunk = chunk_blocks * int(block_size)
    lens = lens.astype(jnp.int32)
    tables = block_tables
    order = None
    if len(widths) > 1:
        order = jnp.argsort(-lens, stable=True).astype(jnp.int32)
        q32, lens, tables = q32[order], lens[order], tables[order]
        if starts is not None:
            starts = starts[order]
    trips, which = _step_widths(lens, widths, t_chunk, n_chunks, jnp)
    # table entries past M (the last chunk's fill) read the null block;
    # their positions exceed every length, so the mask kills them.
    # Chunks along the leading axis: [n_chunks, S, C]
    tabs = jnp.swapaxes(
        jnp.pad(tables, ((0, 0), (0, n_chunks * chunk_blocks - m)))
        .reshape(s, n_chunks, chunk_blocks), 0, 1)
    offs = jnp.arange(t_chunk, dtype=jnp.int32)

    def attend(w, ci, bids, carry):
        """Chunk `ci`, whose block ids are `bids` [S, C], of the first
        `w` slots (a static count)."""
        acc_all, mx_all, l_all = carry
        acc, mx, l = acc_all[:w], mx_all[:w], l_all[:w]
        scores, weigh = chunk(w, bids[:w], q32)
        pos = ci * t_chunk + offs
        valid = pos[None, :] <= lens[:w, None]          # [w, t]
        if starts is not None:
            valid = valid & (pos[None, :] >= starts[:w, None])
        scores = jnp.where(valid[:, None, :], scores,
                           jnp.float32(_NEG_INF))
        m_new = jnp.maximum(mx, jnp.max(scores, axis=-1))
        # explicit zero for masked slots: a fully-masked chunk must not
        # leak exp(NEG - NEG) == 1 into the row sums
        p = jnp.where(valid[:, None, :],
                      jnp.exp(scores - m_new[..., None]), 0.0)
        alpha = jnp.exp(mx - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + weigh(p)
        return (acc_all.at[:w].set(acc), mx_all.at[:w].set(m_new),
                l_all.at[:w].set(l))

    branches = [functools.partial(attend, w) for w in widths]

    def step(ci, carry):
        if order is None:                               # one width
            return branches[0](ci, tabs[ci], carry)
        return jax.lax.switch(which[ci], branches, ci, tabs[ci], carry)

    acc0 = jnp.zeros((s, h, value_width), jnp.float32)
    m0 = jnp.full((s, h), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((s, h), jnp.float32)
    if sink is not None:
        m0 = jnp.broadcast_to(sink.astype(jnp.float32)[None, :], (s, h))
        l0 = jnp.ones((s, h), jnp.float32)
    # a traced bound: a while loop whose trip count the device reads
    acc, _, l = jax.lax.fori_loop(0, trips, step, (acc0, m0, l0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    if order is not None:
        # back to the callers' slot order
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(s, dtype=jnp.int32))
        out = out[back]
    return out


def blockwise_paged_attention(q, k_pools, v_pools, layer, block_tables,
                              lens, block_size, k_scales=None, v_scales=None,
                              chunk_blocks=None, starts=None, sink=None):
    """Online-softmax paged attention, one KV chunk at a time, over the
    chunks and the slots that hold tokens.

    q: ``[S, Hq, D]`` this step's queries, Hq a multiple of the H heads
    a row holds (query head i reads key/value head ``i // (Hq // H)``);
    k_pools/v_pools:
    ``[L, num_blocks, bs, H*D]`` (fp, or int8 with `k_scales`/`v_scales`
    ``[L, num_blocks, H]``) and `layer` the one to read; block_tables:
    ``[S, M]`` int32; lens: ``[S]`` int32 EFFECTIVE lengths (position p
    attends iff p <= lens[s]; inactive slots pass 0). Returns
    ``[S, H, D]`` in q's dtype. Each loop step gathers
    ``pool[layer, block ids]`` and splits the GATHERED rows into heads:
    neither a layer of the pool nor the pool in another shape is ever a
    value, so the program reads a donated pool where it lies.

    The loop (`_blockwise_loop`) stops after the chunk that holds the
    longest slot's newest token. Where `_blockwise_plan` gives more than
    one width, the slots are ordered longest first and a step reads its
    chunk for the first W of them only, W the narrowest of those static
    widths that holds every slot with a token in that chunk
    (`_step_widths`, read on the device: the shapes, and so the compiled
    program, do not depend on the lengths). A slot's chunk that is not
    read adds exactly nothing to the recurrence, so every slot's output
    is what a loop over the whole table gives. Inside a step, positions
    past a slot's own length are still gathered and masked; an inactive
    slot reads the null block through one chunk.

    A value may be narrower than its key (``v_pools`` rows of ``H*Dv``:
    the result is ``[S, Hq, Dv]``); `starts` ``[S]`` and `sink` ``[Hq]``
    are `_blockwise_loop`'s (a window's oldest position, a head's learned
    sink).
    """
    s, hq, d = q.shape
    bs = int(block_size)
    quant = k_scales is not None
    # the heads a row holds; `hq // h` queries read each (grouped queries:
    # query head i reads key/value head i // group)
    h = k_pools.shape[-1] // d
    dv = v_pools.shape[-1] // h
    group = hq // h
    plan = _blockwise_plan(s, block_tables.shape[1], bs, h, d, chunk_blocks)
    chunk_blocks = plan[1]
    t_chunk = chunk_blocks * bs
    q32 = q.astype(jnp.float32) * (1.0 / math.sqrt(d))

    def chunk(w, bids, q):
        kc = k_pools[layer, bids]                       # [w, C, bs, H*D]
        vc = v_pools[layer, bids]
        if quant:
            split = (w, chunk_blocks, bs, h, d)
            kc = _dequant(kc.reshape(split), k_scales[layer, bids])
            vc = _dequant(vc.reshape(split), v_scales[layer, bids])
        else:
            kc = kc.astype(jnp.float32)
            vc = vc.astype(jnp.float32)
        kc = kc.reshape(w, t_chunk, h, d)
        vc = vc.reshape(w, t_chunk, h, dv)
        if group == 1:
            return (jnp.einsum("shd,sthd->sht", q[:w], kc),
                    lambda p: jnp.einsum("sht,sthd->shd", p, vc))
        qg = q[:w].reshape(w, h, group, d)
        return (jnp.einsum("shgd,sthd->shgt", qg, kc).reshape(
                    w, hq, t_chunk),
                lambda p: jnp.einsum(
                    "shgt,sthd->shgd", p.reshape(w, h, group, t_chunk),
                    vc).reshape(w, hq, dv))

    return _blockwise_loop(q32, block_tables, lens, plan, bs, dv,
                           chunk, starts, sink).astype(q.dtype)


def blockwise_latent_attention(q, pool, layer, block_tables, lens,
                               block_size, value_width, scale,
                               chunk_blocks=None, min_width=None):
    """The blockwise loop over a LATENT pool: every head attends over the
    one row a token holds, absorbed (multi-head latent attention at
    decode: no key or value of any head is ever made for a cached token).

    q: ``[S, H, W]`` the queries already carried into the row's space (a
    head's compressed-key product beside its rotary part); pool:
    ``[L, num_blocks, bs, >= W]`` (rows zero past W); the value of a
    token is the first `value_width` values of its row. Scores are
    ``scale * q . row``; `lens` as in `blockwise_paged_attention`.
    Returns ``[S, H, value_width]`` float32. The plan, the widths and
    the trip count are that loop's own (`_blockwise_plan` with the row as
    ONE head), so `blockwise_streamed_entries` counts this loop too."""
    s, _, w_q = q.shape
    bs = int(block_size)
    row = pool.shape[-1]
    plan = _blockwise_plan(s, block_tables.shape[1], bs, 1, row,
                           chunk_blocks, min_width)
    q32 = q.astype(jnp.float32) * jnp.float32(scale)
    if row > w_q:                                       # a padded row
        q32 = jnp.pad(q32, ((0, 0), (0, 0), (0, row - w_q)))

    def chunk(w, bids, q):
        # the rows stay in the pool's type and both products round
        # their other operand to it, accumulating in float32: what the
        # chip's matrix unit does with float32 operands anyway, without
        # ever writing the chunk widened (PERF.md section 5)
        rows = pool[layer, bids].reshape(w, plan[1] * bs, row)
        return (jnp.einsum("shd,std->sht", q[:w].astype(rows.dtype), rows,
                           preferred_element_type=jnp.float32),
                lambda p: jnp.einsum("sht,std->shd", p.astype(rows.dtype),
                                     rows[..., :value_width],
                                     preferred_element_type=jnp.float32))

    return _blockwise_loop(q32, block_tables, lens, plan, bs, value_width,
                           chunk)


# ---------------------------------------------------------------------------
# Pallas TPU kernel: a slot a grid step, only its held pages copied
# ---------------------------------------------------------------------------

# The kernel's plan. A group is the pages one step copies and multiplies:
# this many tokens (16 pages of 16; PERF.md section 6, PR 33, has the chip
# readings of 4, 8, 12, 16 and 32), fewer where a group of one pool would pass
# `_GROUP_BYTES_MAX`: two groups a side live in VMEM beside the float32
# scores and products, and the v5e compiler took a group of 2 MB for
# every fp dtype and refused one of 4 (tests/test_tpu_compile.py compiles
# both sides of the line). A page is `_SUBLANE_TILE` rows or a multiple:
# the copies land on whole tiles of the buffers.
_GROUP_TOKENS = 256
_GROUP_BYTES_MAX = 2 * 1024 * 1024
_SUBLANE_TILE = 8


def _group_pages(table_entries, block_size, row, dtype,
                 tokens=_GROUP_TOKENS):
    """Pages a group: `tokens` tokens of them, within `_GROUP_BYTES_MAX`
    a pool and the table; 0 where not even one page fits."""
    page = int(block_size) * int(row) * jnp.dtype(dtype).itemsize
    return min(max(1, int(tokens) // int(block_size)),
               _GROUP_BYTES_MAX // page, int(table_entries))


def _slot_pages(lens, block_size, table_entries, xp):
    """The pages the kernel copies for a slot of effective length `lens`:
    up to the one that holds its newest token (an inactive slot, length
    0, reads one), never past the table. In `xp`: jax.numpy on the
    scalars the kernel reads its trip counts from, numpy for the host's
    count, so the two cannot drift."""
    return xp.minimum(lens // block_size + 1, table_entries)


def pallas_copied_pages(lens, active, table_entries, block_size):
    """The host's count of one decode step's attention under the Pallas
    kernel, in table entries summed over the slots: ``(copied, held)``.
    `copied` is the pages the kernel's copies move for these lengths
    (`_slot_pages`, the kernel's own rule), `held` the entries that hold
    a token some slot attends to, as `blockwise_streamed_entries` counts
    them: the two differ by the one page an inactive slot reads.
    lens/active: numpy ``[S]``, as the engine keeps them."""
    active = np.asarray(active, bool)
    eff = np.where(active, np.asarray(lens, np.int64), 0)
    pages = _slot_pages(eff, int(block_size), int(table_entries), np)
    return int(pages.sum()), int(pages[active].sum())


def _exact_dot(a, b, contract):
    """``a . b`` over `contract` with float32 accumulation and nothing of
    either operand's value lost: two bf16 operands are one pass of the
    matrix unit (their products are exact in float32), anything else runs
    as float32 at `Precision.HIGHEST`."""
    dims = (contract, ((), ()))
    if a.dtype == b.dtype == jnp.bfloat16:
        return jax.lax.dot_general(a, b, dims,
                                   preferred_element_type=jnp.float32)
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32), dims,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _weigh(p, v):
    """``p [Hp, T] float32 x v [T, H*D]`` with p's every bit kept. Over
    bf16 values p goes in as its three bf16 parts (high, middle, low: 24
    bits of significand, p exactly) stacked on the rows of ONE product,
    so the values are loaded into the matrix unit once and not once a
    pass of a float32 product; their sum is what `Precision.HIGHEST`
    gives."""
    if v.dtype != jnp.bfloat16:
        return _exact_dot(p, v, ((1,), (0,)))
    parts, rest = [], p
    for _ in range(3):
        part = rest.astype(jnp.bfloat16)
        parts.append(part)
        rest = rest - part.astype(jnp.float32)
    out = _exact_dot(jnp.concatenate(parts, axis=0), v, ((1,), (0,)))
    hp = p.shape[0]
    return out[:hp] + out[hp:2 * hp] + out[2 * hp:]


def _page_copies(tab_ref, lens_ref, layer, sides, sems, block_size, pages):
    """The copy pipeline both kernels share. `sides` are the pools to
    read, each ``(pool in HBM, its VMEM buffers [2, pages * bs, row])``,
    with a DMA semaphore a side and a buffer half (`sems` ``[sides, 2]``).
    Returns ``(held, start, wait)``: `held(slot)` the pages the slot's
    length says it holds (`_slot_pages`), and `start` / `wait`
    ``(slot, group, half)``, which start, or wait for, the copy of every
    page of the slot's `group` that holds a token (never one past the
    slot's length) from ``pool[layer, block]`` into the buffers' `half`."""
    zero = np.int32(0)
    bs, n_pages = np.int32(block_size), np.int32(pages)
    m = np.int32(tab_ref.shape[1])

    def held(slot):
        return _slot_pages(lens_ref[slot], bs, m, jnp)

    def each_copy(slot, group, half, act):
        first = group * n_pages

        def page(i, carry=None):
            if isinstance(i, int):                      # a static page
                block = tab_ref[slot, first + np.int32(i)]
                rows = pl.ds(i * block_size, block_size)
            else:
                block = tab_ref[slot, first + i]
                rows = pl.ds(pl.multiple_of(i * bs, block_size), block_size)
            for side, (pool, vmem) in enumerate(sides):
                act(pltpu.make_async_copy(pool.at[layer, block],
                                          vmem.at[half, rows],
                                          sems.at[np.int32(side), half]))
            return carry

        count = jnp.minimum(held(slot) - first, n_pages)

        # a whole group needs no trip count and every offset is a
        # constant (PERF.md section 6, PR 33: 6-24% of a call's time)
        @pl.when(count == n_pages)
        def _whole():
            for i in range(pages):
                page(i)

        @pl.when(count < n_pages)
        def _part():
            jax.lax.fori_loop(zero, count, page, zero)

    def start(slot, group, half):
        each_copy(slot, group, half, lambda copy: copy.start())

    def wait(slot, group, half):
        each_copy(slot, group, half, lambda copy: copy.wait())

    return held, start, wait


def _ragged_decode_kernel(layer_ref, tab_ref, lens_ref, q_ref, *rest,
                          block_size, pages, heads_padded, head_dim, scale,
                          per_head=1):
    """One slot a grid step, the steps in order. With `per_head` queries a
    key/value head (grouped queries; `_grouped_constants`) three constant
    operands precede the pools: query head i lies in the lanes of
    key/value head ``i // per_head`` and the output is assembled a query
    head. The slot's pages come out
    of the pools in HBM a group of `pages` at a time, K and V each into
    one of two VMEM buffers ``[2, pages * bs, H*D]``; while a group is
    multiplied the next one's copies are in flight, and the next of a
    slot's LAST group is the first group of the slot after it, so the
    copies do not drain where a slot ends (`first_ref` carries which
    buffer that group went to across the grid steps)."""
    grouped, rest = rest[:len(rest) - 7], rest[len(rest) - 7:]
    k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, first_ref = rest
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    # every constant a 32-bit one: under the framework's x64 mode a Python
    # number traces as 64 bits, which Mosaic cannot legalize
    zero, one = np.int32(0), np.int32(1)
    bs, n_pages = np.int32(block_size), np.int32(pages)
    hp, hd = heads_padded, k_buf.shape[-1]
    t_group = n_pages * bs
    nothing = np.float32(0.0)
    layer = layer_ref[0]

    held, start, wait = _page_copies(
        tab_ref, lens_ref, layer, ((k_hbm, k_buf), (v_hbm, v_buf)), sems,
        block_size, pages)

    @pl.when(s == 0)
    def _first_slot():
        # rows a copy never reaches are multiplied by p == 0: they must
        # be numbers, which fresh VMEM need not hold
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        first_ref[0] = zero
        start(zero, zero, zero)

    base = first_ref[0]
    length = lens_ref[s]
    groups = (held(s) + (n_pages - one)) // n_pages

    if per_head == 1:
        # head h's query in its own D lanes of row h, zeros elsewhere: the
        # rows of a page go to the matrix unit as they lie, all heads at
        # once
        row = jax.lax.broadcasted_iota(jnp.int32, (hp, hd), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (hp, hd), 1)
        own = ((lane >= row * np.int32(head_dim))
               & (lane < (row + one) * np.int32(head_dim)))
        # (selected as float32: the mask's layout is a 32-bit one)
        q = jnp.where(own, q_ref[...].astype(jnp.float32),
                      nothing).astype(q_ref.dtype)
    else:
        # query head i ([Hp, D] here) repeated into every head's lanes by
        # a 0/1 product, of which it keeps the lanes of key/value head
        # i // per_head: `per_head` rows a head's lanes
        tile_ref, own_ref, pick_ref = grouped
        own = own_ref[...]                              # [Hp, H*D] 0/1
        q = (_exact_dot(q_ref[...], tile_ref[...], ((1,), (0,)))
             * own).astype(q_ref.dtype)
    offs = jax.lax.broadcasted_iota(jnp.int32, (hp, t_group), 1)

    def group(g, carry):
        mx, l, acc = carry
        cur = (base + g) & one
        more = g + one < groups
        nxt_slot = jnp.where(more, s, s + one)

        @pl.when(nxt_slot < n_slots)
        def _prefetch():
            start(nxt_slot, jnp.where(more, g + one, zero), one - cur)

        wait(s, g, cur)
        k = k_buf[cur]                                  # [T, H*D]
        v = v_buf[cur]
        scores = _exact_dot(q, k, ((1,), (1,))) * np.float32(scale)
        valid = g * t_group + offs <= length            # [Hp, T]
        scores = jnp.where(valid, scores, np.float32(_NEG_INF))
        m_new = jnp.maximum(mx, jnp.max(scores, axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(scores - m_new), nothing)
        alpha = jnp.exp(mx - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + _weigh(p, v)                # [Hp, H*D]
        return m_new, l, acc

    _, l, acc = jax.lax.fori_loop(
        zero, groups, group,
        (jnp.full((hp, 1), np.float32(_NEG_INF), jnp.float32),
         jnp.zeros((hp, 1), jnp.float32),
         jnp.zeros((hp, hd), jnp.float32)))
    first_ref[0] = (base + groups) & one
    if per_head == 1:
        # head h keeps its own D lanes of row h
        out = jnp.where(own, acc / jnp.maximum(l, np.float32(1e-30)),
                        nothing)
        o_ref[...] = jnp.sum(out, axis=0, keepdims=True).astype(o_ref.dtype)
    else:
        # a query head's output is the D lanes of its key/value head in
        # its own row: picked out by a 0/1 product (every bit kept), a
        # row a query head
        out = acc / jnp.maximum(l, np.float32(1e-30)) * own
        o_ref[...] = _weigh(out, pick_ref[...]).astype(o_ref.dtype)


def _grouped_constants(heads_padded, kv_heads, head_dim, group, dtype):
    """The three 0/1 operands of the kernel with `group` queries a
    key/value head: `tile` ``[D, H*D]`` repeats a query's D values into
    every head's lanes, `own` ``[Hp, H*D]`` float32 keeps for query row i
    the lanes of head ``i // group`` (rows past the query heads keep
    none), `pick` ``[H*D, D]`` bfloat16 folds a row's kept lanes back to
    D. Made on the host: the kernel has no integer division to make them
    with."""
    hd = kv_heads * head_dim
    lane = np.arange(hd)
    tile = (lane[None, :] % head_dim == np.arange(head_dim)[:, None])
    rows = np.arange(heads_padded)
    own = (lane[None, :] // head_dim == rows[:, None] // group) \
        & (rows[:, None] < kv_heads * group)
    return (jnp.asarray(tile, dtype), jnp.asarray(own, jnp.float32),
            jnp.asarray(tile.T, jnp.bfloat16))


@functools.partial(jax.jit, static_argnames=("block_size", "interpret",
                                             "group_pages"))
def pallas_paged_attention(q, k_pools, v_pools, layer, block_tables, lens,
                           block_size, interpret=False, group_pages=None):
    """The Pallas kernel: `blockwise_paged_attention`'s contract over fp
    pools (int8 pools resolve to that loop: `resolve_paged_kernel`).
    The pools stay in HBM where they lie; block tables, lengths and the
    layer index ride as scalar prefetch, and the kernel copies only
    `_slot_pages` pages of a slot, `group_pages` a step (default: the
    plan's, `_group_pages`). `interpret=True` runs it through the Pallas
    interpreter on any backend (the CPU parity path).

    The layer is an OPERAND and the function is jitted, so a program
    traces and lowers ONE kernel for all its layers' calls: traced anew
    for each of twelve layers the body cost the backlog cell 21 s of
    set-up (PERF.md section 6, PR 33).

    q may hold a multiple of the heads a pool's row holds (grouped
    queries: ``[S, Hq, D]`` over rows of ``H*D``, query head i reading
    key/value head ``i // (Hq // H)``): the row, the pages and the copies
    are the pool's, only the query's placement in the lanes and the
    output's assembly differ (`_grouped_constants`)."""
    s, h, d = q.shape
    hd = k_pools.shape[-1]
    group = h * d // hd
    bs = int(block_size)
    m = block_tables.shape[1]
    pages = int(group_pages or _group_pages(m, bs, hd, k_pools.dtype))
    hp = -(-h // 16) * 16                   # whole tiles of rows, bf16's
    zero = _ZERO

    slot_spec = pl.BlockSpec((None, 1, hd),
                             lambda si, *_: (si, zero, zero))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    buf = pltpu.VMEM((2, pages * bs, hd), k_pools.dtype)
    scratch = [buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
               pltpu.SMEM((1,), jnp.int32)]
    kernel = functools.partial(
        _ragged_decode_kernel, block_size=bs, pages=pages, heads_padded=hp,
        head_dim=d, scale=1.0 / math.sqrt(d))
    scalars = (jnp.asarray(layer, jnp.int32).reshape(1),
               block_tables.astype(jnp.int32), lens.astype(jnp.int32))
    params = pltpu.CompilerParams(dimension_semantics=("arbitrary",))
    if group == 1:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s,),
            in_specs=[slot_spec, pool_spec, pool_spec],
            out_specs=slot_spec,
            scratch_shapes=scratch)
        out = pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((s, 1, hd), q.dtype),
            compiler_params=params,
            interpret=interpret,
            name="paged_decode_attention")(
                *scalars, q.reshape(s, 1, hd), k_pools, v_pools)
        return out.reshape(s, h, d)
    # a row a query head, `group` of them over each head's lanes
    heads_spec = pl.BlockSpec((None, hp, d), lambda si, *_: (si, zero, zero))
    consts = _grouped_constants(hp, hd // d, d, group, q.dtype)
    whole = [pl.BlockSpec(c.shape, lambda si, *_: (zero, zero))
             for c in consts]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s,),
        in_specs=[heads_spec] + whole + [pool_spec, pool_spec],
        out_specs=heads_spec,
        scratch_shapes=scratch)
    out = pl.pallas_call(
        functools.partial(kernel, per_head=group), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, hp, d), q.dtype),
        compiler_params=params,
        interpret=interpret,
        name="paged_decode_attention")(
            *scalars, jnp.pad(q, ((0, 0), (0, hp - h), (0, 0))), *consts,
            k_pools, v_pools)
    return out[:, :h]


# ---------------------------------------------------------------------------
# Pallas TPU kernel over a LATENT pool: the same plan, one pool, a shared row
# ---------------------------------------------------------------------------

# A latent group: one pool's pages leave VMEM room for more tokens a step
# than two pools' do (PERF.md section 5 has the chip readings of 8, 16, 32,
# 48 and 64 pages of 16 tokens: 32 is the cell's contexts' best by 3% and a
# full table's by 20%).
_LATENT_GROUP_TOKENS = 512


def _latent_decode_kernel(layer_ref, tab_ref, lens_ref, q_ref, pool_hbm,
                          o_ref, buf, sems, first_ref, *, block_size, pages):
    """`_ragged_decode_kernel`'s plan over ONE pool whose row every head
    shares: a slot a grid step, its held pages copied a group of `pages`
    at a time into one of two VMEM buffers ``[2, pages * bs, row]``, the
    next group (or the next slot's first) in flight while this one is
    multiplied. The multiply step is latent attention's: scores
    ``[H, tokens]`` are ONE product of the queries ``[H, row]`` (already
    scaled, in the row's space and the pool's dtype) with the rows as
    they lie, and the output ``[H, value]`` is p x the first `value`
    lanes of the same rows, `value` being the output block's width."""
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    # every constant a 32-bit one (Mosaic cannot legalize x64's)
    zero, one = np.int32(0), np.int32(1)
    bs, n_pages = np.int32(block_size), np.int32(pages)
    h, value = o_ref.shape
    t_group = n_pages * bs
    nothing = np.float32(0.0)
    layer = layer_ref[0]

    held, start, wait = _page_copies(
        tab_ref, lens_ref, layer, ((pool_hbm, buf),), sems, block_size,
        pages)

    @pl.when(s == 0)
    def _first_slot():
        # rows a copy never reaches are multiplied by p == 0: they must
        # be numbers, which fresh VMEM need not hold
        buf[...] = jnp.zeros_like(buf)
        first_ref[0] = zero
        start(zero, zero, zero)

    base = first_ref[0]
    length = lens_ref[s]
    groups = (held(s) + (n_pages - one)) // n_pages
    q = q_ref[...]                                      # [H, row]
    offs = jax.lax.broadcasted_iota(jnp.int32, (h, t_group), 1)

    def group(g, carry):
        mx, l, acc = carry
        cur = (base + g) & one
        more = g + one < groups
        nxt_slot = jnp.where(more, s, s + one)

        @pl.when(nxt_slot < n_slots)
        def _prefetch():
            start(nxt_slot, jnp.where(more, g + one, zero), one - cur)

        wait(s, g, cur)
        rows = buf[cur]                                 # [T, row]
        scores = _exact_dot(q, rows, ((1,), (1,)))      # [H, T]
        valid = g * t_group + offs <= length
        scores = jnp.where(valid, scores, np.float32(_NEG_INF))
        m_new = jnp.maximum(mx, jnp.max(scores, axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(scores - m_new), nothing)
        alpha = jnp.exp(mx - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        # p in the rows' type, as the loop weighs it: its one caller
        # rounds the output to that type at once, and p's other 16 bits
        # cost a fifth of the call (PERF.md section 5)
        acc = acc * alpha + _exact_dot(p.astype(rows.dtype),
                                       rows[:, :value], ((1,), (0,)))
        return m_new, l, acc

    _, l, acc = jax.lax.fori_loop(
        zero, groups, group,
        (jnp.full((h, 1), np.float32(_NEG_INF), jnp.float32),
         jnp.zeros((h, 1), jnp.float32),
         jnp.zeros((h, value), jnp.float32)))
    first_ref[0] = (base + groups) & one
    o_ref[...] = (acc / jnp.maximum(l, np.float32(1e-30))).astype(
        o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "block_size", "value_width", "scale", "interpret", "group_pages"))
def pallas_latent_attention(q, pool, layer, block_tables, lens, block_size,
                            value_width, scale, interpret=False,
                            group_pages=None):
    """The Pallas kernel over a latent pool: `blockwise_latent_attention`'s
    contract (q ``[S, H, W]`` in the row's space, pool
    ``[L, num_blocks, bs, >= W]`` fp, the value the first `value_width`
    values of a row; returns ``[S, H, value_width]`` float32), by
    `pallas_paged_attention`'s plan: the pool stays in HBM, the kernel
    copies only `_slot_pages` pages of a slot, `group_pages` a step
    (default: `_LATENT_GROUP_TOKENS` of them), and the layer is an
    OPERAND of this jitted function, so a program lowers ONE kernel for
    all its sublayers' calls.

    Numerics, the loop's: the queries are scaled in float32 and rounded
    ONCE to the pool's dtype, p is rounded to it for the second product,
    both products accumulate in float32 (bf16 operands: one exact pass
    each), and the scores, the recurrence, the accumulator and the
    result are float32; masked positions weigh exactly zero."""
    s, h, w_q = q.shape
    bs = int(block_size)
    m = block_tables.shape[1]
    row = pool.shape[-1]
    pages = int(group_pages or _group_pages(m, bs, row, pool.dtype,
                                            _LATENT_GROUP_TOKENS))
    # whole tiles: of rows for the queries (bf16's 16), of lanes for the
    # value; zero queries and the lanes past the value are cut off below
    hp = -(-h // 16) * 16
    value = min(-(-int(value_width) // 128) * 128, row)
    q = q.astype(jnp.float32) * np.float32(scale)
    q = jnp.pad(q, ((0, 0), (0, hp - h), (0, row - w_q))).astype(pool.dtype)
    zero = _ZERO

    def slot_spec(width):
        return pl.BlockSpec((None, hp, width),
                            lambda si, *_: (si, zero, zero))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s,),
        in_specs=[slot_spec(row), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=slot_spec(value),
        scratch_shapes=[pltpu.VMEM((2, pages * bs, row), pool.dtype),
                        pltpu.SemaphoreType.DMA((1, 2)),
                        pltpu.SMEM((1,), jnp.int32)])
    out = pl.pallas_call(
        functools.partial(_latent_decode_kernel, block_size=bs, pages=pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, hp, value), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_decode_attention")(
            jnp.asarray(layer, jnp.int32).reshape(1),
            block_tables.astype(jnp.int32), lens.astype(jnp.int32),
            q, pool)
    return out[:, :h, :value_width]


# ---------------------------------------------------------------------------
# Pallas TPU kernel over rows whose key is wider than their value, with a
# window's oldest position a slot and a learned sink a head
# ---------------------------------------------------------------------------

def _banded_decode_kernel(layer_ref, tab_ref, lens_ref, starts_ref, q_ref,
                          *rest, block_size, pages, scale, has_sink):
    """`_ragged_decode_kernel`'s plan (a slot a grid step, its held pages
    copied a group at a time into one of two VMEM buffers a pool, the next
    group in flight while this one is multiplied) with `per_head` queries a
    key/value head, over K rows ``H*Dk`` and V rows ``H*Dv`` of their own
    widths (two sets of the 0/1 constants, `_grouped_constants` at each
    width). A position attends iff ``starts[slot] <= position <=
    lens[slot]``; with `has_sink` a head's learned score joins the
    denominator and adds no value: the recurrence starts from max = sink,
    sum = 1."""
    if has_sink:
        sink_ref, rest = rest[0], rest[1:]
    (tile_ref, own_k_ref, own_v_ref, pick_ref, k_hbm, v_hbm, o_ref, k_buf,
     v_buf, sems, first_ref) = rest
    s = pl.program_id(0)
    n_slots = pl.num_programs(0)
    # every constant a 32-bit one (Mosaic cannot legalize x64's)
    zero, one = np.int32(0), np.int32(1)
    bs, n_pages = np.int32(block_size), np.int32(pages)
    hp = q_ref.shape[0]
    t_group = n_pages * bs
    nothing = np.float32(0.0)
    layer = layer_ref[0]

    held, start, wait = _page_copies(
        tab_ref, lens_ref, layer, ((k_hbm, k_buf), (v_hbm, v_buf)), sems,
        block_size, pages)

    @pl.when(s == 0)
    def _first_slot():
        # rows a copy never reaches are multiplied by p == 0: they must
        # be numbers, which fresh VMEM need not hold
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)
        first_ref[0] = zero
        start(zero, zero, zero)

    base = first_ref[0]
    length, oldest = lens_ref[s], starts_ref[s]
    groups = (held(s) + (n_pages - one)) // n_pages
    # query head i repeated into every head's key lanes by a 0/1 product,
    # of which it keeps the lanes of key/value head i // per_head
    q = (_exact_dot(q_ref[...], tile_ref[...], ((1,), (0,)))
         * own_k_ref[...]).astype(q_ref.dtype)
    offs = jax.lax.broadcasted_iota(jnp.int32, (hp, t_group), 1)

    def group(g, carry):
        mx, l, acc = carry
        cur = (base + g) & one
        more = g + one < groups
        nxt_slot = jnp.where(more, s, s + one)

        @pl.when(nxt_slot < n_slots)
        def _prefetch():
            start(nxt_slot, jnp.where(more, g + one, zero), one - cur)

        wait(s, g, cur)
        k = k_buf[cur]                                  # [T, H*Dk]
        v = v_buf[cur]                                  # [T, H*Dv]
        scores = _exact_dot(q, k, ((1,), (1,))) * np.float32(scale)
        pos = g * t_group + offs
        valid = (pos <= length) & (pos >= oldest)       # [Hp, T]
        scores = jnp.where(valid, scores, np.float32(_NEG_INF))
        m_new = jnp.maximum(mx, jnp.max(scores, axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(scores - m_new), nothing)
        alpha = jnp.exp(mx - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + _weigh(p, v)                # [Hp, H*Dv]
        return m_new, l, acc

    if has_sink:
        first = (sink_ref[...], jnp.ones((hp, 1), jnp.float32))
    else:
        first = (jnp.full((hp, 1), np.float32(_NEG_INF), jnp.float32),
                 jnp.zeros((hp, 1), jnp.float32))
    _, l, acc = jax.lax.fori_loop(
        zero, groups, group,
        first + (jnp.zeros((hp, v_buf.shape[-1]), jnp.float32),))
    first_ref[0] = (base + groups) & one
    # a query head's output is the Dv lanes of its key/value head in its
    # own row: picked out by a 0/1 product (every bit kept)
    out = acc / jnp.maximum(l, np.float32(1e-30)) * own_v_ref[...]
    o_ref[...] = _weigh(out, pick_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_size", "interpret",
                                             "group_pages", "name"))
def pallas_banded_attention(q, k_pools, v_pools, layer, block_tables, lens,
                            block_size, starts=None, sink=None,
                            interpret=False, group_pages=None,
                            name="banded_decode_attention"):
    """`blockwise_paged_attention`'s contract with `starts` and `sink`, by
    `pallas_paged_attention`'s plan, over fp pools whose K rows
    (``H*Dk``) and V rows (``H*Dv``) have their own widths: q
    ``[S, Hq, Dk]``, Hq a multiple of H; a position attends iff
    ``starts[s] <= position <= lens[s]`` (`starts` None: from 0); `sink`
    ``[Hq]`` (or None) joins each head's denominator. Returns
    ``[S, Hq, Dv]`` in q's dtype. The kernel copies only `_slot_pages`
    pages of a slot: over a window layer's ring table (nn/functional/
    attention.py `paged_window_decode_attention`), whose first entry is
    the block of the window's oldest position, that is the window's pages
    and no more. The layer is an OPERAND of this jitted function: a
    program lowers one kernel for all its layers of a kind, under `name`
    (the device trace tells a model's kinds of layer apart by it)."""
    s, h, d = q.shape
    hd_k, hd_v = k_pools.shape[-1], v_pools.shape[-1]
    kv_heads = hd_k // d
    dv = hd_v // kv_heads
    group = h // kv_heads
    bs = int(block_size)
    m = block_tables.shape[1]
    pages = int(group_pages or _group_pages(m, bs, hd_k, k_pools.dtype))
    hp = -(-h // 16) * 16                   # whole tiles of rows, bf16's
    zero = _ZERO
    if starts is None:
        starts = jnp.zeros((s,), jnp.int32)

    def heads_spec(width):
        return pl.BlockSpec((None, hp, width),
                            lambda si, *_: (si, zero, zero))

    tile, own_k, _ = _grouped_constants(hp, kv_heads, d, group, q.dtype)
    _, own_v, pick = _grouped_constants(hp, kv_heads, dv, group, q.dtype)
    consts = (tile, own_k, own_v, pick)
    if sink is not None:
        consts = (jnp.pad(sink.astype(jnp.float32), (0, hp - h),
                          constant_values=_NEG_INF)[:, None],) + consts
    whole = [pl.BlockSpec(c.shape, lambda si, *_: (zero, zero))
             for c in consts]
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s,),
        in_specs=[heads_spec(d)] + whole + [pool_spec, pool_spec],
        out_specs=heads_spec(dv),
        scratch_shapes=[pltpu.VMEM((2, pages * bs, hd_k), k_pools.dtype),
                        pltpu.VMEM((2, pages * bs, hd_v), v_pools.dtype),
                        pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((1,), jnp.int32)])
    out = pl.pallas_call(
        functools.partial(_banded_decode_kernel, block_size=bs, pages=pages,
                          scale=1.0 / math.sqrt(d),
                          has_sink=sink is not None),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, hp, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name)(
            jnp.asarray(layer, jnp.int32).reshape(1),
            block_tables.astype(jnp.int32), lens.astype(jnp.int32),
            starts.astype(jnp.int32),
            jnp.pad(q, ((0, 0), (0, hp - h), (0, 0))), *consts,
            k_pools, v_pools)
    return out[:, :h]
