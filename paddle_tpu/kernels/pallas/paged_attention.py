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
    their scores are masked, never branched on: this kernel visits all
    ``S x M`` entries whatever the lengths. Runs under
    ``interpret=True`` on CPU for the fused-vs-reference parity tests.
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
    once cleared) and are masked. This is the
    default variant on every platform, the CPU/parity fallback AND a
    standalone win: it replaces the dense gather's ``[S, T, H, D]``
    materialization with cache-resident chunks
    (tests/test_kernel_tier.py reads the traced program for it; its
    speed is the `serve_124m_backlog` cell's).
    `blockwise_streamed_entries` is the host's count
    of what that loop reads, from the same plan and step widths.

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

__all__ = ["blockwise_paged_attention", "blockwise_latent_attention",
           "blockwise_streamed_entries",
           "pallas_paged_attention", "is_eligible"]

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
                    chunk):
    """The loop both blockwise attentions share: online softmax over the
    chunks that hold tokens, at the widths `_step_widths` picks.

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
                              chunk_blocks=None):
    """Online-softmax paged attention, one KV chunk at a time, over the
    chunks and the slots that hold tokens.

    q: ``[S, H, D]`` this step's queries; k_pools/v_pools:
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
    """
    s, h, d = q.shape
    bs = int(block_size)
    quant = k_scales is not None
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
        vc = vc.reshape(w, t_chunk, h, d)
        return (jnp.einsum("shd,sthd->sht", q[:w], kc),
                lambda p: jnp.einsum("sht,sthd->shd", p, vc))

    return _blockwise_loop(q32, block_tables, lens, plan, bs, d,
                           chunk).astype(q.dtype)


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
