"""Scaled-dot-product attention functional.

Reference analog: the fused attention path (fluid/operators/fused/
fused_attention_op.cu, fmha_ref.h). TPU-first: defaults to the Pallas
flash-attention kernel on TPU (paddle_tpu/kernels/flash_attention.py) and a
plain XLA softmax(QK^T)V fallback elsewhere / for odd shapes.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...framework.core import Tensor
from ...ops._helpers import ensure_tensor, call_op, const_input
from ...ops.registry import register_op

__all__ = ["scaled_dot_product_attention"]


class _ShapeMeta:
    """Shape/ndim view for kernel eligibility checks that must not force a
    deferred fusion placeholder's buffer."""

    __slots__ = ("ndim", "shape")

    def __init__(self, ndim, shape):
        self.ndim = ndim
        self.shape = shape


_FLASH_SPEC = jax.sharding.PartitionSpec(("data", "sharding"), None, "model",
                                         None)


def _flash_partition(batch, heads):
    """How a [B, N, H, D] Mosaic kernel has to be called here. The TPU
    lowering refuses to partition a Mosaic kernel automatically, so under
    a global multi-device mesh the kernel runs per shard through
    `shard_map`: batch over the data axes, heads over "model" (where
    `gpt._qkv_by_heads` leaves q, k and v). Returns None to call it
    directly (no mesh, or already inside a manual region that binds every
    sharded axis), the mesh's key to wrap it, or False when it cannot run
    (shapes that do not divide, or a partly manual region)."""
    from ...distributed.fleet.meta_parallel.mp_ops import in_spmd_axis
    from ...distributed.mesh import current_mesh, mesh_key
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return None
    sharded = [a for a in mesh.axis_names if mesh.shape[a] > 1]
    bound = [a for a in sharded if in_spmd_axis(a)]
    if len(bound) == len(sharded):
        return None
    if bound or batch % (mesh.shape["data"] * mesh.shape["sharding"]) \
            or heads % mesh.shape["model"]:
        return False
    return mesh_key(mesh)


def _run_flash(q, k, v, causal, scale, mkey):
    """The flash kernel, per shard of the mesh keyed `mkey` (None: as is).
    The key, not the mesh, rides in the op's closure so the op stays
    keyable; the mesh is read back here, at trace time."""
    from ...kernels import flash_attention as fa

    def kernel(qq, kk, vv):
        return fa.flash_attention_bnhd(qq, kk, vv, causal=causal,
                                       scale=scale)
    if mkey is None:
        return kernel(q, k, v)
    from ...distributed.mesh import current_mesh, mesh_key
    mesh = current_mesh()
    if mesh_key(mesh) != mkey:
        raise RuntimeError("the global mesh changed between the dispatch "
                           "of flash attention and its trace")
    return jax.shard_map(kernel, mesh=mesh, in_specs=(_FLASH_SPEC,) * 3,
                         out_specs=_FLASH_SPEC, check_vma=False)(q, k, v)


def _plain_attention(q, k, v, mask, is_causal, scale, dropout_p=0.0,
                     dropout_key=None):
    # q,k,v: [B, N, H, D] (paddle layout: batch, seq, heads, head_dim)
    qt = jnp.swapaxes(q, 1, 2)  # [B, H, N, D]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    scores = jnp.einsum("bhnd,bhmd->bhnm", qt, kt) * scale
    if is_causal:
        n, m = scores.shape[-2], scores.shape[-1]
        # bottom-right alignment: with cached keys (m > n), query i sits at
        # absolute position i + (m - n) and may attend to keys <= that
        q_pos = jnp.arange(n)[:, None] + (m - n)
        k_pos = jnp.arange(m)[None, :]
        scores = jnp.where(q_pos >= k_pos, scores,
                           jnp.asarray(-1e30, scores.dtype))
    if mask is not None:
        if mask.dtype == jnp.bool_.dtype:
            scores = jnp.where(mask, scores, jnp.asarray(-1e30, scores.dtype))
        else:
            scores = scores + mask.astype(scores.dtype)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1) \
        .astype(scores.dtype)
    if dropout_p and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = probs * keep.astype(probs.dtype) / \
            jnp.asarray(1.0 - dropout_p, probs.dtype)
    out = jnp.einsum("bhnm,bhmd->bhnd", probs, vt)
    return jnp.swapaxes(out, 1, 2)


@register_op("scaled_dot_product_attention", "fused",
             ref="fluid/operators/fused/fused_attention_op.cu")
def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None,
                                 use_flash_attention=None):
    """query/key/value: [batch, seq, num_heads, head_dim] (paddle convention).

    On TPU with flash-eligible shapes this runs the Pallas flash-attention
    kernel; otherwise the XLA fallback (still one fused HLO cluster).
    """
    q = ensure_tensor(query)
    k = ensure_tensor(key)
    v = ensure_tensor(value)
    scale = 1.0 / math.sqrt(q.shape[-1])
    # the mask stays a Tensor: it becomes a dispatch INPUT below (not a
    # closure capture), and eligibility checks only need its presence —
    # never force a deferred fusion placeholder's buffer here
    mask_t = ensure_tensor(attn_mask) if attn_mask is not None else None

    # sequence/context parallelism: inside an SPMD trace binding the "sep"
    # axis, q/k/v are sequence shards — use ring attention so no chip ever
    # materializes the full sequence (paddle_tpu sep_parallel; the reference
    # has no sequence parallelism, SURVEY.md §5)
    from ...distributed.fleet.meta_parallel.mp_ops import in_spmd_axis
    if in_spmd_axis("sep"):
        eff_dropout = dropout_p if training else 0.0
        if mask_t is not None or eff_dropout:
            # a shard-local dense fallback would attend only to this chip's
            # keys — globally wrong. Fail loudly instead.
            raise NotImplementedError(
                "sequence-parallel attention (sep axis) supports causal/full "
                "attention without attn_mask or attention dropout; got "
                f"attn_mask={attn_mask is not None}, dropout_p={dropout_p}")

        def fn(qq, kk, vv):
            from ...distributed.fleet.meta_parallel.sep_parallel import (
                ring_attention)
            return ring_attention(qq, kk, vv, "sep", causal=is_causal,
                                  scale=scale)
        return call_op("ring_attention", fn, (q, k, v))

    eff_dropout = dropout_p if training else 0.0
    from ...kernels import flash_attention as fa
    # eligibility only needs shapes: answer from tensor meta (aval-safe on
    # deferred fusion placeholders) instead of forcing q/k/v buffers
    _shape_of = lambda t: _ShapeMeta(t.ndim, tuple(t.shape))
    if use_flash_attention is not False and \
            fa.is_eligible(_shape_of(q), _shape_of(k), _shape_of(v), mask_t,
                           eff_dropout, is_causal=is_causal):
        mkey = _flash_partition(q.shape[0], q.shape[2])
        if mkey is not False:
            def fn(qq, kk, vv):
                return _run_flash(qq, kk, vv, is_causal, scale, mkey)
            return call_op("flash_attention", fn, (q, k, v))

    # the mask AND the dropout key are dispatch INPUTS (not closure
    # captures): closing over a per-batch array — or a per-call PRNG key —
    # would make every masked/regularized attention un-keyable, bypassing
    # the per-op cache and poisoning chain/step fusion cycles. The key is a
    # hoisted stream position (framework/random.rng_key_input), so dropout
    # attention promotes to the fused whole-step executable.
    eff_p = dropout_p if training else 0.0
    kd = None
    if eff_p:
        from ...framework.random import rng_key_input
        kd = rng_key_input()

    if mask_t is not None:
        if kd is not None:
            def fn(qq, kk, vv, mm, key_data):
                return _plain_attention(
                    qq, kk, vv, mm, is_causal, scale, eff_p,
                    jax.random.wrap_key_data(key_data))
            return call_op("scaled_dot_product_attention", fn,
                           (q, k, v, mask_t, kd))
        def fn(qq, kk, vv, mm):
            return _plain_attention(qq, kk, vv, mm, is_causal, scale)
        return call_op("scaled_dot_product_attention", fn, (q, k, v, mask_t))

    if kd is not None:
        def fn(qq, kk, vv, key_data):
            return _plain_attention(qq, kk, vv, None, is_causal, scale,
                                    eff_p, jax.random.wrap_key_data(key_data))
        return call_op("scaled_dot_product_attention", fn, (q, k, v, kd))

    def fn(qq, kk, vv):
        return _plain_attention(qq, kk, vv, None, is_causal, scale)
    return call_op("scaled_dot_product_attention", fn, (q, k, v))


PAGED_KERNELS = ("pallas", "blockwise", "reference")


def resolve_paged_kernel(kernel=None, num_heads=None, head_dim=None,
                         block_size=None, interpret=False, kv_dtype=None):
    """Resolve the serving attention variant -> the one that will run.

    With NO request (no `kernel`, FLAGS_serve_attention_kernel unset) the
    choice follows what can be observed here: `pallas` on a TPU over an fp
    pool of `kv_dtype` whose shape the kernels take
    (`paged_attention.is_eligible`; a latent pool is asked as the ONE head
    its row is, `CacheSpec`'s `num_heads` 1), `blockwise` for anything
    else: an int8 pool, another platform, a row or a block off the tiles.
    An explicit request that cannot run falls back to
    `blockwise` (same math, no Mosaic constraints) and is VISIBLE: a
    `kernel.fallback` flight-recorder event attributes the demotion,
    never silent. `interpret` (the CPU parity path) lifts the platform's
    and the shape's conditions from an explicit `pallas`, not the pool's:
    the kernel reads fp rows."""
    from ...framework.flags import _FLAGS
    from ...profiler.events import EVENTS as _EVENTS
    from ...kernels.pallas import paged_attention as _pk
    kv_dtype = jnp.dtype(jnp.bfloat16 if kv_dtype is None else kv_dtype)
    req = kernel or str(_FLAGS.get("FLAGS_serve_attention_kernel") or "")
    if not req:
        ok, _ = _pk.is_eligible(num_heads, head_dim, block_size, kv_dtype)
        return "pallas" if ok else "blockwise"
    if req not in PAGED_KERNELS:
        raise ValueError(
            f"unknown paged attention kernel {req!r}; expected one of "
            f"{PAGED_KERNELS}")
    actual, why = req, None
    if req == "pallas":
        if not _pk._HAS_PALLAS:
            # interpret mode still needs the pallas import itself
            actual, why = "blockwise", "no_pallas"
        elif not jnp.issubdtype(kv_dtype, jnp.floating):
            actual, why = "blockwise", "quantized_pool"
        elif not interpret:
            ok, why = _pk.is_eligible(num_heads, head_dim, block_size,
                                      kv_dtype)
            if not ok:
                actual = "blockwise"
    if actual != req:
        _EVENTS.emit("kernel.fallback", "paged_decode_attention",
                     reason="kernel_fallback",
                     detail={"requested": req, "actual": actual,
                             "why": why, "num_heads": num_heads,
                             "head_dim": head_dim,
                             "block_size": block_size,
                             "kv_dtype": kv_dtype.name})
    return actual


def _dense_gather_attention(qh, k_pools, v_pools, layer, block_tables, lens,
                            block_size, k_scales=None, v_scales=None):
    """The reference oracle: gather `layer`'s blocks by block table into a
    dense ``[S, T, H, D]`` context, full softmax. Scores and the
    softmax/PV accumulation run in fp32 (matching `_plain_attention`) so
    bf16 serving keeps its tail tokens; only the output casts back. With
    more query heads than a row holds, query head i reads key/value head
    ``i // group``."""
    s, hq, d = qh.shape
    h = k_pools.shape[-1] // d
    group = hq // h
    m = block_tables.shape[1]
    t_max = m * block_size
    # the GATHERED rows are split into heads, never the pool
    kg = k_pools[layer, block_tables].reshape(s, m, block_size, h, d)
    vg = v_pools[layer, block_tables].reshape(s, m, block_size, h, d)
    if k_scales is not None:
        from ...quantization.kv_cache import dequantize
        kg = dequantize(kg, k_scales[layer, block_tables])
        vg = dequantize(vg, v_scales[layer, block_tables])
    else:
        kg = kg.astype(jnp.float32)
        vg = vg.astype(jnp.float32)
    keys = kg.reshape(s, t_max, h, d)
    vals = vg.reshape(s, t_max, h, d)
    if group > 1:
        keys = jnp.repeat(keys, group, axis=2)
        vals = jnp.repeat(vals, group, axis=2)
    scores = jnp.einsum("shd,sthd->sht", qh.astype(jnp.float32), keys) \
        / jnp.sqrt(jnp.asarray(d, jnp.float32))
    valid = jnp.arange(t_max, dtype=jnp.int32)[None, :] <= lens[:, None]
    scores = jnp.where(valid[:, None, :], scores,
                       jnp.asarray(-1e30, jnp.float32))
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("sht,sthd->shd", probs, vals).astype(qh.dtype)


def paged_decode_attention(q, k_new, v_new, k_pools, v_pools, layer,
                           block_tables, seq_lens, active, block_size,
                           k_scales=None, v_scales=None, kernel=None,
                           interpret=False):
    """One layer's decode step of attention against a paged block-pool KV
    cache (the PagedAttention memory model; serving/cache.py).

    q/k_new/v_new: ``[S, 1, H, D]`` — this step's projections for every
    batch slot (S is the engine's fixed max-batch slot count). q may hold
    a multiple of k_new's heads (grouped queries: query head i reads
    key/value head ``i // group``); the pools' row is k_new's.
    k_pools/v_pools: ``[L, num_blocks, block_size, H*D]`` — the pools of
    ALL layers (fp, or int8 with per-block-per-head `k_scales`/`v_scales`
    ``[L, num_blocks, H]``; quantization/kv_cache.py); `layer` (a Python
    int) is the one this call writes and reads. The token is written at
    ``(layer, block, offset)`` of the stacked pool and attention gathers
    ``pool[layer, block ids]``, so the layer's pool is never a value of
    its own: a program that threads donated pools through its layers
    updates them where they lie.
    block_tables: ``[S, max_blocks]`` int32 — per-slot ordered block ids;
    gathered position ``t`` of slot ``s`` is token position ``t`` of that
    sequence (tables are dense prefixes, padded with the null block).
    seq_lens: ``[S]`` int32 — cached tokens per slot; the new token is
    written at position ``seq_lens[s]`` and attended to (self-attention).
    active: ``[S]`` bool — inactive slots write to the reserved null
    block and their outputs are garbage by design (the engine never reads
    them).

    `kernel` selects the attention implementation (`pallas` |
    `blockwise` | `reference`; default FLAGS_serve_attention_kernel,
    else what `resolve_paged_kernel` chooses from platform, pool and
    shape); every variant shares the SAME write path, masking, and fp32
    softmax numerics — only the schedule differs. Shape-static: ONE
    compiled program serves every token of every tenant mix —
    join/leave/evict is a table edit, never a retrace.

    Returns ``(out [S, 1, H, D], new_k_pools, new_v_pools)`` — plus
    ``(new_k_scales, new_v_scales)`` in int8 mode. Every other layer of
    the returned pools is what came in.
    """
    s, _, _, head_dim = q.shape
    num_heads = k_new.shape[2]              # the heads a pool's row holds
    quantized = k_scales is not None
    lens = jnp.where(active, seq_lens, 0).astype(jnp.int32)
    rows = jnp.arange(s, dtype=jnp.int32)
    # write the new token's K/V at (table[len // bs], len % bs); inactive
    # slots all target the null block (duplicate writes there are fine —
    # its content is never unmasked)
    write_block = jnp.where(
        active, block_tables[rows, lens // block_size], 0).astype(jnp.int32)
    write_off = lens % block_size
    # the scopes name the two parts in the compiled program's op names, so
    # a device trace can tell them from the rest of the decode step
    with jax.named_scope("paged_kv_write"):
        if quantized:
            from ...quantization.kv_cache import quantize_block_write
            k_pools, k_scales = quantize_block_write(
                k_pools, k_scales, layer, k_new[:, 0], write_block,
                write_off)
            v_pools, v_scales = quantize_block_write(
                v_pools, v_scales, layer, v_new[:, 0], write_block,
                write_off)
        else:
            k_pools = k_pools.at[layer, write_block, write_off].set(
                k_new[:, 0].reshape(s, -1).astype(k_pools.dtype))
            v_pools = v_pools.at[layer, write_block, write_off].set(
                v_new[:, 0].reshape(s, -1).astype(v_pools.dtype))

    variant = resolve_paged_kernel(kernel, num_heads, head_dim, block_size,
                                   interpret=interpret,
                                   kv_dtype=k_pools.dtype)
    qh = q[:, 0]                                       # [S, H, D]
    with jax.named_scope("paged_attention"):
        if variant == "reference":
            out = _dense_gather_attention(
                qh, k_pools, v_pools, layer, block_tables, lens, block_size,
                k_scales, v_scales)
        elif variant == "blockwise":
            from ...kernels.pallas.paged_attention import (
                blockwise_paged_attention)
            out = blockwise_paged_attention(
                qh, k_pools, v_pools, layer, block_tables, lens, block_size,
                k_scales, v_scales)
        else:
            from ...kernels.pallas.paged_attention import (
                pallas_paged_attention)
            out = pallas_paged_attention(
                qh, k_pools, v_pools, layer, block_tables, lens, block_size,
                interpret=interpret)
    if quantized:
        return out[:, None], k_pools, v_pools, k_scales, v_scales
    return out[:, None], k_pools, v_pools


def paged_latent_decode_attention(q, parts_new, pool, layer, block_tables,
                                  seq_lens, active, block_size, value_width,
                                  scale, kernel=None, chunk_blocks=None,
                                  min_width=None, interpret=False):
    """`paged_decode_attention` for a LATENT cache (serving/cache.py
    `CacheSpec`, kind ``"latent"``): a token holds ONE row that all heads
    share, and attention reads it absorbed.

    q: ``[S, H, W]`` this step's queries in the row's space; parts_new:
    the token's row as its parts ``([S, w0], [S, w1])``, written side by
    side (zeros up to the pool's width) into `pool`
    ``[L, num_blocks, block_size, >= W]``. A token's value is the first
    `value_width` values of its row; scores are ``scale * q . row``.
    `kernel`: ``"pallas"`` (the TPU kernel that copies only the pages
    that hold tokens; `interpret` runs it on any backend), ``"blockwise"``
    (the loop over the chunks that hold tokens, which alone reads
    `chunk_blocks` and `min_width`) or ``"reference"`` (a dense gather of
    the whole table). Returns ``(out [S, H, value_width] float32, the
    written pool)``."""
    s = q.shape[0]
    lens = jnp.where(active, seq_lens, 0).astype(jnp.int32)
    rows = jnp.arange(s, dtype=jnp.int32)
    write_block = jnp.where(
        active, block_tables[rows, lens // block_size], 0).astype(jnp.int32)
    write_off = lens % block_size
    with jax.named_scope("paged_kv_write"):
        new = jnp.concatenate(parts_new, axis=-1)
        new = jnp.pad(new, ((0, 0), (0, pool.shape[-1] - new.shape[-1])))
        pool = pool.at[layer, write_block, write_off].set(
            new.astype(pool.dtype))
    with jax.named_scope("paged_attention"):
        if kernel == "reference":
            m = block_tables.shape[1]
            ctx = pool[layer, block_tables].astype(jnp.float32).reshape(
                s, m * block_size, -1)[..., :q.shape[-1]]
            scores = jnp.einsum("shd,std->sht", q.astype(jnp.float32),
                                ctx) * jnp.float32(scale)
            valid = jnp.arange(m * block_size,
                               dtype=jnp.int32)[None, :] <= lens[:, None]
            scores = jnp.where(valid[:, None, :], scores,
                               jnp.asarray(-1e30, jnp.float32))
            out = jnp.einsum("sht,std->shd", jax.nn.softmax(scores, -1),
                             ctx[..., :value_width])
        elif kernel == "blockwise":
            from ...kernels.pallas.paged_attention import (
                blockwise_latent_attention)
            out = blockwise_latent_attention(
                q, pool, layer, block_tables, lens, block_size,
                value_width, scale, chunk_blocks, min_width)
        elif kernel == "pallas":
            from ...kernels.pallas.paged_attention import (
                pallas_latent_attention)
            out = pallas_latent_attention(
                q, pool, layer, block_tables, lens, block_size,
                value_width, scale, interpret=interpret)
        else:
            raise ValueError(
                f"no {kernel!r} attention over a latent cache: "
                "'pallas', 'blockwise' or 'reference'")
    return out, pool


def paged_banded_decode_attention(q, k_new, v_new, k_pools, v_pools, layer,
                                  block_tables, seq_lens, active, block_size,
                                  window=None, sink=None, kernel=None,
                                  interpret=False,
                                  name="banded_decode_attention"):
    """`paged_decode_attention` for layers it does not take: a key wider
    than its value (K rows ``H*Dk``, V rows ``H*Dv``), a learned `sink`
    ``[Hq]`` in the softmax's denominator, and, given `window`, a WINDOW
    layer over its ring pools (serving/cache.py `CacheSpec`'s rule).

    q ``[S, 1, Hq, Dk]``, k_new ``[S, 1, H, Dk]``, v_new ``[S, 1, H, Dv]``,
    Hq a multiple of H. `window` None: the pools are the paged pools and
    `block_tables` the slots' tables, as there. `window` W: the pools are
    the ring pools ``[L, 1 + S * ring, bs, .]`` and `block_tables` is not
    read: the new token of an active slot is written at its position's
    ring block (an inactive slot's to the null block), and the slot reads
    a table of its OWN ring's blocks from the one that holds the window's
    oldest position ``max(0, p - W + 1)`` to the newest's, positions
    before the oldest masked: at most ``ring`` entries, and under
    ``"pallas"`` exactly the pages the window lies in are copied.
    `kernel`: ``"pallas"`` (`pallas_banded_attention`, under `name` in the
    device trace), ``"blockwise"`` (the loop) or ``"reference"`` (a dense
    gather of the whole table). Returns ``(out [S, 1, Hq, Dv], the two
    written pools)``."""
    s, _, hq, dk = q.shape
    heads = k_new.shape[2]
    lens = jnp.where(active, seq_lens, 0).astype(jnp.int32)
    rows = jnp.arange(s, dtype=jnp.int32)
    starts = None
    if window is None:
        tables, eff = block_tables, lens
        write_block = jnp.where(active,
                                block_tables[rows, lens // block_size], 0)
    else:
        from ...serving.cache import ring_block
        ring = (k_pools.shape[1] - 1) // s
        write_block = jnp.where(
            active, ring_block(rows, lens, block_size, ring), 0)
        oldest = jnp.maximum(lens - (int(window) - 1), 0)
        first = oldest // block_size                   # its block, absolute
        entry = (first[:, None] + jnp.arange(ring, dtype=jnp.int32)[None]) \
            * block_size
        tables = jnp.where(active[:, None], ring_block(
            rows[:, None], entry, block_size, ring), 0).astype(jnp.int32)
        eff, starts = lens - first * block_size, oldest - first * block_size
    write_block = write_block.astype(jnp.int32)
    write_off = lens % block_size
    with jax.named_scope("paged_kv_write"):
        k_pools = k_pools.at[layer, write_block, write_off].set(
            k_new[:, 0].reshape(s, -1).astype(k_pools.dtype))
        v_pools = v_pools.at[layer, write_block, write_off].set(
            v_new[:, 0].reshape(s, -1).astype(v_pools.dtype))
    variant = resolve_paged_kernel(kernel, heads, dk, block_size,
                                   interpret=interpret,
                                   kv_dtype=k_pools.dtype)
    qh = q[:, 0]
    with jax.named_scope("paged_attention"):
        if variant == "reference":
            out = _dense_banded_attention(qh, k_pools, v_pools, layer,
                                          tables, eff, block_size, starts,
                                          sink)
        elif variant == "blockwise":
            from ...kernels.pallas.paged_attention import (
                blockwise_paged_attention)
            out = blockwise_paged_attention(
                qh, k_pools, v_pools, layer, tables, eff, block_size,
                starts=starts, sink=sink)
        else:
            from ...kernels.pallas.paged_attention import (
                pallas_banded_attention)
            out = pallas_banded_attention(
                qh, k_pools, v_pools, layer, tables, eff, block_size,
                starts=starts, sink=sink, interpret=interpret, name=name)
    return out[:, None], k_pools, v_pools


def _dense_banded_attention(qh, k_pools, v_pools, layer, tables, lens,
                            block_size, starts=None, sink=None):
    """`_dense_gather_attention` with a value narrower than its key, a
    window's oldest position a slot and a sink a head: the oracle of
    `paged_banded_decode_attention`'s other two variants."""
    s, hq, dk = qh.shape
    h = k_pools.shape[-1] // dk
    t_max = tables.shape[1] * block_size
    keys = k_pools[layer, tables].astype(jnp.float32).reshape(
        s, t_max, h, dk)
    vals = v_pools[layer, tables].astype(jnp.float32).reshape(
        s, t_max, h, -1)
    qg = qh.astype(jnp.float32).reshape(s, h, hq // h, dk)
    scores = jnp.einsum("shgd,sthd->shgt", qg, keys).reshape(s, hq, t_max) \
        / jnp.sqrt(jnp.asarray(dk, jnp.float32))
    pos = jnp.arange(t_max, dtype=jnp.int32)[None, :]
    valid = pos <= lens[:, None]
    if starts is not None:
        valid = valid & (pos >= starts[:, None])
    scores = jnp.where(valid[:, None, :], scores,
                       jnp.asarray(-1e30, jnp.float32))
    if sink is not None:
        scores = jnp.concatenate([scores, jnp.broadcast_to(
            sink.astype(jnp.float32)[None, :, None], (s, hq, 1))], -1)
    probs = jax.nn.softmax(scores, axis=-1)[..., :t_max]
    out = jnp.einsum("shgt,sthd->shgd",
                     probs.reshape(s, h, hq // h, t_max), vals)
    return out.reshape(s, hq, -1).astype(qh.dtype)


__all__ += ["paged_decode_attention", "paged_latent_decode_attention",
            "paged_banded_decode_attention", "resolve_paged_kernel",
            "PAGED_KERNELS"]


@register_op("sparse_attention", "attention",
             ref="fluid/operators/sparse_attention_op.cu")
def sparse_attention(query, key, value, sparse_csr_offset,
                     sparse_csr_columns, key_padding_mask=None,
                     attn_mask=None, name=None):
    """Block-free CSR-sampled attention: for each query row i, attend only
    to the key columns listed in the CSR pattern (offset/columns per
    [batch, head]).

    TPU-first: the reference's cuSPARSE SDDMM+softmax+SpMM chain becomes a
    fixed-width gather — rows are padded to the max row degree so shapes
    stay static under jit; padded slots get -inf before the softmax.
    Layouts follow the reference: q/k/v [B, H, M, D], offset [B, H, M+1],
    columns [B, H, nnz].
    """
    import numpy as np
    q = ensure_tensor(query)
    k = ensure_tensor(key)
    v = ensure_tensor(value)
    off = np.asarray(ensure_tensor(sparse_csr_offset)._value)
    cols = np.asarray(ensure_tensor(sparse_csr_columns)._value)

    B, H, M, D = q._value.shape
    deg = np.diff(off, axis=-1)                      # [B, H, M]
    width = int(deg.max()) if deg.size else 1
    # static gather table: [B, H, M, width] column ids + validity
    col_tab = np.zeros((B, H, M, width), np.int32)
    val_tab = np.zeros((B, H, M, width), bool)
    for b in range(B):
        for h in range(H):
            for m in range(M):
                s, e = off[b, h, m], off[b, h, m + 1]
                col_tab[b, h, m, :e - s] = cols[b, h, s:e]
                val_tab[b, h, m, :e - s] = True
    # the block tables ride as dispatch inputs: captured arrays would
    # re-key the op per call even though the layout is config-derived
    col_t = const_input(col_tab)
    val_t = const_input(val_tab)

    def fn(qv, kv, vv, col_j, valid):
        scale = 1.0 / math.sqrt(D)
        kg = jnp.take_along_axis(kv[:, :, None], col_j[..., None], axis=3)
        scores = jnp.einsum("bhmd,bhmwd->bhmw", qv, kg) * scale
        scores = jnp.where(valid, scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        p = jnp.where(valid, p, 0.0)
        vg = jnp.take_along_axis(vv[:, :, None], col_j[..., None], axis=3)
        return jnp.einsum("bhmw,bhmwd->bhmd", p, vg)

    return call_op("sparse_attention", fn, (q, k, v, col_t, val_t))


__all__ += ["sparse_attention"]
