"""The main path's Pallas kernels, compiled for a TPU v5e that is described
and not attached.

The TPU's compiler is installed beside the CPU backend and compiles for a
topology description, so what it refuses — a block shape off the (8, 128)
tiling, more VMEM than a kernel may use — is found here at no chip time.
Interpret mode hides all of that: the paged-attention kernel passed every
interpret-mode parity test while the compiler refused its `(1, d)` blocks.
Shapes are those of `chip_smoke.py`. A compile that passes is not a chip
run: nothing executes and nothing here is a device number.

The serving programs' KV pool is held to the same compiler: at the backlog
cell's geometry the donated pool has to stay in one row-major layout and be
updated where it lies (no pool-shaped `copy`, `concatenate` or `pad`,
temporaries under one layer of one pool), and the blockwise decode
attention, whose loops stop at the lengths, holds nothing of a whole
context's size.
"""
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")
# several test processes (pytest-xdist workers) may each describe a chip:
# no hardware is opened, so libtpu's one-process lockfile has nothing to guard
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.kernels import cross_entropy, flash_attention, fused_ln
from paddle_tpu.kernels.pallas import paged_attention
from paddle_tpu.nn.functional.attention import paged_decode_attention
from paddle_tpu.serving.cache import scatter_prefill


@pytest.fixture(scope="module")
def v5e():
    """One described v5e chip; the persistent compile cache stays off
    around these compiles (an entry written for a described chip cannot be
    read back without one, and the next compile would warn)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def compile_for(chip, fn, *shapes):
    """Compile `fn` for the described chip; returns the program text."""
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_flash_attention_fwd_bwd(v5e):
    """GPT-2 124M training shape: batch 16, seq 1024, 12 heads of 64."""
    qkv = ((16, 1024, 12, 64), jnp.bfloat16)

    def loss(q, k, v):
        out = flash_attention.flash_attention_bnhd(q, k, v, True, None)
        return out.astype(jnp.float32).sum()

    text = compile_for(v5e, jax.grad(loss, argnums=(0, 1, 2)), qkv, qkv, qkv)
    assert text.count("tpu_custom_call") >= 3      # fwd, dq, dkv


def test_flash_attention_at_unequal_head_widths(v5e):
    """Latent attention's training shape in `train_joyai_mtp_4k`: batch 4,
    seq 4096, 32 heads, q and k 192 wide (128 + 64 rotary), v 128. At 4096
    rows the dK/dV kernel keeps Q, dO and two row statistics resident:
    past the compiler's default scoped VMEM, which `_vmem` raises."""
    qk, v = ((4, 4096, 32, 192), jnp.bfloat16), ((4, 4096, 32, 128),
                                                 jnp.bfloat16)

    def loss(q, k, v):
        out = flash_attention.flash_attention_bnhd(q, k, v, True, 192 ** -.5)
        assert out.shape == (4, 4096, 32, 128)
        return out.astype(jnp.float32).sum()

    text = compile_for(v5e, jax.grad(loss, argnums=(0, 1, 2)), qk, qk, v)
    assert text.count("tpu_custom_call") >= 3      # fwd, dq, dkv
    assert flash_attention._vmem(1024, 64, 64, True) is None   # GPT's calls
    assert flash_attention._vmem(2048, 128, 128, True) is None
    assert flash_attention._vmem(4096, 192, 128, True) is not None


def test_the_grouped_expert_block_compiles_at_the_cells_size(v5e):
    """16,384 tokens top 8 of 256 with 8 held: the buffer of 131,072 rows
    that no routing overflows, grouped products, forward and backward."""
    from paddle_tpu.incubate.distributed.models.moe import grouped_experts
    bf16, t, d, f = jnp.bfloat16, 16384, 2048, 768

    def loss(u, router, gate, up, down):
        out, counters, load = grouped_experts.grouped_held_expert_block(
            u, router, None, gate, up, down, topk=8, scaling=2.5)
        return out.sum() + counters.sum() + load.sum()

    args = [jax.ShapeDtypeStruct(shape, bf16, sharding=v5e) for shape in (
        (t, d), (d, 256), (8, d, f), (8, d, f), (8, f, d))]
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 6e9


def test_fused_layer_norm(v5e):
    rows, d = 16 * 1024, 768
    assert fused_ln._pick_block_r(d) is not None
    act, vec = ((rows, d), jnp.bfloat16), ((d,), jnp.bfloat16)
    compile_for(v5e, fused_ln.fused_bias_residual_layer_norm,
                act, act, vec, vec, vec)


def test_fused_cross_entropy_fwd_bwd(v5e):
    """The lm-head's [tokens, vocab] logits (the flag is off by default;
    the kernel must still be one the chip accepts)."""
    rows, vocab = 16 * 1024, 50304

    def loss(logits, labels):
        return cross_entropy.fused_softmax_cross_entropy(logits,
                                                         labels).sum()

    compile_for(v5e, jax.grad(loss), ((rows, vocab), jnp.bfloat16),
                ((rows,), jnp.int32))


# the serving shape of chip_smoke.py: 8 slots, block 16, a pool that lets
# every slot reach 1024 tokens; the kernel reads layer 1 of a stack of two
SLOTS, BLOCK, TABLE, POOL, LAYERS = 8, 16, 64, 513, 2


def paged_shapes(heads, head_dim, block, pool_dtype, slots=SLOTS,
                 table=TABLE, pool=(LAYERS, POOL)):
    pool = (pool + (block, heads * head_dim), pool_dtype)
    shapes = [((slots, heads, head_dim), jnp.bfloat16), pool, pool,
              ((slots, table), jnp.int32), ((slots,), jnp.int32)]
    if pool_dtype == jnp.int8:
        shapes += [((LAYERS, POOL, heads), jnp.float32)] * 2
    return shapes


def paged_kernel(block, group_pages=None):
    return lambda q, k, v, tables, lens: \
        paged_attention.pallas_paged_attention(q, k, v, 1, tables, lens,
                                               block,
                                               group_pages=group_pages)


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("heads", [12, 16], ids=["124m", "355m"])
def test_paged_decode_attention(v5e, monkeypatch, heads, pool_dtype):
    """The serve legs of `chip_smoke.py` ask for `pallas` by name: over
    the bf16 pool the kernel compiles; over an int8 pool the request
    falls back to the loop, attributed, and that compiles."""
    if pool_dtype == jnp.bfloat16:
        compile_for(v5e, paged_kernel(BLOCK),
                    *paged_shapes(heads, 64, BLOCK, pool_dtype))
        return
    from paddle_tpu.framework.flags import get_flags, set_flags
    from paddle_tpu.profiler.events import (clear_fusion_events,
                                            fusion_events)
    monkeypatch.setattr(paged_attention, "_on_tpu", lambda: True)

    def decode(q, k, v, tables, lens, k_scales, v_scales):
        token = q[:, None]
        return paged_decode_attention(
            token, token, token, k, v, 1, tables, lens, lens > 0, BLOCK,
            k_scales=k_scales, v_scales=v_scales, kernel="pallas")

    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e) for shape,
            dtype in paged_shapes(heads, 64, BLOCK, pool_dtype)]
    prev = get_flags(["FLAGS_profiler_events"])
    set_flags({"FLAGS_profiler_events": True})
    clear_fusion_events()
    try:
        text = jax.jit(decode).lower(*args).compile().as_text()
    finally:
        set_flags(prev)
    assert "tpu_custom_call" not in text
    (event,) = fusion_events("kernel.fallback")
    assert event["detail"]["why"] == "quantized_pool"
    assert event["detail"]["actual"] == "blockwise"


@pytest.mark.parametrize("config", ["gpt2_124m", "gpt3_1p3b"])
def test_paged_kernel_at_the_cells_geometry(v5e, config):
    """`serve_124m_backlog`'s decode attention as the engine traces it:
    128 slots, 64 table entries, the cell's whole `bf16[12,8193,16,768]`
    pools; and the same slots at `gpt3_1p3b`'s widths, 16 heads of 128
    (two layers of 2,049 blocks stand for its 24)."""
    heads, head_dim, pool = {"gpt2_124m": (12, 64, (12, 8193)),
                             "gpt3_1p3b": (16, 128, (2, 2049))}[config]
    text = compile_for(v5e, paged_kernel(BLOCK), *paged_shapes(
        heads, head_dim, BLOCK, jnp.bfloat16, slots=128, pool=pool))
    assert "while" not in text


def test_paged_eligibility_is_what_the_compiler_accepts(v5e, monkeypatch):
    """`is_eligible` is true exactly where the kernel compiles: a row of
    whole 128-lane tiles, a page of whole sublane tiles, and a page that
    leaves VMEM room for two groups a side, whatever the fp dtype."""
    monkeypatch.setattr(paged_attention, "_on_tpu", lambda: True)

    def compiles(heads, head_dim, block, pool_dtype):
        ok, why = paged_attention.is_eligible(heads, head_dim, block,
                                              pool_dtype)
        shapes = paged_shapes(heads, head_dim, block, pool_dtype, table=8,
                              pool=(LAYERS, 65))
        if ok:
            assert why is None
            compile_for(v5e, paged_kernel(block), *shapes)
        else:
            # (past the VMEM line the plan has no group: try a page)
            pages = 1 if why == "block_exceeds_vmem" else 8
            with pytest.raises(Exception, match="vmem|aligned to tiling"):
                compile_for(v5e, paged_kernel(block, pages), *shapes)
        return why

    # the shapes the engine serves today, and the smallest that fit
    for heads, head_dim in ((12, 64), (16, 64), (16, 128), (2, 64)):
        for pool_dtype in (jnp.bfloat16, jnp.float32):
            assert compiles(heads, head_dim, BLOCK, pool_dtype) is None
    assert compiles(12, 64, 8, jnp.bfloat16) is None
    # a row or a page off the tiles
    assert compiles(3, 16, BLOCK, jnp.bfloat16) == "row_not_whole_lane_tiles"
    assert compiles(12, 64, 4, jnp.float32) == \
        "block_not_whole_sublane_tiles"
    # a page of 2 MB is a group of its own; one of 4 MB has no VMEM
    assert compiles(32, 128, 128, jnp.float32) is None
    assert compiles(32, 128, 256, jnp.bfloat16) is None
    assert compiles(32, 128, 256, jnp.float32) == "block_exceeds_vmem"
    assert compiles(32, 128, 512, jnp.bfloat16) == "block_exceeds_vmem"
    # the plan: 256 tokens a group where they fit
    assert paged_attention._group_pages(64, 16, 768, jnp.bfloat16) == 16
    assert paged_attention._group_pages(64, 16, 2048, jnp.bfloat16) == 16
    assert paged_attention._group_pages(4, 16, 768, jnp.bfloat16) == 4
    assert paged_attention._group_pages(64, 16, 32 * 128, jnp.float32) == 8
    assert paged_attention._group_pages(64, 64, 32 * 128, jnp.float32) == 2
    # what is not a shape's to decide
    assert paged_attention.is_eligible(12, 64, 16, jnp.int8) == (
        False, "quantized_pool")
    assert paged_attention.is_eligible(None, 64, 16) == (
        False, "shape_unknown")


def test_the_unrequested_variant_follows_what_is_observed(monkeypatch):
    """No flag, no argument: `pallas` on a TPU over an fp pool whose shape
    the kernels take, per-head or latent (a latent row is asked as the
    one head `CacheSpec` describes); `blockwise` for an int8 pool, a row
    off the lane tiles or a page off the sublane tiles of either kind,
    and off the TPU: with no event, for nothing was asked for and nothing
    fell back."""
    from paddle_tpu.framework.flags import get_flags, set_flags
    from paddle_tpu.nn.functional.attention import resolve_paged_kernel
    from paddle_tpu.profiler.events import (clear_fusion_events,
                                            fusion_events)
    assert get_flags(["FLAGS_serve_attention_kernel"]) == {
        "FLAGS_serve_attention_kernel": ""}
    cell = dict(num_heads=12, head_dim=64, block_size=16)
    # `serve_longcat_decode`'s row: 512 + 64 values padded to 640
    latent = dict(num_heads=1, head_dim=LATENT_ROW, block_size=16)
    prev = get_flags(["FLAGS_profiler_events"])
    set_flags({"FLAGS_profiler_events": True})
    clear_fusion_events()
    try:
        assert resolve_paged_kernel(**cell) == "blockwise"      # a CPU
        assert resolve_paged_kernel(**latent) == "blockwise"
        monkeypatch.setattr(paged_attention, "_on_tpu", lambda: True)
        assert resolve_paged_kernel(**cell) == "pallas"
        assert resolve_paged_kernel(**latent) == "pallas"
        assert resolve_paged_kernel(**latent, kv_dtype=jnp.float32) \
            == "pallas"
        assert resolve_paged_kernel(**cell, kv_dtype=jnp.float32) == "pallas"
        assert resolve_paged_kernel(num_heads=16, head_dim=128,
                                    block_size=16) == "pallas"
        assert resolve_paged_kernel(num_heads=1, head_dim=576,
                                    block_size=16) == "blockwise"
        assert resolve_paged_kernel(**dict(latent, block_size=4)) \
            == "blockwise"
        assert resolve_paged_kernel(**latent, kv_dtype=jnp.int8) \
            == "blockwise"
        assert resolve_paged_kernel(**cell, kv_dtype=jnp.int8) == "blockwise"
        assert resolve_paged_kernel(num_heads=3, head_dim=16,
                                    block_size=16) == "blockwise"
        assert resolve_paged_kernel(num_heads=12, head_dim=64,
                                    block_size=4) == "blockwise"
        assert fusion_events("kernel.fallback") == []
        # the explicit overrides stay what they were
        assert resolve_paged_kernel("blockwise", **cell) == "blockwise"
        assert resolve_paged_kernel("reference", **cell) == "reference"
        assert resolve_paged_kernel("pallas", num_heads=3, head_dim=16,
                                    block_size=16) == "blockwise"
        (event,) = fusion_events("kernel.fallback")
        assert event["detail"]["why"] == "row_not_whole_lane_tiles"
    finally:
        set_flags(prev)


# The backlog cell's serving geometry (benchmark/traffic/backlog_mixed.json:
# 128 slots, block 16, 64 table entries, GPT-2 124M's 12 heads of 64); two
# layers of 2,049 blocks stand for the cell's 12 layers of 8,193.
CELL_SLOTS, CELL_TABLE, CELL_HEADS, CELL_HEAD_DIM = 128, 64, 12, 64
CELL_LAYERS, CELL_BLOCKS, CELL_BUCKET = 2, 2049, 256
CELL_POOL = (CELL_LAYERS, CELL_BLOCKS, BLOCK, CELL_HEADS * CELL_HEAD_DIM)


def _decode_layers(variant):
    """The decode step's KV write and attention, threaded through the
    layers as the engine's program threads them: a layer's K and V come
    from the layer before, so its write cannot move ahead of that
    layer's reads."""
    def decode(q, k_new, v_new, tables, lens, active, k_pools, v_pools):
        for layer in range(CELL_LAYERS):
            out, k_pools, v_pools = paged_decode_attention(
                q, q + k_new, q + v_new, k_pools, v_pools, layer, tables,
                lens, active, BLOCK, kernel=variant)
            q = q + out
        return q, k_pools, v_pools

    token = ((CELL_SLOTS, 1, CELL_HEADS, CELL_HEAD_DIM), jnp.bfloat16)
    return decode, [token, token, token,
                    ((CELL_SLOTS, CELL_TABLE), jnp.int32),
                    ((CELL_SLOTS,), jnp.int32), ((CELL_SLOTS,), jnp.bool_)]


def _prefill_scatter():
    def prefill(k_layers, v_layers, block_row, length, k_pools, v_pools):
        return scatter_prefill(k_pools, v_pools, k_layers, v_layers,
                               block_row, length, BLOCK)

    prompt = ((CELL_LAYERS, CELL_BUCKET, CELL_HEADS, CELL_HEAD_DIM),
              jnp.bfloat16)
    return prefill, [prompt, prompt, ((CELL_TABLE,), jnp.int32),
                     ((), jnp.int32)]


@pytest.mark.parametrize("program", ["decode_blockwise", "decode_pallas",
                                     "prefill_256"])
def test_the_donated_kv_pool_is_updated_where_it_lies(v5e, monkeypatch,
                                                      program):
    """No serving program copies a whole KV pool: the pools come in
    row-major, go out in the buffers they came in, and between the two no
    instruction of a pool's shape is a `copy`, a `concatenate` or a `pad`
    (the relayouts and the slice-and-stack the 5-D pool cost); what the
    program keeps besides is less than one layer of one pool."""
    monkeypatch.setattr(paged_attention, "_on_tpu", lambda: True)
    if program == "prefill_256":
        fn, shapes = _prefill_scatter()
    else:
        fn, shapes = _decode_layers(program[len("decode_"):])
    shapes += [(CELL_POOL, jnp.bfloat16)] * 2
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
            for shape, dtype in shapes]
    pools = (len(args) - 2, len(args) - 1)
    compiled = jax.jit(fn, donate_argnums=pools).lower(*args).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (program == "decode_pallas")

    pool = "bf16[" + ",".join(map(str, CELL_POOL)) + "]"
    made = re.findall(r"= " + re.escape(pool) + r"\{([\d,]*)\S* ([\w-]+)\(",
                      text)
    layouts = {layout for layout, opcode in made if opcode == "parameter"}
    assert layouts == {"3,2,1,0"}, layouts
    opcodes = {opcode for _, opcode in made}
    assert "scatter" in opcodes
    assert not opcodes & {"copy", "concatenate", "pad"}, opcodes

    if program == "decode_blockwise":
        # the length-bounded loop gathers a chunk of one group of slots
        # at a time: nothing in the program is as large as the slots'
        # whole contexts [S, M x bs, H x D], in any shape or dtype
        context = CELL_SLOTS * CELL_TABLE * BLOCK * CELL_HEADS * CELL_HEAD_DIM
        largest = max(
            (math.prod(map(int, dims.split(","))), dims)
            for dims in re.findall(r"\w+\[([\d,]+)\]", text)
            if dims != ",".join(map(str, CELL_POOL)))
        assert largest[0] < context // 8, largest

    one_layer = 2 * CELL_BLOCKS * BLOCK * CELL_HEADS * CELL_HEAD_DIM
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * CELL_LAYERS * one_layer
    assert memory.temp_size_in_bytes < one_layer, memory.temp_size_in_bytes


@pytest.mark.parametrize("panel,counters", [(0, 0), (3, 6)])
def test_a_prefill_hands_its_token_on_without_a_copy_of_a_pool(v5e, panel,
                                                               counters):
    """What PR 36 adds to a prefill program (`LLMEngine._hand_on`): the
    token into the decode launch's input vector at the slot and a row of
    int32 for the host, beside the donated pools' scatter: it compiles
    for the chip, the pools still go out where they came in, and the two
    small results are not aliased to their arguments (the commit of the
    launch before still reads those)."""
    from paddle_tpu.serving import LLMEngine
    width = 2 + 2 * panel + counters
    scatter, shapes = _prefill_scatter()

    def fn(nxt, logp, alt_ids, alt_lps, extra, slot, feedback, firsts,
           k_layers, v_layers, block_row, length, k_pools, v_pools):
        return LLMEngine._hand_on(
            feedback, firsts, slot, nxt, logp, alt_ids, alt_lps,
            (extra,) if counters else ()) + tuple(scatter(
                k_layers, v_layers, block_row, length, k_pools, v_pools))

    shapes = [((1,), jnp.int32), ((1,), jnp.float32),
              ((1, panel), jnp.int32), ((1, panel), jnp.float32),
              ((counters,), jnp.int32), ((), jnp.int32),
              ((CELL_SLOTS,), jnp.int32), ((CELL_SLOTS, width), jnp.int32)
              ] + shapes + [(CELL_POOL, jnp.bfloat16)] * 2
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
            for shape, dtype in shapes]
    compiled = jax.jit(fn, donate_argnums=(len(args) - 2, len(args) - 1)
                       ).lower(*args).compile()
    memory = compiled.memory_analysis()
    pools = 2 * 2 * math.prod(CELL_POOL)
    assert pools <= memory.alias_size_in_bytes < pools + 4 * CELL_SLOTS


# The long-decode cell's latent geometry (benchmark/traffic/
# backlog_long_decode.json: 128 slots, block 16, 128 table entries; a row
# of 512 + 64 values padded to 640, 64 heads attending absorbed); two
# sublayers of 2,049 blocks stand for the cell's 8 of 16,385.
LATENT_TABLE, LATENT_HEADS, LATENT_ROW, LATENT_VALUE = 128, 64, 640, 512
LATENT_POOL = (CELL_LAYERS, CELL_BLOCKS, BLOCK, LATENT_ROW)


@pytest.mark.parametrize("program", ["decode_blockwise", "decode_pallas",
                                     "prefill_512"])
def test_the_donated_latent_pool_is_updated_where_it_lies(v5e, program):
    """The ONE latent pool comes in row-major and goes out in the buffer it
    came in; no instruction of the pool's shape is a `copy`, a
    `concatenate` or a `pad`; the second (empty) pool costs nothing. Under
    `pallas` the kernel reads the written pool where it lies, and the
    sublayers share ONE lowered kernel (the layer is its operand)."""
    from paddle_tpu.nn.functional.attention import \
        paged_latent_decode_attention
    if program == "prefill_512":
        def fn(rows, keys, block_row, length, k_pools, v_pools):
            return scatter_prefill(k_pools, v_pools, rows, keys, block_row,
                                   length, BLOCK)
        shapes = [((CELL_LAYERS, 512, LATENT_VALUE), jnp.bfloat16),
                  ((CELL_LAYERS, 512, 64), jnp.bfloat16),
                  ((LATENT_TABLE,), jnp.int32), ((), jnp.int32)]
    else:
        def fn(q, row, key, tables, lens, active, k_pools, v_pools):
            for layer in range(CELL_LAYERS):
                out, k_pools = paged_latent_decode_attention(
                    q, (row, key), k_pools, layer, tables, lens, active,
                    BLOCK, value_width=LATENT_VALUE, scale=0.07,
                    kernel=program[len("decode_"):], chunk_blocks=16)
                q = q + jnp.pad(out, ((0, 0), (0, 0), (0, 64)))
            return q, k_pools, v_pools
        shapes = [((CELL_SLOTS, LATENT_HEADS, 576), jnp.float32),
                  ((CELL_SLOTS, LATENT_VALUE), jnp.bfloat16),
                  ((CELL_SLOTS, 64), jnp.bfloat16),
                  ((CELL_SLOTS, LATENT_TABLE), jnp.int32),
                  ((CELL_SLOTS,), jnp.int32), ((CELL_SLOTS,), jnp.bool_)]
    shapes += [(LATENT_POOL, jnp.bfloat16), ((0,), jnp.bfloat16)]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
            for shape, dtype in shapes]
    compiled = jax.jit(fn, donate_argnums=(len(args) - 2,
                                           len(args) - 1)).lower(
        *args).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (program == "decode_pallas")
    if program == "decode_pallas":
        assert "while" not in text
        lowered = jax.jit(fn).lower(*args).as_text()
        assert lowered.count("tpu_custom_call") == 1 < CELL_LAYERS
    pool = "bf16[" + ",".join(map(str, LATENT_POOL)) + "]"
    made = re.findall(r"= " + re.escape(pool) + r"\{([\d,]*)\S* ([\w-]+)\(",
                      text)
    assert {layout for layout, opcode in made
            if opcode == "parameter"} == {"3,2,1,0"}
    opcodes = {opcode for _, opcode in made}
    assert "scatter" in opcodes
    assert not opcodes & {"copy", "concatenate", "pad"}, opcodes
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * math.prod(LATENT_POOL)


def latent_kernel(block, value=LATENT_VALUE, group_pages=None):
    return lambda q, pool, tables, lens: \
        paged_attention.pallas_latent_attention(
            q, pool, 1, tables, lens, block, value, 0.07,
            group_pages=group_pages)


def latent_shapes(heads, row, block, pool_dtype, slots=SLOTS, table=TABLE,
                  pool=(LAYERS, POOL), width=None):
    return [((slots, heads, width or row), jnp.float32),
            (pool + (block, row), pool_dtype),
            ((slots, table), jnp.int32), ((slots,), jnp.int32)]


def test_the_latent_kernel_at_the_cells_geometry(v5e):
    """`serve_longcat_decode`'s decode attention as the engine traces it:
    128 slots, 128 table entries, 64 heads' queries of 576 values in
    float32 over the cell's whole `bf16[8,16385,16,640]` pool, the value
    a row's first 512: the blockwise loop is gone from the program."""
    text = compile_for(v5e, latent_kernel(BLOCK), *latent_shapes(
        LATENT_HEADS, LATENT_ROW, BLOCK, jnp.bfloat16, slots=CELL_SLOTS,
        table=LATENT_TABLE, pool=(8, 16385), width=576))
    assert "while" not in text


def test_latent_eligibility_is_what_the_compiler_accepts(v5e, monkeypatch):
    """`is_eligible`, asked of a latent row as the ONE head `CacheSpec`
    describes, is true exactly where the latent kernel compiles: a row of
    whole 128-lane tiles and a page of whole sublane tiles, whatever the
    fp dtype, the number of heads and the value's width (both are padded
    to whole tiles around the kernel)."""
    monkeypatch.setattr(paged_attention, "_on_tpu", lambda: True)

    def compiles(row, block, pool_dtype, heads=LATENT_HEADS, value=None):
        ok, why = paged_attention.is_eligible(1, row, block, pool_dtype)
        value = value or min(row, LATENT_VALUE)
        shapes = latent_shapes(heads, row, block, pool_dtype, table=8,
                               pool=(LAYERS, 65))
        if ok:
            assert why is None
            compile_for(v5e, latent_kernel(block, value), *shapes)
        else:
            with pytest.raises(Exception, match="vmem|aligned to tiling"):
                compile_for(v5e, latent_kernel(block, value, 8), *shapes)
        return why

    for pool_dtype in (jnp.bfloat16, jnp.float32):
        assert compiles(LATENT_ROW, BLOCK, pool_dtype) is None
        assert compiles(128, 8, pool_dtype, heads=4, value=16) is None
    # DeepSeek-V3's 128 heads; a count of heads off the tiles
    assert compiles(LATENT_ROW, BLOCK, jnp.bfloat16, heads=128) is None
    assert compiles(LATENT_ROW, BLOCK, jnp.bfloat16, heads=12) is None
    # the row as published (512 + 64), a page of four tokens
    assert compiles(576, BLOCK, jnp.bfloat16) == "row_not_whole_lane_tiles"
    assert compiles(LATENT_ROW, 4, jnp.float32) == \
        "block_not_whole_sublane_tiles"
    # the plan at the cell's geometry: 512 tokens a group
    assert paged_attention._group_pages(
        LATENT_TABLE, BLOCK, LATENT_ROW, jnp.bfloat16,
        paged_attention._LATENT_GROUP_TOKENS) == 32


# The retrieval cell's geometry (benchmark/traffic/backlog_rag_2k.json,
# benchmark/configs/lfm2_8b_a1b_l16.json: 128 slots, block 16, 128 table
# entries; 32 query heads over 8 key/value heads of 64, so a pool's row is
# 512; 12 convolution layers that keep a row of 2 x 2048 a slot).
GQ_HEADS, GQ_KV_HEADS, GQ_HEAD_DIM, GQ_TABLE = 32, 8, 64, 128


def test_the_grouped_query_kernel_at_the_cells_geometry(v5e):
    """`serve_lfm2_rag_backlog`'s decode attention as the engine traces
    it: four query heads a key/value head over the cell's whole
    `bf16[4,16385,16,512]` pools, the query's placement in the lanes and
    the output's assembly by the kernel's 0/1 products: Mosaic takes it,
    and the program holds no loop."""
    pool = ((4, 16385, BLOCK, GQ_KV_HEADS * GQ_HEAD_DIM), jnp.bfloat16)
    text = compile_for(
        v5e, paged_kernel(BLOCK),
        ((CELL_SLOTS, GQ_HEADS, GQ_HEAD_DIM), jnp.bfloat16), pool, pool,
        ((CELL_SLOTS, GQ_TABLE), jnp.int32), ((CELL_SLOTS,), jnp.int32))
    assert "while" not in text


def test_a_decode_step_updates_the_pool_and_the_slots_state_where_they_lie(
        v5e, monkeypatch):
    """A model with two kinds of layer through ONE `PagedCacheView`, as
    the engine's decode program threads it (weights as arguments): the
    donated pools AND the donated per-slot state go out in the buffers
    they came in; no instruction of either's shape is a `copy`, a
    `concatenate` or a `pad`."""
    import paddle_tpu as paddle
    from paddle_tpu.incubate.models.lfm2_moe import (Lfm2MoeConfig,
                                                     Lfm2MoeForCausalLM)
    from paddle_tpu.serving.cache import PagedCacheView
    monkeypatch.setattr(paged_attention, "_on_tpu", lambda: True)
    kinds = ("conv", "full_attention", "conv", "full_attention")
    model = Lfm2MoeForCausalLM(Lfm2MoeConfig(
        vocab_size=512, hidden_size=2048, intermediate_size=256,
        moe_intermediate_size=128, num_hidden_layers=4, layer_types=kinds,
        num_dense_layers=4, num_attention_heads=GQ_HEADS,
        num_key_value_heads=GQ_KV_HEADS, num_experts=4,
        num_experts_per_tok=2))
    params = model.parameters()
    pool = (2, CELL_BLOCKS, BLOCK, GQ_KV_HEADS * GQ_HEAD_DIM)
    state = (2, CELL_SLOTS, 2 * 2048)

    def decode(values, tokens, tables, lens, active, k_pools, v_pools,
               slot_state):
        saved = [p._value for p in params]
        for p, v in zip(params, values):
            p._value = v
        try:
            view = PagedCacheView(k_pools, v_pools, 0, tables, lens, active,
                                  BLOCK, kernel="pallas",
                                  slot_state=(slot_state,))
            logits, (view,) = model(paddle.Tensor(tokens[:, None]),
                                    caches=[view])
        finally:
            for p, v in zip(params, saved):
                p._value = v
        assert (view.layer, view.state_layer) == (2, 2)
        return (logits._value, view.k_pools, view.v_pools) + view.slot_state

    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=v5e)
    args = [[sd(p._value.shape, jnp.bfloat16) for p in params],
            sd((CELL_SLOTS,), jnp.int32),
            sd((CELL_SLOTS, GQ_TABLE), jnp.int32),
            sd((CELL_SLOTS,), jnp.int32), sd((CELL_SLOTS,), jnp.bool_),
            sd(pool, jnp.bfloat16), sd(pool, jnp.bfloat16),
            sd(state, jnp.bfloat16)]
    compiled = jax.jit(decode, donate_argnums=(5, 6, 7)).lower(
        *args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for shape in (pool, state):
        name = "bf16[" + ",".join(map(str, shape)) + "]"
        made = re.findall(
            r"= " + re.escape(name) + r"\{([\d,]*)\S* ([\w-]+)\(", text)
        assert {layout for layout, opcode in made
                if opcode == "parameter"} == {
                    ",".join(map(str, reversed(range(len(shape)))))}, name
        opcodes = {opcode for _, opcode in made}
        assert not opcodes & {"copy", "concatenate", "pad"}, (name, opcodes)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * (2 * math.prod(pool)
                                              + math.prod(state))


@pytest.mark.parametrize("tokens", [128, 2048],
                         ids=["decode_launch", "prefill_2048"])
def test_the_served_expert_block_with_every_expert_held(v5e, tokens):
    """All 32 experts of 1,792 held, top 4, sigmoid scores normalised over
    the chosen: a decode launch of 128 tokens and a 2,048-token prefill
    through the form the call's shape picks (the grouped products, forward
    only), compiled for the chip; what it keeps besides its operands is a
    few buffers of `tokens * 4` rows, not `tokens * 32`."""
    from paddle_tpu.incubate.distributed.models.moe import held_experts
    bf16, d, f, experts = jnp.bfloat16, 2048, 1792, 32
    assert held_experts.products_form(tokens, 4, experts) == "grouped"

    def block(u, router, bias, w1, w3, w2, valid):
        return held_experts.held_expert_block(
            u, router, bias, w1, w3, w2, topk=4, real_experts=experts,
            scaling=1.0, valid=valid, scoring="sigmoid", normalise=True,
            epsilon=1e-6)

    shapes = [((tokens, d), bf16), ((d, experts), bf16),
              ((experts,), jnp.float32), ((experts, d, f), bf16),
              ((experts, d, f), bf16), ((experts, f, d), bf16),
              ((tokens,), jnp.bool_)]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
            for shape, dtype in shapes]
    compiled = jax.jit(block).lower(*args).compile()
    assert "ragged" in compiled.as_text()
    rows = tokens * 4
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 16 * rows * d * 4 + (64 << 20)


@pytest.mark.parametrize("rows,k,n", [(512, 2048, 1792), (8192, 1792, 2048)],
                         ids=["a_launchs_gate", "a_2048_buckets_down"])
def test_the_grouped_matmul_kernel_at_the_cells_extremes(v5e, rows, k, n):
    """The tiled grouped matmul at the two ends of what
    `serve_lfm2_rag_backlog` hands it, by the tiles its shape rule picks,
    under the framework's x64 mode (every scalar of the kernel 32-bit):
    the instruction carries the name the benchmark's readers match."""
    from paddle_tpu.kernels.pallas import grouped_matmul as gm
    text = compile_for(v5e, gm.grouped_matmul, ((rows, k), jnp.bfloat16),
                       ((32, k, n), jnp.bfloat16), ((32,), jnp.int32))
    assert len(re.findall(r"^\s*(?:ROOT )?%\S*ragged\S* = ", text,
                          re.M)) == 1
    assert "%ragged_expert_matmul" in text


def test_grouped_matmul_eligibility_is_what_the_compiler_accepts(
        v5e, monkeypatch):
    """What `is_eligible` lets through on a TPU compiles, at the row
    counts of every program of the cell and at a tile's least; what it
    refuses for its shape is refused by the compiler or never asked."""
    from paddle_tpu.kernels.pallas import grouped_matmul as gm
    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    for rows, k, n in [(16, 128, 128), (1024, 2048, 1792),
                       (2048, 1792, 2048), (4096, 2048, 1792),
                       (1536, 6144, 2048)]:
        assert gm.is_eligible(rows, k, n) == (True, None)
        compile_for(v5e, gm.grouped_matmul, ((rows, k), jnp.bfloat16),
                    ((16, k, n), jnp.bfloat16), ((16,), jnp.int32))
    assert gm.is_eligible(512, 2048, 1800)[0] is False
    assert gm.is_eligible(520, 2048, 1792)[0] is False


@pytest.mark.parametrize("tokens", [128, 2048],
                         ids=["decode_launch", "prefill_2048"])
def test_the_served_expert_block_through_the_kernel(v5e, monkeypatch,
                                                    tokens):
    """On a TPU (here: told so) the block's three grouped products are
    three calls of the kernel and nothing of the library's product is
    left: exactly three instructions carry `ragged` in their names, which
    is what `lfm2.expert_products_roofline` counts a block call by."""
    from paddle_tpu.incubate.distributed.models.moe import held_experts
    from paddle_tpu.kernels.pallas import grouped_matmul as gm
    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    bf16, d, f, experts = jnp.bfloat16, 2048, 1792, 32
    assert held_experts.products_run(tokens, 4, experts, d, f, bf16) == (3, 3)

    def block(u, router, bias, w1, w3, w2, valid):
        return held_experts.held_expert_block(
            u, router, bias, w1, w3, w2, topk=4, real_experts=experts,
            scaling=1.0, valid=valid, scoring="sigmoid", normalise=True,
            epsilon=1e-6)

    text = compile_for(
        v5e, block, ((tokens, d), bf16), ((d, experts), bf16),
        ((experts,), jnp.float32), ((experts, d, f), bf16),
        ((experts, d, f), bf16), ((experts, f, d), bf16),
        ((tokens,), jnp.bool_))
    named = re.findall(r"^\s*(?:ROOT )?%(\S*ragged\S*) = ", text, re.M)
    assert len(named) == 3
    assert all(name.startswith("ragged_expert_matmul") for name in named)
    assert "ragged-dot" not in text


# -- MiMo-V2-Flash: keys wider than values, window layers' rings --------------

MIMO_HEADS, MIMO_DK, MIMO_DV, MIMO_WINDOW = 64, 192, 128, 128
MIMO_TABLE = 8192 // BLOCK                         # a context of 8,192
MIMO_RING = MIMO_WINDOW // BLOCK + 1


@pytest.mark.parametrize("kind", ["full", "window"])
def test_the_banded_decode_kernel_at_the_cells_geometry(v5e, kind):
    """`serve_mimo_reasoning_8k`'s decode attention as the engine traces
    it: 64 query heads over 4 key/value heads (full layers: the cell's
    whole pools, K rows of 768 and V rows of 512) or over 8 (window
    layers: the rings, rows of 1,536 and 1,024, a table of 9 entries, the
    window's oldest position and the sink): Mosaic takes both, and the
    program holds no loop."""
    kv, layers, blocks, table = {
        "full": (4, 2, 1 + CELL_SLOTS * MIMO_TABLE, MIMO_TABLE),
        "window": (8, 5, 1 + CELL_SLOTS * MIMO_RING, MIMO_RING)}[kind]
    windowed = kind == "window"

    def call(q, k_pools, v_pools, tables, lens, starts, sink):
        return paged_attention.pallas_banded_attention(
            q, k_pools, v_pools, 1, tables, lens, BLOCK,
            starts=starts if windowed else None,
            sink=sink if windowed else None,
            name=kind + "_decode_attention")

    text = compile_for(
        v5e, call, ((CELL_SLOTS, MIMO_HEADS, MIMO_DK), jnp.bfloat16),
        ((layers, blocks, BLOCK, kv * MIMO_DK), jnp.bfloat16),
        ((layers, blocks, BLOCK, kv * MIMO_DV), jnp.bfloat16),
        ((CELL_SLOTS, table), jnp.int32), ((CELL_SLOTS,), jnp.int32),
        ((CELL_SLOTS,), jnp.int32), ((MIMO_HEADS,), jnp.float32))
    assert "while" not in text
    assert kind + "_decode_attention" in text


@pytest.mark.parametrize("bucket", [2048, 8192])
def test_the_band_prefill_kernel_at_the_cells_buckets(v5e, bucket):
    """A window layer's prefill: 64 query heads of 192 over 8 key/value
    heads (no head repeated: the index map divides), values of 128, a
    band of 128 with the sink."""
    text = compile_for(
        v5e, lambda q, k, v, sink: flash_attention.flash_band_attention_bnhd(
            q, k, v, MIMO_WINDOW, sink),
        ((1, bucket, MIMO_HEADS, MIMO_DK), jnp.bfloat16),
        ((1, bucket, 8, MIMO_DK), jnp.bfloat16),
        ((1, bucket, 8, MIMO_DV), jnp.bfloat16),
        ((MIMO_HEADS,), jnp.float32))
    assert "flash_band_attention" in text


def test_a_decode_step_updates_both_caches_where_they_lie(v5e, monkeypatch):
    """A model with window and full layers through ONE `PagedCacheView`,
    as the engine's decode program threads it (weights as arguments): the
    donated paged pools AND the donated ring pools go out in the buffers
    they came in; no instruction of any of their shapes is a `copy`, a
    `concatenate` or a `pad`."""
    import paddle_tpu as paddle
    from paddle_tpu.incubate.models.mimo_v2_flash import (
        FULL, WINDOW, MiMoV2FlashConfig, MiMoV2FlashForCausalLM)
    from paddle_tpu.serving.cache import PagedCacheView
    monkeypatch.setattr(paged_attention, "_on_tpu", lambda: True)
    model = MiMoV2FlashForCausalLM(MiMoV2FlashConfig(
        vocab_size=512, hidden_size=512, intermediate_size=256,
        moe_intermediate_size=128, num_hidden_layers=4,
        layer_types=(FULL, WINDOW, WINDOW, FULL), first_k_dense_replace=4,
        n_routed_experts=8, num_experts_per_tok=2))
    params = model.parameters()
    blocks = 1 + CELL_SLOTS * 128                    # contexts of 2,048
    pools = [(2, blocks, BLOCK, 4 * MIMO_DK), (2, blocks, BLOCK, 4 * MIMO_DV)]
    rings = [(2, 1 + CELL_SLOTS * MIMO_RING, BLOCK, 8 * MIMO_DK),
             (2, 1 + CELL_SLOTS * MIMO_RING, BLOCK, 8 * MIMO_DV)]

    def decode(values, tokens, tables, lens, active, k_pools, v_pools,
               ring_k, ring_v):
        saved = [p._value for p in params]
        for p, v in zip(params, values):
            p._value = v
        try:
            view = PagedCacheView(k_pools, v_pools, 0, tables, lens, active,
                                  BLOCK, kernel="pallas",
                                  window_pools=(ring_k, ring_v),
                                  window=MIMO_WINDOW)
            logits, (view,) = model(paddle.Tensor(tokens[:, None]),
                                    caches=[view])
        finally:
            for p, v in zip(params, saved):
                p._value = v
        assert (view.layer, view.window_layer) == (2, 2)
        return (logits._value, view.k_pools, view.v_pools) \
            + tuple(view.window_pools)

    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=v5e)
    args = [[sd(p._value.shape, jnp.bfloat16) for p in params],
            sd((CELL_SLOTS,), jnp.int32), sd((CELL_SLOTS, 128), jnp.int32),
            sd((CELL_SLOTS,), jnp.int32), sd((CELL_SLOTS,), jnp.bool_)] \
        + [sd(shape, jnp.bfloat16) for shape in pools + rings]
    compiled = jax.jit(decode, donate_argnums=(5, 6, 7, 8)).lower(
        *args).compile()
    text = compiled.as_text()
    assert text.count("full_decode_attention") \
        and text.count("window_decode_attention")
    for shape in pools + rings:
        name = "bf16[" + ",".join(map(str, shape)) + "]"
        made = re.findall(
            r"= " + re.escape(name) + r"\{([\d,]*)\S* ([\w-]+)\(", text)
        assert {layout for layout, opcode in made
                if opcode == "parameter"} == {
                    ",".join(map(str, reversed(range(len(shape)))))}, name
        opcodes = {opcode for _, opcode in made}
        assert not opcodes & {"copy", "concatenate", "pad"}, (name, opcodes)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * sum(
        math.prod(shape) for shape in pools + rings)


# -- the delta rule of `serve_solar_longdoc_16k` (kernels/kda.py) -------------
# 64 heads of 128 x 128 float32 a slot a KDA layer, 64 slots, 3 such layers;
# prompts in buckets of 4,096 to 16,384 tokens.
KDA_HEADS, KDA_WIDTH, KDA_SLOTS, KDA_LAYERS = 64, 128, 64, 3


def _layouts(text, name):
    """{(layout, opcode)} of the instructions that make an array `name`."""
    return set(re.findall(
        r"= " + re.escape(name) + r"\{([\d,]*)\S* ([\w-]+)\(", text))


def _entry_instructions(text):
    """[(the instruction as a device trace names it: no indent, no `ROOT`;
    its `op_name`)] of the ENTRY computation of a compiled program's text:
    the operations a device runs one after the other, each one event."""
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    out = []
    for line in entry.splitlines()[2:]:
        line = line.strip()
        line = line[5:] if line.startswith("ROOT ") else line
        scope = re.search(r'op_name="([^"]*)"', line)
        out.append((line, scope.group(1) if scope else ""))
    return out


def _the_scan_metrics_find_the_outer_loop(text, loops=1):
    """`solar.kda_prefill_time_share` and `solar.kda_prefill_roofline` find
    the scan by a pattern over an XLA `while`'s result tuple (a trace
    names an instruction and its shapes, never its `op_name`). Held HERE to
    what the chip's compiler makes of the scan: the pattern of each
    metric's file matches ONE operation a KDA layer of the program, the
    loop over the spans under the scope `kda_chunk_scan`, and no other. A change of
    `CHUNK`, of the layout or of the compiler's tuple order fails this
    test where it would have made the metrics read nothing."""
    import json
    import os
    metrics = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "metrics")
    ops = _entry_instructions(text)
    found = [line for line, scope in ops
             if scope.endswith("/kda_chunk_scan/while")
             and re.search(r"[\])}] while\(", line)]
    assert len(found) == loops, [scope for _, scope in ops
                                 if "while" in scope]
    for name in ("solar.kda_prefill_time_share",
                 "solar.kda_prefill_roofline"):
        with open(os.path.join(metrics, name + ".json")) as f:
            pattern = json.load(f)["args"]["pattern"]
        assert [line for line, _ in ops if re.search(pattern, line)] \
            == found, name


@pytest.mark.parametrize("bucket", [4096, 16384])
def test_the_kda_chunk_scan_at_the_cells_buckets(v5e, bucket):
    """The chunked scan of a prompt as the prefill traces it (q, k, v in
    bfloat16, the decays and the state float32): the chip's compiler takes
    it, its loops are the two scans (spans, and a span's chunks), and what
    it holds besides its operands is a span's worth, not the prompt's."""
    from paddle_tpu.kernels import kda
    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=v5e)
    row = (1, bucket, KDA_HEADS, KDA_WIDTH)
    compiled = jax.jit(kda.kda_chunk_scan).lower(
        sd(row, jnp.bfloat16), sd(row, jnp.bfloat16), sd(row, jnp.bfloat16),
        sd(row, jnp.float32), sd(row[:3], jnp.float32),
        sd((1, KDA_HEADS, KDA_WIDTH, KDA_WIDTH), jnp.float32)).compile()
    text = compiled.as_text()
    _the_scan_metrics_find_the_outer_loop(text)
    memory = compiled.memory_analysis()
    # q, k, v, g in; o out; under a gigabyte of temporaries at 16,384
    assert memory.temp_size_in_bytes < 2 ** 30


def test_the_scan_metrics_find_the_loop_inside_a_models_prefill(v5e):
    """The same, where the scan lies as the cell's prefill has it: behind
    the model's own projections, convolutions, norms and gates and before
    its output norm (their fusions decide the layouts the compiler gives
    the loop's operands), a bucket of 4,096 through two KDA layers at the
    cell's heads: one loop a layer, each found by the metrics' pattern."""
    import paddle_tpu as paddle
    from paddle_tpu.incubate.models.solar_open2 import (
        KDA, SolarOpen2Config, SolarOpen2ForCausalLM)
    model = SolarOpen2ForCausalLM(SolarOpen2Config(
        vocab_size=512, hidden_size=512, moe_intermediate_size=128,
        num_hidden_layers=2, layer_types=(KDA, KDA),
        n_routed_experts=8, num_experts_per_tok=2))
    params = model.parameters()
    spec = model.cache_spec()
    bucket = 4096

    def prefill(values, ids, length):
        saved = [p._value for p in params]
        for p, v in zip(params, values):
            p._value = v
        try:
            valid = jnp.arange(bucket)[None, :] < length[:, None]
            logits, caches = model(paddle.Tensor(ids),
                                   caches=spec.empty_prefill(jnp.bfloat16),
                                   valid=valid)
        finally:
            for p, v in zip(params, saved):
                p._value = v
        return logits._value, [[part._value for part in state]
                               for state in caches]

    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=v5e)
    text = jax.jit(prefill).lower(
        [sd(p._value.shape, jnp.bfloat16) for p in params],
        sd((1, bucket), jnp.int32), sd((1,), jnp.int32)).compile().as_text()
    _the_scan_metrics_find_the_outer_loop(text, loops=2)


def test_the_kda_update_kernel_at_the_cells_geometry(v5e, monkeypatch):
    """The one-token update of 64 slots' states as the decode program
    traces it: Mosaic takes the kernel (a head's columns broadcast along
    the lanes), it is NAMED (`kda_decode_step`: what the device trace and
    `solar.kda_decode_*` read), and the donated float32 states go out in
    the buffer they came in: no instruction makes an array of their shape
    but the parameter and the kernel's own result."""
    from paddle_tpu.kernels import kda
    monkeypatch.setattr(kda, "on_tpu", lambda: True)
    assert kda.update_form(KDA_HEADS, KDA_WIDTH, KDA_WIDTH) == "pallas"
    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=v5e)
    token = (KDA_SLOTS, KDA_HEADS, KDA_WIDTH)
    states = (KDA_LAYERS, KDA_SLOTS, KDA_HEADS, KDA_WIDTH, KDA_WIDTH)
    compiled = jax.jit(
        lambda q, k, v, g, beta, held, active: kda.kda_decode_step(
            q, k, v, g, beta, held, 1, active),
        donate_argnums=(5,)).lower(
            sd(token, jnp.bfloat16), sd(token, jnp.bfloat16),
            sd(token, jnp.bfloat16), sd(token, jnp.float32),
            sd(token[:2], jnp.float32), sd(states, jnp.float32),
            sd((KDA_SLOTS,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "%kda_decode_step" in text
    name = "f32[" + ",".join(map(str, states)) + "]"
    assert {opcode for _, opcode in _layouts(text, name)} <= {
        "parameter", "get-tuple-element", "custom-call", "bitcast"}
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 4 * math.prod(states)
    assert memory.temp_size_in_bytes < 2 ** 24


def test_a_decode_step_updates_the_pool_and_both_state_parts_where_they_lie(
        v5e, monkeypatch):
    """A model with KDA and softmax layers through ONE `PagedCacheView`,
    as the engine's decode program threads it (weights as arguments): the
    donated pools AND the two donated state parts (the convolutions'
    inputs in bfloat16, the matrix states in float32) go out in the
    buffers they came in. No instruction of any of their shapes is a
    `copy`, a `concatenate` or a `pad`, and every one lies row-major, as
    the parameter does: the `[S, 1, d]` trap the LFM2 model's docstring
    records (a state turned into another layout and back, every launch)
    shows as a second layout here. (The kept inputs may be staged through
    the faster memory a layer at a time, `copy-start/-done` in their own
    layout: that moves 28 MB, not 800, and changes no layout.)"""
    import paddle_tpu as paddle
    from paddle_tpu.incubate.models.solar_open2 import (
        ATTENTION, KDA, SolarOpen2Config, SolarOpen2ForCausalLM)
    from paddle_tpu.kernels import kda
    from paddle_tpu.serving.cache import PagedCacheView
    monkeypatch.setattr(paged_attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(kda, "on_tpu", lambda: True)
    model = SolarOpen2ForCausalLM(SolarOpen2Config(
        vocab_size=512, hidden_size=512, moe_intermediate_size=128,
        num_hidden_layers=3, layer_types=(ATTENTION, KDA, KDA),
        n_routed_experts=8, num_experts_per_tok=2))
    params = model.parameters()
    slots, table = KDA_SLOTS, 128                    # contexts of 2,048
    pool = (1, 1 + slots * table, BLOCK, 8 * 128)
    conv = (2, slots, 3 * 3 * KDA_HEADS * KDA_WIDTH)
    delta = (2, slots, KDA_HEADS, KDA_WIDTH, KDA_WIDTH)

    def decode(values, tokens, tables, lens, active, k_pools, v_pools,
               kept, matrices):
        saved = [p._value for p in params]
        for p, v in zip(params, values):
            p._value = v
        try:
            view = PagedCacheView(k_pools, v_pools, 0, tables, lens, active,
                                  BLOCK, kernel="pallas",
                                  slot_state=(kept, matrices))
            logits, (view,) = model(paddle.Tensor(tokens[:, None]),
                                    caches=[view])
        finally:
            for p, v in zip(params, saved):
                p._value = v
        assert (view.layer, view.state_layer) == (1, 2)
        return (logits._value, view.k_pools, view.v_pools) + view.slot_state

    sd = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                   sharding=v5e)
    args = [[sd(p._value.shape, jnp.bfloat16) for p in params],
            sd((slots,), jnp.int32), sd((slots, table), jnp.int32),
            sd((slots,), jnp.int32), sd((slots,), jnp.bool_),
            sd(pool, jnp.bfloat16), sd(pool, jnp.bfloat16),
            sd(conv, jnp.bfloat16), sd(delta, jnp.float32)]
    compiled = jax.jit(decode, donate_argnums=(5, 6, 7, 8)).lower(
        *args).compile()
    text = compiled.as_text()
    assert "%kda_decode_step" in text and "paged_decode_attention" in text
    for shape, dtype in ((pool, "bf16"), (conv, "bf16"), (delta, "f32")):
        name = dtype + "[" + ",".join(map(str, shape)) + "]"
        made = _layouts(text, name)
        assert {layout for layout, _ in made} == {
            ",".join(map(str, reversed(range(len(shape)))))}, (name, made)
        opcodes = {opcode for _, opcode in made}
        assert "parameter" in opcodes
        assert not opcodes & {"copy", "concatenate", "pad"}, (name, opcodes)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * (
        2 * math.prod(pool) + math.prod(conv)) + 4 * math.prod(delta)
