"""Grouped matrix product over rows sorted by expert: each group of rows
against its own expert's matrix, the served expert block's three products
(`incubate/distributed/models/moe/held_experts.py grouped_products`).

`jax.lax.ragged_dot` costs a decode launch's 512 rows in 32 groups of
about 16 the same 0.85 ms whatever the bytes (`PERF.md` section 4); the
work is the experts' bytes once and the rows' products once. This is the
tiled kernel that does that much and no more:

  * rows a ``[rows, k]`` sorted by group, stacked matrices w ``[E, k, n]``,
    `load` ``[E]`` int32 the groups' sizes in expert order; the result
    ``[rows, n]`` float32, the operands as they are into the matrix unit
    and float32 accumulation: `ragged_dot(a, w, load,
    preferred_element_type=float32)`'s contract.
  * a grid step multiplies ONE row tile ``[tm, k]`` by ONE column tile
    ``[k, tn]`` of ONE expert. Which expert and which row tile a step
    takes is computed in the program from `load` (`group_visits`) and
    rides as scalar prefetch; the weight tile's `index_map` picks the
    expert from it, as `_ragged_decode_kernel` picks pages from the block
    table. Groups are not tile-aligned: a row tile that spans a boundary
    is VISITED once for each group that has rows in it, and a visit keeps
    only its own group's rows (the scheme of
    `jax.experimental.pallas.ops.tpu.megablox`, which does not compile
    under this framework's x64 mode; every scalar and constant here is
    explicitly 32-bit).
  * the grid is (column tiles, visits), visits innermost and in row
    order, k whole: consecutive visits of one expert find its column
    tile in VMEM and copy nothing, so each expert some row chose is read
    ONCE a product, an expert no row chose is never read, and the rows
    are read once a column tile. Visits past the last group's (row tiles
    no group reaches, and the slack of the static bound) repeat the last
    live visit's block indices, so they copy nothing, and multiply
    nothing (`pl.when`): what the result holds there is whatever the
    memory held, as `ragged_dot`'s on the chip; the caller cuts it off.
  * the tiles follow the call's static shape (`tiles`): the row tile the
    largest of `_ROW_TILES` that divides the rows, the column tile the
    widest split of n into whole 128-lane tiles whose double-buffered
    weight tile stays inside the default scoped VMEM.

`is_eligible` says where it runs compiled (a TPU, bf16 operands, k, n and
the rows on the tiles); ``interpret=True`` runs it through the Pallas
interpreter on any backend (the CPU parity path). `PERF.md` section 4 has
the chip's readings beside the library product's.
"""
from __future__ import annotations

import functools

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

__all__ = ["grouped_matmul", "group_visits", "tiles", "is_eligible"]

# row-tile heights, tallest first: a call takes the tallest that divides
# its rows. 128 at a decode launch's 512 rows and at a 2,048 bucket's 8,192
# alike: the launch is bound by the experts' bytes at any height to 256,
# and a taller tile costs the bucket in boundary rows (a tile is multiplied
# once for each group in it) what it gains in the matrix unit (PERF.md
# section 4 has the chip's readings of 16 to 512 at both ends)
_ROW_TILES = (128, 64, 32, 16)
# one weight tile's bytes at most: two of them (the pipeline's buffers)
# beside two row tiles and two float32 result tiles stay inside the 16 MB
# a kernel may use of VMEM without asking
_WEIGHT_TILE_BYTES = 4 * 1024 * 1024
_LANES, _SUBLANES_BF16 = 128, 16


def tiles(rows, k, n, itemsize=2):
    """``(tm, tn)`` of a call's static shape: the tallest row tile of
    `_ROW_TILES` that divides `rows` (the rows whole where none does),
    and the fewest column tiles of whole lanes that divide n with a
    weight tile ``[k, tn]`` of at most `_WEIGHT_TILE_BYTES` (n whole
    where it is off the lanes)."""
    tm = next((t for t in _ROW_TILES if rows % t == 0), rows)
    lanes = 1 if n % _LANES else n // _LANES
    parts = next((p for p in range(1, lanes + 1) if lanes % p == 0
                  and k * (n // p) * itemsize <= _WEIGHT_TILE_BYTES), lanes)
    return tm, n // parts


def is_eligible(rows, k, n, dtype=jnp.bfloat16):
    """Can the kernel run compiled (non-interpret) here, over `rows` rows
    of `dtype` against matrices ``[k, n]``? Returns (ok, why): `why` is
    the attribution detail of the `kernel.fallback` flight-recorder event
    when a TPU's call goes to `jax.lax.ragged_dot` for its shape. The
    shape's part is what the v5e compiler accepts
    (tests/test_tpu_compile.py): bf16 operands, k and n whole lane tiles,
    the rows whole row tiles."""
    if not _HAS_PALLAS:
        return False, "no_pallas"
    if not _on_tpu():
        return False, "not_on_tpu"
    if jnp.dtype(dtype) != jnp.bfloat16:
        return False, "operands_not_bf16"
    if k % _LANES or n % _LANES:
        return False, "matrix_not_whole_lane_tiles"
    if rows % _SUBLANES_BF16:
        return False, "rows_not_whole_sublane_tiles"
    if k * tiles(rows, k, n)[1] * 2 > _WEIGHT_TILE_BYTES:
        return False, "weight_tile_exceeds_vmem"
    return True, None


def group_visits(load, rows, tm):
    """The grid's plan from the groups' sizes, every value int32: a VISIT
    is one (row tile, group) pair with a row of the group in the tile, in
    row order. Returns ``(offsets [E + 1], group [V], tile [V], live
    [1])``: the row each group starts at; each visit's group and row
    tile; how many visits are live. ``V = rows / tm + E - 1`` bounds them
    whatever the routing (a group adds a visit only where it starts
    inside a tile); the visits past `live` repeat the last live one, so a
    grid step that takes them copies nothing new."""
    e = load.shape[0]
    n_visits = rows // tm + e - 1
    load = load.astype(jnp.int32)
    tm32 = np.int32(tm)
    ends = jnp.cumsum(load, dtype=jnp.int32)
    starts = ends - load
    first = starts // tm32                         # the tile a group starts in
    spans = jnp.where(load > 0, (ends - np.int32(1)) // tm32 - first
                      + np.int32(1), np.int32(0))
    upto = jnp.cumsum(spans, dtype=jnp.int32)      # visits through group g
    live = upto[-1:]
    v = jnp.minimum(jnp.arange(n_visits, dtype=jnp.int32),
                    jnp.maximum(live - np.int32(1), np.int32(0)))
    # the group of visit v: how many groups' visits all lie before it
    group = jnp.minimum(
        jnp.sum(upto[None, :] <= v[:, None], axis=1, dtype=jnp.int32),
        np.int32(e - 1))
    tile = first[group] + v - (upto[group] - spans[group])
    # (sizes that sum past the buffer must not send a copy outside it)
    tile = jnp.clip(tile, np.int32(0), np.int32(rows // tm - 1))
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group, tile, live


def _visit_kernel(offsets_ref, group_ref, tile_ref, live_ref, a_ref, w_ref,
                  o_ref, *, tm):
    """One visit: the row tile times the visit's expert's column tile, of
    which the result keeps the rows of the visit's own group; the other
    rows of the tile are other visits' (or no one's: past the groups)."""
    v = pl.program_id(1)

    @pl.when(v < live_ref[0])
    def _live():
        g = group_ref[v]
        # 32-bit constants: under the framework's x64 mode a Python number
        # traces as 64 bits, which Mosaic cannot legalize
        row = tile_ref[v] * np.int32(tm) + jax.lax.broadcasted_iota(
            jnp.int32, o_ref.shape, 0)
        own = (row >= offsets_ref[g]) & (row < offsets_ref[g + np.int32(1)])
        y = jnp.dot(a_ref[...], w_ref[...],
                    preferred_element_type=jnp.float32)
        o_ref[...] = jnp.where(own, y, o_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret", "row_tile",
                                             "col_tile"))
def grouped_matmul(a, w, load, interpret=False, row_tile=None,
                   col_tile=None):
    """``ragged_dot(a, w, load, preferred_element_type=float32)`` by the
    tiled kernel: a ``[rows, k]`` sorted by group, w ``[E, k, n]``, load
    ``[E]`` the groups' sizes; ``[rows, n]`` float32. The rows past the
    last group hold whatever the memory held. `row_tile` / `col_tile`
    default to `tiles`' (the chip's micro-calls pass others).

    Jitted, so a program traces and lowers ONE kernel for all its layers'
    calls of a shape (gate and up share one, down has its own), as
    `pallas_paged_attention`."""
    rows, k = a.shape
    e, _, n = w.shape
    tm, tn = tiles(rows, k, n, jnp.dtype(w.dtype).itemsize)
    tm, tn = int(row_tile or tm), int(col_tile or tn)
    plan = group_visits(load, rows, tm)
    zero = _ZERO

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n // tn, plan[1].shape[0]),
        in_specs=[
            pl.BlockSpec((tm, k), lambda j, v, off, grp, til, live:
                         (til[v], zero)),
            pl.BlockSpec((None, k, tn), lambda j, v, off, grp, til, live:
                         (grp[v], zero, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda j, v, off, grp, til, live:
                               (til[v], j)))
    return pl.pallas_call(
        functools.partial(_visit_kernel, tm=tm), grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ragged_expert_matmul")(*plan, a, w)
