"""The tiled grouped matmul kernel (`kernels/pallas/grouped_matmul.py`) in
the Pallas interpreter, held to `jax.lax.ragged_dot` over the same sorted
rows: both compute in float32 at "highest", so they differ only in the
order of a product's sums (TOL of the largest value). What the TPU's
compiler says of the kernel's blocks is `tests/test_tpu_compile.py`'s."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu  # noqa: F401  (x64 mode, as every program runs)
from paddle_tpu import get_flags, set_flags
from paddle_tpu.incubate.distributed.models.moe import held_experts
from paddle_tpu.kernels.pallas import grouped_matmul as gm
from paddle_tpu.profiler.events import clear_fusion_events, fusion_events

TOL = 1e-5


def operands(rows, k, n, experts, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(rows, k)), dtype),
            jnp.asarray(rng.normal(size=(experts, k, n)), dtype))


def both(a, w, load, **tiles):
    load = jnp.asarray(load, jnp.int32)
    with jax.default_matmul_precision("highest"):
        want = jax.lax.ragged_dot(a, w, load,
                                  preferred_element_type=jnp.float32)
        got = gm.grouped_matmul(a, w, load, interpret=True, **tiles)
    assert got.dtype == jnp.float32 and got.shape == want.shape
    return np.asarray(got), np.asarray(want), int(load.sum())


def agree(got, want, live):
    assert np.isfinite(got[:live]).all()
    assert np.abs(got[:live] - want[:live]).max() \
        <= TOL * max(np.abs(want[:live]).max(), 1.0)


@pytest.mark.parametrize("load", [
    [5, 11, 3, 20, 7, 9],           # every boundary inside a row tile
    [16, 16, 16, 16, 0, 0],         # every boundary ON one
    [1, 1, 1, 1, 1, 1],             # six groups in one tile
    [0, 9, 0, 0, 14, 0],            # empty groups first, in the middle, last
    [0, 0, 64, 0, 0, 0],            # one expert holds every row
    [64, 0, 0, 0, 0, 0],
    [0, 0, 0, 0, 0, 64],
], ids=lambda load: "-".join(map(str, load)))
@pytest.mark.parametrize("col_tile", [8, 24], ids=["3_column_tiles", "1"])
def test_the_kernel_is_the_librarys_product_over_the_same_rows(load,
                                                               col_tile):
    a, w = operands(64, 16, 24, len(load))
    got, want, live = both(a, w, load, row_tile=16, col_tile=col_tile)
    agree(got, want, live)


def test_rows_past_the_last_group_reach_no_live_row():
    """A buffer mostly past its groups (a bucket's padding, a share of the
    experts held): those rows POISONED, and nothing of them in a live
    row; the last live tile holds live and poisoned rows side by side."""
    load = [4, 0, 13, 4]
    a, w = operands(128, 16, 24, 4, seed=1)
    a = a.at[21:].set(jnp.nan)
    got, want, live = both(a, w, load, row_tile=16, col_tile=8)
    assert live == 21
    agree(got, want, live)


def test_no_row_at_all_is_no_visit():
    a, w = operands(32, 16, 24, 4)
    offsets, group, tile, n_live = gm.group_visits(
        jnp.zeros(4, jnp.int32), 32, 8)
    assert int(n_live[0]) == 0 and np.asarray(offsets).tolist() == [0] * 5
    got, _, _ = both(a, w, [0, 0, 0, 0], row_tile=8)
    assert got.shape == (32, 24)


@pytest.mark.parametrize("rows", [512, 8192],
                         ids=["a_launch", "a_2048_bucket"])
def test_the_tile_heights_the_shape_rule_picks(rows):
    """The cell's two extremes by their row counts (a launch's 512 rows
    in 32 groups of about 16; a 2,048 bucket's 8,192 holding a prompt of
    1,300 tokens), narrow matrices: the rule's own tiles."""
    experts, k, n = 32, 128, 256
    tm, tn = gm.tiles(rows, k, n)
    assert rows % tm == 0 and n % tn == 0 and tm in gm._ROW_TILES
    live = rows if rows == 512 else 5200
    rng = np.random.default_rng(rows)
    load = np.bincount(rng.integers(0, experts, live), minlength=experts)
    a, w = operands(rows, k, n, experts, seed=2)
    a = a.at[live:].set(jnp.nan)
    got, want, live = both(a, w, load)
    agree(got, want, live)


def test_the_tiles_follow_the_static_shape():
    # the cell's products: whole lane tiles, a weight tile of at most 4 MB
    for rows in (512, 1024, 2048, 4096, 8192):
        for k, n in ((2048, 1792), (1792, 2048)):
            tm, tn = gm.tiles(rows, k, n)
            assert rows % tm == 0 and tm % 16 == 0
            assert n % tn == 0 and tn % 128 == 0
            assert k * tn * 2 <= gm._WEIGHT_TILE_BYTES
    assert gm.tiles(512, 2048, 1792)[1] == 896      # two column tiles
    assert gm.tiles(512, 1792, 2048)[1] == 1024
    assert gm.tiles(512, 6144, 2048)[1] == 256      # LongCat's width
    # off the tiles (the interpreter's sizes): the rows or the columns whole
    assert gm.tiles(100, 16, 24) == (100, 24)
    assert gm.tiles(96, 16, 24) == (32, 24)


@pytest.mark.parametrize("seed", range(6))
def test_the_visits_are_the_row_tiles_each_group_has_a_row_in(seed):
    """`group_visits` against the plain enumeration: (row tile, group)
    pairs with a row of the group in the tile, in row order; the visits
    past the live ones repeat the last, so they copy nothing."""
    rng = np.random.default_rng(seed)
    experts, tm, rows = 7, 8, 96
    live = int(rng.integers(0, rows + 1))
    load = np.bincount(rng.integers(0, experts, live), minlength=experts)
    if seed % 2:
        load[rng.integers(0, experts)] = 0
    offsets, group, tile, n_live = (np.asarray(x) for x in gm.group_visits(
        jnp.asarray(load, jnp.int32), rows, tm))
    assert all(x.dtype == np.int32 for x in (offsets, group, tile, n_live))
    starts = np.concatenate([[0], np.cumsum(load)])
    assert offsets.tolist() == starts.tolist()
    want = [(t, g) for t in range(rows // tm) for g in range(experts)
            if max(starts[g], t * tm) < min(starts[g + 1], (t + 1) * tm)]
    assert int(n_live[0]) == len(want) <= len(group)
    assert len(group) == rows // tm + experts - 1
    got = list(zip(tile.tolist(), group.tolist()))
    assert got[:len(want)] == want
    assert set(got[len(want):]) <= {want[-1] if want else (0, experts - 1)}


@pytest.fixture
def recorder():
    prev = get_flags(["FLAGS_profiler_events"])
    set_flags({"FLAGS_profiler_events": True})
    clear_fusion_events()
    yield
    set_flags(prev)


def test_the_eligibility_table(monkeypatch, recorder):
    """A TPU and a shape on the tiles: the kernel. The CPU: the library's
    product, silently (nothing was refused). A TPU and a shape off the
    tiles: the library's product and ONE `kernel.fallback` event."""
    cell = [(512, 2048, 1792), (512, 1792, 2048), (8192, 2048, 1792),
            (8192, 1792, 2048)]
    clear_fusion_events()
    assert {held_experts.product_kernel(*s, jnp.bfloat16)
            for s in cell} == {"ragged_dot"}
    assert gm.is_eligible(*cell[0]) == (False, "not_on_tpu")
    assert held_experts.products_run(128, 4, 32, 2048, 1792,
                                     jnp.bfloat16) == (3, 0)
    assert fusion_events("kernel.fallback") == []
    monkeypatch.setattr(gm, "_on_tpu", lambda: True)
    assert {held_experts.product_kernel(*s, jnp.bfloat16)
            for s in cell} == {"pallas"}
    assert held_experts.products_run(128, 4, 32, 2048, 1792,
                                     jnp.bfloat16) == (3, 3)
    # 16 held of top 12 resolves to the masked form: no grouped product
    assert held_experts.products_run(128, 12, 16, 6144, 2048,
                                     jnp.bfloat16) == (0, 0)
    assert fusion_events("kernel.fallback") == []
    for shape, dtype, why in [
            ((512, 2048, 1800), jnp.bfloat16, "matrix_not_whole_lane_tiles"),
            ((512, 2000, 1792), jnp.bfloat16, "matrix_not_whole_lane_tiles"),
            ((100, 2048, 1792), jnp.bfloat16, "rows_not_whole_sublane_tiles"),
            ((512, 2048, 1792), jnp.float32, "operands_not_bf16"),
            ((512, 32768, 128), jnp.bfloat16, "weight_tile_exceeds_vmem")]:
        clear_fusion_events()
        assert held_experts.product_kernel(*shape, dtype) == "ragged_dot"
        events = fusion_events("kernel.fallback")
        assert len(events) == 1
        assert events[0]["detail"]["why"] == why
        assert events[0]["detail"]["actual"] == "ragged_dot"
    # the count follows the same rule: gate and up off the tiles, down on
    assert held_experts.products_run(25, 4, 32, 2048, 1792,
                                     jnp.bfloat16) == (3, 0)
