"""Pallas kernel correctness (interpret mode on the CPU mesh)."""

import numpy as np
import pytest


def test_intersect_count_matches_reference():
    import jax.numpy as jnp
    from jax import lax

    from libgrape_lite_tpu.ops.pallas_kernels import intersect_count

    rng = np.random.default_rng(0)
    n, words = 1024, 64
    a = rng.integers(0, 1 << 32, (n, words), dtype=np.uint32)
    b = rng.integers(0, 1 << 32, (n, words), dtype=np.uint32)
    got = np.asarray(
        intersect_count(jnp.asarray(a), jnp.asarray(b), block=256,
                        interpret=True)
    )
    expect = np.asarray(
        lax.population_count(jnp.asarray(a) & jnp.asarray(b)).sum(
            axis=1, dtype=np.int32
        )
    )
    assert np.array_equal(got, expect)


def test_intersect_count_rejects_ragged():
    import jax.numpy as jnp

    from libgrape_lite_tpu.ops.pallas_kernels import intersect_count

    a = jnp.zeros((100, 8), jnp.uint32)
    with pytest.raises(ValueError):
        intersect_count(a, a, block=64, interpret=True)


# ---- the fold's row ends: sorted indices, the table slice by slice ----

# Slices of 16 rows of 128 (2,048 places) and blocks of 8 (1,024
# indices) where the shipped kernel has 8,192 and 32: the same grid,
# at a size the interpreter can afford.
# name -> (dtype, table length, the sorted indices from (rng, length),
# keywords)
SMALL = {"slice_rows": 16, "end_rows": 8}
SPAN = 16 * 128


def _sorted_draw(rng, e, n):
    return np.sort(rng.integers(0, e, n))


ROW_GATHER_CASES = {
    # empty rows repeat the row end before them: runs of hundreds
    "f32_long_runs_of_repeats": (
        "float32", 20 * SPAN,
        lambda rng, e: np.repeat(_sorted_draw(rng, e, 40),
                                 rng.integers(1, 400, 40)), SMALL),
    # one block's ends jump over nine slices (a hub's row)
    "s32_a_row_spans_slices": (
        "int32", 20 * SPAN,
        lambda rng, e: np.concatenate([
            _sorted_draw(rng, 3 * SPAN, 700),
            12 * SPAN + _sorted_draw(rng, 8 * SPAN, 2500)]), SMALL),
    "f32_all_ends_in_one_slice": (
        "float32", 20 * SPAN,
        lambda rng, e: 7 * SPAN + _sorted_draw(rng, SPAN, 3000), SMALL),
    "s32_first_and_last_slice_only": (
        "int32", 20 * SPAN,
        lambda rng, e: np.concatenate([
            _sorted_draw(rng, SPAN, 1500),
            19 * SPAN + _sorted_draw(rng, SPAN, 1500)]), SMALL),
    # 3,333 indices: the last block ragged and not whole 128s; 45 rows
    # of table: the last slice ragged
    "f32_ragged_last_block_and_slice": (
        "float32", 45 * 128, lambda rng, e: _sorted_draw(rng, e, 3333),
        SMALL),
    "s32_stream_shorter_than_a_slice": (
        "int32", 5 * 128, lambda rng, e: _sorted_draw(rng, e, 2000), SMALL),
    "f32_fewer_ends_than_a_block": (
        "float32", 20 * SPAN, lambda rng, e: _sorted_draw(rng, e, 100),
        SMALL),
    # the shipped sizes: three slices of 4 MiB, three blocks of 4,096
    "s32_shipped_sizes": (
        "int32", 2 * 8192 * 128 + 1000 * 128,
        lambda rng, e: _sorted_draw(rng, e, 10000), {}),
}


def _row_ends_of(name):
    """`(rng, the case's sorted int32 indices)`, the rng ready for the
    table's draw."""
    _, e, draw, _ = ROW_GATHER_CASES[name]
    rng = np.random.default_rng(e + len(name))
    return rng, draw(rng, e).astype(np.int32)


def test_row_gather_cases_cover_the_grid():
    """The cases are what their names say (a check of the test's own
    data: order and bounds, slices touched, repeats)."""
    touched = {}
    for name, (_, e, _, _) in ROW_GATHER_CASES.items():
        idx = _row_ends_of(name)[1]
        assert (np.diff(idx) >= 0).all() and 0 <= idx[0] and idx[-1] < e
        touched[name] = set((idx // SPAN).tolist()), idx
    assert len(touched["f32_all_ends_in_one_slice"][0]) == 1
    assert touched["s32_first_and_last_slice_only"][0] == {0, 19}
    hub = touched["s32_a_row_spans_slices"][1]
    assert np.diff(hub // SPAN).max() >= 9
    runs = touched["f32_long_runs_of_repeats"][1]
    assert np.unique(runs).size <= 40 < runs.size // 50


@pytest.mark.parametrize("case", sorted(ROW_GATHER_CASES))
def test_row_gather_bit_equal(case):
    """`vmem_row_gather` is `table[idx]`, bit for bit, for sorted
    in-bounds indices, however the ends fall into the slices."""
    import jax.numpy as jnp

    from libgrape_lite_tpu.ops.pallas_kernels import vmem_row_gather

    dtype, e, _, sizes = ROW_GATHER_CASES[case]
    rng, idx = _row_ends_of(case)
    if dtype == "float32":
        table = rng.standard_normal(e).astype(dtype)
    else:
        table = rng.integers(-2**31, 2**31 - 1, e).astype(dtype)
    got = np.asarray(vmem_row_gather(
        jnp.asarray(table), jnp.asarray(idx), interpret=True, **sizes))
    assert got.dtype == table.dtype and got.shape == idx.shape
    assert got.tobytes() == table[idx].tobytes()


# ---- the scan's first level: seven steps a tile, one pass ----

T = 128
# Blocks of 16 tiles and chunks of 8 where the shipped kernel has 2,048
# and 32: the same grid and loop, at the interpreter's size.
# name -> (row degrees, tiles, keywords); places behind the last row
# are padding with id = the row count
SCAN_SMALL = {"block_rows": 16, "chunk": 8}
TILE_SCAN_SHAPES = {
    "rows_inside_a_tile": ([3, 1, 0, 40, 7, 0, 0, 25, 30, 9, 2], 1,
                           SCAN_SMALL),
    "rows_span_tiles_and_blocks": (
        [50, 3 * T + 40, 7, 20 * T + 3, 1, 0, 15 * T, 90], 48, SCAN_SMALL),
    "a_row_fills_a_tile": ([T, T, 60, T - 60, T, 2 * T], 7, SCAN_SMALL),
    "padding_ids_at_the_end": ([9, 0, 33, 70], 5, SCAN_SMALL),
    "one_tile": ([40, 0, 50, 20], 1, SCAN_SMALL),
    # 37 tiles in blocks of 16: the last block holds five
    "ragged_last_block": ([11] * 300 + [9 * T + 5, 1, 1], 37, SCAN_SMALL),
    "degree_one": ([1] * (3 * T), 3, SCAN_SMALL),
    # the shipped sizes: two blocks of 2,048 tiles and a ragged third
    "shipped_sizes": ([700] * 700 + [40 * T] + [3] * 1000, 4500, {}),
}
TILE_SCAN_FOLDS = [("sum", "float32"), ("min", "int32"), ("min", "float32"),
                   ("max", "int32")]


def _scan_stream(shape):
    deg, tiles, _ = TILE_SCAN_SHAPES[shape]
    ids = np.full(tiles * T, len(deg), np.int32)
    filled = int(np.sum(deg))
    assert filled <= ids.size
    ids[:filled] = np.repeat(np.arange(len(deg), dtype=np.int32), deg)
    return ids.reshape(-1, T)


@pytest.mark.parametrize("shape,kind,dtype", [
    (s, k, d) for s in sorted(TILE_SCAN_SHAPES) for k, d in TILE_SCAN_FOLDS
    # the shipped sizes once a kind of value
    if s != "shipped_sizes" or (k, d) in TILE_SCAN_FOLDS[:2]])
def test_tile_scan_bit_equal(shape, kind, dtype):
    """`tile_scan` is the first level of `_segmented_scan` as XLA's
    seven steps write it, bit for bit: the same distances in the same
    order with the same operands, so a float sum keeps its grouping."""
    import jax.numpy as jnp

    from libgrape_lite_tpu.ops import segment
    from libgrape_lite_tpu.ops.pallas_kernels import tile_scan

    ids = _scan_stream(shape)
    rng = np.random.default_rng(ids.size + len(shape))
    if dtype == "float32":
        # magnitudes far apart: a regrouped sum would show
        vals = (rng.standard_normal(ids.shape)
                * 10.0 ** rng.integers(-3, 6, ids.shape)).astype(dtype)
    else:
        vals = rng.integers(-2**31, 2**31 - 1, ids.shape).astype(dtype)
    _, combine, ident = segment._FOLDS[kind]
    want = np.asarray(segment._tile_steps(
        jnp.asarray(vals), jnp.asarray(ids), combine,
        ident(jnp.dtype(dtype))))
    got = np.asarray(tile_scan(jnp.asarray(vals), jnp.asarray(ids), combine,
                               interpret=True, **TILE_SCAN_SHAPES[shape][2]))
    assert got.dtype == vals.dtype and got.shape == vals.shape
    assert got.tobytes() == want.tobytes()
    if shape == "rows_span_tiles_and_blocks":
        # the steps did something: a tile of one row holds its scan
        row = vals[30]
        assert (ids[30] == ids[30, 0]).all()
        if kind != "sum":
            np.testing.assert_array_equal(
                got[30], getattr(np, kind + "imum").accumulate(row))
