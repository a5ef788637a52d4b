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
