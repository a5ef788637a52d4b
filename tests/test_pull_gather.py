"""The pull's gather by kernel or by XLA (`ops/segment.pull_gather`).

On the TPU backend a 1-D 32-bit table that fits the VMEM budget is
gathered by `ops/pallas_kernels.vmem_gather`; everything else by XLA's
`full[nbr]`.  Query lanes of a batched call under `jax.vmap` take what
their single call takes: the kernel, one lane after another, where the
lanes share their indices, `full[nbr]` everywhere else.
Pinned here: the kernel (interpret mode) bit-equal to `full[nbr]` over
the dtypes, table lengths and stream lengths it meets, on every kind of
int32 index; the choice, through the trace-time counter
`GATHER_STATS`; the `vmap` rule (kernel lanes are handed a lane's 1-D
table each and answer with their single calls' bytes; every other lane
lowers to the text it lowered to before; a call counts once).
Whole queries through the kernel, app by app, are in
tests/test_pull_gather_apps.py (a file of its own so that another
worker takes it).  The choice reads the backend, so the tests steer it
(`pull_kernel`, tests/conftest.py).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from libgrape_lite_tpu.ops import pallas_kernels, segment
from libgrape_lite_tpu.ops.segment import pull_gather
from tests.conftest import GATHER_BUDGET as BUDGET, gather_took

# name -> (dtype, table length, stream length).  A CSR's stream is
# whole 128s (the loader's rule) but not whole 1024s on a shard; the
# dyn overlay's may be anything.
KERNEL_CASES = {
    "f32_whole_1024s": ("float32", 40 * 128, 2 * 1024),
    "s32_table_ragged_whole_128s": ("int32", 5000, 5 * 128),
    "u32_both_ragged": ("uint32", 777, 1000),
    "f32_one_row_table": ("float32", 128, 17 * 128),
    # two grid steps, the second ragged: 300 rows of 128 in blocks of
    # 256; the table must still be there in the second
    "s32_two_steps": ("int32", 3000, 300 * 128),
}


@functools.lru_cache(maxsize=None)
def _kernel_case(name: str):
    dtype, v, n = KERNEL_CASES[name]
    rng = np.random.default_rng(v + n)
    if dtype == "float32":
        full = rng.standard_normal(v).astype(dtype)
    else:
        full = rng.integers(0, np.iinfo(dtype).max, v).astype(dtype)
    nbr = rng.integers(0, v, n).astype(np.int32)
    # both ends, a tile's edge, the loader's padding value (0), and
    # what `full[nbr]` wraps or clamps
    edge = np.asarray([0, v - 1, 127, 128 % v, -1, -v, -v - 1, v, v + 5,
                       np.iinfo(np.int32).max, np.iinfo(np.int32).min],
                      np.int32)
    nbr[:edge.size] = edge
    nbr[-1] = v - 1
    got = pallas_kernels.vmem_gather(full, nbr, interpret=True)
    return full, nbr, edge.size, np.asarray(got)


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_bit_equal_in_bounds(case):
    full, nbr, edges, got = _kernel_case(case)
    assert got.dtype == full.dtype and got.shape == nbr.shape
    assert got[edges:].tobytes() == full[nbr[edges:]].tobytes()


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_follows_xla_at_the_edges(case):
    """Negative indices count from the end and what is still outside
    is clamped, as `full[nbr]` has it: the two agree on every int32."""
    full, nbr, edges, got = _kernel_case(case)
    want = np.asarray(jnp.asarray(full)[jnp.asarray(nbr[:edges])])
    assert got[:edges].tobytes() == want.tobytes()
    v = full.shape[0]
    assert got[0] == full[0] and got[1] == full[v - 1]
    assert got[4] == full[v - 1] and got[5] == full[0]


# ---- the choice -----------------------------------------------------------

@pytest.fixture
def on_tpu(pull_kernel):
    """`pull_gather` as it chooses on the TPU backend; the kernel is a
    stand-in that notes its calls (the choice is what is under test)."""
    return pull_kernel("stand_in")


CHOICES = {
    # name: (table shape, table dtype, index dtype, what it takes)
    "f32": ((4096,), "float32", "int32", "kernel"),
    "s32": ((4096,), "int32", "int32", "kernel"),
    "u32": ((4096,), "uint32", "int32", "kernel"),
    "at_the_budget": ((BUDGET // 4,), "float32", "int32", "kernel"),
    "over_the_budget": ((BUDGET // 4 + 1,), "float32", "int32", "xla"),
    "f64": ((4096,), "float64", "int32", "xla"),
    "s64": ((4096,), "int64", "int32", "xla"),
    "bool": ((4096,), "bool", "int32", "xla"),
    "f16": ((4096,), "float16", "int32", "xla"),
    "rows_of_four": ((4096, 4), "float32", "int32", "xla"),
    "i64_indices": ((4096,), "float32", "int64", "xla"),
}


@pytest.mark.parametrize("name", sorted(CHOICES))
def test_choice_on_tpu(name, on_tpu):
    shape, dtype, idtype, want = CHOICES[name]
    full = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    nbr = jax.ShapeDtypeStruct((512,), jnp.dtype(idtype))
    took = gather_took(lambda: jax.eval_shape(
        lambda f, i: pull_gather(f, i), full, nbr))
    assert took == {"kernel": 0, "xla": 0, want: 1}
    assert len(on_tpu) == (want == "kernel")


@pytest.mark.parametrize("name", ["f32", "s32", "u32", "f64"])
def test_choice_off_tpu_is_xla(name, monkeypatch):
    """Every other backend keeps the parent's program, to the text."""
    shape, dtype, idtype, _ = CHOICES[name]
    monkeypatch.setattr(
        segment, "vmem_gather",
        lambda *a: pytest.fail("the kernel off the TPU backend"))
    full = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    nbr = jax.ShapeDtypeStruct((512,), jnp.dtype(idtype))
    mask = jax.ShapeDtypeStruct((512,), jnp.bool_)
    took = gather_took(lambda: jax.eval_shape(
        lambda f, i: pull_gather(f, i), full, nbr))
    assert took == {"kernel": 0, "xla": 1}

    def parent(full, nbr, mask):
        with jax.named_scope("grape.pull.gather"):
            return jnp.where(mask, full[nbr] + 1, 0)

    def text(pull):
        return jax.jit(lambda *a: pull(*a)).lower(full, nbr, mask).as_text()

    assert text(lambda f, i, m: pull_gather(f, i, m, 0, add=1)) == text(parent)


@pytest.mark.parametrize("block,want", [
    ((4, 128), "kernel"), ((2, 3, 64), "kernel"),
    ((), "xla"), ((4, 0), "xla"),
])
def test_choice_reads_an_index_block_as_its_stream(block, want, on_tpu):
    """`table_gather` handed a block of indices (the mirror exchange's
    `[fnum, m]` send table): the kernel reads it as the 1-D stream it
    is in memory and the values get the block's shape back; a scalar
    index and an empty block keep `full[nbr]`."""
    from libgrape_lite_tpu.ops.segment import table_gather

    rng = np.random.default_rng(9)
    full = jnp.asarray(rng.standard_normal(1000).astype(np.float32))
    nbr = jnp.asarray(rng.integers(0, 1000, block).astype(np.int32))
    out = []
    took = gather_took(lambda: out.append(table_gather(full, nbr)))
    assert took == {"kernel": 0, "xla": 0, want: 1}
    assert on_tpu == ([("float32", (1000,), (nbr.size,))]
                      if want == "kernel" else [])
    assert out[0].shape == block
    assert np.asarray(out[0]).tobytes() == np.asarray(full[nbr]).tobytes()


def test_an_index_block_off_tpu_is_the_parents_text():
    """Off the TPU backend a block's gather is `full[nbr]` as written:
    no reshape beside it."""
    from libgrape_lite_tpu.ops.segment import table_gather

    full = jax.ShapeDtypeStruct((4096,), jnp.float32)
    nbr = jax.ShapeDtypeStruct((4, 256), jnp.int32)

    def text(gather):
        return jax.jit(lambda *a: gather(*a)).lower(full, nbr).as_text()

    assert text(table_gather) == text(lambda f, i: f[i])


def test_empty_stream_is_xla(on_tpu):
    full = jnp.arange(256, dtype=jnp.float32)
    took = gather_took(lambda: pull_gather(full, jnp.zeros((0,), jnp.int32)))
    assert took == {"kernel": 0, "xla": 1}


ARGS = {
    "bare": {},
    "mask_fill": {"mask": True, "fill": 7},
    "add": {"add": 3},
    "absent": {"absent": 5, "fill": -1},
    "mask_absent_add": {"mask": True, "absent": 5, "add": 1, "fill": 9},
}


@pytest.mark.parametrize("args", sorted(ARGS))
def test_what_follows_the_gather_is_the_same(args, on_tpu, monkeypatch):
    """`mask`, `add`, `absent` and `fill` act on the kernel's output as
    they acted on XLA's."""
    rng = np.random.default_rng(3)
    full = jnp.asarray(rng.integers(0, 9, 640).astype(np.int32))
    nbr = jnp.asarray(rng.integers(0, 640, 1024).astype(np.int32))
    kw = dict(ARGS[args])
    if kw.pop("mask", False):
        kw["mask"] = jnp.asarray(rng.integers(0, 2, 1024).astype(bool))
    got = jax.jit(lambda f, i: pull_gather(f, i, **kw))(full, nbr)
    assert len(on_tpu) == 1
    monkeypatch.setattr(segment, "use_pallas", lambda: False)
    want = pull_gather(full, nbr, **kw)
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


# ---- query lanes ----------------------------------------------------------


def _lane_args(dtype, lanes=4, v=640, n=1024, seed=5):
    rng = np.random.default_rng(seed)
    full = jnp.asarray(rng.integers(0, 99, (lanes, v)).astype(dtype))
    nbr = jnp.asarray(rng.integers(0, v, n).astype(np.int32))
    mask = jnp.asarray(rng.integers(0, 2, n).astype(bool))
    return full, nbr, mask


@pytest.mark.parametrize("dtype,kind", [
    ("float32", "stand_in"), ("int32", "stand_in"),
    ("int32", "interpreted"),
])
def test_lanes_take_the_kernel_lane_by_lane(dtype, kind, pull_kernel):
    """Under `jax.vmap` lanes that share their indices take the single
    call's kernel one after another: every call of it is handed one
    lane's 1-D table and the shared stream, the loop is one traced
    body, the call stays `kernel` in GATHER_STATS, and each lane has
    its single call's bytes."""
    calls = pull_kernel(kind)
    full, nbr, mask = _lane_args(dtype)

    def one(f):
        return pull_gather(f, nbr, mask, jnp.asarray(0, f.dtype), add=1)

    took = gather_took(lambda: jax.jit(jax.vmap(one)).lower(full))
    assert took == {"kernel": 1, "xla": 0}
    assert calls and set(calls) == {(dtype, (640,), (1024,))}
    text = jax.jit(jax.vmap(one)).lower(full).as_text()
    assert "stablehlo.while" in text  # a loop over the lanes, not four copies
    got = np.asarray(jax.jit(jax.vmap(one))(full))
    want = np.stack([np.asarray(jax.jit(one)(f)) for f in full])
    assert got.dtype == want.dtype and got.tobytes() == want.tobytes()


def _parent_lanes(full, nbr, mask):
    """The batched call as the parent lowered it: `full[nbr]` under
    `jax.vmap`, for XLA to fuse into the lanes' fold."""
    axes = tuple(0 if x.ndim == 2 else None for x in (full, nbr))

    def one(f, i):
        with jax.named_scope("grape.pull.gather"):
            return jnp.where(mask, f[i] + 1, jnp.asarray(0, f.dtype))

    return jax.vmap(one, in_axes=axes)(full, nbr)


# name: (table dtype, lanes bring their own indices, armed, budget)
XLA_LANES = {
    "own_indices": ("float32", True, True, BUDGET),
    "own_indices_shared_table": ("int32", "only", True, BUDGET),
    "f64": ("float64", False, True, BUDGET),
    "s64": ("int64", False, True, BUDGET),
    "over_the_budget": ("float32", False, True, 640 * 4 - 1),
    "off_tpu": ("float32", False, False, BUDGET),
}


@pytest.mark.parametrize("name", sorted(XLA_LANES))
def test_lanes_that_keep_xla_lower_to_the_parents_text(name, pull_kernel,
                                                       monkeypatch):
    """Lanes with their own indices, 64-bit tables, tables over the
    budget and every other backend: `full[nbr]` under `jax.vmap`, to
    the lowered text, counted once as `xla`, the kernel never called."""
    dtype, own, armed, budget = XLA_LANES[name]
    if armed:
        calls = pull_kernel("stand_in")
        monkeypatch.setattr(segment, "gather_table_budget", lambda: budget)
    else:
        calls = []
        monkeypatch.setattr(
            segment, "vmem_gather",
            lambda *a: pytest.fail("the kernel off the TPU backend"))
    full, nbr, mask = _lane_args(dtype)
    if own:
        nbr = jnp.stack([nbr, nbr[::-1], (nbr + 1) % 640, nbr])
    if own == "only":
        full = full[0]
    axes = tuple(0 if x.ndim == 2 else None for x in (full, nbr))

    def lanes(full, nbr, mask):
        return jax.vmap(
            lambda f, i: pull_gather(f, i, mask, jnp.asarray(0, f.dtype),
                                     add=1),
            in_axes=axes)(full, nbr)

    took = gather_took(
        lambda: jax.jit(lanes).lower(full, nbr, mask))
    assert took == {"kernel": 0, "xla": 1}
    # (where the single call chooses the kernel, `custom_vmap` traces
    # it once on the lane's shapes before the rule is asked)
    assert len(calls) == bool(own)

    def parent(full, nbr, mask):
        return _parent_lanes(full, nbr, mask)

    parent.__name__ = parent.__qualname__ = "lanes"
    assert (jax.jit(lanes).lower(full, nbr, mask).as_text()
            == jax.jit(parent).lower(full, nbr, mask).as_text())
    got = np.asarray(jax.jit(lanes)(full, nbr, mask))
    assert got.tobytes() == np.asarray(parent(full, nbr, mask)).tobytes()


@pytest.mark.parametrize("own,want", [
    (False, {"kernel": 1, "xla": 0}), (True, {"kernel": 0, "xla": 1}),
], ids=["shared_indices", "own_indices"])
def test_lanes_rule_moves_the_count_once(own, want, on_tpu):
    """A loop's batching rule may run the `vmap` rule again for one
    call: lanes that share their indices stay `kernel` however often
    it runs, and lanes with their own move to `xla` once."""
    full = jnp.zeros((4, 640), jnp.float32)
    nbr = jnp.zeros((4, 1024) if own else (1024,), jnp.int32)

    def rounds(f, i):
        return jax.lax.fori_loop(
            0, 3, lambda _, x: x + pull_gather(x, i)[:640], f)

    took = gather_took(lambda: jax.jit(jax.vmap(
        rounds, in_axes=(0, 0 if own else None))).lower(full, nbr))
    assert took == want
    assert set(on_tpu) == {("float32", (640,), (1024,))}


def test_batched_indices_take_xla(on_tpu):
    """Lanes that bring their own indices (no caller does) still get
    `full[nbr]`, lane by lane."""
    rng = np.random.default_rng(7)
    full = jnp.asarray(rng.standard_normal(640).astype(np.float32))
    nbr = jnp.asarray(rng.integers(0, 640, (3, 1024)).astype(np.int32))
    got = jax.vmap(lambda i: pull_gather(full, i))(nbr)
    assert np.asarray(got).tobytes() == np.asarray(full[nbr]).tobytes()
