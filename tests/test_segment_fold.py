"""The sorted fold without a scatter (`ops/segment.segment_reduce`).

Handed a CSR's row offsets, the fold is a tile-segmented scan and one
gather of the row ends; without them it is `jax.ops.segment_*`, as it
always was.  Under `jax.vmap` the query lanes of a fold scan one lane
after another where their gather was the kernel's (the TPU backend,
32-bit values, a table within the kernel's budget); elsewhere an exact
fold's lanes keep the scatter XLA fuses their gather into and a float
sum's lanes scan.  Pinned here: the scan against the scatter
(byte-equal for integers, min and max; float sums against an f64 NumPy
fold) over the shapes a CSR takes; which fold lanes take, armed and
not, bit for bit their single calls; that lanes the kernel does not
serve lower to the text they lowered to before; that `row_ptr`
with unsorted ids is refused; that a call without
`row_ptr` lowers to the text it lowered to before; and, through the
trace-time counter `FOLD_STATS`, which fold each app's round takes,
PageRank's being this one and no other whatever the backend says.
Since PR 47 also the scan's first level: by the tile-scan kernel where
the choice is steered as the TPU backend steers it, with the scatter's
answer and the XLA steps' bytes, counted once a call site in
`SCAN_STATS`; by XLA's seven steps, in the text they lowered to before,
everywhere else.
"""

import jax
import jax.numpy as jnp
import jax.ops as jops
import numpy as np
import pytest

from libgrape_lite_tpu.models import APP_REGISTRY
from libgrape_lite_tpu.ops.segment import (
    FOLD_STATS,
    ROW_END_STATS,
    SCAN_STATS,
    SCAN_TILE,
    segment_reduce,
    segment_top_label,
)
from libgrape_lite_tpu.worker.worker import Worker

T = SCAN_TILE

# name -> (row degrees, Ep): the shapes a CSR takes.  Places behind the
# last row are padding with id = num_rows.
SHAPES = {
    "empty_rows": ([0, 3, 0, 0, 130, 0, 5, 0], 2 * T),
    "hub_three_tiles": ([2, 1, 3 * T + 40, 7, 1], 4 * T),
    # 130 whole tiles of one row: its partials fill more than one tile
    # one level up, so a third level carries them
    "hub_two_levels": ([5, 130 * T + 9, 3, 0, 11], 131 * T),
    "row_ends_on_last_lane": ([100, 28, T, 60, 3 * T - 60, 1], 6 * T),
    "pad_tail": ([9, 0, 33, 70], 3 * T),
    "one_tile": ([40, 0, 50, 20], T),
    "degree_one": ([1] * (2 * T), 2 * T),
}

FOLDS = [(k, d) for k in ("sum", "min", "max")
         for d in ("float32", "int32", "float64")] + [("prod", "int32")]

_SCATTER = {"sum": jops.segment_sum, "min": jops.segment_min,
            "max": jops.segment_max, "prod": jops.segment_prod}


def _csr(shape: str):
    deg, ep = SHAPES[shape]
    deg = np.asarray(deg, np.int64)
    rows = len(deg)
    ptr = np.zeros(rows + 1, np.int32)
    ptr[1:] = np.cumsum(deg)
    assert ptr[-1] <= ep and ep % T == 0
    ids = np.full(ep, rows, np.int32)
    ids[:ptr[-1]] = np.repeat(np.arange(rows, dtype=np.int32), deg)
    return rows, ptr, ids


def _values(kind: str, dtype: str, shape, seed: int):
    rng = np.random.default_rng(seed)
    if kind == "prod":  # exact under any grouping
        return rng.choice(np.asarray([-1, 1], dtype), shape)
    if dtype == "int32":
        return rng.integers(-1000, 1000, shape).astype(dtype)
    return rng.uniform(1.0, 100.0, shape).astype(dtype)


@pytest.mark.parametrize("lanes,armed", [(None, False), (4, False),
                                         (4, True)],
                         ids=["single", "vmap4", "vmap4_armed"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("kind,dtype", FOLDS)
def test_scan_fold_matches_the_scatter(kind, dtype, shape, lanes, armed,
                                       pull_kernel):
    if armed:  # as on the TPU backend: 32-bit lanes scan one by one
        pull_kernel("stand_in")
    rows, ptr, ids = _csr(shape)
    ep = ids.shape[0]
    vals = _values(kind, dtype, (ep,) if lanes is None else (lanes, ep),
                   seed=ep + rows)

    def scan_one(v):
        return segment_reduce(v, jnp.asarray(ids), rows, kind,
                              row_ptr=jnp.asarray(ptr))

    def scatter(v):
        return segment_reduce(v, jnp.asarray(ids), rows, kind)

    scan = scan_one
    if lanes is not None:
        scan, scatter = jax.vmap(scan_one), jax.vmap(scatter)
    float_sum = kind == "sum" and dtype != "int32"
    before = FOLD_STATS.snapshot()
    got = np.asarray(jax.jit(scan)(vals))
    # lanes over one CSR scan where their gather is the kernel's; where
    # it is not, an exact fold keeps the scatter and a float sum scans
    lanes_scan = float_sum or (armed and dtype != "float64")
    took = "scan" if lanes is None or lanes_scan else "scatter"
    assert FOLD_STATS.snapshot() == {**before, took: before[took] + 1}
    want = np.asarray(jax.jit(scatter)(vals))
    assert got.dtype == want.dtype and got.shape == want.shape
    if lanes_scan and lanes is not None:
        # each lane with the bytes of its own single call
        one = jax.jit(scan_one)
        assert got.tobytes() == np.stack(
            [np.asarray(one(v)) for v in vals]).tobytes()
    if float_sum:
        # groups by tile, so not the scatter's bits: an f64 fold of the
        # same addends is the judge (empty rows hold 0, as reduceat
        # cannot say)
        v64 = np.asarray(vals, np.float64).reshape(-1, ep)
        full = np.nonzero(np.diff(ptr))[0]
        ref = np.zeros((v64.shape[0], rows))
        ref[:, full] = np.add.reduceat(
            v64[:, :ptr[-1]], ptr[full], axis=1)
        np.testing.assert_allclose(
            got.reshape(-1, rows), ref, rtol=1e-6, atol=0)
        np.testing.assert_array_equal(got == 0, want == 0)
    else:
        assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("kind,sorted_ids", [
    ("sum", True), ("min", True), ("max", True), ("prod", True),
    ("min", False),
])
def test_no_row_ptr_lowers_to_the_scatter_as_before(kind, sorted_ids):
    """Without `row_ptr` the function lowers to the text it lowered to
    before it knew another fold (the old body, spelled out here)."""
    rows, ep = 37, 2 * T

    def before(values, segment_ids):
        with jax.named_scope("grape.pull.fold"):
            out = _SCATTER[kind](
                values, segment_ids, num_segments=rows + 1,
                indices_are_sorted=sorted_ids,
            )
            return out[:rows]

    def fold(values, segment_ids):
        return segment_reduce(values, segment_ids, rows, kind,
                              sorted_ids=sorted_ids)

    before.__name__ = before.__qualname__ = "fold"
    args = (jax.ShapeDtypeStruct((ep,), jnp.float32),
            jax.ShapeDtypeStruct((ep,), jnp.int32))
    assert (jax.jit(fold).lower(*args).as_text()
            == jax.jit(before).lower(*args).as_text())


# ---- which fold each round takes ------------------------------------------

QUERY = {"pagerank": {}, "sssp": {"source": 6}, "bfs": {"source": 6},
         "wcc": {}}


def _folds_traced(worker, **query_args) -> dict:
    """FOLD_STATS' rise over the tracing of `worker`'s fused runner."""
    frag = worker.fragment
    state = worker._place_state(worker.app.init_state(frag, **query_args))
    eph = frozenset(getattr(worker.app, "ephemeral_keys", ()) or ())
    carry = {k: v for k, v in state.items() if k not in eph}
    eph_part = {k: v for k, v in state.items() if k in eph}
    before = FOLD_STATS.snapshot()
    worker._runner_for(0, state).lower(frag.dev, carry, eph_part)
    return {k: v - before[k] for k, v in FOLD_STATS.snapshot().items()}


@pytest.mark.parametrize("fnum", [1, 4])
@pytest.mark.parametrize("app", sorted(QUERY))
def test_whole_csr_pulls_fold_by_scan(app, fnum, graph_cache):
    """The four pulls over a whole CSR hand over its offsets: one scan
    in the round (the undirected graph has one pull), no scatter."""
    w = Worker(APP_REGISTRY[app](), graph_cache(fnum))
    assert _folds_traced(w, **QUERY[app]) == {"scan": 1, "scatter": 0}


# name: (values dtype, armed, budget in bytes): lanes the kernel does
# not serve (rows is 37 below: a table of 148 bytes)
SCATTER_LANES = {
    "off_tpu": ("float32", False, None),
    "f64": ("float64", True, 64 << 20),
    "s64": ("int64", True, 64 << 20),
    "over_the_budget": ("int32", True, 37 * 4 - 1),
}


@pytest.mark.parametrize("name", sorted(SCATTER_LANES))
def test_lanes_the_kernel_does_not_serve_lower_as_before(name, pull_kernel,
                                                         monkeypatch):
    """Other backends, 64-bit values, tables over the budget: the lanes
    of an exact pull lower to the parent's text, `full[nbr]` under
    `jax.vmap` fused into the scatter."""
    from libgrape_lite_tpu.ops import segment
    from libgrape_lite_tpu.ops.segment import pull_gather

    dtype, armed, budget = SCATTER_LANES[name]
    if armed:
        pull_kernel("stand_in")
        monkeypatch.setattr(segment, "gather_table_budget", lambda: budget)
    rows, ep = 37, 2 * T
    big = (jnp.iinfo(dtype).max if jnp.issubdtype(dtype, jnp.integer)
           else jnp.inf)

    def parent(full, nbr, mask, ids, ptr):
        def one(f):
            with jax.named_scope("grape.pull.gather"):
                cand = jnp.where(mask, f[nbr] + 1, jnp.asarray(big, f.dtype))
            with jax.named_scope("grape.pull.fold"):
                return jops.segment_min(
                    cand, ids, num_segments=rows + 1,
                    indices_are_sorted=True)[:rows]
        return jax.vmap(one)(full)

    def lanes(full, nbr, mask, ids, ptr):
        def one(f):
            cand = pull_gather(f, nbr, mask, jnp.asarray(big, f.dtype),
                               add=1)
            return segment_reduce(cand, ids, rows, "min", row_ptr=ptr)
        return jax.vmap(one)(full)

    parent.__name__ = parent.__qualname__ = "lanes"
    args = (jax.ShapeDtypeStruct((4, rows), jnp.dtype(dtype)),
            jax.ShapeDtypeStruct((ep,), jnp.int32),
            jax.ShapeDtypeStruct((ep,), jnp.bool_),
            jax.ShapeDtypeStruct((ep,), jnp.int32),
            jax.ShapeDtypeStruct((rows + 1,), jnp.int32))
    before = FOLD_STATS.snapshot()
    text = jax.jit(lanes).lower(*args).as_text()
    assert FOLD_STATS.snapshot() == {**before,
                                     "scatter": before["scatter"] + 1}
    assert text == jax.jit(parent).lower(*args).as_text()


def _lanes_graph(app, graph, graph_cache):
    from tests.conftest import rand_frag

    if graph == "p2p_f64":  # this lane's x64: SSSP's state is f64 there
        return graph_cache(1)
    return rand_frag(1, weighted=app == "sssp")


@pytest.mark.parametrize("app,graph,armed,took", [
    ("sssp", "p2p_f64", False, "scatter"),
    ("bfs", "p2p_f64", False, "scatter"),
    ("pagerank", "p2p_f64", False, "scan"),
    ("sssp", "rand_f32", False, "scatter"),
    ("sssp", "rand_f32", True, "scan"),
    ("bfs", "rand_f32", True, "scan"),
    ("pagerank", "rand_f32", True, "scan"),
    ("sssp", "p2p_f64", True, "scatter"),
])
def test_query_lanes_fold_by_kind(app, graph, armed, took, graph_cache,
                                  pull_kernel):
    """The batched runner's lanes share one CSR under `jax.vmap`.
    Where a lane's single query gathers by the kernel (armed here as
    the TPU backend arms it: 32-bit state) the lanes take that query's
    pull one after another, kernel and scan.  Elsewhere an exact fold
    keeps the scatter XLA fuses the lanes' gather into, and a float sum
    scans, as each lane's single query does (tests/test_serve.py pins
    the bytes)."""
    from tests.conftest import gather_took

    if armed:
        pull_kernel("stand_in")
    w = Worker(APP_REGISTRY[app](), _lanes_graph(app, graph, graph_cache))
    before = FOLD_STATS.snapshot()
    gathers = gather_took(lambda: w.query_batch(
        [{"source": s} for s in (6, 17, 522, 31)]))
    after = FOLD_STATS.snapshot()
    assert {k: after[k] - before[k] for k in after} == {
        "scan": 0, "scatter": 0, took: 1}
    kernel = armed and graph == "rand_f32"
    assert gathers == {"kernel": int(kernel), "xla": int(not kernel)}


class _NoDevice:
    """Stands where a fragment's device arrays do while a state is
    built: whatever is read of them is a read-back."""

    def __getattr__(self, name):
        raise AssertionError(f"init_state read frag.dev.{name}")


@pytest.mark.parametrize("fnum,lanes", [(1, 0), (2, 0), (4, 0), (1, 4)],
                         ids=["1", "2", "4", "lanes4"])
def test_pagerank_fold_is_the_one_fold(fnum, lanes, monkeypatch):
    """PageRank's f32 state on a backend that says it is a TPU, where
    an earlier tree copied the E-wide `edge_src` to the host to plan
    another fold: the state is built from host arrays alone and holds
    no plan leaf, and the round (or the lanes' round) folds by scan."""
    from libgrape_lite_tpu.ops import pallas_kernels
    from tests.conftest import rand_frag

    monkeypatch.setattr(pallas_kernels, "use_pallas", lambda: True)
    frag = rand_frag(fnum, weighted=False)
    app = APP_REGISTRY["pagerank"]()
    sources = list(range(lanes))
    with monkeypatch.context() as m:
        m.setattr(frag, "dev", _NoDevice())
        state = (app.init_state_batch(frag, [{"source": s}
                                             for s in sources])
                 if lanes else app.init_state(frag))
    assert state["rank"].dtype == np.float32
    assert set(state) == {"rank", "step", "dangling_sum",
                          "total_dangling"} | ({"seed"} if lanes else set())
    w = Worker(APP_REGISTRY["pagerank"](), frag)
    before = FOLD_STATS.snapshot()
    if lanes:
        w.query_batch([{"source": s} for s in sources])
    else:
        w.query()
    after = FOLD_STATS.snapshot()
    assert {k: after[k] - before[k] for k in after} == {
        "scan": 1, "scatter": 0}


def test_row_ptr_with_unsorted_ids_is_refused():
    rows, ptr, ids = _csr("pad_tail")
    with pytest.raises(ValueError, match="sorted"):
        segment_reduce(jnp.zeros(ids.shape[0]), jnp.asarray(ids), rows,
                       "min", sorted_ids=False, row_ptr=jnp.asarray(ptr))


@pytest.mark.parametrize("app", ["sssp", "bfs", "wcc"])
def test_dyn_overlay_folds_by_scatter(app):
    """The overlay's slots come sorted but without offsets: its fold
    stays a scatter beside the base CSR's scan."""
    from libgrape_lite_tpu.dyn import DynGraph, RepackPolicy
    from tests.test_dyn import ADDS, build_graph

    dg = DynGraph(build_graph(1), RepackPolicy(threshold=0.9, capacity=64))
    assert dg.ingest(ADDS)["mode"] == "overlay"
    w = Worker(APP_REGISTRY[app](), dg.fragment)
    kw = {} if app == "wcc" else {"source": 0}
    assert _folds_traced(w, **kw) == {"scan": 1, "scatter": 1}


# ---- the row ends: by the kernel where the values are its kind -----------


def _skewed_csr(rows=3000, ep=200 * T, seed=3):
    """A CSR with a few hubs, many empty rows and padding behind the
    last row; 25,600 places: thirteen of the armed kernel's slices,
    three of its blocks of row ends."""
    rng = np.random.default_rng(seed)
    deg = np.where(rng.random(rows) < 0.4, 0, rng.geometric(0.25, rows))
    # rows that span slices, the second behind a run of empty ones
    deg[[100, rows // 2, rows - 100]] = 3000, 5000, 2500
    deg[rows // 2 - 40:rows // 2] = 0
    ptr = np.zeros(rows + 1, np.int32)
    ptr[1:] = np.cumsum(deg)
    ids = np.full(ep, rows, np.int32)
    ids[:ptr[-1]] = np.repeat(np.arange(rows, dtype=np.int32), deg)
    assert ptr[-1] < ep - 300 and (deg == 0).sum() > rows // 3
    return rows, ptr, ids


def _row_ends_took(fn):
    before = ROW_END_STATS.snapshot()
    out = fn()
    return out, {k: v - before[k] for k, v in ROW_END_STATS.snapshot().items()}


def _top_label_scatter(count, label, ids, rows):
    """`segment_top_label` by scatters: the largest count of a row,
    then the smallest label that has it."""
    top = jops.segment_max(count, ids, num_segments=rows + 1)
    best = jnp.where(count == top[ids], label, jnp.iinfo(label.dtype).max)
    return jops.segment_min(best, ids, num_segments=rows + 1)[:rows]


ROW_END_FOLDS = {
    "sum_f32": ("sum", "float32"), "min_s32": ("min", "int32"),
    "max_f32": ("max", "float32"), "top_label": ("top", "int32"),
}


@pytest.mark.parametrize("fold,how", [
    (f, h) for f in sorted(ROW_END_FOLDS)
    for h in ("single", "vmap4", "shard_map2")
    # no caller batches CDLP's fold
    if (f, h) != ("top_label", "vmap4")])
def test_row_ends_by_the_kernel(fold, how, pull_kernel):
    """With the choice steered as the TPU backend steers it and the
    row-end kernel interpreted behind it, the scan fold and CDLP's
    `segment_top_label` equal the scatter fold on a skewed CSR with
    empty rows: a single call, query lanes under `jax.vmap` (each with
    its single call's bytes) and the shards of a two-fragment
    `shard_map`; each call site counts once, as `kernel`."""
    from jax.sharding import Mesh, PartitionSpec as P

    kind, dtype = ROW_END_FOLDS[fold]
    pull_kernel("stand_in", rows="interpreted")
    rows, ptr, ids = _skewed_csr()
    ep = ids.shape[0]
    lead = {"single": (), "vmap4": (4,), "shard_map2": (2,)}[how]
    rng = np.random.default_rng(ep)
    label = rng.integers(0, 50, lead + (ep,)).astype(np.int32)
    count = rng.integers(1, 9, lead + (ep,)).astype(np.int32)
    vals = _values(kind, dtype, lead + (ep,), seed=7) if kind != "top" \
        else label
    jids, jptr = jnp.asarray(ids), jnp.asarray(ptr)

    def one(v, c):
        if kind == "top":
            return segment_top_label(c, v, jids, rows, row_ptr=jptr)
        return segment_reduce(v, jids, rows, kind, row_ptr=jptr)

    def scatter(v, c):
        if kind == "top":
            return _top_label_scatter(c, v, jids, rows)
        return segment_reduce(v, jids, rows, kind)

    def over(f):
        if how == "vmap4":
            return jax.vmap(f)
        if how == "shard_map2":
            mesh = Mesh(np.array(jax.devices()[:2]), ("f",))
            return jax.shard_map(
                lambda v, c: f(v[0], c[0])[None], mesh=mesh,
                in_specs=(P("f"), P("f")), out_specs=P("f"))
        return f

    got, took = _row_ends_took(
        lambda: np.asarray(jax.jit(over(one))(vals, count)))
    assert took == {"kernel": 1, "xla": 0}
    want = np.asarray(jax.jit(over(scatter))(vals, count))
    assert got.dtype == want.dtype and got.shape == lead + (rows,)
    if lead:
        single = jax.jit(one)
        assert got.tobytes() == np.stack([
            np.asarray(single(v, c)) for v, c in zip(vals, count)]).tobytes()
    if kind == "sum":
        # groups by tile, so not the scatter's bits
        np.testing.assert_allclose(got, want, rtol=1e-5)
        np.testing.assert_array_equal(got == 0, want == 0)
    else:
        assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("name,dtype,armed,offsets", [
    ("f64_on_tpu", "float64", True, True),
    ("s64_on_tpu", "int64", True, True),
    ("f32_off_tpu", "float32", False, True),
    ("s32_off_tpu", "int32", False, True),
    ("top_label_looks_its_offsets_up", "int32", True, False),
])
def test_row_ends_by_xla_lower_as_before(name, dtype, armed, offsets,
                                         pull_kernel):
    """64-bit values, other backends and a `segment_top_label` that has
    to look its offsets up keep XLA's gather of the row ends: counted
    as `xla`, and lowered to the text they lowered to before there was
    a choice (the old lines, spelled out here)."""
    from libgrape_lite_tpu.ops import segment

    if armed:
        pull_kernel("stand_in")
    rows, ep = 37, 2 * T
    top = not offsets

    def old_ends(scanned, row_ptr, empty):
        last = row_ptr[1:rows + 1] - 1
        out = scanned.at[jnp.maximum(last, 0)].get(
            mode="promise_in_bounds", indices_are_sorted=True)
        return jnp.where(last >= row_ptr[:rows], out,
                         jnp.asarray(empty, scanned.dtype))

    def before(values, ids, ptr):
        with jax.named_scope("grape.pull.fold"):
            if top:
                empty = jnp.iinfo(values.dtype).max
                ptr = jnp.searchsorted(
                    ids, jnp.arange(rows + 1, dtype=ids.dtype))
                _, best = segment._segmented_scan_pair(
                    (values, values), ids, segment._more_then_smaller,
                    (0, empty))
                return old_ends(best, ptr, empty)
            identity = segment._FOLDS["min"][2](values.dtype)
            scanned = segment._segmented_scan(
                values, ids, jnp.minimum, identity)
            return old_ends(scanned, ptr, identity)

    def fold(values, ids, ptr):
        if top:
            return segment_top_label(values, values, ids, rows)
        return segment_reduce(values, ids, rows, "min", row_ptr=ptr)

    before.__name__ = before.__qualname__ = "fold"
    args = (jax.ShapeDtypeStruct((ep,), jnp.dtype(dtype)),
            jax.ShapeDtypeStruct((ep,), jnp.int32),
            jax.ShapeDtypeStruct((rows + 1,), jnp.int32))
    text, took = _row_ends_took(lambda: jax.jit(fold).lower(*args).as_text())
    assert took == {"kernel": 0, "xla": 1}
    assert text == jax.jit(before).lower(*args).as_text()


@pytest.mark.parametrize("name,kind,dtype,budget,fold,ends", [
    # the kernel's lanes: one scan, one gather of row ends, however
    # often the `vmap` rules trace the fold
    ("exact_lanes", "min", "int32", 1 << 20, "scan", "kernel"),
    # a table over the gather kernel's budget: a float sum's lanes scan
    # behind XLA's gather and still read their row ends by the kernel,
    # one lane after another
    ("float_sum_over_budget", "sum", "float32", 0, "scan", "kernel"),
    # and an exact fold's lanes go back to the scatter, which reads no
    # row ends: the entry stays, as the choice the call made before
    # its `vmap` rule ran (the single query it was traced as)
    ("exact_lanes_over_budget", "min", "int32", 0, "scatter", "kernel"),
])
def test_lanes_count_their_row_ends_once(name, kind, dtype, budget, fold,
                                         ends, pull_kernel, monkeypatch):
    from libgrape_lite_tpu.ops import segment

    pull_kernel("stand_in")
    monkeypatch.setattr(segment, "gather_table_budget", lambda: budget)
    rows, ptr, ids = _csr("empty_rows")
    vals = _values(kind, dtype, (4, ids.shape[0]), seed=9)

    def one(v):
        return segment_reduce(v, jnp.asarray(ids), rows, kind,
                              row_ptr=jnp.asarray(ptr))

    folds = FOLD_STATS.snapshot()
    got, took = _row_ends_took(
        lambda: np.asarray(jax.jit(jax.vmap(one))(vals)))
    assert FOLD_STATS.snapshot() == {**folds, fold: folds[fold] + 1}
    assert took == {"kernel": int(ends == "kernel"), "xla": 0}
    single = jax.jit(one)
    assert got.tobytes() == np.stack(
        [np.asarray(single(v)) for v in vals]).tobytes()


# ---- the scan's first level: by the kernel where the values are its kind --


def _scans_took(fn):
    before = SCAN_STATS.snapshot()
    out = fn()
    return out, {k: v - before[k] for k, v in SCAN_STATS.snapshot().items()}


FIRST_LEVEL_FOLDS = {
    "sum_f32": ("sum", "float32"), "min_s32": ("min", "int32"),
    "min_f32": ("min", "float32"), "max_s32": ("max", "int32"),
}


@pytest.mark.parametrize("fold,how", [
    (f, h) for f in sorted(FIRST_LEVEL_FOLDS)
    for h in ("single", "vmap4", "shard_map2")])
def test_first_level_by_the_kernel(fold, how, pull_kernel):
    """With the choice steered as the TPU backend steers it and the
    tile-scan kernel interpreted behind it, the scan fold equals the
    scatter (exact folds) and, byte for byte, the scan whose first
    level is XLA's seven steps (a float sum keeps its grouping): a
    single call, query lanes under `jax.vmap` and the shards of a
    two-fragment `shard_map`; each call site counts once, as
    `kernel`, beside its row ends.  (Under a `shard_map` that checks
    varying axes the interpreter cannot evaluate a kernel's loop over
    its refs, so the shards run the stand-in and the kernel itself is
    lowered for the TPU there, where the check is the same one.)"""
    from jax.sharding import Mesh, PartitionSpec as P

    from libgrape_lite_tpu.ops import pallas_kernels, segment

    kind, dtype = FIRST_LEVEL_FOLDS[fold]
    rows, ptr, ids = _skewed_csr()
    ep = ids.shape[0]
    lead = {"single": (), "vmap4": (4,), "shard_map2": (2,)}[how]
    vals = _values(kind, dtype, lead + (ep,), seed=11)
    jids, jptr = jnp.asarray(ids), jnp.asarray(ptr)

    def one(v):
        return segment_reduce(v, jids, rows, kind, row_ptr=jptr)

    def scatter(v):
        return segment_reduce(v, jids, rows, kind)

    def over(f):
        if how == "vmap4":
            return jax.vmap(f)
        if how == "shard_map2":
            mesh = Mesh(np.array(jax.devices()[:2]), ("f",))
            return jax.shard_map(lambda v: f(v[0])[None], mesh=mesh,
                                 in_specs=(P("f"),), out_specs=P("f"))
        # a function of its own: `jax.jit` remembers one it has traced
        return lambda v: f(v)

    # the parent's scan: nothing armed, XLA's steps and XLA's row ends
    parent, took = _scans_took(lambda: np.asarray(jax.jit(over(one))(vals)))
    float_sum = kind == "sum"
    # off the TPU an exact fold's lanes go back to the scatter; the
    # entry stays, as the single query's choice
    assert took == {"kernel": 0, "xla": 1}
    sharded = how == "shard_map2"
    pull_kernel("stand_in", scan="stand_in" if sharded else "interpreted")
    ends = ROW_END_STATS.snapshot()
    got, took = _scans_took(lambda: np.asarray(jax.jit(over(one))(vals)))
    assert took == {"kernel": 1, "xla": 0}
    assert ROW_END_STATS.snapshot() == {**ends, "kernel": ends["kernel"] + 1}
    if sharded:
        # x32, as a chip run is: Mosaic's lowering refuses this lane's x64
        with jax.enable_x64(False):
            text = jax.jit(over(lambda v: pallas_kernels.tile_scan(
                v.reshape(-1, T), jids.reshape(-1, T),
                segment._FOLDS[kind][1]).reshape(-1))
            ).trace(vals).lower(lowering_platforms=("tpu",)).as_text()
        assert text.count("tpu_custom_call") == 1 and "tile_scan" in text
    assert got.dtype == parent.dtype and got.shape == lead + (rows,)
    assert got.tobytes() == parent.tobytes()
    if not float_sum:
        assert got.tobytes() == np.asarray(
            jax.jit(over(scatter))(vals)).tobytes()
    if lead:
        single = jax.jit(one)
        assert got.tobytes() == np.stack(
            [np.asarray(single(v)) for v in vals]).tobytes()


def test_run_position_keeps_xlas_steps(pull_kernel):
    """CDLP's count scans by XLA's steps even where the choice is
    steered as the TPU backend steers it (the scan of a pair behind it
    paid for the kernel with more than it gave: `run_position`): no
    first level is chosen or counted, and the positions are what the
    definition says."""
    from libgrape_lite_tpu.ops.segment import run_position

    rows, _, ids = _skewed_csr()
    label = np.random.default_rng(5).integers(0, 4, ids.shape[0]).astype(
        np.int32)
    # a run is a (row, label) pair in order
    order = np.lexsort((label, ids))
    label = label[order]
    args = jnp.asarray(ids), jnp.asarray(label)
    text = jax.jit(run_position).lower(*args).as_text()
    pull_kernel("stand_in", scan="interpreted")
    armed = jax.jit(lambda s, v: run_position(s, v))
    got, took = _scans_took(lambda: np.asarray(armed(*args)))
    assert took == {"kernel": 0, "xla": 0}
    lowered = armed.lower(*args).as_text()
    assert lowered.replace("jit__lambda", "jit_run_position") == text
    opens = np.r_[True, (ids[1:] != ids[:-1]) | (label[1:] != label[:-1])]
    at = np.arange(ids.shape[0])
    np.testing.assert_array_equal(
        got, at - np.maximum.accumulate(np.where(opens, at, 0)) + 1)


@pytest.mark.parametrize("name,dtype,ids_dtype,entries,took", [
    ("f32", "float32", "int32", 2 * T, "kernel"),
    ("s32_one_tile", "int32", "int32", T, "kernel"),
    ("f64", "float64", "int32", 2 * T, "xla"),
    ("s64_ids", "float32", "int64", 2 * T, "xla"),
    # the levels above the first: whatever the tiles' tails come to
    ("not_whole_tiles", "float32", "int32", 2 * T + 5, "xla"),
    ("nothing", "float32", "int32", 0, "xla"),
    # three streams of 4,096 bytes: over a VMEM of 12,287, not of 12,288
    ("over_the_vmem_line", "float32", "int32", 8 * T, "kernel"),
    ("on_the_vmem_line", "int32", "int32", 8 * T, "xla"),
])
def test_first_level_reads_what_the_call_can_see(name, dtype, ids_dtype,
                                                 entries, took, pull_kernel,
                                                 monkeypatch):
    """On the TPU backend (steered) the kernel takes 32-bit values with
    int32 ids in whole tiles whose three streams do not fit the
    device's VMEM together, and nothing else; off it, nothing."""
    from libgrape_lite_tpu.ops import segment

    def choose():
        return segment._first_level(jnp.minimum, jnp.dtype(dtype), entries,
                                    jnp.dtype(ids_dtype))

    chosen, counted = _scans_took(choose)
    assert chosen is None and counted == {"kernel": 0, "xla": 1}
    pull_kernel("stand_in")
    if "vmem_line" in name:
        monkeypatch.setattr(
            segment, "tile_scan_floor",
            lambda: 3 * 4 * entries - (name == "over_the_vmem_line"))
    chosen, counted = _scans_took(choose)
    assert (chosen is not None) == (took == "kernel")
    assert counted == {"kernel": 0, "xla": 0, took: 1}


@pytest.mark.parametrize("name,dtype,armed", [
    ("f64_on_tpu", "float64", True),
    ("s64_on_tpu", "int64", True),
    ("f32_off_tpu", "float32", False),
    ("s32_off_tpu", "int32", False),
])
def test_first_level_by_xla_lowers_as_before(name, dtype, armed, pull_kernel):
    """64-bit values and other backends keep XLA's seven steps: counted
    as `xla`, and lowered to the text they lowered to before there was
    a choice (the old function, spelled out here)."""
    from libgrape_lite_tpu.ops import segment
    from libgrape_lite_tpu.ops.segment import _shift

    if armed:
        pull_kernel("stand_in")
    rows, entries = 37, 2 * T

    def old_scan(values, ids, combine, identity):
        n = ids.shape[0]
        pad = -n % T
        if pad:
            values = jax.lax.pad(values, jnp.asarray(identity, values.dtype),
                                 [(0, pad, 0)])
            ids = jax.lax.pad(ids, ids[-1], [(0, pad, 0)])
        v = values.reshape(-1, T)
        i = ids.reshape(-1, T)
        d = 1
        while d < T:
            v = jnp.where(i == _shift(i, d, -1),
                          combine(v, _shift(v, d, identity)), v)
            d *= 2
        if v.shape[0] > 1:
            tail_i = i[:, -1]
            above = old_scan(v[:, -1], tail_i, combine, identity)
            carry_i = _shift(tail_i, 1, -1)[:, None]
            carry = _shift(above, 1, identity)[:, None]
            v = jnp.where(i == carry_i, combine(carry, v), v)
        return v.reshape(-1)[:n]

    def before(values, ids, ptr):
        with jax.named_scope("grape.pull.fold"):
            identity = segment._FOLDS["min"][2](values.dtype)
            scanned = old_scan(values, ids, jnp.minimum, identity)
            return segment._row_ends(
                scanned, ptr, rows, identity,
                lambda s, at: s.at[at].get(mode="promise_in_bounds",
                                           indices_are_sorted=True))

    def fold(values, ids, ptr):
        return segment_reduce(values, ids, rows, "min", row_ptr=ptr)

    before.__name__ = before.__qualname__ = "fold"
    args = (jax.ShapeDtypeStruct((entries,), jnp.dtype(dtype)),
            jax.ShapeDtypeStruct((entries,), jnp.int32),
            jax.ShapeDtypeStruct((rows + 1,), jnp.int32))
    text, took = _scans_took(lambda: jax.jit(fold).lower(*args).as_text())
    assert took == {"kernel": 0, "xla": 1}
    assert text == jax.jit(before).lower(*args).as_text()


@pytest.mark.parametrize("name,kind,dtype,budget,fold", [
    # the kernel's lanes: one scan, one first level, however often the
    # `vmap` rules trace the fold
    ("exact_lanes", "min", "int32", 1 << 20, "scan"),
    # a table over the gather kernel's budget: a float sum's lanes scan
    # behind XLA's gather, the kernel batched over them
    ("float_sum_over_budget", "sum", "float32", 0, "scan"),
    # and an exact fold's lanes go back to the scatter, which scans
    # nothing: the entry stays, as the choice the call made before its
    # `vmap` rule ran (the single query it was traced as)
    ("exact_lanes_over_budget", "min", "int32", 0, "scatter"),
])
def test_lanes_count_their_first_level_once(name, kind, dtype, budget, fold,
                                            pull_kernel, monkeypatch):
    from libgrape_lite_tpu.ops import segment

    pull_kernel("stand_in", scan="interpreted")
    monkeypatch.setattr(segment, "gather_table_budget", lambda: budget)
    rows, ptr, ids = _csr("hub_three_tiles")
    vals = _values(kind, dtype, (4, ids.shape[0]), seed=9)

    def one(v):
        return segment_reduce(v, jnp.asarray(ids), rows, kind,
                              row_ptr=jnp.asarray(ptr))

    folds = FOLD_STATS.snapshot()
    got, took = _scans_took(lambda: np.asarray(jax.jit(jax.vmap(one))(vals)))
    assert FOLD_STATS.snapshot() == {**folds, fold: folds[fold] + 1}
    assert took == {"kernel": 1, "xla": 0}
    single = jax.jit(one)
    assert got.tobytes() == np.stack(
        [np.asarray(single(v)) for v in vals]).tobytes()
