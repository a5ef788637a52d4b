"""CDLP's count without a scatter (`models/cdlp.CDLP._mode_fold`).

After the sort the (row, label) pairs are in order, so the run lengths
and each row's mode are scans (`ops/segment.run_position`,
`segment_top_label`): no scatter into rows, no E-row table, no gather
back.  Pinned here: the fold against the scatter formulation it
replaced (kept below as `_mode_fold`'s plain reference) and against a
`Counter` per row, bit for bit, over the shapes that could break a scan
(ties, empty rows, a hub over several tiles of both levels, runs = E,
one run) or, since PR 37, the ranks the dynamic branch folds (an empty
row's sentinel, the table's last slots, the budget's edge), in each sort
branch, with the CSR's offsets and without; whole
queries against `benchmarks/references/cdlp.py` on graphs of the same
shapes, on one fragment, two and four; `CDLPOpt` equal
to `CDLP`; the contract the offsets rest on (no masked entry inside a
row of `oe`); and that the serial round lowers without a scatter and
counts itself in FOLD_STATS as scans.
"""

import types
from collections import Counter

import jax
import jax.numpy as jnp
import jax.ops as jops
import numpy as np
import pytest

from benchmarks.graphs import kronecker
from benchmarks.graphs.csr import symmetric_csr
from benchmarks.references import cdlp as cdlp_reference
from libgrape_lite_tpu.fragment.loader import LoadGraph, LoadGraphSpec
from libgrape_lite_tpu.models import APP_REGISTRY
from libgrape_lite_tpu.ops.segment import SCAN_TILE
from libgrape_lite_tpu.parallel.comm_spec import CommSpec
from libgrape_lite_tpu.worker.worker import Worker
from tests.test_cdlp_kronecker import CONFIG, lowered
from tests.test_segment_fold import _folds_traced

T = SCAN_TILE  # a second-level tile of the scan holds T * T = 16,384 places

BRANCHES = {
    # name -> the test hooks that force it
    "packed": {},
    "dynamic": {"_force_dynamic": True},
    "dynamic_wide_arm": {"_force_dynamic": True, "_u_budget_override": 16},
    "wide": {"_force_wide": True},
}


def _app(branch: str, name: str = "cdlp"):
    app = APP_REGISTRY[name]()
    for hook, value in BRANCHES[branch].items():
        setattr(app, hook, value)
    return app


# ---- the fold alone, on rows of labels -----------------------------------

def _hub(*runs):
    """One row's labels from (label, count) runs, shuffled: the sort
    puts them back."""
    row = np.concatenate([np.full(c, lab) for lab, c in runs])
    return np.random.default_rng(len(row)).permutation(row).tolist()


# name -> rows of neighbour labels (a label is an id below the number of
# rows' power of two); a row's entries arrive in any order
ROWS = {
    "multigraph_self_loops": [[1, 1, 0, 0, 2], [0, 0, 1, 1], [0, 3, 3, 3],
                              [2, 2, 2, 3, 3]],
    "tie_smallest_wins": [[5, 5, 3, 3, 9], [7, 2], [4, 4, 6, 6, 1, 1],
                          [9, 8, 8, 9]],
    "rows_without_entries": [[], [3, 3, 1], [], [], [2], []],
    # 3 tiles and 40 places; the runs end inside, on and across tiles
    "hub_three_tiles": [[1, 2], _hub((6, T), (4, T + 20), (9, T + 20)),
                        [3]],
    # 3 second-level tiles and 77 places; two runs of 20,000 tie
    "hub_second_level": [[7], [], _hub((4, 20000), (2, 20000), (7, 9229)),
                         [5, 5, 6]],
    # every (row, label) pair once: as many runs as entries, no padding
    "all_pairs_distinct": [list(range(r, r + 16)) for r in range(16)],
    # one run is all there is: a whole tile, no padding
    "all_pairs_equal": [[], [], [], [5] * T],
    # the rows before and after hold entries, the one between holds none:
    # its answer is no rank, and decodes to `big`
    "empty_row_between": [[2, 2, 6], [], [6, 6, 2], [], []],
    # the winning label is the largest real one: in the table of the live
    # universe it is the last slot before `big`'s (vertices 16..31 hold it)
    "winner_is_bigs_neighbour": [[15, 15, 0], [15], [3, 15, 15, 3, 15]],
    # the smallest and the largest live label tie: the smallest wins
    "tie_of_smallest_and_largest": [[0, 15, 15, 0, 7], [15, 0], [7, 15, 0]],
}

# shape -> `full` where it is not the identity: vertices past the rows'
# labels hold the pad label, as the padded vertices of a fragment do
BIG_HELD = {"winner_is_bigs_neighbour": 16}


def _stream(shape: str, rows=None, vp: int = 0):
    """(src, lab, full, lut, vp, row_ptr, the answer by Counter)."""
    rows = ROWS[shape] if rows is None else rows
    vp = max(8, vp, 1 << int(np.ceil(np.log2(max(
        [len(rows)] + [lab + 1 for r in rows for lab in r])))))
    if shape in BIG_HELD:
        vp *= 2
    deg = np.asarray([len(r) for r in rows] + [0] * (vp - len(rows)))
    ptr = np.zeros(vp + 1, np.int32)
    ptr[1:] = np.cumsum(deg)
    ep = max(T, -(-int(ptr[-1]) // T) * T)
    big = np.iinfo(np.int32).max
    src = np.full(ep, vp, np.int32)
    lab = np.full(ep, big, np.int32)
    src[:ptr[-1]] = np.repeat(np.arange(vp, dtype=np.int32), deg)
    lab[:ptr[-1]] = [x for r in rows for x in r]
    full = np.arange(vp, dtype=np.int32)
    full[BIG_HELD.get(shape, vp):] = big
    lut = np.sort(np.append(full, np.int32(big)))
    want = np.full(vp, big, np.int32)
    for r, row in enumerate(rows):
        if row:
            counts = Counter(row)
            top = max(counts.values())
            want[r] = min(x for x, c in counts.items() if c == top)
    return src, lab, full, lut, vp, ptr, want


def _scatter_mode_fold(ss, ll, vp, big):
    """`_mode_fold` after its sort as it stood before the scans: run
    ids by `cumsum`, run lengths by a scatter into E rows and a gather
    back, the row's largest by a scatter and a gather back, the
    smallest label that reaches it by a third scatter."""
    valid = ss != jnp.int32(vp)
    first = jnp.ones_like(ss, dtype=bool).at[1:].set(
        jnp.logical_or(ss[1:] != ss[:-1], ll[1:] != ll[:-1]))
    run_id = jnp.cumsum(first.astype(jnp.int32)) - 1
    run_len = jops.segment_sum(valid.astype(jnp.int32), run_id,
                               num_segments=ss.shape[0] + 1)[:-1]
    c_e = run_len[run_id]
    cmax = jops.segment_max(c_e, ss, num_segments=vp + 1)[:vp]
    is_best = jnp.logical_and(valid, c_e == cmax[jnp.minimum(ss, vp - 1)])
    cand = jnp.where(is_best, ll, big)
    return jops.segment_min(cand, ss, num_segments=vp + 1)[:vp]


def _fold_both_ways(app, stream, offsets: str):
    """(`_mode_fold` as `_propagate` calls it, the scatter formulation on
    the labels of the same sorted pairs, the distinct labels the pass
    counted, the branch's budget).  An entry's neighbour is the vertex
    that holds its label: `full` is the identity below the pad labels."""
    src, lab, full, lut, vp, ptr, _ = stream
    big = np.iinfo(np.int32).max
    mask = lab != big
    nbr = np.where(mask, lab, 0)

    def entries(full, nbr, mask):
        n_live, values, fill, table = app._live_labels(full, vp)
        return n_live, jnp.where(mask, values[nbr], fill), table

    def fold(src, full, lut, ptr, nbr, mask):
        n_live, val, table = entries(full, nbr, mask)
        return app._mode_fold(
            src, val, lut, vp, n_live, table,
            row_ptr=ptr if offsets == "row_ptr" else None), n_live

    def before(src, full, lut, nbr, mask):
        n_live, val, table = entries(full, nbr, mask)
        ss, vv = app._sorted_pairs(src, val, lut, vp, n_live)
        ll = vv if table is None else jnp.where(ss == vp, big, table[vv])
        return _scatter_mode_fold(ss, ll, vp, big)

    got, n_live = jax.jit(fold)(src, full, lut, ptr, nbr, mask)
    return (np.asarray(got), np.asarray(jax.jit(before)(src, full, lut, nbr, mask)),
            int(n_live), app._sort_plan(full.shape[0], vp)[2])


@pytest.mark.parametrize("offsets", ["row_ptr", "looked_up"])
@pytest.mark.parametrize("branch", sorted(BRANCHES))
@pytest.mark.parametrize("shape", sorted(ROWS))
def test_mode_fold_is_the_scatter_formulation_bit_for_bit(shape, branch,
                                                          offsets):
    stream = _stream(shape)
    want = stream[-1]
    got, before, _, _ = _fold_both_ways(_app(branch), stream, offsets)
    assert got.dtype == np.int32
    assert got.tobytes() == want.tobytes()
    assert got.tobytes() == before.tobytes()


@pytest.mark.parametrize("offsets", ["row_ptr", "looked_up"])
@pytest.mark.parametrize("over", [0, 1], ids=["n_live_is_the_budget",
                                               "n_live_is_one_more"])
def test_mode_fold_at_the_budgets_edge(over, offsets):
    """The `lax.cond` packs while the distinct labels are at most the
    budget: at exactly `u_budget` the largest rank is `u_budget - 1`, the
    key's whole label field, and one label more takes the two-key sort."""
    vp, budget = 32, 16
    rows = [[r, 31, 31, r] for r in range(6)] + [[], [31, 5, 5, 31, 0]]
    stream = _stream("budget", rows, vp)
    full = stream[2]
    # `budget + over` distinct labels in the state: 0..14 or 0..15, and 31
    full[budget - 1 + over:] = 31
    app = _app("dynamic")
    app._u_budget_override = budget
    got, before, n_live, u_budget = _fold_both_ways(app, stream, offsets)
    assert (n_live, u_budget) == (budget + over, budget)
    assert got.tobytes() == stream[-1].tobytes() == before.tobytes()
    # the smallest label of the tie (31 is the table's last slot in use)
    assert got[:6].tolist() == list(range(6)) and got[7] == 5


# ---- whole queries, against the benchmark's reference ---------------------

def _edges(shape: str):
    """(n, src, dst): graphs whose rounds put the shapes above in front
    of the fold."""
    rng = np.random.default_rng(29)
    if shape == "multigraph_self_loops":
        n = 48
        src, dst = rng.integers(0, n, 400), rng.integers(0, n, 400)
        loops = rng.integers(0, n, 12)
        return n, np.r_[src, loops, src[:60]], np.r_[dst, loops, dst[:60]]
    if shape == "tie_smallest_wins":
        # vertex 0 counts 5 twice, 3 twice, 9 once in the first round
        src = [0, 0, 0, 0, 0, 1, 1, 2, 2, 2, 2, 6, 7]
        dst = [5, 5, 3, 3, 9, 7, 4, 8, 8, 6, 6, 9, 9]
        return 10, np.asarray(src), np.asarray(dst)
    if shape == "rows_without_entries":
        # ids 0, 5, 9 and 20..31 have no edge
        src, dst = rng.integers(10, 20, 40), rng.integers(10, 20, 40)
        return 32, np.r_[src, 1, 2, 3], np.r_[dst, 2, 3, 4]
    if shape == "hub_second_level":
        # vertex 0's row holds 3 second-level tiles and 77 entries, in
        # three runs of which two tie; rows 1-3 hold a run over tiles
        hub = np.repeat([1, 2, 3], [20000, 20000, 9229])
        src, dst = rng.integers(0, 40, 300), rng.integers(0, 40, 300)
        return 40, np.r_[np.zeros_like(hub), src], np.r_[hub, dst]
    if shape == "all_pairs_distinct":
        # a simple graph: in the first round no label comes twice
        pairs = {(min(a, b), max(a, b))
                 for a, b in rng.integers(0, 64, (300, 2)) if a != b}
        src, dst = np.asarray(sorted(pairs)).T
        return 64, src, dst
    if shape == "all_pairs_equal":
        # one vertex, self-loops only: one run of one tile
        return 8, np.full(T // 2, 3), np.full(T // 2, 3)
    raise KeyError(shape)


GRAPHS = ["multigraph_self_loops", "tie_smallest_wins",
          "rows_without_entries", "hub_second_level", "all_pairs_distinct",
          "all_pairs_equal"]


def load_as_the_cell_does(efile: str, vfile: str, fnum: int):
    """`LoadGraph` under the configuration's `load_graph_spec`."""
    spec = dict(CONFIG["load_graph_spec"])
    spec["edata_dtype"] = np.dtype(spec["edata_dtype"]).type
    return LoadGraph(efile, vfile, CommSpec(fnum=fnum), LoadGraphSpec(**spec))


def labels_by_id(frag, app, rounds: int) -> np.ndarray:
    """tests/test_cdlp_kronecker.py's, over every fragment."""
    w = Worker(app, frag)
    w.query(max_round=rounds)
    values = np.asarray(w.result_values())
    out = np.empty(frag.dev.total_vnum, dtype=values.dtype)
    for f in range(frag.fnum):
        out[frag.inner_oids(f)] = values[f, :frag.inner_vertices_num(f)]
    return cdlp_reference.to_reference_form(out)


@pytest.fixture(scope="module")
def shaped(tmp_path_factory):
    """(shape, fnum) -> (fragment through LoadGraph as the cell loads
    it, the reference's graph)."""
    made = {}

    def get(shape: str, fnum: int):
        if (shape, fnum) not in made:
            n, src, dst = _edges(shape)
            d = tmp_path_factory.mktemp(f"{shape}{fnum}")
            efile, vfile = str(d / "graph.e"), str(d / "graph.v")
            with open(efile, "w") as f:
                f.writelines(f"{s} {t} 1\n" for s, t in zip(src, dst))
            with open(vfile, "w") as f:
                f.writelines(f"{v}\n" for v in range(n))
            frag = load_as_the_cell_does(efile, vfile, fnum)
            _, mult = symmetric_csr(n, src, dst, np.ones(len(src)))
            made[shape, fnum] = frag, types.SimpleNamespace(n=n, mult=mult)
        return made[shape, fnum]

    return get


# the cuts: one fragment, four, and two (a fragment's labels cross to
# one peer only)
RUNS = [1, 4, 2]


@pytest.mark.parametrize("fnum", RUNS, ids=["1", "4", "2"])
@pytest.mark.parametrize("branch", ["packed", "dynamic", "wide"])
@pytest.mark.parametrize("shape", GRAPHS)
def test_queries_are_exact_against_the_benchmarks_reference(
        shape, branch, fnum, shaped):
    frag, graph = shaped(shape, fnum)
    app = _app(branch)
    rounds = 3
    got = labels_by_id(frag, app, rounds)
    want = cdlp_reference.reference(graph, {"max_round": rounds})
    assert (got != want).sum() == 0


@pytest.mark.parametrize("fnum", RUNS, ids=["1", "4", "2"])
def test_cdlp_opt_is_cdlp(fnum, graph_cache):
    """The first-round minimum (by scan too, over the whole CSR) and
    the inherited count: CDLP's bytes on the simple graph."""
    frag = graph_cache(fnum)

    def run(name):
        w = Worker(APP_REGISTRY[name](), frag)
        w.query(max_round=10)
        return np.asarray(w.result_values()).tobytes()

    assert run("cdlp_opt") == run("cdlp")


# ---- what the offsets rest on ---------------------------------------------

@pytest.mark.parametrize("graph", ["p2p-1", "p2p-4", "kronecker-1",
                                   "kronecker-4", "hub-4"])
def test_oe_holds_no_masked_entry_inside_a_row(graph, graph_cache, shaped,
                                               tmp_path):
    """`_propagate` hands `oe.indptr` over as the offsets of the sorted
    pairs.  That holds because the masked row ids `where(edge_mask,
    edge_src, vp)` are `edge_src` already and in order: an entry masked
    inside a row would sort behind the last row and shift every offset
    after it."""
    kind, fnum = graph.split("-")
    fnum = int(fnum)
    if kind == "p2p":
        frag = graph_cache(fnum)
    elif kind == "hub":
        frag, _ = shaped("hub_second_level", fnum)
    else:
        efile, vfile = str(tmp_path / "graph.e"), str(tmp_path / "graph.v")
        kronecker.write_files(CONFIG["generator"], 8, efile, vfile)
        frag = load_as_the_cell_does(efile, vfile, fnum)
    vp = frag.vp
    for f in range(frag.fnum):
        oe = frag.host_oe[f]
        src = np.where(oe.edge_mask, oe.edge_src, vp)
        assert (src == oe.edge_src).all()
        assert (np.diff(src) >= 0).all()
        assert (np.searchsorted(src, np.arange(vp + 1)) == oe.indptr).all()


# ---- the program ------------------------------------------------------------

@pytest.mark.parametrize("fnum", [1, 4])
@pytest.mark.parametrize("branch", ["packed", "dynamic", "wide"])
def test_serial_round_lowers_without_a_scatter(branch, fnum, graph_cache):
    """The serial round over a whole CSR: PEval's pass and the loop's
    each fold once, by scan, and no scatter of any width is left in
    the program beside those the sort brings along."""
    frag = graph_cache(fnum)
    assert _folds_traced(Worker(_app(branch), frag)) == {
        "scan": 2, "scatter": 0}
    # in no branch: since PR 37 the dynamic branch builds its live universe
    # by V-wide sorts, where it marked and compacted the labels by scatters
    assert "stablehlo.scatter" not in lowered(_app(branch), frag, False)


@pytest.mark.parametrize("name,fnum", [
    ("cdlp_opt", 4), ("cdlp", 2), ("cdlp_opt", 2)])
def test_every_cdlp_fold_counts_as_a_scan(name, fnum, graph_cache):
    """PEval's pass (`cdlp_opt`: its first-round minimum) and the
    loop's count, whatever the cut."""
    assert _folds_traced(Worker(APP_REGISTRY[name](), graph_cache(fnum))) == {
        "scan": 2, "scatter": 0}
