"""BFS's round that follows its frontier, inside the fused loop.

`Worker._make_runner`'s loop carries, for an app that offers it, the list of
the rows whose proposals are pending and takes `ops/segment.frontier_relax`
wherever the list fits its budgets, the dense round elsewhere
(`worker._frontier_loop`, `models/bfs.py`).  Here against the dense loop, the
benchmark's plain reference and a plain relaxation in NumPy, on graphs small
enough that the budgets have to come down with them: the budgets are
arguments of the primitive, and the app's constants are what it passes.
"""

import hashlib
import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

import libgrape_lite_tpu.models.bfs as bfs_module
import libgrape_lite_tpu.models.sssp as sssp_module
from benchmarks.graphs import kronecker, road_like
from benchmarks.references import bfs as bfs_reference
from libgrape_lite_tpu.fragment.edgecut import ShardedEdgecutFragment
from libgrape_lite_tpu.models import APP_REGISTRY
from libgrape_lite_tpu.obs import federation
from libgrape_lite_tpu.ops import segment
from libgrape_lite_tpu.parallel.comm_spec import CommSpec
from libgrape_lite_tpu.vertex_map.partitioner import MapPartitioner
from libgrape_lite_tpu.vertex_map.vertex_map import VertexMap
from libgrape_lite_tpu.worker import worker as worker_module
from libgrape_lite_tpu.worker.worker import ROUND_STATS, Worker

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)


ROAD, KRON = config("road-like")["generator"], config("g500-s21")["generator"]
SENTINEL = np.iinfo(np.int32).max


def fragment(n, src, dst, directed=False, fnum=1):
    oids = np.arange(n, dtype=np.int64)
    vm = VertexMap.build(oids, MapPartitioner(fnum, oids))
    return ShardedEdgecutFragment.build(
        CommSpec(fnum=fnum), vm, src, dst, np.ones(len(src), np.float32), directed=directed)


def road(scale):
    src, dst, _ = road_like.edges(ROAD, scale)
    return 1 << scale, np.asarray(src, np.int64), np.asarray(dst, np.int64)


def two_components():
    n, src, dst = road(9)
    return 2 * n, np.concatenate([src, src + n]), np.concatenate([dst, dst + n])


def multigraph():
    src, dst, _ = kronecker.edges(KRON, 7)
    src, dst = np.asarray(src, np.int64), np.asarray(dst, np.int64)
    assert (src == dst).any()  # self-loops
    assert len(np.unique(src << 32 | dst)) < len(src)  # duplicate edges
    return 1 << 7, src, dst


def directed_graph():
    rng = np.random.default_rng(40)
    n = 600
    src, dst = rng.integers(0, n, 1500), rng.integers(0, n, 1500)
    # a one-way ring under it, so that a search runs long and reaches all
    return n, np.concatenate([src, np.arange(n)]), np.concatenate([dst, (np.arange(n) + 7) % n])


# name: (graph, directed, B, C, seeded rows, what the case is there for)
CASES = {
    "road8": (lambda: road(8), False, 32, 128, 0),
    "road9": (lambda: road(9), False, 64, 256, 0),
    "road10": (lambda: road(10), False, 64, 256, 0),
    "road11": (lambda: road(11), False, 128, 512, 0),
    "multigraph": (multigraph, False, 16, 512, 0),
    "directed": (directed_graph, True, 64, 256, 0),
    "two_components": (two_components, False, 64, 256, 0),
    "rows_overflow": (lambda: road(10), False, 16, 256, 0),
    "entries_overflow": (lambda: road(10), False, 64, 48, 0),
    "seeded": (lambda: road(10), False, 64, 256, 300),
}


@pytest.fixture(scope="module")
def loaded():
    made = {}

    def get(name):
        graph, directed = CASES[name][:2]
        n, src, dst = graph()
        sig = (n, len(src), int(src.sum()), int(dst.sum()), directed)
        if sig not in made:
            made[sig] = (fragment(n, src, dst, directed), n, src, dst)
        return made[sig]

    return get


def by_id(frag, values):
    out = np.empty(frag.dev.total_vnum, dtype=values.dtype)
    out[frag.inner_oids(0)] = values[0, :frag.inner_vertices_num(0)]
    return out


def search(frag, source, seeds=None):
    """(depths by id, rounds, ROUND_STATS) of the default `bfs`; `seeds`
    (depths by lid, the sentinel where none) are folded into the fresh state
    as an incremental query's are."""
    w = Worker(APP_REGISTRY["bfs"](), frag)
    if seeds is not None:
        w._seed_fn = lambda st: {**st, "depth": np.minimum(st["depth"], seeds[None])}
    w.query(source=source)
    return by_id(frag, w.result_values()), w.rounds, ROUND_STATS.snapshot(), w


def relaxation(n, src, dst, directed, depth):
    """The changed rows of every round of the plain min relaxation from
    `depth` (by id, SENTINEL where none), and the fixed point."""
    if not directed:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    depth = depth.astype(np.int64)
    rounds = []
    while True:
        cand = np.where(depth[src] == SENTINEL, SENTINEL, depth[src] + 1)
        new = depth.copy()
        np.minimum.at(new, dst, cand)
        rounds.append(np.flatnonzero(new < depth))
        depth = new
        if not len(rounds[-1]):
            return rounds, depth


@pytest.fixture
def budgets(monkeypatch):
    def set_to(rows, entries, floor=0):
        monkeypatch.setattr(bfs_module, "_FRONTIER_ROWS", rows)
        monkeypatch.setattr(bfs_module, "_FRONTIER_ENTRIES", entries)
        monkeypatch.setattr(bfs_module, "_DENSE_FLOOR", floor)

    return set_to


@pytest.mark.parametrize("case", list(CASES))
def test_the_frontier_loop_is_the_dense_loop(case, loaded, budgets):
    _, directed, rows, entries, n_seeds = CASES[case]
    frag, n, src, dst = loaded(case)
    source = int(src[0])
    start = np.full(n, SENTINEL, np.int64)
    start[source] = 0
    seeds = None
    if n_seeds:
        # an incremental query's seeds: true depths of some rows, here by lid
        true = relaxation(n, src, dst, directed, start)[1]
        some = np.random.default_rng(1).choice(n, n_seeds, replace=False)
        start[some] = true[some]
        seeds = np.full(frag.vp, SENTINEL, np.int32)
        seeds[frag.oid_to_pid(some)] = true[some]
    changed, fixed = relaxation(n, src, dst, directed, start)

    graph = types.SimpleNamespace(minw=sp.csr_matrix(
        (np.ones(len(src)), (src, dst)), shape=(n, n)) if directed else
        sp.csr_matrix((np.ones(2 * len(src)), (np.r_[src, dst], np.r_[dst, src])), shape=(n, n)))
    want = bfs_reference.reference(graph, {"source": source})
    assert (np.where(fixed == SENTINEL, -1, fixed) != want).sum() == 0
    if case == "two_components":
        assert (want < 0).sum() == n // 2  # the other half keeps the sentinel

    dense, dense_rounds, dense_stats, w = search(frag, source, seeds)
    assert w.app.frontier_budget is None and dense_stats["frontier_rounds"] == 0
    budgets(rows, entries)
    got, rounds, stats, w = search(frag, source, seeds)
    assert w.app.frontier_budget == (rows, entries)

    # depths exact on every vertex, against the reference and the dense loop
    assert (bfs_reference.to_reference_form(got) != want).sum() == 0
    assert got.tobytes() == dense.tobytes()
    # the same rounds, the same vote every round: the whole record
    assert rounds == dense_rounds == len(changed)
    took = stats.pop("frontier_rounds")
    dense_stats.pop("frontier_rounds")
    assert stats == dense_stats
    assert stats["active_sum"] == sum(len(c) for c in changed)
    assert stats["active_max"] == max(len(c) for c in changed)

    # a round follows its frontier where the rows pending before it, and
    # their entries in the CSR it pushes along, fit the budgets
    degree = np.diff(frag.host_oe[0].indptr)
    pending = [np.flatnonzero(start != SENTINEL)] + changed[:-1]
    fits = [len(p) <= rows and degree[frag.oid_to_pid(p)].sum() <= entries for p in pending]
    # the first list is the query's source alone; any more rows start dense
    fits[0] = fits[0] and len(pending[0]) == 1
    assert took == sum(fits)
    if not n_seeds:
        assert [len(c) for c in changed[:-1]] == np.bincount(want[want > 0]).tolist()[1:]
    if case in ("rows_overflow", "entries_overflow"):
        # falls back, and comes back after a dense round
        assert any(a and not b for a, b in zip(fits, fits[1:]))
        assert any(b and not a for a, b in zip(fits, fits[1:]))
        over = [len(p) > rows for p in pending]
        assert any(over) == (case == "rows_overflow")
    elif n_seeds:
        assert not fits[0] and any(fits)  # starts dense
    elif "road" in case or case == "two_components":
        assert all(fits)
    else:
        assert any(fits) and not all(fits)  # the middle levels are dense ones


@pytest.mark.parametrize("add,below,dtype", [
    (1, None, np.int32), (3, None, np.int32),
    # a value an entry, and a threshold the next list stays under: SSSP's push
    ("weights", None, np.float32), ("weights", 40, np.float32), ("weights", 9, np.float64),
    ("block", 25, np.float32), (2, 5, np.int32)])
def test_the_primitive_alone(add, below, dtype):
    """`frontier_spans`, `frontier_relax` and `frontier_rows` with no loop
    around them, the candidate a row's value plus a constant or plus a value
    an entry, the next list whole or cut at a threshold, against a plain
    relaxation."""
    rng = np.random.default_rng(3)
    n, e, cap, room = 200, 700, 200, 768
    src = np.sort(rng.integers(0, n, e))
    dst = rng.integers(0, n, e)
    indptr = np.r_[0, np.cumsum(np.bincount(src, minlength=n))].astype(np.int32)
    nbr = jnp.asarray(np.r_[dst, np.zeros(1024 - e, dst.dtype)].astype(np.int32))
    absent = SENTINEL if dtype == np.int32 else np.inf
    dist = np.full(n, absent, dtype)
    dist[5] = 0
    weight = np.full(e, add, dtype) if isinstance(add, int) else rng.integers(1, 12, e).astype(dtype)
    given = add
    if not isinstance(add, int):
        given = jnp.asarray(np.r_[weight, np.zeros(1024 - e, dtype)])
        given = given[None] if add == "block" else given  # a shard's block

    @jax.jit
    def step(values, front):
        lo, count, total = segment.frontier_spans(front, jnp.asarray(indptr))
        return (*segment.frontier_relax(
            values, front, lo, count, nbr, room, add=given, below=below,
            absent=SENTINEL if dtype == np.int32 else None), total)

    values = jnp.asarray(dist)
    assert values.dtype == dtype
    listed = np.flatnonzero(dist != absent)
    front = segment.frontier_rows(values != absent, cap)
    for _ in range(8):
        values, front, active, total = step(values, front)
        assert active <= cap and total <= room
        # the rows listed push; a row off the list (over the threshold) waits
        new = dist.astype(np.float64)
        ok = np.isin(src, listed)
        np.minimum.at(new, dst[ok], dist[src][ok].astype(np.float64) + weight[ok])
        near = np.flatnonzero((new < dist) & (new < (np.inf if below is None else below)))
        assert int(active) == len(near)
        assert (np.asarray(front)[:int(active)] == near).all()
        assert (np.asarray(front)[int(active):] == n).all()
        dist, listed = new.astype(dtype), near
        assert np.asarray(values).dtype == dtype and (np.asarray(values) == dist).all()
    assert below is None or (dist[dist != absent] >= below).any()  # the threshold cut something off


# ---- what the offer leaves alone -------------------------------------------


# sha256 of the serial runner's lowered text as PR 39's `_make_runner` gave it
# (commit 61161c0, this container's jax), before the loop could follow a
# frontier: what every app that offers no such round still has to lower to.
# A change meant to move one of these texts replaces its line.
PARENT_TEXT = {
    "pagerank": "c505e22e4bcc26553357c3585c2463998be321f91feae0d3cf483078387bd8cb",
    "cdlp": "2019197f2c118ea799032ba6c8ff6d2b5a49c88a4cd7adfbcd45da81e751c815",
    "lcc": "b13a0f96b90a8d67991545a4bbff3367173179e43ee9a1c6a04feee4a798cafb",
    # since PR 44: the dense round reads the fragment's weights under its
    # mask, and the runner is handed no pre-masked copy of them
    "sssp": "3ec33ce7f0c86554f5692cf7974f03ee0959ddacd609d1e6fc725ba8ba68c228",
    "wcc": "32a688aaa47087505676a0425ff8a4f9be78c1972e592422e663cf0a5b9c0dba",
    "bfs": "8a957b1fd2d6780eb2efe01a64303a8016d9477fdba856efe0c1e3efd69f868b",  # road10
    "bfs_overlay": "7dba0f9665967474b85e6050bf768be2f052280ef93254a5f612966827b711b9",
    "bfs_four_fragments": "cdb00f7d100a6b4fd839375a0d0de4bce99b8f07a987abb80684270aa64b41d9",
}


def is_parents(text, case):
    return hashlib.sha256(text.encode()).hexdigest() == PARENT_TEXT[case]


def split(w, state):
    eph = frozenset(getattr(w.app, "ephemeral_keys", ()) or ())
    return ({k: v for k, v in state.items() if k not in eph},
            {k: v for k, v in state.items() if k in eph})


def serial_text(app, frag, **params):
    """The serial runner's lowered text."""
    w = Worker(app, frag)
    state = w._place_state(app.init_state(frag, **params))
    return w._make_runner(0)(state).lower(frag.dev, *split(w, state)).as_text()


@pytest.mark.parametrize("name,params", [
    ("pagerank", {}), ("cdlp", {}), ("lcc", {}), ("sssp", {"source": 6}), ("wcc", {}),
    ("bfs", {"source": 6})])
def test_apps_that_offer_no_such_round_keep_the_parents_runner(name, params, graph_cache, loaded,
                                                              budgets, monkeypatch):
    """Byte for byte, budgets within reach or not: PageRank, CDLP, LCC and
    WCC have no frontier round, and BFS offers none, at the budgets it ships
    with, on a graph where a dense round is the cheaper one; nor does SSSP
    under its dense floor (p2p-31 lies over the floor it ships with)."""
    if name != "bfs":
        budgets(8, 32)
    if name == "sssp":
        monkeypatch.setattr(sssp_module, "_DENSE_FLOOR", 1 << 30)
    frag = loaded("road10")[0] if name == "bfs" else graph_cache(1)
    got = serial_text(APP_REGISTRY[name](), frag, **params)
    assert is_parents(got, name) and ("stablehlo.case" not in got or name == "cdlp")
    assert f"tensor<{worker_module._RECORD_WORDS}xui32>" in got


def test_bfs_other_runners_never_see_the_round(graph_cache, budgets):
    """The batched and chunked runners and the serial runner under a dyn
    overlay lower to one text whether the serial runner of the same graph
    would follow its frontier or not, and none holds a conditional."""
    from libgrape_lite_tpu.dyn import DynGraph, RepackPolicy
    from tests.test_dyn import ADDS, _mutable_fragment

    one = graph_cache(1)
    dg = DynGraph(_mutable_fragment(), RepackPolicy(threshold=0.9, capacity=64))
    assert dg.ingest(ADDS)["mode"] == "overlay"

    def texts():
        out = {}
        w = Worker(APP_REGISTRY["bfs"](), one)
        state = w._place_state_batch(w.app.init_state(one, source=[6, 0]))
        out["batched"] = w._batched_runner_for(0, 2, state).lower(one.dev, *split(w, state)).as_text()
        assert w.app.frontier_budget is None
        w = Worker(APP_REGISTRY["bfs"](), one)
        state = w._place_state(w.app.init_state(one, source=6))
        out["chunked"] = w._chunk_runner_for(4, 0, state).lower(
            one.dev, *split(w, state), jnp.int32(1), jnp.int32(0)).as_text()
        out["overlay"] = serial_text(APP_REGISTRY["bfs"](), dg.fragment, source=0)
        assert is_parents(out["overlay"], "bfs_overlay")
        return out

    shipped = texts()
    budgets(8, 32)
    w = Worker(APP_REGISTRY["bfs"](), one)
    w.app.init_state(one, source=6)
    assert w.app.frontier_budget == (8, 32)  # the serial runner would
    assert texts() == shipped
    for name, text in shipped.items():
        assert "stablehlo.case" not in text, name


def test_several_fragments_keep_the_dense_round(graph_cache, budgets):
    budgets(8, 32)
    assert is_parents(serial_text(APP_REGISTRY["bfs"](), graph_cache(4), source=6),
                      "bfs_four_fragments")


# ---- what the round is made of ---------------------------------------------

ROWS, ENTRIES = 24, 96  # unlike vp (8,192), vp + 1 and Ep below


@pytest.fixture(scope="module")
def offered():
    """BFS's serial runner on p2p-31, following its frontier, and its parts."""
    from libgrape_lite_tpu.fragment.loader import LoadGraph, LoadGraphSpec
    from tests.conftest import dataset_path

    frag = LoadGraph(dataset_path("p2p-31.e"), dataset_path("p2p-31.v"), CommSpec(fnum=1),
                     LoadGraphSpec(directed=False, weighted=True, edata_dtype=np.float64))
    saved = bfs_module._FRONTIER_ROWS, bfs_module._FRONTIER_ENTRIES, bfs_module._DENSE_FLOOR
    bfs_module._FRONTIER_ROWS, bfs_module._FRONTIER_ENTRIES, bfs_module._DENSE_FLOOR = ROWS, ENTRIES, 0
    try:
        w = Worker(APP_REGISTRY["bfs"](), frag)
        state = w._place_state(w.app.init_state(frag, source=6))
        runner = w._runner_for(0, state)
        yield types.SimpleNamespace(frag=frag, w=w, state=state, runner=runner,
                                    lowered=runner.lower(frag.dev, state, {}))
    finally:
        bfs_module._FRONTIER_ROWS, bfs_module._FRONTIER_ENTRIES, bfs_module._DENSE_FLOOR = saved


def test_nothing_in_the_frontier_arm_is_as_wide_as_the_graph(offered):
    """No `scatter` and no `gather` of the round, or of the spans it reads
    first, has vp or Ep indices: B or C, whatever the graph's size."""
    frag = offered.frag.dev  # the shard's block, as the runner hands it over
    app = offered.w.app
    vp, ep = frag.vp, frag.ie.edge_nbr.shape[-1]
    assert len({ROWS, ENTRIES, vp, vp + 1, ep}) == 5

    def arm(frag, depth, front):
        lo, count, total = segment.frontier_spans(front, app.frontier_csr(frag).indptr)
        return app.inceval_frontier(frag, {"depth": depth}, front, lo, count), total

    text = jax.jit(arm).lower(frag, jnp.zeros(vp, jnp.int32), jnp.zeros(ROWS, jnp.int32)).as_text()
    ops = re.findall(r'"?stablehlo\.(gather|scatter)"?\(.*?\) -> ', text, flags=re.S)
    found = []
    for m in re.finditer(r'stablehlo\.(gather|scatter)"?\((.*)', text):
        kind, rest = m.groups()
        sig = text[m.start():text.index("->", text.index(" : (", m.start()))]
        operands = re.findall(r"tensor<([0-9x]*)x?[a-z0-9]+>", sig[sig.rindex(" : ("):])
        indices = [int(d) for d in operands[1].split("x") if d]
        found.append((kind, indices[0]))
    assert len(found) == len(ops) >= 6
    assert {k for k, _ in found} == {"gather", "scatter"}
    assert {width for _, width in found} == {ROWS, ENTRIES}
    assert sorted(w for k, w in found if k == "scatter") == [ROWS, ENTRIES]
    # and the whole runner holds both arms under one conditional a round
    assert "stablehlo.case" in offered.lowered.as_text()
    assert "grape.frontier.compact" in offered.lowered.as_text(debug_info=True)


def loop_carries(jaxpr):
    """The carried avals of every `while` under `jaxpr`."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "while":
            out.append([str(v.aval) for v in eqn.params["body_jaxpr"].jaxpr.outvars])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(loop_carries(sub))
    return out


def test_the_carry_gains_one_list_and_scalars(offered):
    w, frag, state = offered.w, offered.frag, offered.state
    ours = max(loop_carries(jax.make_jaxpr(offered.runner)(frag.dev, state, {}).jaxpr), key=len)
    # the plain loop's: BFS's one leaf, the vote, the round, the record
    for aval in [f"int32[{frag.vp}]", "int32[]", "int32[]"] + ["uint32[]"] * worker_module._RECORD_WORDS:
        ours.remove(aval)
    assert sorted(ours) == sorted([f"int32[{ROWS}]", "int32[]", "uint32[]"])


def test_frontier_rounds_is_federated_with_the_other_counts(graph_cache, budgets):
    frag = graph_cache(1)
    budgets(ROWS, ENTRIES)
    w = Worker(APP_REGISTRY["bfs"](), frag)
    w.query(source=6)
    budgets(ROWS, ENTRIES, floor=1 << 30)
    want = Worker(APP_REGISTRY["bfs"](), frag)
    want.query(source=6)
    dense = want.result_values()
    assert ROUND_STATS["frontier_rounds"] == 0
    assert w.result_values().tobytes() == dense.tobytes()
    stats = ROUND_STATS.snapshot()
    assert 0 < stats["frontier_rounds"] < stats["rounds"] == w.rounds == want.rounds
    assert federation.EXPECTED["rounds"] == "libgrape_lite_tpu.worker.worker"
    assert federation.snapshot("rounds") == stats and not federation.self_check()
    assert list(stats) == ["app", "rounds", "active_bits", "active_max", "active_sum", "frontier_rounds",
                           "advances", "pushed_sum"]
    # BFS carries no threshold: nothing steps, and the pushes are not counted
    assert stats["advances"] == stats["pushed_sum"] == 0
    json.dumps(stats)
