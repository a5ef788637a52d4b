"""The default `sssp`'s round that follows its frontier under a distance
threshold (near/far), inside the fused loop.

`models/sssp.py` offers `worker._frontier_loop` a list of rows and a bucket's
width; the loop carries the threshold, lists the improved rows under it,
moves it on where the list runs empty and falls back to the dense `inceval`
where a list, its entries or a bucket outgrow the budgets.  Here against the
benchmark's plain reference (SciPy's Dijkstra), the dense loop bit for bit,
and the plain NumPy relaxations of `tests/sssp_oracles.py` for what the
rounds count, on graphs small enough that the budgets have to come down with
them.
"""

import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import libgrape_lite_tpu.models.sssp as sssp_module
from benchmarks.graphs import kronecker, road_like
from benchmarks.graphs.csr import symmetric_csr
from benchmarks.references import sssp as sssp_reference
from libgrape_lite_tpu.fragment.edgecut import ShardedEdgecutFragment
from libgrape_lite_tpu.models import APP_REGISTRY
from libgrape_lite_tpu.ops import segment
from libgrape_lite_tpu.parallel.comm_spec import CommSpec
from libgrape_lite_tpu.vertex_map.partitioner import MapPartitioner
from libgrape_lite_tpu.vertex_map.vertex_map import VertexMap
from libgrape_lite_tpu.worker import worker as worker_module
from libgrape_lite_tpu.worker.worker import ROUND_STATS, Worker
from tests.sssp_oracles import bellman_ford, near_far
from tests.test_frontier_round import by_id, loop_carries, split

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def generator(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return json.load(f)["generator"]


ROAD, KRON = generator("road-like"), generator("g500-s21")


def fragment(n, src, dst, w, directed=False, fnum=1):
    oids = np.arange(n, dtype=np.int64)
    vm = VertexMap.build(oids, MapPartitioner(fnum, oids))
    return ShardedEdgecutFragment.build(
        CommSpec(fnum=fnum), vm, np.asarray(src, np.int64), np.asarray(dst, np.int64),
        w, directed=directed, edata_dtype=w.dtype.type)


def road(scale):
    return (1 << scale, *road_like.edges(ROAD, scale))


def kron10():
    return (1 << 10, *kronecker.edges(KRON, 10))


def two_components():
    n, src, dst, w = road(9)
    return 2 * n, np.r_[src, src + n], np.r_[dst, dst + n], np.r_[w, w]


def with_a_lone_vertex():
    """road9 and one vertex more, which has no edge."""
    n, src, dst, w = road(9)
    return n + 1, src, dst, w


# name: (graph, B, C, a bucket's width in largest weights, weight dtype, keys)
CASES = {
    "road9": (lambda: road(9), 64, 256, 16, np.float32, (0, 1, 2)),
    "road10": (lambda: road(10), 64, 256, 16, np.float32, (0, 1, 2)),
    "road11": (lambda: road(11), 128, 512, 16, np.float64, (0, 1, 2)),
    "kron10": (kron10, 64, 2048, 16, np.float32, (0,)),
    "label_setting": (lambda: road(10), 64, 256, 1, np.float64, (0,)),
    "rows_overflow": (lambda: road(10), 16, 256, 16, np.float32, (0,)),
    "entries_overflow": (lambda: road(10), 64, 48, 16, np.float32, (0,)),
    "bucket_overflow": (lambda: road(10), 24, 256, 64, np.float32, (0,)),
    "two_components": (two_components, 64, 256, 16, np.float32, (0,)),
    "lone_source": (with_a_lone_vertex, 64, 256, 16, np.float32, ("lone",)),
    "narrower_than_spacing": (lambda: road(9), 64, 256, 1e-9, np.float32, (0,)),
}
KEYED = [(name, key) for name, case in CASES.items() for key in case[-1]]


@pytest.fixture(scope="module")
def loaded():
    made = {}

    def get(name):
        if name not in made:
            graph, *_, dtype, _ = CASES[name]
            n, src, dst, w = graph()
            made[name] = (fragment(n, src, dst, w.astype(dtype)), n, src, dst, w)
        return made[name]

    return get


@pytest.fixture
def budgets(monkeypatch):
    def set_to(rows, entries, weights=16, floor=0):
        monkeypatch.setattr(sssp_module, "_FRONTIER_ROWS", rows)
        monkeypatch.setattr(sssp_module, "_FRONTIER_ENTRIES", entries)
        monkeypatch.setattr(sssp_module, "_BUCKET_WEIGHTS", weights)
        monkeypatch.setattr(sssp_module, "_DENSE_FLOOR", floor)

    return set_to


NEVER = 1 << 30  # a dense floor no graph reaches


def ask(frag, source):
    w = Worker(APP_REGISTRY["sssp"](), frag)
    w.query(source=source)
    return by_id(frag, w.result_values()), w.rounds, ROUND_STATS.snapshot(), w


def oracle_csr(frag):
    """The fragment's own CSR on the host, rows and neighbours by lid (one
    fragment: a pid is a lid), pads cut off."""
    ie = frag.host_ie[0]
    real = int(ie.indptr[-1])
    return (ie.indptr.astype(np.int64), ie.edge_nbr[:real].astype(np.int64),
            ie.edge_w[:real])


@pytest.mark.parametrize("case,key", KEYED)
def test_near_far_reaches_the_dense_loops_distances(case, key, loaded, budgets):
    _, rows, entries, weights, dtype, _ = CASES[case]
    frag, n, src, dst, w = loaded(case)
    source = n - 1 if key == "lone" else int(src[17 * key])
    minw, _ = symmetric_csr(n, src, dst, w)
    want = sssp_reference.reference(types.SimpleNamespace(minw=minw), {"source": source})

    budgets(rows, entries, weights, floor=NEVER)
    dense, dense_rounds, dense_stats, worker = ask(frag, source)
    assert worker.app.frontier_budget is None and worker.app.frontier_step is None
    assert dense_stats["frontier_rounds"] == dense_stats["advances"] == 0
    budgets(rows, entries, weights)
    got, rounds, stats, worker = ask(frag, source)
    assert worker.app.frontier_budget == (rows, entries)
    assert worker.app.frontier_step == weights * float(w.max())

    # exact on every vertex (integer weights: every path sum is), and the
    # dense loop's bytes
    assert got.dtype == dtype and (got != want).sum() == 0
    assert got.tobytes() == dense.tobytes()
    if case == "two_components":
        assert np.isinf(got).sum() == n // 2  # inf stays inf, and the loop ends
    if case == "lone_source":
        # the source's push, and the look that finds no row left
        assert rounds == 2 and np.isinf(got).sum() == n - 1 and got[source] == 0

    # what the rounds counted, against the plain relaxations
    indptr, nbr, weight = oracle_csr(frag)
    start = np.full(frag.vp, np.inf, dtype)
    start[frag.oid_to_pid(np.array([source]))] = 0
    fixed, told = near_far(indptr, nbr, weight, start, rows, entries, weights * float(w.max()))
    assert fixed[:n].tobytes() == np.asarray(worker.result_values())[0, :n].tobytes()
    assert rounds == stats["rounds"] == told["rounds"]
    assert {k: stats[k] for k in ("frontier_rounds", "advances", "pushed_sum")} == {
        k: told[k] for k in ("frontier_rounds", "advances", "pushed_sum")}
    assert stats["active_max"] == max(told["active"])
    assert stats["active_sum"] == sum(told["active"])
    assert stats["pushed_sum"] == 1 + stats["active_sum"]  # a list is pushed the round after
    bits = np.bincount([int(a).bit_length() for a in told["active"]], minlength=33)
    assert stats["active_bits"] == bits.tolist()
    # the dense loop is the hop-synchronous Bellman-Ford
    _, hops, pushed, widest = bellman_ford(indptr, nbr, weight, int(np.flatnonzero(start == 0)[0]))
    assert dense_rounds == hops and dense_stats["active_sum"] + 1 == pushed
    assert dense_stats["active_max"] == max(widest[0], 1) or hops == 1

    took, n_rounds = stats["frontier_rounds"], stats["rounds"]
    if case.startswith("road") or case in ("two_components", "label_setting"):
        # every push follows its frontier (the other rounds are the
        # threshold's steps and the last look), and a vertex pushes less often
        assert took == n_rounds - stats["advances"] - 1 and stats["pushed_sum"] <= pushed
        assert stats["advances"] >= (case != "road9")
    elif case in ("rows_overflow", "entries_overflow", "bucket_overflow", "kron10"):
        # falls back, mid-bucket, and comes back
        assert 0 < took < n_rounds
    if case == "bucket_overflow":
        # a bucket that holds more rows than the list: the vote says so
        assert stats["active_max"] > rows
    if case == "label_setting":
        assert stats["pushed_sum"] <= 1.2 * n
    if case == "narrower_than_spacing":
        # a bucket under the values' spacing: the threshold still moves on,
        # a value at a time
        assert stats["advances"] == len(np.unique(got)) - 1


def test_a_wider_bucket_trades_advances_for_pushes(loaded, budgets):
    frag, n, src, dst, w = loaded("road11")
    seen = []
    for weights in (1, 4, 16, 64):
        budgets(1 << 11, 1 << 13, weights)
        _, rounds, stats, _ = ask(frag, int(src[0]))
        assert stats["frontier_rounds"] == rounds - stats["advances"] - 1
        seen.append((stats["advances"], stats["pushed_sum"]))
    assert [a for a, _ in seen] == sorted((a for a, _ in seen), reverse=True)
    assert [p for _, p in seen] == sorted(p for _, p in seen)
    assert seen[0] != seen[-1]


# ---- what the offer rests on, and what it leaves alone ---------------------


def test_no_query_holds_a_second_copy_of_the_weights(loaded, budgets):
    """Offered the frontier round or not, a state is the distances alone:
    the dense round reads the fragment's own weights under its own mask."""
    frag = loaded("road10")[0]
    ep = frag.dev.ie.edge_nbr.shape[-1]
    budgets(64, 256)
    app = APP_REGISTRY["sssp"]()
    state = app.init_state(frag, source=5)
    assert app.frontier_budget == (64, 256) and set(state) == {"dist"}
    assert not app.ephemeral_keys
    budgets(64, 256, floor=NEVER)
    state = app.init_state(frag, source=5)
    assert app.frontier_budget is None and set(state) == {"dist"}
    assert not app.ephemeral_keys
    assert all(np.shape(v)[-1] < ep for v in state.values())


def test_zero_weights_are_offered_nothing(budgets):
    n, src, dst, w = road(9)
    frag = fragment(n, src, dst, np.zeros(len(src), np.float32))
    budgets(64, 256)
    got, rounds, stats, worker = ask(frag, 3)
    assert worker.app.frontier_budget is None and stats["frontier_rounds"] == 0
    assert (got == 0).all()


@pytest.mark.parametrize("how", ["batched", "four_fragments", "directed", "overlay", "chunked"])
def test_every_other_query_keeps_the_dense_round(how, budgets):
    """Batched lanes, several fragments, directed fragments, the dyn overlay
    and the chunked runner: no offer (the chunked runner never asks), and one
    lowered text whether the budgets are within reach or not."""
    from libgrape_lite_tpu.dyn import DynGraph, RepackPolicy
    from tests.test_dyn import ADDS, _mutable_fragment

    n, src, dst, w = road(10)
    w = w.astype(np.float32)

    def text():
        if how == "overlay":
            dg = DynGraph(_mutable_fragment(), RepackPolicy(threshold=0.9, capacity=64))
            assert dg.ingest(ADDS)["mode"] == "overlay"
            frag = dg.fragment
        else:
            frag = fragment(n, src, dst, w, directed=how == "directed",
                            fnum=4 if how == "four_fragments" else 1)
        worker = Worker(APP_REGISTRY["sssp"](), frag)
        if how == "batched":
            state = worker._place_state_batch(worker.app.init_state(frag, source=[6, 0]))
            runner = worker._batched_runner_for(0, 2, state)
            lowered = runner.lower(frag.dev, *split(worker, state))
        elif how == "chunked":
            state = worker._place_state(worker.app.init_state(frag, source=6))
            lowered = worker._chunk_runner_for(4, 0, state).lower(
                frag.dev, *split(worker, state), jnp.int32(1), jnp.int32(0))
        else:
            state = worker._place_state(worker.app.init_state(frag, source=0))
            lowered = worker._make_runner(0)(state).lower(frag.dev, *split(worker, state))
        ep = frag.dev.ie.edge_nbr.shape[-1]
        assert all(v.shape[-1] < ep for v in state.values())
        assert worker.app.ephemeral_keys == {k for k in state if k.startswith("dyn_ie_")}
        return lowered.as_text(), worker.app.frontier_budget

    budgets(64, 256, floor=NEVER)
    shipped, offer = text()
    assert offer is None
    budgets(64, 256)
    got, offer = text()
    assert "stablehlo.case" not in got
    # the chunked runner never asks for the offer its state was made under,
    # and that state is the dense one's: the distances
    assert offer == ((64, 256) if how == "chunked" else None) and got == shipped


def pushing_csr(frag, state):
    """The fragments' own pull entries, and the overlay's staged ones, turned
    about into one CSR over pids whose rows push along their entries: what
    `tests/sssp_oracles.py` relaxes.  Pads are cut off."""
    vp, rows, nbrs, ws = frag.vp, [], [], []
    for f in range(frag.fnum):
        ie = frag.host_ie[f]
        real = int(ie.indptr[-1])
        assert ie.edge_mask[:real].all() and not ie.edge_mask[real:].any()
        rows.append(f * vp + np.repeat(np.arange(vp), np.diff(ie.indptr)))
        nbrs.append(ie.edge_nbr[:real].astype(np.int64))
        ws.append(ie.edge_w[:real])
        if "dyn_ie_nbr" in state:
            live = np.asarray(state["dyn_ie_mask"][f], bool)
            rows.append(f * vp + np.asarray(state["dyn_ie_src"][f], np.int64)[live])
            nbrs.append(np.asarray(state["dyn_ie_nbr"][f], np.int64)[live])
            ws.append(np.asarray(state["dyn_ie_w"][f])[live])
    rows, nbrs, ws = map(np.concatenate, (rows, nbrs, ws))
    order = np.argsort(nbrs, kind="stable")
    indptr = np.r_[0, np.cumsum(np.bincount(nbrs, minlength=frag.fnum * vp))]
    return indptr, rows[order], ws[order]


@pytest.mark.parametrize("how", ["single", "batched", "chunked", "two_fragments",
                                 "two_fragments_mirrored", "overlay"])
def test_a_state_is_its_lanes_and_the_answer_the_oracles(how, monkeypatch):
    """Whatever runs the query, `init_state` hands the host a lane of
    distances a source and what a mirror plan or an overlay adds, nothing of
    the fragment's own (its weights are read where they lie), and the
    distances are the plain Bellman-Ford's bit for bit."""
    from libgrape_lite_tpu.dyn import DynGraph, RepackPolicy
    from libgrape_lite_tpu.dyn.ingest import overlay_state_entries
    from libgrape_lite_tpu.guard.config import GuardConfig
    from tests.test_dyn import ADDS, _mutable_fragment

    monkeypatch.setenv("GRAPE_EXCHANGE", "mirror" if how.endswith("mirrored") else "gather")
    if how == "overlay":
        dg = DynGraph(_mutable_fragment(), RepackPolicy(threshold=0.9, capacity=64))
        assert dg.ingest(ADDS)["mode"] == "overlay"
        frag = dg.fragment
    else:
        n, src, dst, w = road(10)
        frag = fragment(n, src, dst, w.astype(np.float32),
                        fnum=2 if how.startswith("two_fragments") else 1)
    sources = [6, 0, 17] if how == "batched" else [6]
    worker = Worker(APP_REGISTRY["sssp"](), frag)
    app = worker.app

    state = app.init_state(frag, source=sources if how == "batched" else sources[0])
    dist = state["dist"]
    assert dist.shape == (len(sources),) * (how == "batched") + (frag.fnum, frag.vp)
    added = {}
    if how == "overlay":
        added = overlay_state_entries(frag, "ie", dist.dtype, "dyn_ie_")
    elif how.endswith("mirrored"):
        added = app._mx.state_entries("mx_")
    assert set(state) == {"dist"} | set(added) and app.ephemeral_keys == set(added)
    assert (how in ("overlay", "two_fragments_mirrored")) == bool(added)
    assert sum(np.asarray(v).nbytes for v in state.values()) <= (
        len(sources) * frag.fnum * frag.vp * dist.dtype.itemsize
        + sum(np.asarray(v).nbytes for v in added.values()))

    if how == "batched":
        worker.query_batch([{"source": s} for s in sources])
        got = [worker.batch_result_values(lane) for lane in range(len(sources))]
    elif how == "chunked":
        worker.query(guard=GuardConfig(policy="halt", every=3), source=sources[0])
        assert "chunk" in {k[0] for k in worker._runner_cache}
        got = [worker.result_values()]
    else:
        worker.query(source=sources[0])
        got = [worker.result_values()]
    indptr, nbr, weight = pushing_csr(frag, state)
    for source, values in zip(sources, got):
        pid = int(frag.oid_to_pid(np.array([source]))[0])
        want, *_ = bellman_ford(indptr, nbr, weight.astype(dist.dtype), pid)
        assert values.dtype == want.dtype
        assert np.asarray(values).reshape(-1).tobytes() == want.tobytes()
        assert np.isfinite(want).sum() > frag.vp // 2


# ---- what the round is made of ---------------------------------------------

ROWS, ENTRIES = 24, 96


@pytest.fixture
def offered(loaded, budgets):
    frag = loaded("road10")[0]
    budgets(ROWS, ENTRIES)
    w = Worker(APP_REGISTRY["sssp"](), frag)
    state = w._place_state(w.app.init_state(frag, source=6))
    return types.SimpleNamespace(frag=frag, w=w, state=state, runner=w._runner_for(0, state))


def test_nothing_in_the_frontier_arm_is_as_wide_as_the_graph(offered):
    frag, app = offered.frag.dev, offered.w.app
    vp, ep = frag.vp, frag.ie.edge_nbr.shape[-1]
    assert len({ROWS, ENTRIES, vp, vp + 1, ep}) == 5

    def arm(frag, dist, front, below):
        lo, count, total = segment.frontier_spans(front, app.frontier_csr(frag).indptr)
        return app.inceval_frontier(frag, {"dist": dist}, front, lo, count, below), total

    text = jax.jit(arm).lower(frag, jnp.zeros(vp, jnp.float32), jnp.zeros(ROWS, jnp.int32),
                              jnp.float32(1)).as_text()
    found = []
    for m in re.finditer(r'stablehlo\.(gather|scatter)"?\(', text):
        sig = text[m.start():text.index("->", text.index(" : (", m.start()))]
        operands = re.findall(r"tensor<([0-9x]*)x?[a-z0-9]+>", sig[sig.rindex(" : ("):])
        found.append((m.group(1), int(operands[1].split("x")[0])))
    # the offsets' pairs, the rows' values, the pairs at the slots, the
    # neighbours, their weights, their values; the openers and the fold
    assert sorted(found) == sorted(
        [("gather", ROWS)] * 2 + [("gather", ENTRIES)] * 4
        + [("scatter", ROWS), ("scatter", ENTRIES)])
    lowered = offered.runner.lower(offered.frag.dev, offered.state, {})
    assert lowered.as_text().count("stablehlo.case") >= 2
    named = lowered.as_text(debug_info=True)
    assert "grape.frontier.advance" in named and "grape.frontier.compact" in named


def test_the_carry_gains_a_list_a_threshold_and_scalars(offered):
    frag = offered.frag
    ours = max(loop_carries(jax.make_jaxpr(offered.runner)(frag.dev, offered.state, {}).jaxpr),
               key=len)
    # the plain loop's: SSSP's one leaf, the vote, the round, the record
    for aval in ([f"float32[{frag.vp}]", "int32[]", "int32[]"]
                 + ["uint32[]"] * worker_module._RECORD_WORDS):
        ours.remove(aval)
    # the list and its length; the threshold; the rounds that followed their
    # frontier, the threshold's steps, the rows pushed in two words
    assert sorted(ours) == sorted([f"int32[{ROWS}]", "int32[]", "float32[]"] + ["uint32[]"] * 4)
