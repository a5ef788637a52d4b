"""dyn/ — dynamic-graph runtime (ISSUE 7 acceptance).

Pins: staged additive deltas ride the overlay side-path with results
byte-identical to a cold query on the rebuilt mutated graph (SSSP/BFS/
WCC, fnum 1 and 2); below the repack threshold `ServeSession.ingest`
triggers ZERO XLA recompiles (compile_events / runner_cache_stats)
while queries still see the delta; repacks are
counted recompile events; `Worker.query_incremental` after staged
deltas equals a cold full query byte-for-byte — including under
guard=halt and through a checkpoint/kill/resume crossing the mutation
boundary; the guard watchdog resets its digest history at mutation
boundaries (a pre-mutation digest match is not a cycle proof); the
rebuild-on-mutate path honors GRAPE_VALIDATE_LOAD=1; the serve CLI
ingests a delta stream while a query stream runs.
"""

import json

import numpy as np
import pytest

from tests.conftest import dataset_path

ADDS = [("a", 0, 17, 0.01), ("a", 17, 31, 0.01), ("a", 3, 29, 0.05)]


def build_graph(fnum, n=32, seed=3, edge_factor=4):
    """Small weighted undirected graph, built mutable."""
    from libgrape_lite_tpu.fragment.edgecut import ShardedEdgecutFragment
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec
    from libgrape_lite_tpu.vertex_map.partitioner import MapPartitioner
    from libgrape_lite_tpu.vertex_map.vertex_map import VertexMap

    rng = np.random.default_rng(seed)
    e = edge_factor * n
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    w = rng.uniform(0.5, 2.0, e)
    oids = np.arange(n, dtype=np.int64)
    vm = VertexMap.build(oids, MapPartitioner(fnum, oids))
    return ShardedEdgecutFragment.build(
        CommSpec(fnum=fnum), vm, src, dst, w, directed=False,
        retain_edge_list=True,
    )


def build_path(fnum, n=24):
    """Path 0-1-...-(n-1), unit weights — diameter n-1, so cold SSSP
    pays ~n rounds and a localized delta shows the incremental win."""
    from libgrape_lite_tpu.fragment.edgecut import ShardedEdgecutFragment
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec
    from libgrape_lite_tpu.vertex_map.partitioner import MapPartitioner
    from libgrape_lite_tpu.vertex_map.vertex_map import VertexMap

    src = np.arange(n - 1)
    dst = np.arange(1, n)
    w = np.ones(n - 1)
    oids = np.arange(n, dtype=np.int64)
    vm = VertexMap.build(oids, MapPartitioner(fnum, oids))
    return ShardedEdgecutFragment.build(
        CommSpec(fnum=fnum), vm, src, dst, w, directed=False,
        retain_edge_list=True,
    )


def oid_values(worker) -> dict:
    """oid -> assembled value (layout-independent comparison)."""
    vals = worker.result_values()
    frag = worker.fragment
    out = {}
    for f in range(frag.fnum):
        for o, v in zip(
            frag.inner_oids(f).tolist(),
            vals[f, : frag.inner_vertices_num(f)].tolist(),
        ):
            out[o] = v
    return out


def oid_bytes(worker) -> bytes:
    """Byte-exact, layout-independent: values sorted by oid."""
    d = oid_values(worker)
    return np.asarray([d[k] for k in sorted(d)]).tobytes()


# ---- delta buffer --------------------------------------------------------


def test_delta_buffer_typed_and_bounded():
    from libgrape_lite_tpu.dyn import (
        DeltaBuffer, DeltaOverflowError, parse_ops_line,
    )

    buf = DeltaBuffer(capacity=4)
    assert buf.stage([("a", 1, 2, 0.5), ("d", 3, 4), ("u", 5, 6, 1.0)]) == 3
    assert buf.n_edge_ops == 3 and not buf.additive_only
    buf.add_vertex(9)
    with pytest.raises(DeltaOverflowError):
        buf.add_edge(7, 8)
    s = buf.summary()
    assert (s.n_add_edges, s.n_remove_edges, s.n_update_edges,
            s.n_add_vertices) == (1, 1, 1, 1)
    assert set(s.touched_oids) == {1, 2, 3, 4, 5, 6, 9}
    assert s.n_edge_ops == 3 and s.n_ops == 4

    add_only = DeltaBuffer()
    add_only.stage([("a", 1, 2, 0.5)])
    assert add_only.additive_only
    assert add_only.delta_ratio(100) == pytest.approx(0.01)

    assert parse_ops_line("a 3 4 1.5") == ("a", 3, 4, 1.5)
    assert parse_ops_line("d 3 4") == ("d", 3, 4)
    assert parse_ops_line("# comment") is None
    with pytest.raises(ValueError, match="unknown delta op"):
        parse_ops_line("x 1 2")
    # review regression: a truncated update must not silently zero
    # the edge weight
    with pytest.raises(ValueError, match="malformed 'u' op"):
        parse_ops_line("u 3 5")
    # ... and neither must a weightless add in a WEIGHTED stream
    # (an unweighted stream legitimately omits it)
    with pytest.raises(ValueError, match="malformed 'a' op"):
        parse_ops_line("a 3 5", weighted=True)
    assert parse_ops_line("a 3 5", weighted=False) == ("a", 3, 5, 0.0)
    # every truncated form gets the grammar error, never an IndexError
    for bad in ("d 5", "a 5", "av", "dv", "u 3"):
        with pytest.raises(ValueError, match="malformed"):
            parse_ops_line(bad)

    # review regression: stage() is atomic against the bound — an
    # overflowing batch stages NOTHING, so the repack-and-retry
    # recovery never folds a half-staged prefix twice
    small = DeltaBuffer(capacity=2)
    with pytest.raises(DeltaOverflowError):
        small.stage([("a", 1, 2, 0.5), ("a", 2, 3, 0.5),
                     ("a", 3, 4, 0.5)])
    assert small.n_ops == 0
    # ... and atomic against malformed input: the valid prefix must
    # not stay staged (a retry after fixing the batch would fold it
    # twice as a duplicate edge)
    with pytest.raises(ValueError, match="malformed delta op"):
        small.stage([("a", 1, 2, 0.5), ("x", 3)])
    assert small.n_ops == 0


# ---- overlay: consistent view, byte-identical to a rebuild ---------------


@pytest.mark.parametrize("fnum", [1, 2])
@pytest.mark.parametrize("app_name", ["sssp", "bfs", "wcc"])
def test_overlay_byte_identity_vs_rebuild(fnum, app_name):
    """A query over base CSR + overlay must equal a cold query on the
    rebuilt mutated graph byte-for-byte: the overlay merges extra min
    candidates at the fold, and min is associative/exact."""
    from libgrape_lite_tpu.dyn import DynGraph, RepackPolicy
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.worker.worker import Worker

    kw = {} if app_name == "wcc" else {"source": 0}
    frag = build_graph(fnum)
    dg = DynGraph(frag, RepackPolicy(threshold=0.9, capacity=64))
    rep = dg.ingest(ADDS)
    assert rep["mode"] == "overlay" and dg.fragment is frag

    dg2 = DynGraph(build_graph(fnum), RepackPolicy(threshold=0.0))
    assert dg2.ingest(ADDS)["mode"] == "repack"

    w_ov = Worker(APP_REGISTRY[app_name](), dg.fragment)
    w_ov.query(**kw)
    w_cold = Worker(APP_REGISTRY[app_name](), dg2.fragment)
    w_cold.query(**kw)
    assert oid_bytes(w_ov) == oid_bytes(w_cold)


def test_empty_overlay_is_inert():
    """A dyn-managed fragment with nothing staged must answer exactly
    like an unmanaged one (the always-attached empty overlay adds
    masked slots only)."""
    from libgrape_lite_tpu.dyn import DynGraph, RepackPolicy
    from libgrape_lite_tpu.models import SSSP
    from libgrape_lite_tpu.worker.worker import Worker

    plain = build_graph(2)
    managed = build_graph(2)
    DynGraph(managed, RepackPolicy())
    w1 = Worker(SSSP(), plain)
    w1.query(source=0)
    w2 = Worker(SSSP(), managed)
    w2.query(source=0)
    assert oid_bytes(w1) == oid_bytes(w2)


def test_undirected_removal_applies_both_orientations():
    """Review regression: the retained edge list stores each
    undirected edge in ONE arbitrary orientation — a removal staged in
    the REVERSED orientation must still take the edge out (the
    reference's both-orientations rule, ev_fragment_mutator.h)."""
    from libgrape_lite_tpu.dyn import DynGraph, RepackPolicy
    from libgrape_lite_tpu.models import SSSP
    from libgrape_lite_tpu.worker.worker import Worker

    frag = build_path(1, n=8)  # edge list stores (i, i+1)
    dg = DynGraph(frag, RepackPolicy(threshold=0.0))
    rep = dg.ingest([("d", 5, 4)])  # reversed orientation of (4, 5)
    assert rep["mode"] == "repack"
    w = Worker(SSSP(), dg.fragment)
    w.query(source=0)
    vals = oid_values(w)
    assert vals[4] == 4.0
    assert vals[5] == np.inf, "reversed-orientation removal no-opped"


def test_stepwise_rejects_stale_view():
    """Review regression: the public stepwise/profiling surface must
    reject a staged dyn view like query() and query_batch() do."""
    from libgrape_lite_tpu.dyn import DynGraph, RepackPolicy
    from libgrape_lite_tpu.models import PageRank
    from libgrape_lite_tpu.worker.worker import Worker

    dg = DynGraph(build_graph(1), RepackPolicy(threshold=0.9,
                                               capacity=64))
    dg.ingest(ADDS)
    w = Worker(PageRank(max_round=3), dg.fragment)
    with pytest.raises(ValueError, match="no dyn-overlay contract"):
        w.query_stepwise()


def test_nonadditive_and_unknown_endpoints_force_repack():
    from libgrape_lite_tpu.dyn import DynGraph, RepackPolicy

    dg = DynGraph(build_graph(1), RepackPolicy(threshold=0.9))
    rep = dg.ingest([("d", 0, 1)])
    assert rep["mode"] == "repack"
    assert "non-additive" in rep["reason"]

    dg2 = DynGraph(build_graph(1), RepackPolicy(threshold=0.9))
    rep2 = dg2.ingest([("av", 999), ("a", 0, 999, 1.0)])
    assert rep2["mode"] == "repack"
    # the new vertex is queryable after the fold
    assert int(dg2.fragment.oid_to_pid(np.array([999]))[0]) >= 0


def test_stream_longer_than_capacity_folds_and_continues():
    """Review regression: a delta stream longer than the buffer
    capacity must degrade to amortized counted folds, not raise
    DeltaOverflowError out of a live ingest loop — every op lands."""
    from libgrape_lite_tpu.dyn import DynGraph, RepackPolicy
    from libgrape_lite_tpu.models import SSSP
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession
    from libgrape_lite_tpu.worker.worker import Worker

    sess = ServeSession(
        build_graph(1, n=64, edge_factor=8),
        policy=BatchPolicy(max_batch=1),
        # tiny capacity + never-by-ratio: only the capacity fold fires
        dyn=RepackPolicy(threshold=10.0, capacity=8),
    )
    rng = np.random.default_rng(11)
    ops = [("a", int(s), int(d), 1.0) for s, d in
           zip(rng.integers(0, 64, 20), rng.integers(0, 64, 20))]
    for lo in range(0, 20, 5):
        sess.ingest(ops[lo:lo + 5])
    assert sess.stats["ingested_ops"] == 20
    assert sess.stats["repacks"] >= 2  # capacity folds, all counted
    # everything landed: total edge count grew by exactly the stream
    # (pending overlay edges + folded edges)
    pending = sess.dyn.buffer.n_edge_ops
    assert sess.fragment.total_edges_num + pending == 64 * 8 + 20
    res = sess.serve([("sssp", {"source": 0})])
    assert res[0].ok


def test_worker_rejects_stale_view_for_uncontracted_app():
    """An app with no overlay contract must not silently run against
    the stale base graph while deltas are staged."""
    from libgrape_lite_tpu.dyn import DynGraph, RepackPolicy
    from libgrape_lite_tpu.models import PageRank
    from libgrape_lite_tpu.worker.worker import Worker

    dg = DynGraph(build_graph(1), RepackPolicy(threshold=0.9,
                                               capacity=64))
    dg.ingest(ADDS)
    w = Worker(PageRank(max_round=3), dg.fragment)
    with pytest.raises(ValueError, match="no dyn-overlay contract"):
        w.query()
    # after folding, the same worker runs
    dg.fold_now()
    w.fragment = dg.fragment
    w.query()
    assert w.rounds == 3


# ---- serve ingest: zero recompiles ---------------------------------------


def _mutable_fragment():
    """f32-weighted single-shard fragment, built mutable — the
    test_serve counter idiom."""
    from libgrape_lite_tpu.fragment.edgecut import ShardedEdgecutFragment
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec
    from libgrape_lite_tpu.vertex_map.partitioner import MapPartitioner
    from libgrape_lite_tpu.vertex_map.vertex_map import VertexMap

    rng = np.random.default_rng(21)
    n, e = 700, 6000
    src, dst = rng.integers(0, n, e), rng.integers(0, n, e)
    w = rng.uniform(0.5, 2.0, e).astype(np.float32)
    oids = np.arange(n, dtype=np.int64)
    vm = VertexMap.build(oids, MapPartitioner(1, oids))
    return ShardedEdgecutFragment.build(
        CommSpec(fnum=1), vm, src, dst, w, directed=False,
        retain_edge_list=True,
    )


def test_session_ingest_below_threshold_zero_recompile(monkeypatch):
    """THE acceptance pin: an overlay ingest triggers zero XLA
    compilation — the post-ingest query is a pure cache hit AND sees
    the delta."""
    from libgrape_lite_tpu.dyn import RepackPolicy
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession

    sess = ServeSession(
        _mutable_fragment(), policy=BatchPolicy(max_batch=1),
        dyn=RepackPolicy(threshold=0.5, capacity=128),
    )
    r1 = sess.serve([("sssp", {"source": 0})])
    assert r1[0].ok, r1[0].error
    s1 = sess.cache_stats()

    rep = sess.ingest([("a", 0, 600, 0.001), ("a", 600, 650, 0.001)])
    assert rep["mode"] == "overlay"
    # zero XLA compilation pinned on the real compile stream
    # (analysis.compile_events) — the counter a per-dispatch re-jit
    # cannot hide from
    from libgrape_lite_tpu.analysis import compile_events

    with compile_events() as ev:
        r2 = sess.serve([("sssp", {"source": 0})])
    assert r2[0].ok, r2[0].error
    assert ev.compiles == 0, ("ingest caused a recompile", ev.events)
    s2 = sess.cache_stats()
    assert s2["runner"]["hits"] > s1["runner"]["hits"]
    assert s2["runner"]["misses"] == s1["runner"]["misses"], (s1, s2)
    # the delta is visible, not a stale cache reuse
    assert r1[0].values.tobytes() != r2[0].values.tobytes()

    # past the policy: a repack is a COUNTED recompile event
    rng = np.random.default_rng(9)
    big = [("a", int(s), int(d), 1.0) for s, d in
           zip(rng.integers(0, 700, 120), rng.integers(0, 700, 120))]
    assert sess.ingest(big)["mode"] == "repack"
    r3 = sess.serve([("sssp", {"source": 0})])
    assert r3[0].ok, r3[0].error
    s3 = sess.cache_stats()
    assert s3["runner"]["misses"] > s2["runner"]["misses"]
    assert sess.stats["repacks"] == 1
    assert sess.stats["overlay_applies"] == 1


@pytest.mark.parametrize("app_name", ["sssp", "bfs"])
def test_overlay_pull_through_the_kernel(app_name, pull_kernel):
    """With an overlay attached a round pulls twice, the base CSR's
    entries and the overlay's slots (which are not whole 128s): on a
    TPU backend both gathers are the kernel's, and the answer has the
    bytes of the round through XLA's gather.  BFS compiles the
    interpreted kernel, SSSP puts `full[nbr]` behind the choice
    (tests/conftest.py)."""
    from libgrape_lite_tpu.dyn import DynGraph, RepackPolicy
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.worker.worker import Worker
    from tests.conftest import gather_took

    dg = DynGraph(_mutable_fragment(),
                  RepackPolicy(threshold=0.9, capacity=64))
    assert dg.ingest(ADDS)["mode"] == "overlay"
    want = Worker(APP_REGISTRY[app_name](), dg.fragment)
    want.query(source=0)
    calls = pull_kernel("interpreted" if app_name == "bfs" else "stand_in")
    got = Worker(APP_REGISTRY[app_name](), dg.fragment)
    took = gather_took(lambda: got.query(source=0))
    assert took == {"kernel": 2, "xla": 0}
    ep = dg.fragment.host_ie[0].edge_nbr.shape[0]
    assert sorted(c[2][0] for c in calls) == sorted([64, ep])
    assert got.rounds == want.rounds
    assert got.result_values().tobytes() == want.result_values().tobytes()


def test_session_forced_repack_for_uncontracted_app():
    """Dispatching an app without an overlay contract while deltas are
    staged folds first — a counted forced repack, never a stale read."""
    from libgrape_lite_tpu.dyn import RepackPolicy
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession

    sess = ServeSession(
        build_graph(2), policy=BatchPolicy(max_batch=1),
        dyn=RepackPolicy(threshold=0.9, capacity=64),
    )
    assert sess.ingest(ADDS)["mode"] == "overlay"
    res = sess.serve([("pagerank", {})])
    assert res[0].ok, res[0].error
    assert sess.stats["forced_repacks"] == 1
    assert sess.dyn.overlay_count == 0


def test_session_without_dyn_rejects_ingest(graph_cache):
    from libgrape_lite_tpu.serve import ServeSession

    sess = ServeSession(graph_cache(2))
    with pytest.raises(RuntimeError, match="without dyn="):
        sess.ingest([("a", 1, 2, 0.5)])


def test_guarded_batch_rejects_stale_view():
    """Review regression: the GUARDED query_batch path must reject a
    stale dyn view exactly like the plain one (the check used to sit
    after the guard routing, so guarded batches silently computed on
    the pre-delta graph)."""
    from libgrape_lite_tpu.dyn import DynGraph, RepackPolicy
    from libgrape_lite_tpu.models import PageRank
    from libgrape_lite_tpu.worker.worker import Worker

    dg = DynGraph(build_graph(2), RepackPolicy(threshold=0.9,
                                               capacity=64))
    dg.ingest(ADDS)
    w = Worker(PageRank(max_round=3), dg.fragment)
    with pytest.raises(ValueError, match="no dyn-overlay contract"):
        w.query_batch([{"source": 0}, {"source": 1}], guard="halt")


def test_session_failed_forced_repack_yields_error_results():
    """Review regression: a forced repack that cannot run (fragment
    loaded without retain_edge_list) must become per-request error
    results, not an exception out of the serve loop."""
    from libgrape_lite_tpu.dyn import DynGraph, RepackPolicy
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession

    frag = build_graph(2)
    frag.edge_list = None  # as if loaded without retain_edge_list
    sess = ServeSession(
        frag, policy=BatchPolicy(max_batch=1),
        dyn=RepackPolicy(threshold=0.9, capacity=64),
    )
    assert sess.ingest(ADDS)["mode"] == "overlay"
    bad = sess.submit("pagerank", {})
    good = sess.submit("sssp", {"source": 0})
    res = sess.drain()
    assert len(res) == 2
    assert not bad.result.ok
    assert "retained host edge list" in bad.result.error["error"]
    assert good.result.ok  # the loop kept serving


# ---- incremental IncEval -------------------------------------------------


@pytest.mark.parametrize("app_name", ["sssp", "bfs", "wcc"])
def test_incremental_byte_identity(app_name):
    """query_incremental after staged deltas == a cold full query on
    the mutated graph, byte-for-byte; on a long-diameter graph with a
    localized delta the seeded run converges in fewer rounds."""
    from libgrape_lite_tpu.dyn import DeltaBuffer, DynGraph, RepackPolicy
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.worker.worker import Worker

    kw = {} if app_name == "wcc" else {"source": 0}
    base = build_path(2, n=24)
    w_prev = Worker(APP_REGISTRY[app_name](), base)
    prev = w_prev.query(**kw)

    delta = [("a", 4, 20, 0.5)]
    dg = DynGraph(base, RepackPolicy(threshold=0.0))
    dg.stage(delta)
    summary = dg.summary()
    assert dg.apply()["mode"] == "repack"
    mutated = dg.fragment

    w_inc = Worker(APP_REGISTRY[app_name](), mutated)
    w_inc.query_incremental(prev, summary, prev_fragment=base, **kw)
    assert w_inc.inc_report["mode"] == "seeded"
    assert w_inc.inc_stats["seeded"] == 1

    w_cold = Worker(APP_REGISTRY[app_name](), mutated)
    w_cold.query(**kw)
    assert oid_bytes(w_inc) == oid_bytes(w_cold)
    # the incremental win: only the delta's neighborhood re-converges
    assert w_inc.rounds < w_cold.rounds


def test_incremental_over_overlay_byte_identity():
    """Incremental composes with the overlay: seed from the pre-delta
    fixed point, run against base CSR + overlay (no repack at all) —
    still byte-identical to cold on the overlay view."""
    from libgrape_lite_tpu.dyn import DynGraph, RepackPolicy
    from libgrape_lite_tpu.models import SSSP
    from libgrape_lite_tpu.worker.worker import Worker

    base = build_path(2, n=24)
    dg = DynGraph(base, RepackPolicy(threshold=0.9, capacity=64))
    w_prev = Worker(SSSP(), dg.fragment)
    prev = w_prev.query(source=0)

    dg.ingest([("a", 4, 20, 0.5)])
    w_inc = Worker(SSSP(), dg.fragment)
    w_inc.query_incremental(prev, dg.summary(), source=0)
    assert w_inc.inc_report["mode"] == "seeded"
    w_cold = Worker(SSSP(), dg.fragment)
    w_cold.query(source=0)
    assert oid_bytes(w_inc) == oid_bytes(w_cold)
    assert w_inc.rounds < w_cold.rounds


def test_incremental_under_guard_byte_identity():
    """The seeded run under guard=halt: monitored every round, no
    breach, byte-identical — a seeded carry is a legitimate carry."""
    from libgrape_lite_tpu.dyn import DynGraph, RepackPolicy
    from libgrape_lite_tpu.models import SSSP
    from libgrape_lite_tpu.worker.worker import Worker

    base = build_path(2, n=24)
    w_prev = Worker(SSSP(), base)
    prev = w_prev.query(source=0)
    dg = DynGraph(base, RepackPolicy(threshold=0.0))
    dg.stage([("a", 4, 20, 0.5)])
    summary = dg.summary()
    dg.apply()

    w_inc = Worker(SSSP(), dg.fragment)
    w_inc.query_incremental(prev, summary, prev_fragment=base,
                            guard="halt", source=0)
    assert w_inc.inc_report["mode"] == "seeded"
    assert w_inc.guard_report is not None
    assert w_inc.guard_report["probes"] > 0
    assert not w_inc.guard_report["breaches"]
    w_cold = Worker(SSSP(), dg.fragment)
    w_cold.query(source=0)
    assert oid_bytes(w_inc) == oid_bytes(w_cold)


def test_incremental_nonadditive_and_restart_fall_back_cold():
    from libgrape_lite_tpu.dyn import DeltaBuffer, DynGraph, RepackPolicy
    from libgrape_lite_tpu.models import SSSP, PageRank
    from libgrape_lite_tpu.worker.worker import Worker

    base = build_graph(1)
    w_prev = Worker(SSSP(), base)
    prev = w_prev.query(source=0)
    dg = DynGraph(base, RepackPolicy(threshold=0.0))
    # remove a real edge: non-additive, breaks the upper-bound property
    dg.stage([("d", int(base.edge_list[0][0]),
               int(base.edge_list[1][0]))])
    summary = dg.summary()
    dg.apply()
    w = Worker(SSSP(), dg.fragment)
    w.query_incremental(prev, summary, prev_fragment=base, source=0)
    assert w.inc_report["mode"] == "cold"
    assert w.inc_stats["cold"] == 1
    w_cold = Worker(SSSP(), dg.fragment)
    w_cold.query(source=0)
    assert oid_bytes(w) == oid_bytes(w_cold)

    # PageRank: fixed-round iteration -> declared restart contract
    frag = build_graph(1)
    wp = Worker(PageRank(max_round=5), frag)
    prev_p = wp.query()
    add = DeltaBuffer()
    add.stage([("a", 0, 17, 0.01)])
    wp2 = Worker(PageRank(max_round=5), frag)
    wp2.query_incremental(prev_p, add.summary())
    assert wp2.inc_report["mode"] == "cold"
    assert "restart" in wp2.inc_report["reason"]

    # review regression: an EMPTY delta description (e.g.
    # DynGraph.summary() after a repack cleared the buffer) must not
    # be trusted as "nothing changed" — it falls back cold
    we = Worker(SSSP(), build_graph(1))
    prev_e = we.query(source=0)
    we2 = Worker(SSSP(), we.fragment)
    we2.query_incremental(prev_e, DeltaBuffer().summary(), source=0)
    assert we2.inc_report["mode"] == "cold"
    assert "empty delta" in we2.inc_report["reason"]


def test_incremental_resident_worker_across_repack():
    """Review regression: the resident-worker pattern — query, a
    repack swaps worker.fragment (the serve adopt path), then
    query_incremental WITHOUT prev_fragment= — must migrate the
    previous rows from the OLD layout (worker provenance), not trust
    the rebound fragment."""
    from libgrape_lite_tpu.dyn import DynGraph, RepackPolicy
    from libgrape_lite_tpu.models import SSSP
    from libgrape_lite_tpu.worker.worker import Worker

    base = build_path(2, n=24)
    w = Worker(SSSP(), base)
    prev = w.query(source=0)
    dg = DynGraph(base, RepackPolicy(threshold=0.0))
    rep = dg.ingest([("a", 4, 20, 0.5)])
    assert rep["mode"] == "repack"
    w.fragment = dg.fragment  # what ServeSession._adopt_fragment does
    w.query_incremental(prev, rep["delta"], source=0)
    assert w.inc_report["mode"] == "seeded"
    w_cold = Worker(SSSP(), dg.fragment)
    w_cold.query(source=0)
    assert oid_bytes(w) == oid_bytes(w_cold)


def test_incremental_ft_drill_across_mutation_boundary(tmp_path):
    """The dyn ft drill: checkpoint a query on the pre-delta graph,
    apply the delta (repack), run the seeded incremental query with
    checkpoints, kill it mid-run, resume — byte-identical through the
    mutation boundary; and the PRE-delta checkpoint lineage refuses
    the mutated fragment (fingerprint mismatch), so a resume can never
    silently cross graphs."""
    from libgrape_lite_tpu.dyn import DynGraph, RepackPolicy
    from libgrape_lite_tpu.ft.checkpoint import CheckpointMismatchError
    from libgrape_lite_tpu.ft.faults import FaultPlan, InjectedFault
    from libgrape_lite_tpu.models import SSSP
    from libgrape_lite_tpu.worker.worker import Worker

    dir0 = str(tmp_path / "pre")
    dir1 = str(tmp_path / "post")
    base = build_path(2, n=24)
    w_prev = Worker(SSSP(), base)
    prev = w_prev.query(source=0, checkpoint_every=4,
                        checkpoint_dir=dir0)

    dg = DynGraph(base, RepackPolicy(threshold=0.0))
    dg.stage([("a", 4, 20, 0.5)])
    summary = dg.summary()
    dg.apply()
    mutated = dg.fragment

    # uninterrupted seeded reference
    w_ref = Worker(SSSP(), mutated)
    w_ref.query_incremental(prev, summary, prev_fragment=base,
                            source=0)
    ref = oid_bytes(w_ref)
    assert w_ref.rounds >= 2, "need rounds to kill into"

    # killed run: checkpoint every superstep, die after round 1
    w_kill = Worker(SSSP(), mutated)
    plan = FaultPlan.from_spec("kill@1,mode=raise")
    with pytest.raises(InjectedFault):
        w_kill.query_incremental(
            prev, summary, prev_fragment=base, source=0,
            checkpoint_every=1, checkpoint_dir=dir1, fault_plan=plan,
        )
    # resume continues on the mutated fragment, byte-identically
    w_res = Worker(SSSP(), mutated)
    w_res.resume(dir1)
    assert oid_bytes(w_res) == ref

    # the pre-delta lineage must refuse the mutated graph
    with pytest.raises(CheckpointMismatchError):
        Worker(SSSP(), mutated).resume(dir0)

    # and cold on the mutated graph agrees (the acceptance chain)
    w_cold = Worker(SSSP(), mutated)
    w_cold.query(source=0)
    assert oid_bytes(w_cold) == ref


# ---- guard watchdog at mutation boundaries (satellite) -------------------


def _make_rewind_mutation_app():
    """Toy MutationContext app: a per-vertex counter that increments
    to 5.  The mutation at the round-2 boundary adds a harmless edge
    and REWINDS the counter by one — so round 3's carry re-presents
    round 2's digest.  Without the mutation-boundary watchdog reset
    that is a false-positive 'cycle proof'; with it the run converges."""
    import jax.numpy as jnp

    from libgrape_lite_tpu.app.base import ParallelAppBase

    class RewindMutationApp(ParallelAppBase):
        result_format = "int"

        def __init__(self):
            self.fired = False

        def invariants(self, frag, state):
            return []  # the watchdog alone is under test

        def init_state(self, frag, **_):
            return {"x": np.zeros((frag.fnum, frag.vp), np.int32)}

        def peval(self, ctx, frag, state):
            return state, jnp.int32(1)

        def inceval(self, ctx, frag, state):
            x = state["x"] + jnp.where(frag.inner_mask, 1, 0).astype(
                jnp.int32
            )
            active = ctx.sum(
                jnp.logical_and(frag.inner_mask, x < 5)
                .sum().astype(jnp.int32)
            )
            return {"x": x}, active

        def finalize(self, frag, state):
            return np.asarray(state["x"])

        def collect_mutations(self, frag, host_state, rounds):
            from libgrape_lite_tpu.fragment.mutation import (
                BasicFragmentMutator,
            )

            if rounds == 2 and not self.fired:
                self.fired = True
                m = BasicFragmentMutator()
                m.AddEdge(0, 2, 1.0)
                return m
            return None

        def migrate_state(self, old_frag, new_frag, old_state,
                          new_state):
            out = super().migrate_state(
                old_frag, new_frag, old_state, new_state
            )
            out["x"] = np.maximum(out["x"] - 1, 0)
            return out

    return RewindMutationApp()


def test_guard_mutation_boundary_resets_digest_history():
    """Regression (satellite): mutate mid-query under guard=halt —
    the post-mutation carry re-presents a pre-mutation digest, which
    without the boundary reset raises a false DivergenceError.  The
    run must instead converge, with the monitor armed throughout."""
    from libgrape_lite_tpu.worker.worker import Worker

    frag = build_graph(1, n=8)
    w = Worker(_make_rewind_mutation_app(), frag)
    w.query(guard="halt")
    rep = w.guard_report
    assert rep is not None, "guards were never armed for the mutation app"
    assert rep["probes"] > 0
    assert rep["mutations"] == 1
    assert not rep["breaches"]
    # the rewound counter still reached the fixed point
    vals = oid_values(w)
    assert all(v == 5 for v in vals.values())


def test_guard_mutation_reset_unit():
    """The watchdog-level contract: a digest seen before on_mutation
    is NOT a cycle proof afterwards (the operator changed)."""
    from libgrape_lite_tpu.guard.monitor import GuardMonitor
    from libgrape_lite_tpu.guard.config import GuardConfig
    from libgrape_lite_tpu.guard.watchdog import DivergenceWatchdog

    wd = DivergenceWatchdog()
    assert wd.observe(1, (11, 22)) is None
    assert wd.observe(2, (11, 22)) is not None  # genuine repeat
    wd.reset()
    assert wd.observe(3, (11, 22)) is None  # post-mutation: fresh

    frag = build_graph(1, n=8)
    mon = GuardMonitor(
        app=_make_rewind_mutation_app(), frag=frag,
        config=GuardConfig(policy="halt", every=1),
    )
    mon.watchdog.observe(1, (7, 7))
    mon._probe = object()  # stale compiled probe stand-in
    mon._ledger = {"edges": 1}  # pre-mutation ledger snapshot
    mon.on_mutation(frag)
    assert mon.mutations == 1
    assert mon._probe is None  # re-resolves against the mutated frag
    assert mon._ledger is None  # stale modeled costs never ride a bundle
    assert mon.watchdog.observe(2, (7, 7)) is None
    mon.on_mutation(frag, {"edges": 2})
    assert mon._ledger == {"edges": 2}


# ---- rebuild-on-mutate validation gate (satellite) -----------------------


def test_mutate_validates_rebuilt_shards(monkeypatch):
    """GRAPE_VALIDATE_LOAD=1 must cover the rebuild path: a tampered
    delta rebuild (corrupt neighbor ids) fails loudly at mutate time
    instead of producing wrong results later; without the env the gate
    stays off (no validation cost on the hot path)."""
    import libgrape_lite_tpu.fragment.edgecut as ec
    from libgrape_lite_tpu.fragment.mutation import BasicFragmentMutator
    from libgrape_lite_tpu.graph.csr import CSRValidationError

    frag = build_graph(1)
    m = BasicFragmentMutator()
    m.AddEdge(0, 3, 1.0)

    real_build_csr = ec.build_csr

    def corrupt_build_csr(*args, **kwargs):
        csr = real_build_csr(*args, **kwargs)
        if csr.edge_nbr.size:
            csr.edge_nbr[0] = 1 << 28  # out-of-range pid
        return csr

    monkeypatch.setattr(ec, "build_csr", corrupt_build_csr)
    monkeypatch.setenv("GRAPE_VALIDATE_LOAD", "1")
    with pytest.raises(CSRValidationError):
        m.mutate(frag)

    # gate off: the (corrupt) rebuild sails through unvalidated —
    # proving the env var is what armed the check above
    monkeypatch.delenv("GRAPE_VALIDATE_LOAD")
    m.mutate(frag)


# ---- serve CLI: live ingest while a query stream runs --------------------


def test_cli_serve_delta_stream(capsys, tmp_path):
    from libgrape_lite_tpu.cli import serve_main

    stream = tmp_path / "stream.txt"
    stream.write_text(
        "".join(f"sssp {6 + i}\n" for i in range(12))
    )
    delta = tmp_path / "delta.txt"
    delta.write_text(
        "".join(f"a 6 {100 + i} 0.5\n" for i in range(10))
    )
    serve_main([
        "--efile", dataset_path("p2p-31.e"),
        "--vfile", dataset_path("p2p-31.v"),
        "--fnum", "2", "--max_batch", "4",
        "--stream", str(stream),
        "--delta_stream", str(delta), "--ingest_every", "4",
        "--dyn_repack_ratio", "0.5",
    ])
    out = capsys.readouterr().out
    rec = json.loads(
        [l for l in out.splitlines() if l.startswith("{")][-1]
    )
    assert rec["queries"] == 12 and rec["failed"] == 0
    assert rec["dyn"]["ingested"] == 10
    assert rec["dyn"]["overlay_applies"] >= 1
    assert rec["dyn"]["repack_count"] == 0
    assert rec["dyn"]["updates_per_s"] > 0
    assert rec["dyn"]["queries_ok"] == 12
    # the CLI block validates against the shared bench schema
    import os
    import sys

    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts"))
    from check_bench_schema import _DYN, _check_block

    errors = []
    _check_block(rec["dyn"], _DYN, "dyn", errors)
    assert not errors, errors


def test_cli_serve_delta_stream_ingest_every_zero_terminates(
    capsys, tmp_path
):
    """Review regression: --ingest_every 0 used to spin the streaming
    loop forever (the pump guard compared against the raw flag while
    only the chunk count was clamped) — it must clamp and terminate."""
    from libgrape_lite_tpu.cli import serve_main

    efile = tmp_path / "tiny.e"
    efile.write_text(
        "".join(f"{i} {i + 1} 1.0\n" for i in range(8))
    )
    stream = tmp_path / "stream.txt"
    stream.write_text("sssp 0\nsssp 1\nsssp 2\n")
    delta = tmp_path / "delta.txt"
    delta.write_text("a 0 5 0.5\na 1 6 0.5\n")
    serve_main([
        "--efile", str(efile), "--fnum", "1",
        "--stream", str(stream),
        "--delta_stream", str(delta), "--ingest_every", "0",
    ])
    out = capsys.readouterr().out
    rec = json.loads(
        [l for l in out.splitlines() if l.startswith("{")][-1]
    )
    assert rec["queries"] == 3 and rec["failed"] == 0
    assert rec["dyn"]["ingested"] == 2
