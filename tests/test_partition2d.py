"""2-D vertex-cut partitioning tests (PR 10, ROADMAP item 2).

Pins the tentpole contracts:

* SSSP/BFS/WCC on the 2-D SUMMA mesh are BYTE-identical to the 1-D
  edge-cut pull at fnum {1, 4} (min folds regroup exactly across
  tiles); PageRank (sum fold) is eps-identical — the same documented
  class of decline as the pipeline SUM split;
* identity holds under guard=halt and through a checkpoint kill/
  resume drill crossing 2-D rounds (the consistent-cut argument: the
  2-D carry is observed post-psum, a superstep boundary);
* the serial 1-D path is bit-for-bit untouched when GRAPE_PARTITION
  is unset or "1d" (lowered-HLO pin);
* `resolve_partition` records every decision/decline, and 1-D/2-D
  compiles never share a runner-cache entry (partition mode + k ride
  the app trace_key).
"""

import os

import numpy as np
import pytest

from tests.conftest import dataset_path


def _load_edges(weighted):
    from libgrape_lite_tpu.io.line_parser import (
        read_edge_file,
        read_vertex_file,
    )

    src, dst, w = read_edge_file(dataset_path("p2p-31.e"), weighted=True)
    oids = read_vertex_file(dataset_path("p2p-31.v"))
    return src, dst, (w if weighted else None), oids


def _vc_frag(fnum, weighted=False, symmetrize=True):
    from libgrape_lite_tpu.fragment.vertexcut import (
        ImmutableVertexcutFragment,
    )
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec

    src, dst, w, oids = _load_edges(weighted)
    return ImmutableVertexcutFragment.build(
        CommSpec(fnum=fnum), oids, src, dst, w,
        directed=False, symmetrize=symmetrize,
    )


def _result_dict(app, frag, **kw):
    """{oid: value} across all fragments — the assembly both layouts
    share, so equality below is equality of the user-visible output."""
    from libgrape_lite_tpu.worker.worker import Worker

    w = Worker(app, frag)
    w.query(**kw)
    vals = w.result_values()
    out = {}
    for f in range(frag.fnum):
        n = frag.inner_vertices_num(f)
        for o, v in zip(frag.inner_oids(f), vals[f, :n]):
            out[int(o)] = v
    return out, w


def _apps_2d():
    from libgrape_lite_tpu.models import (
        BFS,
        BFSVC2D,
        SSSP,
        SSSPVC2D,
        WCC,
        WCCVC2D,
    )

    return {
        "sssp": (SSSP, SSSPVC2D, dict(source=6), True),
        "bfs": (BFS, BFSVC2D, dict(source=6), False),
        "wcc": (WCC, WCCVC2D, dict(), False),
    }


def _assert_byte_identical(r1, r2):
    assert r1.keys() == r2.keys()
    bad = [
        k for k in r1
        if np.asarray(r1[k]).tobytes() != np.asarray(r2[k]).tobytes()
    ]
    assert not bad, f"{len(bad)} mismatches, e.g. {bad[:5]}"


@pytest.mark.parametrize("app_name", ["sssp", "bfs", "wcc"])
@pytest.mark.parametrize("fnum", [1, 4])
def test_min_fold_byte_identical_1d_vs_2d(graph_cache, app_name, fnum):
    """The tentpole identity: per-oid results of the 2-D SUMMA pull
    are byte-identical to the 1-D edge-cut pull (min regrouping is
    exact; gpid order is oid order, so the WCC representative
    coincides too) — and the fused 2-D while_loop runs the same
    number of rounds, wherever the 1-D loop is the dense one: one
    fragment's `sssp` on p2p-31 follows a near/far frontier since PR 43
    (models/sssp.py), whose rounds count pushes, the threshold's steps
    and a last look, and reach the same distances in another order."""
    cls1, cls2, kw, weighted = _apps_2d()[app_name]
    r1, w1 = _result_dict(cls1(), graph_cache(fnum), **kw)
    r2, w2 = _result_dict(cls2(), _vc_frag(fnum, weighted), **kw)
    _assert_byte_identical(r1, r2)
    if getattr(w1.app, "frontier_step", None) is None:
        assert w1.rounds == w2.rounds
    else:
        assert (app_name, fnum) == ("sssp", 1) and w1.rounds > w2.rounds


@pytest.mark.parametrize("fnum", [1, 4])
def test_pagerank_vc_eps_identical_to_1d(graph_cache, fnum):
    """Satellite 1 (the pagerank_vc parity pin): the SUMMA-sharded
    vertex-cut PageRank agrees with the 1-D PageRank to float
    tolerance on the same graph — sum folds regroup, so eps rather
    than bytes, with a far tighter bound than the 1e-4 golden eps."""
    from libgrape_lite_tpu.models import PageRank, PageRankVC

    r1, _ = _result_dict(
        PageRank(), graph_cache(fnum), delta=0.85, max_round=10
    )
    r2, _ = _result_dict(
        PageRankVC(), _vc_frag(fnum, weighted=False, symmetrize=False),
        delta=0.85, max_round=10,
    )
    assert r1.keys() == r2.keys()
    rel = max(
        abs(r1[k] - r2[k]) / max(abs(r1[k]), 1e-300) for k in r1
    )
    assert rel < 1e-9, f"max rel err {rel}"


def test_2d_identity_under_guard_halt(graph_cache):
    """guard=halt arms invariant probes + the watchdog on the 2-D
    carry (the post-psum master carry is the consistent cut); results
    must stay byte-identical and no breach may fire on a healthy
    run."""
    from libgrape_lite_tpu.models import SSSP, SSSPVC2D

    r1, _ = _result_dict(SSSP(), graph_cache(4), source=6)
    r2, w2 = _result_dict(
        SSSPVC2D(), _vc_frag(4, weighted=True), source=6, guard="halt"
    )
    _assert_byte_identical(r1, r2)
    rep = w2.guard_report
    assert rep is not None and rep["probes"] > 0
    assert not rep["breaches"]


def test_2d_kill_resume_byte_identical(tmp_path):
    """ft/ drill on the 2-D path: checkpoint every 3 supersteps, kill
    at superstep 4 (mid-query, crossing 2-D rounds), resume — byte-
    identical to an uninterrupted checkpointed run AND to the fused
    no-checkpoint 2-D run."""
    from libgrape_lite_tpu.ft.checkpoint import list_checkpoints
    from libgrape_lite_tpu.ft.faults import FaultPlan, InjectedFault
    from libgrape_lite_tpu.models import SSSPVC2D
    from libgrape_lite_tpu.worker.worker import Worker

    frag = _vc_frag(4, weighted=True)
    w_ref = Worker(SSSPVC2D(), frag)
    w_ref.query(checkpoint_every=3,
                checkpoint_dir=str(tmp_path / "ref"), source=6)
    ref = w_ref.result_values()
    w_fused = Worker(SSSPVC2D(), frag)
    w_fused.query(source=6)
    np.testing.assert_array_equal(ref, w_fused.result_values())

    kill_dir = str(tmp_path / "kill")
    w_kill = Worker(SSSPVC2D(), frag)
    with pytest.raises(InjectedFault):
        w_kill.query(
            checkpoint_every=3, checkpoint_dir=kill_dir,
            fault_plan=FaultPlan(kill_at_superstep=4, mode="raise"),
            source=6,
        )
    assert list_checkpoints(kill_dir), "kill left no complete checkpoint"
    w_res = Worker(SSSPVC2D(), frag)
    w_res.resume(kill_dir)
    assert w_res.result_values().tobytes() == ref.tobytes()


def test_serial_hlo_unchanged_by_partition_env(graph_cache, monkeypatch):
    """The 1-D serial runner's lowered HLO is byte-equal whether
    GRAPE_PARTITION is unset, '1d', or 'auto' (the decision is a
    host-side load-time read; the compiled 1-D program never sees
    it)."""
    import jax

    from libgrape_lite_tpu.models import SSSP
    from libgrape_lite_tpu.worker.worker import Worker

    frag = graph_cache(2)

    def lowered_text():
        w = Worker(SSSP(), frag)
        state = w._place_state(w.app.init_state(frag, source=6))
        eph = frozenset(getattr(w.app, "ephemeral_keys", ()) or ())
        carry = {k: v for k, v in state.items() if k not in eph}
        eph_part = {k: v for k, v in state.items() if k in eph}
        runner = w._make_runner(0)(state)
        return jax.jit(runner).lower(frag.dev, carry, eph_part).as_text()

    monkeypatch.delenv("GRAPE_PARTITION", raising=False)
    unset = lowered_text()
    monkeypatch.setenv("GRAPE_PARTITION", "1d")
    assert lowered_text() == unset
    monkeypatch.setenv("GRAPE_PARTITION", "auto")
    assert lowered_text() == unset


def test_runner_cache_key_carries_partition_mode_and_k():
    """A 2-D app's trace_key carries the partition mode + mesh k, so
    a 1-D and a 2-D compile (or two different-k 2-D compiles) can
    never share a runner-cache entry."""
    from libgrape_lite_tpu.models import SSSPVC2D

    app = SSSPVC2D()
    app.init_state(_vc_frag(4, weighted=True), source=6)
    key = dict(app.trace_key())
    assert key["_partition"] == "2d"
    assert key["_mesh_k"] == 2
    app1 = SSSPVC2D()
    app1.init_state(_vc_frag(1, weighted=True), source=6)
    assert dict(app1.trace_key())["_mesh_k"] == 1
    assert app.trace_key() != app1.trace_key()


def test_resolve_partition_decisions(monkeypatch):
    """Planner contract: declines are recorded with reasons (never
    silent), auto engages only on a modeled win, and the stats
    counters move."""
    from libgrape_lite_tpu.fragment.partition import (
        PARTITION_STATS,
        partition_mode,
        resolve_partition,
    )

    monkeypatch.delenv("GRAPE_PARTITION", raising=False)
    assert partition_mode() == "1d"
    monkeypatch.setenv("GRAPE_PARTITION", "2d")
    assert partition_mode() == "2d"
    monkeypatch.setenv("GRAPE_PARTITION", "auto")
    assert partition_mode() == "auto"

    src, dst, _, oids = _load_edges(False)

    # fnum not a perfect square -> declined, reason recorded
    d = resolve_partition("sssp", 2, src, dst, oids, mode="2d")
    assert not d["engaged"] and "perfect square" in d["reason"]
    assert PARTITION_STATS["last_decision"] is d

    # unknown app -> declined
    d = resolve_partition("cdlp", 4, src, dst, oids, mode="2d")
    assert not d["engaged"] and "no 2-D" in d["reason"]

    # string ids -> declined before touching the arrays
    d = resolve_partition("sssp", 4, src, dst, oids, mode="2d",
                          string_id=True)
    assert not d["engaged"] and "string ids" in d["reason"]

    # forced 2d on an eligible config -> engaged with both costs
    before = PARTITION_STATS["resolved_2d"]
    d = resolve_partition("sssp", 4, src, dst, oids, mode="2d")
    assert d["engaged"] and d["mode"] == "2d"
    assert "1d" in d["costs"] and "2d" in d["costs"]
    assert PARTITION_STATS["resolved_2d"] == before + 1

    # auto records the modeled comparison either way
    d = resolve_partition("sssp", 4, src, dst, oids, mode="auto")
    t1 = d["costs"]["1d"]["t_round_s"]
    t2 = d["costs"]["2d"]["t_round_s"]
    assert d["engaged"] == (t2 < t1)
    if not d["engaged"]:
        assert "does not beat" in d["reason"]


def test_tile_stats_shape():
    frag = _vc_frag(4)
    st = frag.tile_stats()
    assert st["k"] == 2 and len(st["per_tile"]) == 4
    total = sum(t["edges"] for t in st["per_tile"])
    # symmetrised: every input edge stored in both orientations
    assert total == 2 * frag.total_enum
    assert st["max_tile_edges"] >= st["mean_tile_edges"]


def test_vc2d_fingerprint_covers_tiles(tmp_path):
    """The ft fingerprint hashes the vertex-cut tile content through
    the host CSR views — two fragments differing only in an edge
    weight must not share a checkpoint identity."""
    from libgrape_lite_tpu.ft.fingerprint import fragment_content_hash

    f1 = _vc_frag(4, weighted=True)
    f2 = _vc_frag(4, weighted=True)
    assert fragment_content_hash(f1) == fragment_content_hash(f2)
    src, dst, w, oids = _load_edges(True)
    from libgrape_lite_tpu.fragment.vertexcut import (
        ImmutableVertexcutFragment,
    )
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec

    w3 = np.array(w, copy=True)
    w3[0] += 1.0
    f3 = ImmutableVertexcutFragment.build(
        CommSpec(fnum=4), oids, src, dst, w3,
        directed=False, symmetrize=True,
    )
    assert fragment_content_hash(f1) != fragment_content_hash(f3)
