"""Superstep software pipelining (parallel/pipeline.py, r9).

The fused superstep is restructured as a double-buffered software
pipeline: compute the boundary slice, kick off the next round's halo
exchange, overlap interior compute with the in-flight collective, join
at the fold.  The pinned contract:

* GRAPE_PIPELINE=1 results are BYTE-identical to GRAPE_PIPELINE=0 on
  SSSP/BFS/WCC/PageRank at fnum 1/2/4, under the gather and mirror
  exchange modes, under guard=halt/rollback, through a kill@K/
  resume drill and a corrupt_carry drill crossing pipelined rounds,
  and with tracing armed;
* the serial path is bit-for-bit untouched when the pipeline is off
  or declined (lowered-HLO pin);
* the boundary split agrees with the mirror request lists (a stale
  kickoff payload would be silent corruption, not a test failure);
* the exchange-bytes model is ONE ledger shared by the mirror auto
  mode and the pipeline threshold (the r9 bugfix), and the overlap
  term is max(compute_interior, exchange) + compute_boundary.
"""

import numpy as np
import pytest

from libgrape_lite_tpu import obs

FNUMS = [1, 2, 4]


@pytest.fixture(autouse=True)
def _pipeline_env(monkeypatch):
    """Every test starts with the pipeline (and its mode knobs)
    disarmed and leaves no env or obs state behind."""
    for var in ("GRAPE_PIPELINE", "GRAPE_PIPELINE_MIN_BYTES",
                "GRAPE_EXCHANGE",
                obs.TRACE_ENV, obs.METRICS_ENV):
        monkeypatch.delenv(var, raising=False)
    obs.reset()
    yield monkeypatch
    obs.reset()


def _rand_frag(fnum, n=900, e=7000, seed=11, directed=False):
    from libgrape_lite_tpu.fragment.edgecut import ShardedEdgecutFragment
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec
    from libgrape_lite_tpu.utils.types import LoadStrategy
    from libgrape_lite_tpu.vertex_map.partitioner import MapPartitioner
    from libgrape_lite_tpu.vertex_map.vertex_map import VertexMap

    rng = np.random.default_rng(seed)
    src = rng.integers(0, n, e)
    dst = rng.integers(0, n, e)
    w = rng.uniform(0.5, 4.0, e).astype(np.float32)
    oids = np.arange(n, dtype=np.int64)
    comm = CommSpec(fnum=fnum)
    vm = VertexMap.build(oids, MapPartitioner(fnum, oids))
    return ShardedEdgecutFragment.build(
        comm, vm, src, dst, w, directed=directed,
        load_strategy=LoadStrategy.kBothOutIn,
    )


def _apps():
    from libgrape_lite_tpu.models import BFS, SSSP, WCC, PageRank

    return {
        "sssp": (SSSP, {"source": 0}),
        "bfs": (BFS, {"source": 0}),
        "wcc": (WCC, {}),
        "pagerank": (PageRank, {}),
    }


def _run(app_name, frag, monkeypatch, pipeline, **env):
    """One query under GRAPE_PIPELINE=<pipeline>; returns
    (result bytes, rounds, app) so callers can compare runs and
    inspect the resolved plan."""
    from libgrape_lite_tpu.worker.worker import Worker

    monkeypatch.setenv("GRAPE_PIPELINE", pipeline)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    app_cls, qa = _apps()[app_name]
    app = app_cls()
    w = Worker(app, frag)
    w.query(**qa)
    return w.result_values().tobytes(), w.rounds, app


# ---- the boundary / interior split ----------------------------------------


@pytest.mark.parametrize("fnum", [2, 4])
def test_boundary_split_matches_remote_reads(fnum):
    """A vertex is boundary iff some OTHER fragment's real ie edge
    references it — re-derived here directly from the host CSRs.  If
    the split under-covers, the pipelined kickoff ships stale rows
    (silent corruption); over-covering only wastes overlap."""
    from libgrape_lite_tpu.fragment.edgecut import boundary_split

    frag = _rand_frag(fnum, n=700, e=5000, seed=23)
    bmask = boundary_split(frag, ("ie",))
    vp = frag.vp
    want = np.zeros((fnum, vp), dtype=bool)
    for g in range(fnum):
        h = frag.host_ie[g]
        nbr = h.edge_nbr[h.edge_mask].astype(np.int64)
        remote = nbr[(nbr // vp) != g]
        want[remote // vp, remote % vp] = True
    want &= frag.host_inner_mask()
    np.testing.assert_array_equal(bmask, want)
    # padding rows are never boundary
    assert not bmask[~frag.host_inner_mask()].any()
    # the split is cached per fragment + direction set
    assert boundary_split(frag, ("ie",)) is bmask


@pytest.mark.parametrize("fnum", [2, 4])
def test_boundary_split_covers_mirror_requests(fnum):
    """Every row the mirror exchange actually sends must be boundary:
    the two classifications derive from the same read sets, and the
    kickoff payload is only correct for rows the split marks."""
    from libgrape_lite_tpu.fragment.edgecut import boundary_split
    from libgrape_lite_tpu.parallel.mirror import build_mirror_plan

    frag = _rand_frag(fnum, n=700, e=5000, seed=23)
    plan = build_mirror_plan(frag, "ie")
    assert plan is not None
    bmask = boundary_split(frag, ("ie",))
    vp = frag.vp
    for g in range(fnum):
        # rows of g that receiver f's REAL edges reference
        for f in range(fnum):
            if f == g:
                continue
            h = frag.host_ie[f]
            nbr = h.edge_nbr[h.edge_mask].astype(np.int64)
            rows = np.unique(nbr[(nbr // vp) == g] % vp)
            assert bmask[g][rows].all(), (
                f"fragment {g} rows requested by {f} not all boundary"
            )


def test_boundary_stats_partition():
    """boundary/interior vertex and edge counts partition the inner
    vertices and the real edge set (per fragment and in total)."""
    from libgrape_lite_tpu.fragment.edgecut import (
        boundary_split,
        boundary_stats,
    )

    frag = _rand_frag(4, n=700, e=5000, seed=23)
    bmask = boundary_split(frag, ("ie",))
    stats = boundary_stats(frag, bmask, "ie")
    inner = frag.host_inner_mask()
    for f, p in enumerate(stats["per_fragment"]):
        assert p["boundary_vertices"] + p["interior_vertices"] == (
            int(inner[f].sum())
        )
        real = int(frag.host_ie[f].edge_mask.sum())
        assert p["boundary_edges"] + p["interior_edges"] == real
    t = stats["totals"]
    assert t["boundary_vertices"] == sum(
        p["boundary_vertices"] for p in stats["per_fragment"]
    )
    assert t["boundary_vertices"] > 0  # a random cut has a boundary


# ---- byte-identity: pipelined == serial -----------------------------------


@pytest.mark.parametrize("fnum", FNUMS)
@pytest.mark.parametrize("app_name", ["sssp", "bfs", "wcc", "pagerank"])
def test_byte_identity_matrix(app_name, fnum, monkeypatch):
    """The acceptance matrix: GRAPE_PIPELINE results byte-identical to
    serial on all four apps at fnum 1/2/4 (gather exchange).
    fnum=1 must DECLINE (no exchange to overlap) and still match, and
    so must PageRank at every fnum: its serial sum groups by tile
    (ops/segment.py) and would regroup under a split (the reason is
    pinned in tests/test_segment_fold.py)."""
    frag = _rand_frag(fnum)
    serial, rounds_s, _ = _run(app_name, frag, monkeypatch, "0")
    piped, rounds_p, app = _run(app_name, frag, monkeypatch, "force")
    assert piped == serial
    assert rounds_p == rounds_s
    assert (app._pipeline is not None) == (
        fnum > 1 and app_name != "pagerank"
    )


@pytest.mark.parametrize("app_name,env", [
    ("sssp", {"GRAPE_EXCHANGE": "mirror"}),
    ("bfs", {"GRAPE_EXCHANGE": "mirror"}),
    ("wcc", {"GRAPE_EXCHANGE": "mirror"}),
    ("pagerank", {"GRAPE_EXCHANGE": "mirror"}),
])
def test_byte_identity_exchange_modes(app_name, env, monkeypatch):
    """Exchange-mode interaction: the pipelined loop is pinned
    byte-identical under the mirror all_to_all, not just the full
    all_gather."""
    frag = _rand_frag(4)
    serial, _, _ = _run(app_name, frag, monkeypatch, "0", **env)
    piped, _, app = _run(app_name, frag, monkeypatch, "force", **env)
    assert piped == serial
    if app_name == "pagerank":
        # the tiled serial sum declines under every exchange mode
        assert app._pipeline is None
        return
    assert app._pipeline is not None
    assert app._pipeline.mode == "mirror"


# ---- engagement / decline discipline --------------------------------------


@pytest.mark.parametrize("fnum", [2, 4])
@pytest.mark.parametrize("app_name", ["sssp", "bfs", "wcc"])
def test_pipelined_slices_through_the_kernel(app_name, fnum, monkeypatch,
                                             pull_kernel):
    """The boundary and the interior slice each gather through the
    pull's kernel when the backend is a TPU (their streams are as long
    as the split leaves them), and the round still answers with the
    serial round's bytes through XLA's gather.  One case compiles the
    interpreted kernel; the others put `full[nbr]` behind the choice
    (tests/conftest.py)."""
    from tests.conftest import gather_took

    frag = _rand_frag(fnum)
    serial, rounds_s, _ = _run(app_name, frag, monkeypatch, "0")
    calls = pull_kernel("interpreted" if (app_name, fnum) == ("bfs", 2)
                        else "stand_in")
    out = []
    took = gather_took(lambda: out.append(
        _run(app_name, frag, monkeypatch, "force")))
    piped, rounds_p, app = out[0]
    assert app._pipeline is not None
    assert took == {"kernel": 2, "xla": 0}
    host = app._pipeline.host_entries
    assert sorted(c[2] for c in calls) == sorted(
        host[k].shape[1:] for k in ("pl_b_nbr", "pl_i_nbr"))
    assert (piped, rounds_p) == (serial, rounds_s)


@pytest.mark.parametrize("fnum", [2, 4])
def test_wcc_directed_two_kickoff_identity(fnum, monkeypatch):
    """Directed WCC pipelines via the two-kickoff double-pull round:
    the oe exchange kicks from the ie BOUNDARY fold (complete at every
    remotely-read row under the joint ie+oe mask) and hides under the
    ie interior fold; the next round's ie exchange kicks from the oe
    boundary fold symmetrically.  Byte-identical to the serial
    two-pull round."""
    frag = _rand_frag(fnum, directed=True)
    serial, _, _ = _run("wcc", frag, monkeypatch, "0")
    piped, _, app = _run("wcc", frag, monkeypatch, "force")
    assert app._pipeline is not None
    assert app._pipeline.mode2 is not None
    assert piped == serial


@pytest.mark.parametrize(
    "hook", ["default", "wide", "dynamic", "dynamic_tight"]
)
def test_cdlp_pipelined_identity(hook, monkeypatch):
    """CDLP's mode fold pipelines (boundary fold -> kickoff ->
    interior fold hides the label exchange): byte-identical to serial
    on EVERY sort branch — packed-u32, forced-wide variadic, dynamic
    compression, and the dynamic wide fallback under a tight universe
    budget.  The fold only groups edges of equal destination row, so
    any edge subset closed over rows reproduces the full fold's
    per-row mode exactly."""
    from libgrape_lite_tpu.worker.worker import Worker
    from libgrape_lite_tpu.models import CDLP

    frag = _rand_frag(4)

    def run(pipeline):
        monkeypatch.setenv("GRAPE_PIPELINE", pipeline)
        app = CDLP()
        if hook == "wide":
            app._force_wide = True
        elif hook.startswith("dynamic"):
            app._force_dynamic = True
            if hook == "dynamic_tight":
                app._u_budget_override = 16  # << live labels: wide arm
        w = Worker(app, frag)
        w.query(max_round=10)
        return w.result_values().tobytes(), w.rounds, app

    serial, rounds_s, _ = run("0")
    piped, rounds_p, app = run("force")
    assert app._pipeline is not None
    assert piped == serial
    assert rounds_p == rounds_s


@pytest.mark.parametrize("directed", [False, True])
def test_cdlp_opt_pipelined_identity(directed, monkeypatch):
    """CDLPOpt inherits the pipelined round (only its serial first
    round differs); directed CDLP pulls oe only, so one kickoff
    suffices on either graph form."""
    from libgrape_lite_tpu.worker.worker import Worker
    from libgrape_lite_tpu.models import CDLPOpt

    frag = _rand_frag(4, directed=directed)

    def run(pipeline):
        monkeypatch.setenv("GRAPE_PIPELINE", pipeline)
        w = Worker(CDLPOpt(), frag)
        w.query(max_round=10)
        return w.result_values().tobytes(), w.app

    serial, _ = run("0")
    piped, app = run("force")
    assert app._pipeline is not None
    assert piped == serial


def test_auto_threshold_engagement(monkeypatch):
    """GRAPE_PIPELINE=1 is AUTO: latency-bound exchanges (modeled bytes
    under GRAPE_PIPELINE_MIN_BYTES, default 1 MiB) decline — the
    _AUTO_MIN_BYTES discipline — and the decision is recorded, with
    the bytes read from the SHARED mirror ledger."""
    from libgrape_lite_tpu.parallel.mirror import exchange_bytes_ledger
    from libgrape_lite_tpu.parallel.pipeline import PIPELINE_STATS

    frag = _rand_frag(2)  # vp ~ a few hundred rows << 1 MiB of f32
    _, _, app = _run("sssp", frag, monkeypatch, "1")
    assert app._pipeline is None
    dec = PIPELINE_STATS["last_decision"]
    assert "threshold" in dec["reason"]
    assert dec["exchange_bytes"] == exchange_bytes_ledger(
        frag.fnum, frag.vp
    )["gather"]

    monkeypatch.setenv("GRAPE_PIPELINE_MIN_BYTES", "1")
    _, _, app = _run("sssp", frag, monkeypatch, "1")
    assert app._pipeline is not None
    assert app._pipeline.decision["engaged"]


def test_batched_and_dyn_paths_keep_serial_body(monkeypatch):
    """The vmapped batched runner is not pipelined: query_batch under
    GRAPE_PIPELINE=force must resolve NO plan in the batch lanes and
    stay lane-identical to sequential queries."""
    from libgrape_lite_tpu.worker.worker import Worker
    from libgrape_lite_tpu.models import SSSP

    frag = _rand_frag(2)
    monkeypatch.setenv("GRAPE_PIPELINE", "force")
    w = Worker(SSSP(), frag)
    w.query_batch([{"source": 0}, {"source": 5}])
    assert getattr(w.app, "_pipeline", None) is None
    batch_vals = [np.asarray(w.batch_result_values(b)) for b in range(2)]
    for b, src in enumerate((0, 5)):
        ws = Worker(SSSP(), frag)
        ws.query(source=src)
        np.testing.assert_array_equal(batch_vals[b], ws.result_values())


# ---- guard / ft / obs cross-cutting cuts ----------------------------------


def test_guard_halt_identity(monkeypatch):
    """Guarded (chunked-fused) pipelined execution observes the same
    post-join cut: byte-identical to the serial unguarded run, with no
    breach on a healthy query."""
    frag = _rand_frag(2)
    serial, _, _ = _run("sssp", frag, monkeypatch, "0")
    from libgrape_lite_tpu.worker.worker import Worker
    from libgrape_lite_tpu.models import SSSP

    monkeypatch.setenv("GRAPE_PIPELINE", "force")
    w = Worker(SSSP(), frag)
    w.query(source=0, guard="halt")
    assert w.result_values().tobytes() == serial
    assert w.app._pipeline is not None
    assert not w.guard_report["breaches"]


def test_corrupt_carry_rollback_pipelined(monkeypatch, tmp_path):
    """The self-heal drill across pipelined rounds: corrupt_carry@4 is
    detected at the post-join cut, rolled back, replayed — and the
    final state is byte-identical to a fault-free serial run."""
    from libgrape_lite_tpu.ft.faults import FaultPlan
    from libgrape_lite_tpu.worker.worker import Worker
    from libgrape_lite_tpu.models import SSSP

    frag = _rand_frag(2)
    serial, _, _ = _run("sssp", frag, monkeypatch, "0")
    monkeypatch.setenv("GRAPE_PIPELINE", "force")
    w = Worker(SSSP(), frag)
    w.query(
        source=0, checkpoint_every=3, checkpoint_dir=str(tmp_path / "ck"),
        guard="rollback", fault_plan=FaultPlan(corrupt_carry_at=4),
    )
    assert w.result_values().tobytes() == serial
    rep = w.guard_report
    assert rep["rollbacks"] == 1
    assert rep["breaches"][0]["round"] == 4  # detected same-round


def test_kill_resume_pipelined(monkeypatch, tmp_path):
    """Checkpoint cuts stay consistent under pipelining: kill@4, then
    resume (which re-derives the exchange buffer from the restored
    carry) finishes byte-identical to the serial uninterrupted run."""
    from libgrape_lite_tpu.ft.faults import FaultPlan, InjectedFault
    from libgrape_lite_tpu.worker.worker import Worker
    from libgrape_lite_tpu.models import SSSP

    frag = _rand_frag(2)
    serial, _, _ = _run("sssp", frag, monkeypatch, "0")
    monkeypatch.setenv("GRAPE_PIPELINE", "force")
    kill_dir = str(tmp_path / "kill")
    w = Worker(SSSP(), frag)
    with pytest.raises(InjectedFault):
        w.query(
            source=0, checkpoint_every=3, checkpoint_dir=kill_dir,
            fault_plan=FaultPlan(kill_at_superstep=4, mode="raise"),
        )
    w2 = Worker(SSSP(), frag)
    w2.resume(kill_dir)
    assert w2.result_values().tobytes() == serial


def test_traced_identity_and_span_brief(monkeypatch):
    """Tracing armed changes nothing (byte-identical) and the query
    span carries the pipeline brief: modeled hidden fraction and the
    boundary-set sizes trace_report's overlap column reads."""
    frag = _rand_frag(2)
    serial, _, _ = _run("sssp", frag, monkeypatch, "0")
    obs.configure(in_memory=True)
    piped, _, app = _run("sssp", frag, monkeypatch, "force")
    assert piped == serial
    spans = [e for e in obs.history()
             if e.get("ph") == "X" and e.get("name") == "query"]
    assert spans
    pl = spans[-1]["args"]["pipeline"]
    assert pl["engaged"] is True
    assert 0.0 <= pl["modeled_hidden_frac"] <= 1.0
    assert pl["boundary_vertices"] > 0
    assert pl["boundary_vertices"] + pl["interior_vertices"] > 0
    brief = app._pipeline.span_brief()
    assert brief["boundary_vertices"] == pl["boundary_vertices"]


# ---- the serial path is untouched when off --------------------------------


def test_serial_hlo_unchanged_when_off(monkeypatch):
    """The lowered HLO of the fused serial runner must be byte-equal
    whether GRAPE_PIPELINE is unset, '0', or set-but-declined (fnum=1):
    the off path routes to exactly the program it always compiled."""
    import jax

    from libgrape_lite_tpu.worker.worker import Worker
    from libgrape_lite_tpu.models import SSSP

    frag = _rand_frag(2)

    def lowered_text():
        w = Worker(SSSP(), frag)
        state = w._place_state(w.app.init_state(frag, source=0))
        eph = frozenset(getattr(w.app, "ephemeral_keys", ()) or ())
        carry = {k: v for k, v in state.items() if k not in eph}
        eph_part = {k: v for k, v in state.items() if k in eph}
        runner = w._make_runner(0)(state)
        return jax.jit(runner).lower(frag.dev, carry, eph_part).as_text()

    unset = lowered_text()
    monkeypatch.setenv("GRAPE_PIPELINE", "0")
    assert lowered_text() == unset
    # armed but declined (below auto threshold): same serial program
    monkeypatch.setenv("GRAPE_PIPELINE", "1")
    assert lowered_text() == unset


def test_pipelined_runner_cached_separately(monkeypatch):
    """Serial and pipelined compiles never share a runner-cache entry:
    the plan uid rides in trace_key via `_pipeline_uid`."""
    from libgrape_lite_tpu.models import SSSP

    frag = _rand_frag(2)
    _, _, app_s = _run("sssp", frag, monkeypatch, "0")
    _, _, app_p = _run("sssp", frag, monkeypatch, "force")
    assert app_s._pipeline_uid == -1
    assert app_p._pipeline_uid == app_p._pipeline.uid
    assert app_s.trace_key() != app_p.trace_key()


def test_pipelined_repeat_queries_reuse_runner(monkeypatch):
    """The plan uid is a STABLE content fingerprint: a second query on
    the same worker must HIT the runner cache, not recompile.  (A
    per-resolve counter here once changed trace_key every init_state —
    every pipelined query recompiled and the bench A/B measured XLA
    compile time.)"""
    from libgrape_lite_tpu.worker.worker import Worker
    from libgrape_lite_tpu.models import SSSP

    frag = _rand_frag(2)
    monkeypatch.setenv("GRAPE_PIPELINE", "force")
    w = Worker(SSSP(), frag)
    w.query(source=0)
    uid1 = w.app._pipeline.uid
    misses = w.runner_cache_stats["misses"]
    w.query(source=0)
    assert w.app._pipeline.uid == uid1
    assert w.runner_cache_stats["misses"] == misses
    assert w.runner_cache_stats["hits"] >= 1
    # and with guards armed (the chunked pipelined runner)
    w.query(source=0, guard="halt")
    misses_g = w.runner_cache_stats["misses"]
    w.query(source=0, guard="halt")
    assert w.runner_cache_stats["misses"] == misses_g


# ---- the shared exchange-bytes ledger + overlap model ---------------------


def test_exchange_bytes_one_ledger(monkeypatch):
    """The r9 bugfix: MirrorPlan's byte properties and the pipeline
    threshold read the SAME exchange_bytes_ledger — no private copies
    of 'exchange bytes' that can drift apart."""
    from libgrape_lite_tpu.parallel.mirror import (
        build_mirror_plan,
        exchange_bytes_ledger,
    )

    frag = _rand_frag(4, n=700, e=5000, seed=23)
    plan = build_mirror_plan(frag, "ie")
    assert plan is not None
    led = exchange_bytes_ledger(frag.fnum, frag.vp, plan.m)
    assert plan.bytes_all_gather == led["gather"]
    assert plan.bytes_mirror == led["mirror"]
    assert exchange_bytes_ledger(frag.fnum, frag.vp)["mirror"] is None


def test_pipelined_round_model_is_max_not_sum():
    """t = max(compute_interior, exchange) + compute_boundary.  Under
    pipelining, shrinking the exchange below interior-compute time
    buys nothing — the property mode selection must share."""
    from libgrape_lite_tpu.parallel.mirror import pipelined_round_s
    from libgrape_lite_tpu.parallel.pipeline import overlap_model

    assert pipelined_round_s(10.0, 3.0, 1.0) == 11.0  # compute-bound
    assert pipelined_round_s(3.0, 10.0, 1.0) == 11.0  # exchange-bound
    # exchange fully hidden under interior compute
    m = overlap_model(1000, 100_000, 1000)
    assert m["hidden_frac"] == 1.0
    assert m["t_pipelined_s"] < m["t_serial_s"]
    assert m["round_speedup"] > 1.0
    # exchange-bound: hidden fraction is interior/exchange < 1
    m2 = overlap_model(1000, 10**7, 10**9)
    assert 0.0 < m2["hidden_frac"] < 1.0
    # degenerate: no exchange
    assert overlap_model(10, 10, 0)["hidden_frac"] == 0.0


# ---- the bench `pipeline` block schema ------------------------------------


def _bench_pipeline_block():
    return {
        "scale": 10, "fnum": 2, "app": "sssp", "engaged": True,
        "mode": "gather", "serial_s": 0.01, "pipelined_s": 0.012,
        "byte_identical": True, "modeled_hidden_frac": 0.17,
        "exchange_bytes": 4096, "boundary_vertices": 805,
        "interior_vertices": 219, "boundary_edges": 32521,
        "interior_edges": 247, "overlap_recount_mismatch": 0.0,
        "plan_uid": "gather:2:128:0:-",
        "overlap_truth": {
            "queries": 2, "joined": 1,
            "plan_uid": "gather:2:128:0:-",
            "modeled_hidden_us_per_round": 12.5,
            "measured_round_us": 180.0, "claim_frac": 0.07,
            "compile_rounds_excluded": 1, "ok": True,
        },
    }


def test_bench_pipeline_block_schema():
    """The `pipeline` BENCH block is declared: a well-formed block
    validates, a bool in a numeric field is rejected (engaged /
    byte_identical stay declared bools), and unknown keys are errors."""
    import os
    import sys

    scripts = os.path.join(os.path.dirname(__file__), "..", "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    from check_bench_schema import validate_record

    base = {"metric": "m", "value": 1.0, "unit": "u",
            "vs_baseline": 1.0}
    ok = dict(base, pipeline=_bench_pipeline_block())
    assert validate_record(ok) == []
    missing = dict(base, pipeline={
        k: v for k, v in _bench_pipeline_block().items()
        if k != "modeled_hidden_frac"})
    assert any("modeled_hidden_frac" in e
               for e in validate_record(missing))
    boolnum = dict(base, pipeline=dict(
        _bench_pipeline_block(), serial_s=True))
    assert any("got bool" in e for e in validate_record(boolnum))
    unknown = dict(base, pipeline=dict(
        _bench_pipeline_block(), surprise=1))
    assert any("unknown field" in e for e in validate_record(unknown))


def test_overlap_recount_from_shipped_plan(monkeypatch):
    """pack_cost_model.overlap_recount re-derives boundary/interior
    edge counts and exchange bytes from the SHIPPED plan arrays and
    must agree with the planner's stats (the >5% drift gate bench.py
    applies)."""
    import os
    import sys

    scripts = os.path.join(os.path.dirname(__file__), "..", "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    from pack_cost_model import overlap_recount

    frag = _rand_frag(2)
    _, _, app = _run("sssp", frag, monkeypatch, "force")
    assert app._pipeline is not None
    rc = overlap_recount(app._pipeline)
    assert rc["overlap_recount_mismatch"] <= 0.05
    t = app._pipeline.stats["totals"]
    assert rc["boundary_edges"] == t["boundary_edges"]
    assert rc["interior_edges"] == t["interior_edges"]
    assert rc["exchange_bytes"] == app._pipeline.exchange_bytes


def test_trace_report_overlap_column_and_drift_flag():
    """trace_report prints the boundary/interior split from the query
    span's pipeline brief, an ovl_ms overlap column, and flags a run
    where pipelining is armed but hides <10% of the exchange."""
    import io
    import os
    import sys

    scripts = os.path.join(os.path.dirname(__file__), "..", "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    from trace_report import render

    def events(hidden_frac):
        return [
            {"ph": "X", "name": "superstep", "ts": 10.0, "dur": 900.0,
             "tid": 0, "args": {"round": 1, "active": 5}},
            {"ph": "X", "name": "query", "ts": 0.0, "dur": 1000.0,
             "tid": 0, "args": {
                 "pipeline": {
                     "engaged": True, "mode": "gather",
                     "exchange_bytes": 1 << 20,
                     "modeled_hidden_frac": hidden_frac,
                     "hidden_us_per_round": 12.5,
                     "boundary_vertices": 100,
                     "interior_vertices": 900,
                     "boundary_edges": 1000, "interior_edges": 9000,
                 },
                 "overlap_hidden_us": 125.0,
             }},
        ]

    buf = io.StringIO()
    flagged = render(events(0.85), out=buf)
    out = buf.getvalue()
    assert "ovl_ms" in out
    assert "pipeline split" in out
    assert "100 boundary / 900 interior vertices" in out
    assert "85.00%" in out
    assert "PIPELINE DRIFT" not in out
    assert flagged == 0

    buf = io.StringIO()
    flagged = render(events(0.03), out=buf)
    out = buf.getvalue()
    assert "PIPELINE DRIFT" in out and "<10%" in out
    assert flagged == 1
