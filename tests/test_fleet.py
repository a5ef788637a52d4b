"""fleet/ — multi-tenant serving fleet (ISSUE 13 acceptance).

Pins: evict -> re-admit of a resident session performs ZERO
re-planning and ZERO XLA recompiles (counter- and compile_events-
pinned) and answers byte-identically; the budget's cost-weighted-LRU
eviction and its recorded reject decisions; per-tenant breach
isolation (tenants never share a batched dispatch, a poisoned tenant
lane fails alone); WRR fairness starvation bound; the drain drill —
R in {2, 3} replicas serving a stream with concurrent ingest, one
replica drained mid-stream, zero dropped queries, every per-query
result byte-identical to the undrained R=1 run; version-fence
violations are LOUD errors; priority/deadline scheduling in the
admission queue (expired requests fail with a recorded reason, never
silently dropped); the threaded admission front; the khop
serve-routable sampling app; and the bench-schema self-consistency
gate (every declared block wired into SCHEMA/validate_record).
"""

import sys
import time

import numpy as np
import pytest

from tests.test_dyn import ADDS, build_graph

SOURCES = [0, 7, 19, 30]


def _sequential(frag, app_factory, sources):
    from libgrape_lite_tpu.worker.worker import Worker

    values = {}
    for s in sources:
        w = Worker(app_factory(), frag)
        w.query(source=s)
        values[s] = w.result_values()
    return values


# ---- budget: pricing + cost-weighted LRU ---------------------------------


def test_footprint_prices_existing_ledgers():
    """The footprint comes from the ledgers that already exist: CSR
    bytes, overlay planes, retained runner buffers."""
    from libgrape_lite_tpu.fleet import session_footprint
    from libgrape_lite_tpu.serve import ServeSession

    sess = ServeSession(build_graph(2), dyn=True)
    fp0 = session_footprint(sess)
    assert fp0.frag_bytes > 0
    assert fp0.overlay_bytes > 0  # the empty overlay is pre-attached
    assert fp0.runner_bytes == 0  # nothing resident yet
    res = sess.serve([("sssp", {"source": 0})])
    assert res[0].ok
    fp1 = session_footprint(sess)
    assert fp1.runner_bytes > 0
    assert fp1.frag_bytes == fp0.frag_bytes
    assert fp1.total > fp0.total


def test_budget_cost_weighted_lru_picks_cold_large_victim():
    from libgrape_lite_tpu.fleet import FLEET_STATS, FleetBudget, Footprint

    FLEET_STATS.reset()
    clock = [0.0]
    b = FleetBudget(capacity_bytes=1000, clock=lambda: clock[0])
    evicted = []
    big = Footprint(frag_bytes=600, frag_keys={1: 600})
    small = Footprint(frag_bytes=300, frag_keys={2: 300})
    assert b.admit("cold_big", big, evict=evicted.append)["admitted"]
    clock[0] = 10.0
    assert b.admit("hot_small", small, evict=evicted.append)["admitted"]
    clock[0] = 11.0
    newcomer = Footprint(frag_bytes=500, frag_keys={3: 500})
    d = b.admit("newcomer", newcomer, evict=evicted.append)
    assert d["admitted"]
    # idle * bytes: cold_big (11s idle, 600B) beats hot_small (1s, 300B)
    assert evicted == ["cold_big"]
    assert "hot_small" in b.residents and "newcomer" in b.residents
    assert FLEET_STATS.evictions == 1


def test_budget_weight_protects_heavy_tenants():
    from libgrape_lite_tpu.fleet import FleetBudget, Footprint

    clock = [0.0]
    b = FleetBudget(capacity_bytes=1000, clock=lambda: clock[0])
    fp = lambda k: Footprint(frag_bytes=450, frag_keys={k: 450})  # noqa: E731
    b.admit("weighted", fp(1), weight=100.0)
    b.admit("light", fp(2), weight=1.0)
    clock[0] = 1.0
    evicted = []
    d = b.admit("next", fp(3), evict=evicted.append)
    assert d["admitted"] and evicted == ["light"]


def test_budget_reject_is_recorded_never_silent():
    from libgrape_lite_tpu.fleet import FLEET_STATS, FleetBudget, Footprint

    FLEET_STATS.reset()
    b = FleetBudget(capacity_bytes=100)
    b.admit("pinned", Footprint(frag_bytes=80, frag_keys={1: 80}),
            evictable=False)
    d = b.admit("too_big", Footprint(frag_bytes=90, frag_keys={2: 90}))
    assert not d["admitted"]
    assert "no evictable resident" in d["reason"]
    assert FLEET_STATS.rejects == 1
    assert any(e["kind"] == "reject" for e in FLEET_STATS.events)


def test_budget_shared_fragment_billed_once():
    from libgrape_lite_tpu.fleet import FleetBudget, Footprint

    b = FleetBudget(capacity_bytes=1000)
    shared = {7: 600}
    b.admit("a", Footprint(frag_bytes=600, frag_keys=dict(shared)))
    # the second tenant over the SAME fragment costs only its private
    # bytes — 600 + 600 would not fit, shared dedup does
    d = b.admit("b", Footprint(frag_bytes=600, runner_bytes=100,
                               frag_keys=dict(shared)))
    assert d["admitted"]
    assert b.used_bytes() == 700


# ---- eviction -> re-admission: the zero-replanning pin -------------------


def _weighted_fragment(fnum=1, n=700, e=6000):
    """f32-weighted fragment."""
    from libgrape_lite_tpu.fragment.edgecut import ShardedEdgecutFragment
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec
    from libgrape_lite_tpu.vertex_map.partitioner import MapPartitioner
    from libgrape_lite_tpu.vertex_map.vertex_map import VertexMap

    rng = np.random.default_rng(21)
    src, dst = rng.integers(0, n, e), rng.integers(0, n, e)
    w = rng.uniform(0.5, 2.0, e).astype(np.float32)
    oids = np.arange(n, dtype=np.int64)
    vm = VertexMap.build(oids, MapPartitioner(fnum, oids))
    return ShardedEdgecutFragment.build(
        CommSpec(fnum=fnum), vm, src, dst, w, directed=False,
    )


def test_evict_readmit_zero_replanning_zero_compiles():
    """The acceptance pin: release_device drops the HBM arrays; the
    next query after restore_device hits the warm runner cache (zero
    compiles on the REAL XLA stream, no runner miss) and answers
    byte-identically, and the per-fragment plan cache (weak-keyed on
    the fragment, ops/spgemm_pack.py) survives: a re-resolve plans
    nothing."""
    from libgrape_lite_tpu.analysis import compile_events
    from libgrape_lite_tpu.ops.spgemm_pack import (
        SPGEMM_STATS,
        resolve_spgemm_dispatch,
    )
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession

    sess = ServeSession(_weighted_fragment(),
                        policy=BatchPolicy(max_batch=1))
    r1 = sess.serve([("sssp", {"source": 0})])
    assert r1[0].ok
    want = r1[0].values.tobytes()
    resolve_spgemm_dispatch(sess.fragment)
    planned = SPGEMM_STATS["planned"]
    hits = SPGEMM_STATS["frag_cache_hits"]
    s1 = sess.cache_stats()

    rel = sess.release_device()
    assert rel["fragment_released"] and not sess.resident
    assert sess.fragment.dev is None
    assert sess.restore_device() and sess.resident
    with compile_events() as ev:
        r2 = sess.serve([("sssp", {"source": 0})])
    assert r2[0].ok and r2[0].values.tobytes() == want
    assert ev.compiles == 0, ("re-admission recompiled", ev.events)
    s2 = sess.cache_stats()
    assert s2["runner"]["misses"] == s1["runner"]["misses"], (s1, s2)
    assert s2["runner"]["hits"] > s1["runner"]["hits"]
    resolve_spgemm_dispatch(sess.fragment)
    assert SPGEMM_STATS["planned"] == planned, (
        "re-admission re-ran the spgemm planner"
    )
    assert SPGEMM_STATS["frag_cache_hits"] == hits + 1


def test_release_restore_is_idempotent():
    from libgrape_lite_tpu.serve import ServeSession

    sess = ServeSession(build_graph(2))
    assert sess.fragment.release_device() is True
    assert sess.fragment.release_device() is False
    assert sess.fragment.restore_device() is True
    assert sess.fragment.restore_device() is False
    res = sess.serve([("sssp", {"source": 0})])
    assert res[0].ok


def test_session_close_is_terminal():
    from libgrape_lite_tpu.serve import ServeSession

    sess = ServeSession(build_graph(2))
    assert sess.serve([("sssp", {"source": 0})])[0].ok
    sess.close()
    assert not sess.resident
    assert sess._workers == {}
    with pytest.raises(RuntimeError, match="closed"):
        sess.submit("sssp", {"source": 0})
    sess.close()  # idempotent


def test_manager_evicts_and_readmits_under_pressure():
    """Two single-fragment tenants under a budget that holds one:
    activating B evicts A (cost-weighted LRU), A's next use re-admits
    with correct answers; every transition is counted."""
    from libgrape_lite_tpu.fleet import (
        FLEET_STATS,
        FleetBudget,
        FleetManager,
        fragment_bytes,
    )
    from libgrape_lite_tpu.serve import ServeSession

    FLEET_STATS.reset()
    fa, fb = build_graph(2, seed=3), build_graph(2, seed=5)
    cap = int(max(fragment_bytes(fa), fragment_bytes(fb)) * 1.5)
    mgr = FleetManager(FleetBudget(capacity_bytes=cap))
    sa, sb = ServeSession(fa), ServeSession(fb)
    want_a = _sequential(fa, _sssp_factory(), [0])[0]
    mgr.add_tenant("a", sa)
    mgr.add_tenant("b", sb)

    mgr.submit("a", "sssp", {"source": 0})
    mgr.drain()
    mgr.submit("b", "sssp", {"source": 0})
    mgr.drain()
    assert not sa.resident, "admitting b should have evicted a"
    assert FLEET_STATS.evictions >= 1

    t = mgr.submit("a", "sssp", {"source": 0})
    mgr.drain()
    assert t.done and t.result.ok
    assert t.result.values.tobytes() == want_a.tobytes()
    assert sa.resident
    assert mgr.tenants["a"].stats["readmits"] == 1


def _sssp_factory():
    from libgrape_lite_tpu.models import APP_REGISTRY

    return APP_REGISTRY["sssp"]


# ---- tenancy: isolation + fairness ---------------------------------------


def test_tenants_never_share_a_batched_dispatch():
    """Same app, same shapes, one shared session: requests of two
    tenants must land in separate batches (the tenant tag is in the
    compat key) — the structural half of breach isolation."""
    from libgrape_lite_tpu.fleet import FleetBudget, FleetManager
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession

    sess = ServeSession(build_graph(2), policy=BatchPolicy(max_batch=8))
    mgr = FleetManager(FleetBudget(capacity_bytes=0))
    mgr.add_tenant("a", sess)
    mgr.add_tenant("b", sess)
    for s in SOURCES:
        mgr.submit("a", "sssp", {"source": s})
        mgr.submit("b", "sssp", {"source": s})
    mgr.drain()
    hist = sess.queue.batch_hist
    assert hist == {4: 2}, hist  # one 4-lane batch per tenant, never 8


def test_tenant_breach_isolation(graph_cache):
    """A poisoned lane in tenant A's guarded batch fails ALONE —
    every tenant-B query completes with correct bytes (tenants never
    coalesce, so the blast radius cannot reach a batchmate tenant)."""
    import jax

    from libgrape_lite_tpu.fleet import FleetBudget, FleetManager
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession
    from libgrape_lite_tpu.serve import batch as serve_batch

    frag = graph_cache(2)
    p2p = [6, 17, 3, 42, 11]  # real p2p-31 vertex ids
    want = _sequential(frag, APP_REGISTRY["sssp"], p2p[2:])

    orig = serve_batch.run_guarded_batch
    poisoned_batches = []

    def poisoned(worker, args_list, mr, cfg, **kw):
        # poison lane 0 of tenant a's batch only (identified by its
        # lane count: a submits 2, b submits 3)
        if len(args_list) != 2:
            return orig(worker, args_list, mr, cfg, **kw)

        def hook(carry, rounds):
            if rounds != 2:
                return None
            dist = np.array(jax.device_get(carry["dist"]))
            dist[0, 0, :4] = -5.0
            return {"dist": dist}

        poisoned_batches.append(len(args_list))
        return orig(worker, args_list, mr, cfg, chunk_hook=hook)

    serve_batch.run_guarded_batch = poisoned
    try:
        sess = ServeSession(frag, policy=BatchPolicy(max_batch=8),
                            guard="halt")
        mgr = FleetManager(FleetBudget(capacity_bytes=0))
        mgr.add_tenant("a", sess)
        mgr.add_tenant("b", sess)
        ta = [mgr.submit("a", "sssp", {"source": s})
              for s in p2p[:2]]
        tb = [mgr.submit("b", "sssp", {"source": s})
              for s in p2p[2:]]
        mgr.drain()
    finally:
        serve_batch.run_guarded_batch = orig
    assert poisoned_batches == [2]
    assert not ta[0].result.ok
    assert ta[0].result.error["verdict"]["kind"] == "invariant"
    for t, s in zip(tb, p2p[2:]):
        assert t.result.ok, f"tenant b query {s} hurt by a's breach"
        assert t.result.values.tobytes() == want[s].tobytes()
    snap = mgr.snapshot()
    assert snap["tenants"]["a"]["failed"] == 1
    assert snap["tenants"]["b"]["failed"] == 0


def test_wrr_starvation_bound():
    """A 16-deep backlog on tenant A cannot starve tenant B: B's 4
    tickets all forward within the first 8 forwards (alternating WRR
    cycles)."""
    from libgrape_lite_tpu.fleet import FleetBudget, FleetManager
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession

    sess = ServeSession(build_graph(2), policy=BatchPolicy(max_batch=8))
    mgr = FleetManager(FleetBudget(capacity_bytes=0))
    mgr.add_tenant("a", sess)
    mgr.add_tenant("b", sess)
    for s in range(16):
        mgr.submit("a", "sssp", {"source": s % 32})
    for s in range(4):
        mgr.submit("b", "sssp", {"source": s})
    mgr.drain()
    first8 = mgr.forward_order[:8]
    assert first8 == ["a", "b"] * 4, first8
    assert all(t.done for t in mgr.tenants["b"].tickets)


def test_wrr_weights_shape_the_cycle():
    from libgrape_lite_tpu.fleet import FleetBudget, FleetManager
    from libgrape_lite_tpu.serve import ServeSession

    sess = ServeSession(build_graph(2))
    mgr = FleetManager(FleetBudget(capacity_bytes=0))
    mgr.add_tenant("a", sess, weight=2.0)
    mgr.add_tenant("b", sess, weight=1.0)
    for s in range(6):
        mgr.submit("a", "sssp", {"source": s})
        mgr.submit("b", "sssp", {"source": s})
    mgr.forward_round()
    assert mgr.forward_order == ["a", "a", "b"]
    mgr.drain()


# ---- replica routing + the version fence ---------------------------------


def _router(R, *, dyn=True, max_batch=4):
    from libgrape_lite_tpu.dyn import RepackPolicy
    from libgrape_lite_tpu.fleet import FleetRouter
    from libgrape_lite_tpu.fragment.mutation import replicate_fragment
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession

    base = build_graph(2)
    frags = [base] + [replicate_fragment(base) for _ in range(R - 1)]
    sessions = [
        ServeSession(
            f, policy=BatchPolicy(max_batch=max_batch),
            dyn=RepackPolicy(threshold=0.5, capacity=64) if dyn
            else None,
        )
        for f in frags
    ]
    return FleetRouter(sessions)


def test_router_least_outstanding_alternates():
    router = _router(2, dyn=False)
    picks = []
    for s in range(4):
        router.submit("sssp", {"source": s})
        picks.append([r.outstanding for r in router.replicas])
    assert picks == [[1, 0], [1, 1], [2, 1], [2, 2]]
    res = router.drain()
    assert len(res) == 4 and all(r.ok for r in res)
    assert all(r.outstanding == 0 for r in router.replicas)
    assert all(r.served == 2 for r in router.replicas)


def test_fence_violation_is_loud():
    from libgrape_lite_tpu.fleet import FenceViolationError

    router = _router(2, dyn=False)
    router.replicas[1].version = 99  # tampered: routable at wrong version
    with pytest.raises(FenceViolationError, match="mix graph versions"):
        router.submit("sssp", {"source": 0})
    with pytest.raises(FenceViolationError):
        router.pump()


def test_all_replicas_draining_is_a_fence_error():
    from libgrape_lite_tpu.fleet import FenceError

    router = _router(3, dyn=False)
    router.replicas[0].routable = False
    router.replicas[1].routable = False
    router.replicas[2].routable = False
    with pytest.raises(FenceError, match="no routable replica"):
        router.submit("sssp", {"source": 0})


def test_drain_last_routable_replica_refused():
    router = _router(2, dyn=False)
    router.begin_drain(0)
    with pytest.raises(ValueError, match="last routable"):
        router.begin_drain(1)
    router.rejoin(0)
    with pytest.raises(ValueError, match="already draining"):
        router.begin_drain(0)
        router.begin_drain(0)


def test_rejoin_with_incomplete_catchup_is_loud():
    from libgrape_lite_tpu.fleet import FenceViolationError

    router = _router(2)
    router.begin_drain(0)
    router.fence += 1  # a fence move that never logged catch-up
    with pytest.raises(FenceViolationError, match="catch-up log"):
        router.rejoin(0)


@pytest.mark.parametrize("R", [2, 3])
def test_drain_mid_stream_byte_identity(R):
    """THE drill: R replicas serving a stream with concurrent ingest,
    one replica drained mid-stream (offline forced repack, rejoins
    through its catch-up log) — zero dropped queries, every per-query
    result byte-identical to the undrained R=1 run."""
    from libgrape_lite_tpu.fleet import run_fleet_script

    rng = np.random.default_rng(11)
    queries = [("sssp", {"source": int(s)})
               for s in rng.integers(0, 32, 18)]

    def run(R_, drain_at):
        router = _router(R_)
        reqs = run_fleet_script(
            router, queries, delta_ops=ADDS + [
                ("a", 1, 30, 0.2), ("a", 2, 28, 0.3), ("a", 5, 9, 0.7),
            ],
            ingest_every=6, drain_at=drain_at, drain_idx=0,
            offline=lambda s: s.ingest([], force_repack=True),
        )
        assert all(q.result is not None for q in reqs), "dropped query"
        return [
            q.result.values.tobytes() if q.result.ok else b""
            for q in reqs
        ], router

    want, _ = run(1, None)
    got, router = run(R, 7)
    assert got == want, f"R={R} drained run diverged from R=1"
    assert router.replicas[0].drains == 1
    # the drained replica rejoined at the fence and genuinely served
    assert router.replicas[0].version == router.fence
    assert all(r.served > 0 for r in router.replicas)


def test_drain_catchup_applies_missed_deltas():
    """An ingest landing WHILE a replica drains goes to its catch-up
    log and replays at rejoin — both replicas then answer the
    post-delta query identically."""
    router = _router(2)
    for s in SOURCES:
        router.submit("sssp", {"source": s})
    router.drain()
    router.begin_drain(0)
    rep = router.ingest(ADDS)
    assert rep["applied_replicas"] == 1
    assert router.replicas[0].version == 0  # still pre-delta
    out = router.rejoin(0)
    assert out["catchup_ops"] == len(ADDS)
    assert router.replicas[0].version == router.fence == 1
    # both replicas now answer the delta-dependent query identically
    w = {}
    for r in router.replicas:
        res = r.session.serve([("sssp", {"source": 0})])
        assert res[0].ok
        w[r.idx] = res[0].values.tobytes()
    assert w[0] == w[1]


def test_fleet_script_threads_submit_kwargs():
    """Review-pass regression: a stream-wide --max_rounds must reach
    the underlying queue on the fleet path exactly as on the plain
    one — a dropped limit silently changes results and round counts."""
    from libgrape_lite_tpu.fleet import run_fleet_script

    queries = [("sssp", {"source": s}) for s in SOURCES]
    router = _router(2, dyn=False)
    reqs = run_fleet_script(router, queries,
                            submit_kwargs={"max_rounds": 1})
    assert all(q.result.ok for q in reqs)
    assert all(q.result.rounds <= 1 for q in reqs), [
        q.result.rounds for q in reqs
    ]
    assert all(q.max_rounds == 1 for q in reqs)


def test_rejected_readmission_places_no_buffers():
    """Review-pass regression: a budget REJECT must not leave the
    tenant's fragment re-placed in HBM (admit decides first, buffers
    place second), and a rejected re-pricing must keep the prior
    resident entry so used_bytes stays truthful."""
    from libgrape_lite_tpu.fleet import (
        FleetAdmissionError,
        FleetBudget,
        FleetManager,
        Footprint,
        fragment_bytes,
    )
    from libgrape_lite_tpu.serve import ServeSession

    fa = build_graph(2, seed=3)
    sa = ServeSession(fa)
    cap = int(fragment_bytes(fa) * 1.2)
    mgr = FleetManager(FleetBudget(capacity_bytes=cap))
    mgr.add_tenant("a", sa)
    mgr.submit("a", "sssp", {"source": 0})
    mgr.drain()
    # wedge the budget with a non-evictable phantom bigger than the
    # remaining headroom, then evict a and try to come back
    mgr.budget.release("a")
    mgr.tenants["a"].admitted = False
    sa.release_device()
    mgr.budget.admit(
        "pinned", Footprint(frag_bytes=cap, frag_keys={-1: cap}),
        evictable=False,
    )
    used_before = mgr.budget.used_bytes()
    mgr.submit("a", "sssp", {"source": 0})
    with pytest.raises(FleetAdmissionError, match="rejected"):
        mgr.drain()
    assert not sa.resident, (
        "reject left the evicted tenant's buffers placed"
    )
    assert mgr.budget.used_bytes() == used_before


def test_budget_readmit_reject_restores_prior_entry():
    from libgrape_lite_tpu.fleet import FleetBudget, Footprint

    b = FleetBudget(capacity_bytes=1000)
    b.admit("a", Footprint(frag_bytes=400, frag_keys={1: 400}))
    b.admit("pinned", Footprint(frag_bytes=500, frag_keys={2: 500}),
            evictable=False)
    # re-pricing a at a footprint that no longer fits must keep the
    # OLD entry (a is still resident at 400B), not forget it
    d = b.admit("a", Footprint(frag_bytes=800, frag_keys={1: 800}))
    assert not d["admitted"]
    assert "a" in b.residents
    assert b.used_bytes() == 900


# ---- priority / deadline scheduling --------------------------------------


def test_priority_class_dispatches_first_and_never_coalesces():
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession

    sess = ServeSession(build_graph(2), policy=BatchPolicy(max_batch=8))
    low = [sess.submit("sssp", {"source": s}) for s in SOURCES[:2]]
    high = [sess.submit("sssp", {"source": s}, priority=5)
            for s in SOURCES[2:]]
    first = sess.pump(force=True)
    # the high class ships first, FIFO within the class, and the low
    # requests did NOT ride the urgent batch
    assert {r.request_id for r in first} == {r.id for r in high}
    assert all(not r.done for r in low)
    rest = sess.drain()
    assert {r.request_id for r in rest} == {r.id for r in low}
    assert sess.queue.batch_hist == {2: 2}


def test_deadline_expiry_fails_with_reason_never_drops():
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession

    sess = ServeSession(build_graph(2),
                        policy=BatchPolicy(max_batch=8, max_wait_s=60.0))
    doomed = sess.submit("sssp", {"source": 0}, deadline_s=0.001)
    live = sess.submit("sssp", {"source": 7})
    time.sleep(0.01)
    out = sess.drain()
    assert len(out) == 2
    assert doomed.done and not doomed.result.ok
    assert doomed.result.error["reason"] == "deadline_expired"
    assert doomed.result.error["waited_s"] > 0
    assert sess.queue.expired == 1
    assert live.done and live.result.ok


def test_deadline_expiry_surfaces_through_async_pump():
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession

    sess = ServeSession(build_graph(2), policy=BatchPolicy(max_batch=4))
    pump = sess.async_pump(window=2)
    doomed = sess.submit("sssp", {"source": 0}, deadline_s=0.001)
    live = sess.submit("sssp", {"source": 7})
    time.sleep(0.01)
    out = pump.drain()
    assert doomed.done and not doomed.result.ok
    assert doomed.result.error["reason"] == "deadline_expired"
    assert live.done and live.result.ok
    assert any(r.request_id == doomed.id for r in out), (
        "expired result was not returned by the pump"
    )
    pump.close()


# ---- threaded admission front --------------------------------------------


def test_arrival_feeder_real_wall_clock_arrivals():
    from libgrape_lite_tpu.serve import (
        ArrivalFeeder,
        BatchPolicy,
        ServeSession,
    )

    sess = ServeSession(
        build_graph(2),
        policy=BatchPolicy(max_batch=4, max_wait_s=0.002),
    )
    stream = [("sssp", {"source": s % 32}) for s in range(12)]
    feeder = ArrivalFeeder(sess.submit, stream, rate_qps=400.0)
    results = []
    feeder.start()
    while feeder.is_alive() or sess.queue.pending():
        got = sess.pump()  # NOT forced: max_wait_s genuinely gates
        results.extend(got)
        if not got:
            time.sleep(5e-4)
    feeder.join()
    results.extend(sess.drain())
    assert len(results) == 12 and all(r.ok for r in results)
    # arrivals are genuinely spread in wall-clock time
    stamps = [r.submitted_s for r in feeder.requests]
    assert stamps == sorted(stamps)
    assert stamps[-1] - stamps[0] >= 11 * (1.0 / 400.0) * 0.5
    # the wait record saw real (non-zero) queueing
    assert sess.queue.admission_waits


def test_feeder_rejects_nonpositive_rate():
    from libgrape_lite_tpu.serve import ArrivalFeeder

    with pytest.raises(ValueError, match="rate_qps"):
        ArrivalFeeder(lambda *a, **k: None, [], 0.0)


# ---- khop: the serve-routable sampling workload --------------------------


def test_khop_matches_depth_bounded_bfs(graph_cache):
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.worker.worker import Worker

    frag = graph_cache(2)
    wb = Worker(APP_REGISTRY["bfs"](), frag)
    wb.query(source=6)
    full = wb.result_values()
    wk = Worker(APP_REGISTRY["khop"](k=2), frag)
    wk.query(source=6)
    got = wk.result_values()
    want = np.where((full >= 0) & (full <= 2), full, -1)
    assert got.tobytes() == want.tobytes()
    assert wk.rounds <= 2
    assert (got >= -1).all() and (got <= 2).all()
    assert (got == -1).any()  # p2p-31's 2-hop ball is not the graph


def test_khop_serve_batched_identical_per_lane(graph_cache):
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession

    frag = graph_cache(2)
    sources = [6, 17, 3, 999999]
    want = _sequential(
        frag, lambda: APP_REGISTRY["khop"](k=2), sources
    )
    sess = ServeSession(frag, policy=BatchPolicy(max_batch=4))
    res = sess.serve([("khop", {"source": s}) for s in sources])
    for r, s in zip(res, sources):
        assert r.ok
        assert r.values.tobytes() == want[s].tobytes()
    assert sess.queue.batch_hist == {4: 1}  # genuinely coalesced


def test_khop_k_is_a_compile_key():
    from libgrape_lite_tpu.models import APP_REGISTRY

    a2 = APP_REGISTRY["khop"](k=2)
    a3 = APP_REGISTRY["khop"](k=3)
    assert a2.trace_key() != a3.trace_key()
    assert a2.max_rounds == 2 and a3.max_rounds == 3
    with pytest.raises(ValueError, match="k >= 1"):
        APP_REGISTRY["khop"](k=0)


# ---- CLI fleet surface ----------------------------------------------------


def test_cli_serve_fleet_replicas_and_tenants(capsys, tmp_path):
    import json

    from libgrape_lite_tpu.cli import serve_main
    from tests.conftest import dataset_path

    dump = tmp_path / "fleet.res"
    serve_main([
        "--efile", dataset_path("p2p-31.e"),
        "--vfile", dataset_path("p2p-31.v"),
        "--fnum", "2", "--application", "sssp",
        "--sources", "6,17,3,42,11,12",
        "--max_batch", "4", "--replicas", "2", "--tenants", "2",
        "--drain_at", "3", "--dump_results", str(dump),
    ])
    out = capsys.readouterr().out
    rec = json.loads(
        [l for l in out.splitlines() if l.startswith("{")][-1]
    )
    assert rec["queries"] == 6 and rec["failed"] == 0
    fl = rec["fleet"]
    assert fl["replicas"] == 2 and fl["tenants"] == 2
    assert fl["dropped"] == 0 and fl["drains"] == 1
    assert fl["rejoins"] == 1  # drained AND back in rotation
    assert all(
        r["served"] > 0 for r in fl["router"]["replicas"].values()
    )
    assert "per_app_ms" in rec and "sssp" in rec["per_app_ms"]
    lines = dump.read_text().splitlines()
    assert len(lines) == 6
    assert all(l.split()[2] == "1" for l in lines)  # every query ok


# ---- bench schema: the self-consistency gate -----------------------------


def _schema_mod():
    import importlib
    import os

    sys.path.insert(0, os.path.join(
        os.path.dirname(__file__), "..", "scripts"))
    import check_bench_schema

    return importlib.reload(check_bench_schema)


def test_bench_schema_self_check_clean_and_fleet_wired():
    c = _schema_mod()
    assert c.self_check() == []
    assert "fleet" in c.SCHEMA and "fleet" in c._TOP
    blk = {
        "scale": 10, "replicas": 2, "tenants": 0, "queries": 64,
        "ok": 64, "dropped": 0, "drain_at": 32, "drained_replica": 0,
        "drain_wall_s": 0.5, "catchup_ops": 64, "updates": 128,
        "updates_per_s": 100.0, "fence": 4, "byte_identical": True,
        "per_replica": {
            "r0": {"qps": 10.0, "p50_ms": 1.0, "p99_ms": 2.0,
                   "served": 30, "ok": 30},
        },
        "evictions": 0, "readmit_compiles": 0,
    }
    rec = {"metric": "m", "value": 1, "unit": "u", "vs_baseline": 1.0,
           "fleet": blk}
    assert c.validate_record(rec) == []
    bad = {**rec, "fleet": {**blk, "byte_identical": 1}}
    assert any("byte_identical" in e for e in c.validate_record(bad))
    bad2 = {**rec, "fleet": {**blk, "dropped": True}}
    assert any("dropped" in e for e in c.validate_record(bad2))
    bad3 = {**rec, "fleet": {**blk, "per_replica": {
        "x9": blk["per_replica"]["r0"]}}}
    assert any("r<k>" in e for e in c.validate_record(bad3))


def test_bench_schema_self_check_catches_unwired_block():
    """The wiring-gap gate itself: a block declared in _TOP but absent
    from SCHEMA/_BLOCKS (the PR 9/11/12 bug class) must fail
    self_check — and the CLI exits 2 on it."""
    c = _schema_mod()
    c._TOP["ghost_block"] = (dict, False)
    try:
        import os

        errors = c.self_check()
        assert errors, "an unwired declared block passed self_check"
        assert any("ghost_block" in e for e in errors)
        r05 = os.path.join(
            os.path.dirname(__file__), "..", "BENCH_r05.json")
        assert c.main([r05]) == 2
    finally:
        del c._TOP["ghost_block"]
    assert c.self_check() == []


def test_bench_schema_self_check_catches_unchecked_block(monkeypatch):
    """A block wired into the tables but skipped by validate_record
    must also fail (the derivation is what makes this impossible —
    the gate pins that it STAYS impossible)."""
    c = _schema_mod()
    orig = c.validate_record

    def lazy_validate(record):
        errs = orig(record)
        return [e for e in errs if not e.startswith("fleet")]

    monkeypatch.setattr(c, "validate_record", lazy_validate)
    errors = c.self_check()
    assert any("fleet" in e for e in errors)


# ---- obs: per-replica attribution ----------------------------------------


def test_router_obs_per_replica_tracks():
    from libgrape_lite_tpu import obs

    obs.configure(in_memory=True)
    try:
        router = _router(2, dyn=False)
        for s in SOURCES:
            router.submit("sssp", {"source": s})
        router.drain()
        evs = obs.history()
        reps = {
            e["args"]["replica"] for e in evs
            if e.get("name") == "fleet_replica"
        }
        assert reps == {0, 1}
        router.begin_drain(0)
        router.rejoin(0)
        kinds = {e.get("name") for e in obs.history()}
        assert "fleet_drain_begin" in kinds and "fleet_rejoin" in kinds
    finally:
        obs.reset()
