"""serve/ — the multi-query serving runtime (ISSUE 6 acceptance).

Pins: batched k-source SSSP/BFS is byte-identical per lane to k
sequential Worker.query runs (including ragged convergence and an
absent source), a session's second query compiles nothing and plans
nothing (cache counters), the admission queue's coalescing policy
(FIFO per class, max_batch, max_wait, histogram), per-lane
guard-breach isolation, per-query obs attribution, and the CLI
`serve` subcommand surface.
"""

import json

import numpy as np
import pytest

from tests.conftest import dataset_path

# ragged by construction: eccentric sources (9/10/11 BFS rounds) plus
# one absent id whose lane converges after a single round
SOURCES = [6, 5229, 8200, 999999]


def _sequential(frag, app_cls, sources):
    from libgrape_lite_tpu.worker.worker import Worker

    values, rounds = {}, {}
    for s in sources:
        w = Worker(app_cls(), frag)
        w.query(source=s)
        values[s] = w.result_values()
        rounds[s] = w.rounds
    return values, rounds


# ---- batched dispatch: byte identity + ragged convergence ----------------


@pytest.mark.parametrize("app_name", ["sssp", "bfs"])
def test_batched_byte_identical_per_lane(graph_cache, app_name):
    """k-source batched dispatch vs k sequential queries: per-lane
    values AND round counts must match exactly — the freeze mask pins
    converged lanes, so raggedness never perturbs results."""
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.worker.worker import Worker

    frag = graph_cache(2)
    app_cls = APP_REGISTRY[app_name]
    want, want_rounds = _sequential(frag, app_cls, SOURCES)

    w = Worker(app_cls(), frag)
    w.query_batch([{"source": s} for s in SOURCES])
    assert [int(r) for r in w.batch_rounds] == [
        want_rounds[s] for s in SOURCES
    ]
    # the lanes genuinely finish at different rounds (ragged), and the
    # absent-source lane settled immediately
    assert len(set(int(r) for r in w.batch_rounds)) >= 3
    assert int(w.batch_rounds[-1]) == 1
    for b, s in enumerate(SOURCES):
        assert (
            w.batch_result_values(b).tobytes() == want[s].tobytes()
        ), f"{app_name} lane {b} (source {s}) diverged from sequential"


# (fnum, exchange, path, kernel): the fused batched runner and the
# guarded path's chunk runner, on one fragment and inside `shard_map`
# on two, the mirror exchange's compact table included; one case
# compiles the kernel itself, interpreted (tests/conftest.py)
LANE_KERNEL_CASES = [
    (1, "allgather", "fused", "stand_in"),
    (1, "allgather", "chunked", "stand_in"),
    (2, "allgather", "fused", "stand_in"),
    (2, "mirror", "chunked", "stand_in"),
]


@pytest.mark.parametrize(
    "app_name,fnum,exchange,path,kernel",
    [(a, *c) for a in ("sssp", "bfs") for c in LANE_KERNEL_CASES]
    + [("bfs", 1, "allgather", "fused", "interpreted")],
    ids=lambda v: str(v))
def test_batched_lanes_take_the_kernel_on_a_tpu(app_name, fnum, exchange,
                                                path, kernel, pull_kernel,
                                                monkeypatch):
    """On a TPU backend a single query's pull takes the kernel, and so
    do the batched runners' lanes, one lane after another, with the
    single query's scan behind it (the `vmap` rules of ops/segment.py):
    every call of the kernel is handed one lane's 1-D table, the lanes
    converge raggedly under the freeze mask, and each has its single
    query's bytes and rounds."""
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.ops.segment import FOLD_STATS
    from libgrape_lite_tpu.worker.worker import Worker
    from tests.conftest import gather_took, rand_frag

    if exchange == "mirror":
        monkeypatch.setenv("GRAPE_EXCHANGE", "mirror")
    else:
        monkeypatch.delenv("GRAPE_EXCHANGE", raising=False)
    # f32 weights: SSSP's state is the kernel's kind
    frag = rand_frag(fnum, weighted=app_name == "sssp")
    sources = [0, 5, 17, 999999]  # the last one absent: one round
    calls = pull_kernel("stand_in")  # the single queries' bits are XLA's
    singles, rounds = [], []
    for s in sources:
        w = Worker(APP_REGISTRY[app_name](), frag)
        took = gather_took(lambda: w.query(source=s))
        # the pull's gather and, under the mirror exchange, the pack
        # of the send buffer from the shard's own state
        assert took == {"kernel": 1 + (exchange == "mirror"), "xla": 0}
        singles.append(w.result_values().tobytes())
        rounds.append(w.rounds)
    tables = set(c[1] for c in calls)
    assert len(tables) == 1 + (exchange == "mirror")
    assert all(len(t) == 1 for t in tables)
    if kernel == "interpreted":
        calls = pull_kernel("interpreted")
    else:
        del calls[:]
    w = Worker(APP_REGISTRY[app_name](), frag)
    folds = FOLD_STATS.snapshot()
    took = gather_took(lambda: w.query_batch(
        [{"source": s} for s in sources],
        guard="halt" if path == "chunked" else None))
    # the chunk runner is lowered beside a batched PEval step
    assert took["xla"] == 0 and took["kernel"] >= 1
    assert FOLD_STATS["scatter"] == folds["scatter"]
    assert FOLD_STATS["scan"] > folds["scan"]
    # a lane's table is its single query's
    assert calls and set(c[1] for c in calls) == tables
    assert [int(r) for r in w.batch_rounds] == rounds
    assert len(set(rounds)) >= 2
    for b, want in enumerate(singles):
        assert w.batch_result_values(b).tobytes() == want


def test_batched_rejects_host_only_apps(graph_cache):
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.worker.worker import Worker

    w = Worker(APP_REGISTRY["sssp_msg"](), graph_cache(2))
    with pytest.raises(ValueError, match="host-only"):
        w.query_batch([{"source": 6}, {"source": 3}])


# ---- session: resident artifacts, zero recompile ------------------------


def test_session_second_query_compiles_nothing():
    """The acceptance counter check: after the first SSSP query warms a
    session, a second query of the same shape performs ZERO XLA
    compilation (compile_events, Worker.runner_cache_stats) — only
    cache hits."""
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession
    from tests.test_worker import build_fragment

    rng = np.random.default_rng(21)
    n, e = 700, 6000
    src, dst = rng.integers(0, n, e), rng.integers(0, n, e)
    frag = build_fragment(src, dst, None, n, 1)
    frag = _reweight_f32(frag, src, dst, n)

    sess = ServeSession(frag, policy=BatchPolicy(max_batch=1))

    r1 = sess.serve([("sssp", {"source": 0})])
    assert r1[0].ok
    s1 = sess.cache_stats()
    assert s1["runner"]["misses"] >= 1  # the warm compile

    # the zero-compile side counts the REAL XLA compile stream
    # (analysis.compile_events) rather than the runner-cache
    # counters: a fresh jit wrapper per dispatch compiles identical
    # HLO through a brand-new cache entry and the counters stay flat
    # (the PR 6 guarded-serve incident) — the event stream does not
    from libgrape_lite_tpu.analysis import compile_events

    with compile_events() as ev:
        r2 = sess.serve([("sssp", {"source": 5})])
    assert r2[0].ok
    assert ev.compiles == 0, (
        "second query recompiled", ev.events)
    s2 = sess.cache_stats()
    assert s2["runner"]["hits"] > s1["runner"]["hits"]
    assert s2["runner"]["misses"] == s1["runner"]["misses"], (s1, s2)
    # and the answers are the real per-source answers, not a stale reuse
    assert (
        r1[0].values.tobytes() != r2[0].values.tobytes()
    )


def _reweight_f32(frag, src, dst, n):
    """Rebuild the fragment with f32 unit weights."""
    from libgrape_lite_tpu.fragment.edgecut import ShardedEdgecutFragment
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec
    from libgrape_lite_tpu.utils.types import LoadStrategy
    from libgrape_lite_tpu.vertex_map.partitioner import MapPartitioner
    from libgrape_lite_tpu.vertex_map.vertex_map import VertexMap

    oids = np.arange(n, dtype=np.int64)
    vm = VertexMap.build(oids, MapPartitioner(1, oids))
    rng = np.random.default_rng(3)
    w = rng.uniform(0.5, 2.0, size=len(src)).astype(np.float32)
    return ShardedEdgecutFragment.build(
        CommSpec(fnum=1), vm, np.asarray(src), np.asarray(dst), w,
        directed=False, load_strategy=LoadStrategy.kBothOutIn,
    )


def test_session_coalesced_results_match_sequential(graph_cache):
    """End-to-end through session + queue: a mixed 8-query stream at
    max_batch=4 returns exactly the sequential answers."""
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession

    frag = graph_cache(2)
    sources = [6, 17, 3, 42, 11, 12, 13, 14]
    want, _ = _sequential(frag, APP_REGISTRY["sssp"], sources)

    sess = ServeSession(frag, policy=BatchPolicy(max_batch=4))
    reqs = [sess.submit("sssp", {"source": s}) for s in sources]
    results = sess.drain()
    assert len(results) == len(sources)
    assert sess.queue.batch_hist == {4: 2}
    for req, s in zip(reqs, sources):
        assert req.done and req.result.ok
        assert req.result.values.tobytes() == want[s].tobytes()
        assert req.result.batch_size == 4


def test_session_sequential_fallback_for_host_only(graph_cache):
    """Host-only apps (sssp_msg) never batch: distinct sources stay
    separate dispatches (no batch_query_key -> incompatible), and a
    coalesced pair of identical queries falls back to per-lane
    sequential execution — correct results either way."""
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession

    frag = graph_cache(2)
    want, _ = _sequential(frag, APP_REGISTRY["sssp_msg"], [6, 17])
    sess = ServeSession(frag, policy=BatchPolicy(max_batch=4))
    res = sess.serve([("sssp_msg", {"source": 6}),
                      ("sssp_msg", {"source": 17})])
    assert all(r.ok for r in res)
    # no per-lane query arg declared -> differing sources never share
    # a dispatch
    assert sess.queue.batch_hist == {1: 2}
    assert res[0].values.tobytes() == want[6].tobytes()
    assert res[1].values.tobytes() == want[17].tobytes()
    # identical args DO coalesce, and the dispatcher falls back to
    # sequential execution for the unbatchable app
    res2 = sess.serve([("sssp_msg", {"source": 6}),
                       ("sssp_msg", {"source": 6})])
    assert all(r.ok for r in res2)
    assert sess.stats["sequential_fallbacks"] == 1
    assert res2[0].values.tobytes() == want[6].tobytes()
    assert res2[1].values.tobytes() == want[6].tobytes()


def test_session_unknown_app_rejected(graph_cache):
    from libgrape_lite_tpu.serve import ServeSession

    sess = ServeSession(graph_cache(1), apps={})
    with pytest.raises(ValueError, match="unknown application"):
        sess.worker("sssp")


# ---- admission queue: coalescing policy ----------------------------------


def _stub_queue(policy):
    """AdmissionQueue over a recording stub dispatcher."""
    from libgrape_lite_tpu.serve import AdmissionQueue, ServeResult

    batches = []

    def dispatch(batch):
        batches.append([r.id for r in batch])
        return [
            ServeResult(request_id=r.id, app_key=r.app_key, ok=True,
                        lane=b, batch_size=len(batch))
            for b, r in enumerate(batch)
        ]

    return AdmissionQueue(dispatch, policy), batches


def test_queue_coalesces_compatible_fifo():
    """Only compatible requests share a batch; FIFO within a class; an
    interleaved incompatible request keeps its place."""
    from libgrape_lite_tpu.serve import BatchPolicy

    q, batches = _stub_queue(BatchPolicy(max_batch=4))
    ids = {}
    for i, app in enumerate(
        ["sssp", "sssp", "bfs", "sssp", "sssp", "sssp"]
    ):
        ids[i] = q.submit(app, {"source": i}).id
    q.drain()
    # head class sssp fills to 4 skipping the bfs; bfs next; last sssp
    assert batches == [
        [ids[0], ids[1], ids[3], ids[4]], [ids[2]], [ids[5]],
    ]
    assert q.batch_hist == {4: 1, 1: 2}
    assert q.completed == 6


def test_queue_max_rounds_never_coalesces():
    """Different max_rounds need different compiled runners — the
    satellite fix keys the serve compatibility class on it too."""
    from libgrape_lite_tpu.serve import BatchPolicy

    q, batches = _stub_queue(BatchPolicy(max_batch=8))
    a = q.submit("sssp", {"source": 1})
    b = q.submit("sssp", {"source": 2}, max_rounds=5)
    c = q.submit("sssp", {"source": 3})
    q.drain()
    assert batches == [[a.id, c.id], [b.id]]


def test_queue_max_wait_holds_partial_batches():
    """Below max_batch, the head waits max_wait_s before a partial
    batch ships; drain() forces it."""
    from libgrape_lite_tpu.serve import BatchPolicy

    q, batches = _stub_queue(BatchPolicy(max_batch=4, max_wait_s=60.0))
    r = q.submit("sssp", {"source": 1})
    q.submit("sssp", {"source": 2})
    assert q.pump() == []  # nothing ready: 2 < 4 and head is fresh
    assert q.pending() == 2
    # the head aged past the policy window -> partial batch ships
    out = q.pump(now=r.submitted_s + 61.0)
    assert len(out) == 2 and batches == [[r.id, out[1].request_id]]


def test_queue_full_batch_ships_immediately():
    from libgrape_lite_tpu.serve import BatchPolicy

    q, batches = _stub_queue(BatchPolicy(max_batch=2, max_wait_s=60.0))
    q.submit("sssp", {"source": 1})
    q.submit("sssp", {"source": 2})
    assert len(q.pump()) == 2  # full batch ignores the wait window


# ---- per-lane guard-breach isolation -------------------------------------


def test_guarded_batch_clean_lanes_match_sequential(graph_cache):
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession

    frag = graph_cache(2)
    sources = [6, 17, 3, 42]
    want, _ = _sequential(frag, APP_REGISTRY["sssp"], sources)
    sess = ServeSession(frag, policy=BatchPolicy(max_batch=4),
                        guard="halt")
    res = sess.serve([("sssp", {"source": s}) for s in sources])
    for r, s in zip(res, sources):
        assert r.ok, r.error
        assert r.values.tobytes() == want[s].tobytes()


def test_guarded_batch_breach_isolated_to_one_lane(graph_cache):
    """Poisoning ONE lane mid-flight fails that query with a breach
    bundle while every batchmate converges byte-identically — the
    serving form of the halt policy."""
    import jax

    from libgrape_lite_tpu.guard.config import GuardConfig
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.serve.batch import run_guarded_batch
    from libgrape_lite_tpu.worker.worker import Worker

    frag = graph_cache(2)
    sources = [6, 17, 3, 42]
    want, _ = _sequential(frag, APP_REGISTRY["sssp"], sources)

    def poison_lane_1(carry, rounds):
        if rounds != 3:
            return None
        dist = np.array(jax.device_get(carry["dist"]))
        dist[1, 0, :8] = np.nan
        return {"dist": dist}

    w = Worker(APP_REGISTRY["sssp"](), frag)
    run_guarded_batch(
        w, [{"source": s} for s in sources], 0,
        GuardConfig(policy="halt", every=1), chunk_hook=poison_lane_1,
    )
    assert w.batch_breaches[1] is not None
    assert w.batch_breaches[1]["verdict"]["kind"] == "invariant"
    assert w.batch_breaches[1]["round"] == 3  # same-round detection
    for b in (0, 2, 3):
        assert w.batch_breaches[b] is None
        assert (
            w.batch_result_values(b).tobytes()
            == want[sources[b]].tobytes()
        ), f"breach in lane 1 perturbed healthy lane {b}"


def test_session_reports_breached_lane_as_failed_result(graph_cache):
    """Through the full session path: the poisoned lane surfaces as a
    failed ServeResult carrying the bundle, batchmates stay ok."""
    import jax

    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession
    from libgrape_lite_tpu.serve import batch as serve_batch

    frag = graph_cache(2)
    sources = [6, 17, 3]

    orig = serve_batch.run_guarded_batch

    def poisoned(worker, args_list, mr, cfg, **kw):
        def hook(carry, rounds):
            if rounds != 2:
                return None
            dist = np.array(jax.device_get(carry["dist"]))
            dist[0, 0, :4] = -5.0  # negative distance: in_range breach
            return {"dist": dist}

        return orig(worker, args_list, mr, cfg, chunk_hook=hook)

    serve_batch.run_guarded_batch = poisoned
    try:
        sess = ServeSession(frag, policy=BatchPolicy(max_batch=4),
                            guard="halt")
        res = sess.serve([("sssp", {"source": s}) for s in sources])
    finally:
        serve_batch.run_guarded_batch = orig
    assert not res[0].ok and res[0].error["verdict"]["kind"] == "invariant"
    assert res[1].ok and res[2].ok
    assert sess.stats["failed"] == 1


# ---- per-query obs attribution -------------------------------------------


def test_serve_obs_per_query_lane_spans(graph_cache):
    """Each query of a coalesced batch gets its own lane-track span
    carrying its request id and per-lane round count."""
    from libgrape_lite_tpu import obs
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession

    frag = graph_cache(2)
    obs.configure(in_memory=True)
    try:
        sess = ServeSession(frag, policy=BatchPolicy(max_batch=4))
        reqs = [sess.submit("sssp", {"source": s}) for s in [6, 17, 3]]
        sess.drain()
        evs = obs.history()
        lanes = [e for e in evs if e.get("name") == "serve_query"]
        assert len(lanes) == 3
        got = {e["args"]["query_id"]: e["args"] for e in lanes}
        assert set(got) == {r.id for r in reqs}
        for r in reqs:
            assert got[r.id]["rounds"] == r.result.rounds
            assert got[r.id]["ok"] is True
        batch_spans = [
            e for e in evs if e.get("name") == "serve_batch"
        ]
        assert len(batch_spans) == 1
        assert batch_spans[0]["args"]["batch"] == 3
    finally:
        obs.reset()


# ---- CLI serve subcommand ------------------------------------------------


def test_cli_serve_scripted_stream(capsys):
    from libgrape_lite_tpu.cli import serve_main

    serve_main([
        "--efile", dataset_path("p2p-31.e"),
        "--vfile", dataset_path("p2p-31.v"),
        "--fnum", "2", "--application", "bfs",
        "--sources", "6,17,3,42,11,12",
        "--max_batch", "4",
    ])
    out = capsys.readouterr().out
    rec = json.loads(
        [l for l in out.splitlines() if l.startswith("{")][-1]
    )
    assert rec["queries"] == 6 and rec["failed"] == 0
    assert rec["batch_hist"] == {"4": 1, "2": 1}
    assert rec["apps"] == {"bfs": 6}
    assert rec["cache"]["runner"]["misses"] >= 1


def test_cli_serve_exits_nonzero_when_any_query_failed(capsys):
    """One breached lane of three: the record still prints, and the
    exit code and stderr say that a query failed (it used to take EVERY
    query failing)."""
    import jax

    from libgrape_lite_tpu.cli import serve_main
    from libgrape_lite_tpu.serve import batch as serve_batch

    orig = serve_batch.run_guarded_batch

    def poisoned(worker, args_list, mr, cfg, **kw):
        def hook(carry, rounds):
            if rounds != 2:
                return None
            dist = np.array(jax.device_get(carry["dist"]))
            dist[0, 0, :4] = -5.0  # negative distance: in_range breach
            return {"dist": dist}

        return orig(worker, args_list, mr, cfg, chunk_hook=hook)

    serve_batch.run_guarded_batch = poisoned
    try:
        with pytest.raises(SystemExit) as exc:
            serve_main([
                "--efile", dataset_path("p2p-31.e"),
                "--vfile", dataset_path("p2p-31.v"),
                "--fnum", "2", "--application", "sssp",
                "--sources", "6,17,3", "--max_batch", "4",
                "--guard", "halt",
            ])
    finally:
        serve_batch.run_guarded_batch = orig
    assert exc.value.code == 1
    cap = capsys.readouterr()
    rec = json.loads(
        [l for l in cap.out.splitlines() if l.startswith("{")][-1]
    )
    assert rec["queries"] == 3 and rec["failed"] == 1
    assert "1 of 3 queries failed" in cap.err


# ---- review-pass hardening (each with the failure it pins) ---------------


def test_unknown_app_request_fails_without_wedging_queue(graph_cache):
    """A submitted unknown app must fail as a result, not wedge the
    queue head forever — queries behind it still serve."""
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.serve import ServeSession

    frag = graph_cache(2)
    want, _ = _sequential(frag, APP_REGISTRY["sssp"], [6])
    sess = ServeSession(frag)
    bad = sess.submit("not_an_app", {"source": 1})
    good = sess.submit("sssp", {"source": 6})
    res = sess.drain()
    assert len(res) == 2
    assert bad.done and not bad.result.ok
    assert "unknown application" in bad.result.error["error"]
    assert good.done and good.result.ok
    assert good.result.values.tobytes() == want[6].tobytes()
    assert sess.queue.pending() == 0


def test_explicit_guard_off_disarms_env_for_exchange_apps(
        graph_cache, monkeypatch):
    """guard=\"off\" must beat an env-armed GRAPE_GUARD for host-loop
    (exchange) apps, exactly as it does for superstep apps."""
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.worker.worker import Worker

    frag = graph_cache(2)
    monkeypatch.setenv("GRAPE_GUARD", "halt")
    w = Worker(APP_REGISTRY["sssp_msg"](), frag)
    w.query(source=6, guard="off")
    assert w.guard_report is None  # no monitor ran


def test_guarded_batch_second_dispatch_compiles_nothing(graph_cache):
    """The guarded serve path's batched PEval is cached like every
    other runner — a steady guarded stream must not re-jit per batch.
    Pinned on the real XLA compile stream (analysis.compile_events):
    this exact path once minted a fresh jit wrapper per batch, which
    the runner-cache counters could not see (PR 6); per-lane guard
    monitors also share their compiled probe through the fragment-
    keyed probe cache (grape-lint R2, this PR)."""
    from libgrape_lite_tpu.analysis import compile_events
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession

    frag = graph_cache(2)
    sess = ServeSession(frag, policy=BatchPolicy(max_batch=4),
                        guard="halt")
    assert all(r.ok for r in sess.serve(
        [("sssp", {"source": s}) for s in [6, 17, 3, 42]]
    ))
    with compile_events() as ev:
        assert all(r.ok for r in sess.serve(
            [("sssp", {"source": s}) for s in [11, 12, 13, 14]]
        ))
    assert ev.compiles == 0, ev.events


def test_cli_serve_empty_stream_is_a_usage_error(tmp_path):
    from libgrape_lite_tpu.cli import serve_main

    stream = tmp_path / "empty.txt"
    stream.write_text("# only comments\n")
    with pytest.raises(SystemExit, match="empty"):
        serve_main([
            "--efile", dataset_path("p2p-31.e"),
            "--stream", str(stream),
        ])


# ---- personalized-PageRank seed batching (dyn-PR satellite) --------------


def test_ppr_batched_byte_identical_per_lane(graph_cache):
    """Personalized PageRank through the source-vector contract: k
    seeded lanes in ONE vmapped dispatch, each byte-identical to its
    sequential query (incl. an absent seed, whose lane is all-zero)."""
    from libgrape_lite_tpu.models import PageRank
    from libgrape_lite_tpu.worker.worker import Worker

    frag = graph_cache(2)
    sources = [6, 5229, 999999]
    want = {}
    for s in sources:
        w = Worker(PageRank(max_round=10), frag)
        w.query(source=s, max_round=10)
        want[s] = w.result_values()

    wb = Worker(PageRank(max_round=10), frag)
    wb.query_batch([
        {"source": s, "max_round": 10} for s in sources
    ])
    for b, s in enumerate(sources):
        assert (
            wb.batch_result_values(b).tobytes() == want[s].tobytes()
        ), f"PPR lane {b} (seed {s}) diverged from sequential"
    # seeded mass stays on the seed's side of the graph: a resolved
    # seed keeps unit mass, the absent one keeps none
    assert float(want[6].sum()) == pytest.approx(1.0, rel=1e-6)
    assert float(want[999999].sum()) == 0.0


def test_ppr_and_global_pagerank_do_not_coalesce(graph_cache):
    """A personalized lane (source given) and a global lane (none)
    trace different carries — the compat key must keep them apart and
    both must come back correct."""
    from libgrape_lite_tpu.models import PageRank
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession
    from libgrape_lite_tpu.worker.worker import Worker

    frag = graph_cache(2)
    sess = ServeSession(frag, policy=BatchPolicy(max_batch=4))
    ppr = sess.submit("pagerank", {"source": 6})
    glob = sess.submit("pagerank", {})
    sess.drain()
    assert ppr.result.ok and glob.result.ok
    assert ppr.result.batch_size == 1 and glob.result.batch_size == 1

    w = Worker(PageRank(max_round=10), frag)
    w.query(max_round=10)
    assert glob.result.values.tobytes() == w.result_values().tobytes()
    w2 = Worker(PageRank(max_round=10), frag)
    w2.query(source=6, max_round=10)
    assert ppr.result.values.tobytes() == w2.result_values().tobytes()


def test_ppr_mixed_lanes_fail_loudly(graph_cache):
    """Review regression: a mixed personalized/global PageRank batch
    through the direct Worker API fails with the reason, not a bare
    KeyError out of the lane stacker."""
    from libgrape_lite_tpu.models import PageRank
    from libgrape_lite_tpu.worker.worker import Worker

    w = Worker(PageRank(max_round=5), graph_cache(2))
    with pytest.raises(ValueError, match="cannot share one batch"):
        w.query_batch([{"source": 6}, {}])
