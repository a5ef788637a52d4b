"""Tiled masked SpGEMM (ops/spgemm_pack.py) + the LCC backend switch.

The r11 contract surface:
  * bit-exactness: the spgemm backend's per-vertex triangle credits are
    integer-identical to the popcount intersect's, so the LCC output is
    BIT-identical — pinned on the p2p-31 golden at fnum {1, 2, 4} and
    under every degree_threshold;
  * plan-time pruning: the item stream enumerates exactly the nonzero
    row×col tile products;
  * backend keying: the runner cache and the v3 disk plan cache never
    share entries across backends (repeat query = zero compiles, via
    analysis.compile_events);
  * ledger == recount exactness (scripts/pack_cost_model.spgemm_recount);
  * every non-engagement is a RECORDED decline in SPGEMM_STATS;
  * artifact audits: no baked constants in the compiled spgemm runner
    (streams ride as state), zero surprise compiles when warm.
"""

from __future__ import annotations

import os

import numpy as np
import pytest

from tests.conftest import dataset_path
from tests.test_lcc_threshold import brute_force_lcc, er_graph
from tests.test_worker import build_fragment
from tests.verifiers import (
    collect_worker_result,
    eps_verify,
    load_golden,
)


@pytest.fixture
def backend(monkeypatch):
    def set_backend(value):
        if value is None:
            monkeypatch.delenv("GRAPE_LCC_BACKEND", raising=False)
        else:
            monkeypatch.setenv("GRAPE_LCC_BACKEND", value)

    return set_backend


def _er_fragment(fnum=4, n=48):
    src, dst = er_graph(n)
    return build_fragment(src, dst, None, n, fnum), n, src, dst


def _brute_tri(n, src, dst):
    """Per-vertex triangle counts on oids, from the raw edge list."""
    adj = {v: set() for v in range(n)}
    for s, d in zip(src, dst):
        if s != d:
            adj[int(s)].add(int(d))
            adj[int(d)].add(int(s))
    tri = {v: 0 for v in range(n)}
    for v in range(n):
        for u in adj[v]:
            if u < v:
                continue
            for w in adj[v] & adj[u]:
                if w > u:
                    tri[v] += 1
                    tri[u] += 1
                    tri[w] += 1
    return tri


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------


def test_plan_prunes_to_exact_tile_products():
    """The item stream is exactly the set of (mask edge, K-tile) pairs
    where both operand rows have bits — recomputed here from the raw
    oriented adjacency, independently of the planner's bitsets."""
    from libgrape_lite_tpu.ops.spgemm_pack import plan_spgemm

    frag, n, src, dst = _er_fragment(fnum=1)
    plan = plan_spgemm(frag)
    # brute-force the oriented DAG in oid space (fnum=1: pid == oid
    # up to the loader permutation — use the plan's own mask count
    # for the edge total and recount items from per-row tile sets)
    st = plan.host_streams
    valid = st["valid"].astype(bool)
    assert int(valid.sum()) == plan.items
    # every valid item's decoded AND-block must be consistent: the
    # planner only emits items where both rows share the tile
    bm = st["bm"]
    for f in range(plan.fnum):
        vr = st["vrow"][f][valid[f]]
        ur = st["urow"][f][valid[f]]
        kt = st["kt"][f][valid[f]]
        for i in range(len(vr)):
            w0 = kt[i] * 4
            vw = bm[f, vr[i], w0:w0 + 4]
            uw = bm[f, ur[i], w0:w0 + 4]
            assert vw.any() and uw.any(), \
                "item emitted for an empty operand tile (pruning hole)"
    # ledger totals follow the documented conventions exactly
    t = plan.ledger["totals"]
    assert t["vpu_ops"] == 10 * 128 * plan.items
    assert t["mxu_ops"] == 128 * plan.items
    assert t["gather_rows"] == 2 * plan.items


def test_plan_only_matches_materialized_counts():
    from libgrape_lite_tpu.ops.spgemm_pack import plan_spgemm

    frag, *_ = _er_fragment(fnum=1)
    full = plan_spgemm(frag)
    lite = plan_spgemm(frag, plan_only=True)
    assert lite.host_streams is None
    assert lite.items == full.items
    assert lite.mask_edges == full.mask_edges
    t_full, t_lite = full.ledger["totals"], lite.ledger["totals"]
    for k in ("vpu_ops", "mxu_ops", "gather_rows"):
        assert t_lite[k] == t_full[k]


def test_plan_only_byte_model_not_fnum_inflated():
    """Review-pass regression: the plan_only byte model pads item
    streams to the PER-SHARD max like the materialized plan — billing
    fnum x total items inflated the spgemm HBM ~fnum-fold and biased
    the auto decision toward intersect at fnum > 1."""
    from libgrape_lite_tpu.ops.spgemm_pack import plan_spgemm

    frag, *_ = _er_fragment(fnum=4)
    full = plan_spgemm(frag)
    lite = plan_spgemm(frag, plan_only=True)
    h_full = full.ledger["totals"]["hbm_bytes"]
    h_lite = lite.ledger["totals"]["hbm_bytes"]
    assert h_lite < 2.0 * h_full, (h_lite, h_full)
    assert h_lite > 0.2 * h_full, (h_lite, h_full)


def test_auto_pricing_memoized(backend, monkeypatch):
    """Review-pass regression: repeated auto resolutions on one
    fragment re-price from the per-frag memo instead of re-running
    the host planner (serve-style Worker churn)."""
    import libgrape_lite_tpu.ops.spgemm_pack as sg

    frag, *_ = _er_fragment(fnum=2)
    backend("auto")
    sg.resolve_lcc_backend("LCC", frag, chunk=4096)
    decisions = len(sg.SPGEMM_STATS["decisions"])

    def boom(*a, **k):
        raise AssertionError("auto re-planned a memoized fragment")

    monkeypatch.setattr(sg, "plan_spgemm", boom)
    for _ in range(3):
        sg.resolve_lcc_backend("LCC", frag, chunk=4096)
    # still RECORDS each decision (the never-silent contract)
    assert len(sg.SPGEMM_STATS["decisions"]) == decisions + 3


def test_spgemm_chunk_env_validation(monkeypatch):
    from libgrape_lite_tpu.ops.spgemm_pack import SpGemmConfig

    monkeypatch.setenv("GRAPE_SPGEMM_CHUNK", "256")
    assert SpGemmConfig.from_env().chunk == 256
    monkeypatch.setenv("GRAPE_SPGEMM_CHUNK", "zero")
    with pytest.raises(ValueError, match="GRAPE_SPGEMM_CHUNK"):
        SpGemmConfig.from_env()


# ---------------------------------------------------------------------------
# LCC backend bit-exactness
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fnum", [1, 2, 4])
def test_lcc_golden_bitexact_across_backends(graph_cache, fnum, backend):
    """The acceptance pin: spgemm LCC bit-exact to intersect on the
    golden dataset, and golden-eps in its own right."""
    from libgrape_lite_tpu.models import LCC

    frag = graph_cache(fnum)
    backend("intersect")
    r_int = collect_worker_result(LCC(), frag)
    backend("spgemm")
    r_sp = collect_worker_result(LCC(), frag)
    assert r_int == r_sp, "spgemm LCC diverged from intersect"
    eps_verify(r_sp, load_golden(dataset_path("p2p-31-LCC")))


@pytest.mark.parametrize("thr", [0, 5, 8])
def test_degree_threshold_bitexact(thr, backend):
    """Threshold semantics (apex + middle unfiltered, far end exempt)
    carry over: spgemm == intersect bit-exact AND == the reference
    brute force."""
    from libgrape_lite_tpu.models import APP_REGISTRY

    frag, n, src, dst = _er_fragment(fnum=4)
    backend("intersect")
    r_int = collect_worker_result(
        APP_REGISTRY["lcc_bitmap"](), frag, degree_threshold=thr
    )
    backend("spgemm")
    r_sp = collect_worker_result(
        APP_REGISTRY["lcc_bitmap"](), frag, degree_threshold=thr
    )
    assert r_int == r_sp
    want = brute_force_lcc(frag, n, src, dst, thr)
    for k, v in want.items():
        assert abs(float(r_sp[k]) - v) < 1e-9, (k, r_sp[k], v)


def test_lcc_chunk_env_is_tunable_and_bitexact(backend, monkeypatch):
    """The r1 baked `_CHUNK = 4096` lifted: GRAPE_LCC_CHUNK re-chunks
    the intersect kernel with bit-identical results, rides trace_key
    (a changed chunk must not reuse the old compile), and rejects
    garbage loudly."""
    from libgrape_lite_tpu.models import LCC
    from libgrape_lite_tpu.models.lcc import _lcc_chunk

    frag, *_ = _er_fragment(fnum=2)
    backend("intersect")
    base = collect_worker_result(LCC(), frag)
    monkeypatch.setenv("GRAPE_LCC_CHUNK", "512")
    small = collect_worker_result(LCC(), frag)
    assert base == small
    app_a, app_b = LCC(), LCC()
    app_b.init_state(frag)
    monkeypatch.delenv("GRAPE_LCC_CHUNK")
    app_a.init_state(frag)
    assert app_a.trace_key() != app_b.trace_key()
    monkeypatch.setenv("GRAPE_LCC_CHUNK", "-3")
    with pytest.raises(ValueError, match="GRAPE_LCC_CHUNK"):
        _lcc_chunk()
    monkeypatch.setenv("GRAPE_LCC_CHUNK", "many")
    with pytest.raises(ValueError, match="GRAPE_LCC_CHUNK"):
        _lcc_chunk()


def test_backend_env_validation(monkeypatch):
    from libgrape_lite_tpu.ops.spgemm_pack import lcc_backend_mode

    monkeypatch.setenv("GRAPE_LCC_BACKEND", "fastest")
    with pytest.raises(ValueError, match="GRAPE_LCC_BACKEND"):
        lcc_backend_mode()


def test_path_graph_no_triangles(backend):
    """Triangle-free graph: the spgemm path runs (possibly with zero
    items) and agrees with intersect on all-zero coefficients."""
    from libgrape_lite_tpu.models import LCC

    n = 12
    src = np.arange(n - 1, dtype=np.int64)
    dst = src + 1
    frag = build_fragment(src, dst, None, n, 2)
    backend("spgemm")
    r = collect_worker_result(LCC(), frag)
    assert all(float(v) == 0.0 for v in r.values())


# ---------------------------------------------------------------------------
# backend selection: auto pricing, declines, cache separation
# ---------------------------------------------------------------------------


def test_auto_decision_and_declines_recorded(backend):
    """auto prices both ledgers and records the decision; unsupported
    variants (lcc_beta's merge kernel, lcc_directed) decline with the
    app name and reason — never silently."""
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.ops.spgemm_pack import spgemm_stats

    frag, n, src, dst = _er_fragment(fnum=2)
    backend("auto")
    r_auto = collect_worker_result(APP_REGISTRY["lcc_bitmap"](), frag)
    st = spgemm_stats()
    dec = [d for d in st["decisions"] if d["app"] == "LCC"
           and d["mode"] == "auto"]
    assert dec, "auto decision not recorded"
    assert dec[-1]["backend"] in ("intersect", "spgemm")
    assert dec[-1]["t_spgemm_s"] >= 0 and dec[-1]["t_intersect_s"] >= 0
    backend(None)
    assert r_auto == collect_worker_result(
        APP_REGISTRY["lcc_bitmap"](), frag
    )

    backend("spgemm")
    r_beta = collect_worker_result(APP_REGISTRY["lcc_beta"](), frag)
    declines = spgemm_stats()["declines"]
    assert any(d["app"] == "LCCBeta" and d["requested"] == "spgemm"
               for d in declines), "lcc_beta decline not recorded"
    backend(None)
    assert r_beta == collect_worker_result(
        APP_REGISTRY["lcc_beta"](), frag
    )


def test_lcc_directed_declines_spgemm(backend):
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.ops.spgemm_pack import spgemm_stats

    src, dst = er_graph(32)
    frag = build_fragment(src, dst, None, 32, 2, directed=True)
    backend("spgemm")
    r_sp = collect_worker_result(APP_REGISTRY["lcc_directed"](), frag)
    assert any(d["app"] == "LCCDirected"
               for d in spgemm_stats()["declines"])
    backend(None)
    assert r_sp == collect_worker_result(
        APP_REGISTRY["lcc_directed"](), frag
    )


def test_backend_cache_separation_zero_recompiles(backend):
    """The two backends never share a compiled runner (trace_key keys
    lcc_backend + plan uid), and a repeat query on either backend is
    ZERO compiles on the real XLA stream."""
    from libgrape_lite_tpu.analysis import compile_events
    from libgrape_lite_tpu.models import LCC
    from libgrape_lite_tpu.worker.worker import Worker

    frag, *_ = _er_fragment(fnum=2)
    w = Worker(LCC(), frag)
    backend("intersect")
    w.query()
    r_int = w.result_values()
    backend("spgemm")
    w.query()
    r_sp = w.result_values()
    assert w.runner_cache_stats["misses"] == 2, \
        "backends shared (or over-split) the runner cache"
    assert np.array_equal(r_int, r_sp)
    with compile_events() as ev:
        backend("intersect")
        w.query()
        backend("spgemm")
        w.query()
    assert ev.compiles == 0, \
        f"warm backend flip recompiled ({ev.compiles} compiles)"
    assert w.runner_cache_stats["hits"] >= 2


def test_disk_plan_cache_backend_separation(tmp_path, monkeypatch):
    """spgemm plans persist under their own digest family
    (`spgemmplan_*` entries): a fresh identical fragment loads the
    plan from disk byte-identically."""
    from libgrape_lite_tpu.ops.spgemm_pack import (
        SPGEMM_STATS,
        resolve_spgemm_dispatch,
    )

    monkeypatch.setenv("GRAPE_PACK_PLAN_CACHE", str(tmp_path))
    src, dst = er_graph(40)
    frag_a = build_fragment(src, dst, None, 40, 2)
    before = dict(SPGEMM_STATS)
    d_a = resolve_spgemm_dispatch(frag_a)
    assert SPGEMM_STATS["planned"] == before["planned"] + 1
    files = sorted(os.listdir(tmp_path))
    assert files and all(f.startswith("spgemmplan_") for f in files)
    frag_b = build_fragment(src, dst, None, 40, 2)
    d_b = resolve_spgemm_dispatch(frag_b)
    assert SPGEMM_STATS["disk_cache_hits"] == \
        before["disk_cache_hits"] + 1
    for k, arr in d_a.plan.host_streams.items():
        assert arr.tobytes() == d_b.plan.host_streams[k].tobytes(), \
            f"disk roundtrip changed stream {k!r}"
    # second resolve on the SAME fragment: the per-frag memo answers
    resolve_spgemm_dispatch(frag_b)
    assert SPGEMM_STATS["frag_cache_hits"] >= \
        before["frag_cache_hits"] + 1


# ---------------------------------------------------------------------------
# ledger == recount, worker surfacing
# ---------------------------------------------------------------------------


def test_ledger_recount_exact_and_live():
    """The shipped-stream recount agrees EXACTLY today (drift budget
    is for future planner changes), and the gate is live: a doctored
    ledger trips it."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    from pack_cost_model import spgemm_recount

    from libgrape_lite_tpu.ops.spgemm_pack import plan_spgemm

    frag, *_ = _er_fragment(fnum=2)
    plan = plan_spgemm(frag)
    rec = spgemm_recount(plan)
    assert rec["spgemm_recount_mismatch"] == 0.0, rec
    assert rec["items_recounted"] == plan.items
    doctored = dict(plan.ledger)
    doctored["totals"] = dict(plan.ledger["totals"])
    doctored["totals"]["vpu_ops"] = int(
        doctored["totals"]["vpu_ops"] * 1.5) + 1
    plan.ledger = doctored
    assert spgemm_recount(plan)["spgemm_recount_mismatch"] > 0.05


def test_worker_ledger_surfaces_spgemm(backend):
    from libgrape_lite_tpu.models import LCC
    from libgrape_lite_tpu.worker.worker import Worker

    frag, *_ = _er_fragment(fnum=2)
    backend("spgemm")
    w = Worker(LCC(), frag)
    w.query()
    led = w.pack_ledger()
    assert led is not None, "spgemm ledger not surfaced"
    assert led["totals"]["mxu_ops"] > 0
    assert led["totals"]["vpu_ops"] > 0
    assert "far_scatter" in led["totals"]["per_stage"]


# ---------------------------------------------------------------------------
# artifact audits (satellite: A1 + A3 on the compiled spgemm runner)
# ---------------------------------------------------------------------------


def test_artifact_audits_spgemm_runner(backend):
    """A1: the spgemm streams ride as state arguments, never baked
    XLA constants; A3: the warm second query compiles nothing on the
    real backend_compile stream."""
    from libgrape_lite_tpu.analysis import compile_events
    from libgrape_lite_tpu.analysis.artifact import audit_fused_runner
    from libgrape_lite_tpu.models import LCC
    from libgrape_lite_tpu.worker.worker import Worker

    frag, *_ = _er_fragment(fnum=2)
    backend("spgemm")
    w = Worker(LCC(), frag)
    findings, info = audit_fused_runner(w)
    a1 = [f for f in findings if f.rule == "A1"]
    assert a1 == [], [f.message for f in a1]
    assert info["constants"] > 0  # the scan genuinely saw the module
    w.query()
    with compile_events() as ev:
        w.query()
    assert ev.compiles == 0, \
        f"warm spgemm query recompiled ({ev.compiles})"


# ---------------------------------------------------------------------------
# new apps: triangle_count + common_neighbors
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bk", ["intersect", "spgemm"])
def test_triangle_count_exact(bk, backend):
    from libgrape_lite_tpu.models import APP_REGISTRY

    frag, n, src, dst = _er_fragment(fnum=2)
    backend(bk)
    app = APP_REGISTRY["triangle_count"]()
    res = collect_worker_result(app, frag)
    want = _brute_tri(n, src, dst)
    for k, v in want.items():
        assert int(res[k]) == v, (bk, k, res[k], v)
    assert app.global_triangles == sum(want.values()) // 3


def test_triangle_count_matches_lcc_credits(backend):
    """T(v) relates to the LCC output by exactly the documented
    formula — the 'exact vs the LCC credit counts' pin."""
    from libgrape_lite_tpu.models import APP_REGISTRY

    frag, n, src, dst = _er_fragment(fnum=2)
    backend("spgemm")
    tri = collect_worker_result(APP_REGISTRY["triangle_count"](), frag)
    lcc = collect_worker_result(APP_REGISTRY["lcc_bitmap"](), frag)
    deg = {v: 0 for v in range(n)}
    for s, d in zip(src, dst):
        deg[int(s)] += 1
        deg[int(d)] += 1
    for v in range(n):
        if deg[v] >= 2:
            want = 2.0 * int(tri[v]) / (deg[v] * (deg[v] - 1))
            assert abs(float(lcc[v]) - want) < 1e-12


def test_common_neighbors_point_query():
    from libgrape_lite_tpu.models import APP_REGISTRY

    frag, n, src, dst = _er_fragment(fnum=2)
    adj = {v: set() for v in range(n)}
    for s, d in zip(src, dst):
        adj[int(s)].add(int(d))
        adj[int(d)].add(int(s))
    for q in (0, 7, 23):
        res = collect_worker_result(
            APP_REGISTRY["common_neighbors"](), frag, source=q
        )
        for v in range(n):
            want = 0 if v == q else len(adj[q] & adj[v])
            assert int(res[v]) == want, (q, v, res[v], want)


def test_common_neighbors_batched_matches_sequential():
    """The serve source-vector contract: k sources in one vmapped
    dispatch, per-lane bytes identical to sequential queries."""
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.worker.worker import Worker

    frag, n, *_ = _er_fragment(fnum=2)
    sources = [0, 7, 23, 11]
    seq = []
    for s in sources:
        w = Worker(APP_REGISTRY["common_neighbors"](), frag)
        w.query(source=s)
        seq.append(w.result_values())
    wb = Worker(APP_REGISTRY["common_neighbors"](), frag)
    wb.query_batch([{"source": s} for s in sources])
    for b in range(len(sources)):
        assert wb.batch_result_values(b).tobytes() == \
            seq[b].tobytes(), f"lane {b} diverged from sequential"


# ---------------------------------------------------------------------------
# schema wiring (the PR 9 declared-but-unwired class)
# ---------------------------------------------------------------------------


def test_spgemm_schema_block_wired():
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    from check_bench_schema import SCHEMA, validate_record

    assert "spgemm" in SCHEMA, "spgemm block declared but not in SCHEMA"
    good = {
        "metric": "mteps", "value": 1.0, "unit": "MTEPS",
        "vs_baseline": 1.0,
        "spgemm": {
            "scale": 10, "bench_scale": 20, "intersect_s": 0.5,
            "spgemm_s": 0.1, "byte_identical": True, "items": 100,
            "items_per_edge": 1.5, "mask_edges": 66,
            "ledger_recount_mismatch": 0.0, "bench_mask_edges": 1000,
            "bench_items_per_edge": 4.5, "mxu_elems_per_edge": 500.0,
            "vpu_ops_per_edge": 5000.0,
            "intersect_word_ops_per_edge": 98000.0,
            "modeled_spgemm_s": 0.001, "modeled_intersect_s": 0.01,
            "modeled_win": True, "auto_backend": "spgemm",
        },
    }
    assert validate_record(good) == []
    bad = dict(good, spgemm=dict(good["spgemm"], surprise=1))
    assert any("surprise" in e for e in validate_record(bad)), \
        "unknown spgemm field not rejected — block unwired"
    bad2 = dict(good, spgemm=dict(good["spgemm"], items=True))
    assert any("items" in e for e in validate_record(bad2)), \
        "bool-in-numeric not rejected in the spgemm block"
    bad3 = dict(good, spgemm=dict(good["spgemm"],
                                  auto_backend="popcount"))
    assert any("auto_backend" in e for e in validate_record(bad3))
    missing = dict(good)
    missing["spgemm"] = {
        k: v for k, v in good["spgemm"].items() if k != "modeled_win"
    }
    assert any("modeled_win" in e for e in validate_record(missing))
