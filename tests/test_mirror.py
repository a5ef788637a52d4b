"""Mirror-compressed exchange coverage (VERDICT r3 weak #2 / next #3).

The reference syncs outer-vertex mirrors per neighbor fragment
(`grape/parallel/batch_shuffle_message_manager.h:237-264`, mirror lists
from `grape/fragment/edgecut_fragment_base.h:569-602`); here that is
`parallel/mirror.py` + `StepContext.exchange_mirrors`.  Lanes:

* numpy unit test of `build_mirror_plan`'s `nbr_compact` remap
  (masked edges included) against a direct per-receiver reconstruction,
* golden matrix: GRAPE_EXCHANGE=mirror x {pagerank, sssp, wcc, bfs} x
  fnum {2,4,8} against `dataset/p2p-31-*`,
* mirror against all_gather, byte for byte, on a random multigraph:
  {pagerank, sssp, bfs, wcc} x fnum {2,4} (the exchange feeds the
  same per-edge operands in the same order to the one fold).
"""

import numpy as np
import pytest

from tests.conftest import dataset_path, rand_frag as _rand_frag
from tests.verifiers import (
    collect_worker_result as run_worker,
    eps_verify,
    exact_verify,
    load_golden,
    wcc_verify,
)

FNUMS = [2, 4, 8]


@pytest.mark.parametrize("fnum", [2, 4])
@pytest.mark.parametrize("direction", ["ie", "oe"])
def test_mirror_plan_remap(fnum, direction):
    """nbr_compact must address exactly the values the exchange lays
    out: [local vp | g0 mirrors | g1 mirrors | ...], masked edges
    pinned to column 0."""
    from libgrape_lite_tpu.parallel.mirror import build_mirror_plan

    frag = _rand_frag(fnum, n=700, e=5000, seed=23)
    plan = build_mirror_plan(frag, direction)
    assert plan is not None
    vp = frag.vp
    rng = np.random.default_rng(5)
    x = rng.normal(size=fnum * vp)
    csrs = frag.host_ie if direction == "ie" else frag.host_oe
    for f in range(fnum):
        # receiver f's compact table: local block then, per sender g,
        # the rows g gathered through send_idx[g, f]
        compact = np.concatenate(
            [x[f * vp:(f + 1) * vp]]
            + [x[g * vp + plan.send_idx[g, f]] for g in range(fnum)]
        )
        assert compact.shape[0] == plan.n_compact
        h = csrs[f]
        mask = h.edge_mask
        np.testing.assert_array_equal(
            compact[plan.nbr_compact[f][mask]], x[h.edge_nbr[mask]]
        )
        # masked edges are parked on a valid local column
        assert (plan.nbr_compact[f][~mask] == 0).all()


def test_mirror_bytes_win(graph_cache):
    """On a real cut the mirror exchange must move fewer ICI bytes than
    the all_gather it replaces (else wiring it in is pointless)."""
    from libgrape_lite_tpu.parallel.mirror import build_mirror_plan

    frag = graph_cache(8)
    plan = build_mirror_plan(frag, "ie")
    assert plan is not None
    assert plan.bytes_mirror < plan.bytes_all_gather


def test_exchange_bytes_one_ledger():
    """MirrorPlan's byte properties read `exchange_bytes_ledger`, the
    one model the auto gate and the partition planner price from: no
    private copy of "exchange bytes" that can drift apart."""
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


def test_mirror_auto_gate(monkeypatch, graph_cache):
    """Default (auto) engages mirrors only on a clear ICI-bytes win at
    a size where bytes dominate; env forces override both ways."""
    import libgrape_lite_tpu.parallel.mirror as mx

    frag = _rand_frag(2, n=400, e=2000, seed=7)
    monkeypatch.delenv("GRAPE_EXCHANGE", raising=False)
    assert mx.resolve_mirror_plan(frag) is None  # too small for auto
    monkeypatch.setenv("GRAPE_EXCHANGE", "mirror")
    assert mx.resolve_mirror_plan(frag) is not None
    monkeypatch.setenv("GRAPE_EXCHANGE", "gather")
    assert mx.resolve_mirror_plan(frag) is None

    # with the size floor lifted, auto's decision must track the
    # bytes model exactly
    monkeypatch.delenv("GRAPE_EXCHANGE", raising=False)
    monkeypatch.setattr(mx, "_AUTO_MIN_BYTES", 0)
    p2p = graph_cache(8)
    plan = mx.build_mirror_plan(p2p, "ie")
    got = mx.resolve_mirror_plan(p2p, "ie")
    want = plan.bytes_mirror <= mx._AUTO_RATIO * plan.bytes_all_gather
    assert (got is not None) == want


# ---- golden matrix lanes (p2p-31, the reference app_tests goldens) ----


@pytest.mark.parametrize("fnum", FNUMS)
def test_sssp_mirror_golden(graph_cache, fnum, monkeypatch):
    from libgrape_lite_tpu.models import SSSP

    monkeypatch.setenv("GRAPE_EXCHANGE", "mirror")
    res = run_worker(SSSP(), graph_cache(fnum), source=6)
    exact_verify(res, load_golden(dataset_path("p2p-31-SSSP")))


@pytest.mark.parametrize("fnum", FNUMS)
def test_bfs_mirror_golden(graph_cache, fnum, monkeypatch):
    from libgrape_lite_tpu.models import BFS

    monkeypatch.setenv("GRAPE_EXCHANGE", "mirror")
    res = run_worker(BFS(), graph_cache(fnum), source=6)
    exact_verify(res, load_golden(dataset_path("p2p-31-BFS")))


@pytest.mark.parametrize("fnum", FNUMS)
def test_pagerank_mirror_golden(graph_cache, fnum, monkeypatch):
    from libgrape_lite_tpu.models import PageRank

    monkeypatch.setenv("GRAPE_EXCHANGE", "mirror")
    res = run_worker(
        PageRank(), graph_cache(fnum), delta=0.85, max_round=10
    )
    eps_verify(res, load_golden(dataset_path("p2p-31-PR")))


@pytest.mark.parametrize("fnum", FNUMS)
def test_wcc_mirror_golden(graph_cache, fnum, monkeypatch):
    from libgrape_lite_tpu.models import WCC

    monkeypatch.setenv("GRAPE_EXCHANGE", "mirror")
    res = run_worker(WCC(), graph_cache(fnum))
    wcc_verify(res, load_golden(dataset_path("p2p-31-WCC")))


# ---- mirror against all_gather, byte for byte ----

_IDENTITY_APPS = {
    # app -> (registry name, query kwargs, weighted graph, mirror attr)
    "pagerank": ("pagerank", {"max_round": 6}, False, "_mx"),
    "sssp": ("sssp", {"source": 0}, True, "_mx"),
    "bfs": ("bfs", {"source": 0}, False, "_mx"),
    "wcc": ("wcc", {}, False, "_mx_ie"),
}


@pytest.mark.parametrize("fnum", [2, 4])
@pytest.mark.parametrize("app_name", sorted(_IDENTITY_APPS))
def test_mirror_byte_identical(monkeypatch, app_name, fnum):
    """Every pull app must actually route through exchange_mirrors
    (BFS was once silently inert — ADVICE r3 high) and answer with the
    all_gather path's bytes: the compact table holds the same values
    at the remapped columns, and the fold sees them in CSR order."""
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.worker.worker import Worker

    name, kwargs, weighted, mx_attr = _IDENTITY_APPS[app_name]
    frag = _rand_frag(fnum, seed=110 + fnum, weighted=weighted)
    monkeypatch.delenv("GRAPE_EXCHANGE", raising=False)
    w_ref = Worker(APP_REGISTRY[name](), frag)
    w_ref.query(**kwargs)
    ref = w_ref.result_values()

    monkeypatch.setenv("GRAPE_EXCHANGE", "mirror")
    app = APP_REGISTRY[name]()
    wk = Worker(app, frag)
    wk.query(**kwargs)
    assert getattr(app, mx_attr) is not None, "mirror plan not engaged"
    got = wk.result_values()
    assert got.dtype == ref.dtype
    assert got.tobytes() == ref.tobytes()
