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
  same per-edge operands in the same order to the one fold),
* the send buffer's pack, steered as the TPU backend steers it
  (`pull_kernel`, tests/conftest.py): the same apps hand the gather
  kernel their own `[vp]` state and the plan's `[fnum * m]` stream and
  answer with the unarmed run's bytes; a 64-bit state keeps XLA's
  gather.
"""

import numpy as np
import pytest

from tests.conftest import (
    dataset_path,
    gather_took,
    rand_frag as _rand_frag,
)
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


# ---- the send buffer's pack, by the gather the call can see ----


# what each app's exchanged state is on `_rand_frag`'s f32 weights
_STATE_DTYPE = {"pagerank": "float32", "sssp": "float32",
                "bfs": "int32", "wcc": "int32"}


def _mirror_query(app_name, frag):
    """`(app, answer)` of one query of `app_name` under the exchange."""
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.worker.worker import Worker

    name, kwargs, _, _ = _IDENTITY_APPS[app_name]
    app = APP_REGISTRY[name]()
    wk = Worker(app, frag)
    wk.query(**kwargs)
    return app, wk.result_values()


@pytest.mark.parametrize("fnum", [2, 4])
@pytest.mark.parametrize("app_name", sorted(_IDENTITY_APPS))
def test_pack_through_the_kernel(monkeypatch, pull_kernel, app_name, fnum):
    """Armed as on the TPU backend, `exchange_mirrors` packs its send
    buffer by the kernel: it is handed the shard's own `[vp]` state and
    the send table as one `[fnum * m]` stream, `kernel` moved for it,
    and the answer has the unarmed run's bytes."""
    weighted, mx_attr = _IDENTITY_APPS[app_name][2:]
    frag = _rand_frag(fnum, seed=110 + fnum, weighted=weighted)
    monkeypatch.setenv("GRAPE_EXCHANGE", "mirror")
    _, ref = _mirror_query(app_name, frag)

    calls = pull_kernel("stand_in")
    got = []
    took = gather_took(lambda: got.append(_mirror_query(app_name, frag)))
    app, values = got[0]
    plan = getattr(app, mx_attr)
    assert plan is not None, "mirror plan not engaged"
    packs = [c for c in calls if c[1:] == ((frag.vp,), (fnum * plan.m,))]
    # one pack a traced round; the pulls are the other calls, from the
    # compact table
    assert packs and {c[0] for c in packs} == {_STATE_DTYPE[app_name]}, calls
    assert {c[1] for c in calls if c not in packs} == {(plan.n_compact,)}
    assert took == {"kernel": len(calls), "xla": 0}
    assert values.dtype == ref.dtype
    assert values.tobytes() == ref.tobytes()


def test_pack_by_the_interpreted_kernel(monkeypatch, pull_kernel):
    """The kernel's own bits through the exchange: PageRank on two
    fragments packs and pulls by `vmem_gather` itself, interpreted,
    inside `shard_map(while_loop)`."""
    frag = _rand_frag(2, n=300, e=2000, seed=112, weighted=False)
    monkeypatch.setenv("GRAPE_EXCHANGE", "mirror")
    _, ref = _mirror_query("pagerank", frag)
    calls = pull_kernel("interpreted")
    app, values = _mirror_query("pagerank", frag)
    assert ("float32", (frag.vp,), (2 * app._mx.m,)) in calls
    assert values.tobytes() == ref.tobytes()


def _exchange(frag, plan, x):
    """`exchange_mirrors` alone under the fragment's `shard_map`: the
    compact tables `[fnum, vp + fnum * m]` of the state `x` `[fnum,
    vp]`, or of each of its query lanes `[lanes, fnum, vp]` under
    `jax.vmap` (the lanes share the send table)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from libgrape_lite_tpu import compat
    from libgrape_lite_tpu.app.base import StepContext
    from libgrape_lite_tpu.parallel.comm_spec import FRAG_AXIS

    lanes = x.ndim == 3

    def shard(x, send):
        def one(v):
            return StepContext.exchange_mirrors(v, send[0])

        return jax.vmap(one)(x[:, 0])[:, None] if lanes else one(x[0])[None]

    spec = P(None, FRAG_AXIS) if lanes else P(FRAG_AXIS)
    fn = compat.shard_map(
        shard, mesh=frag.comm_spec.mesh, in_specs=(spec, P(FRAG_AXIS)),
        out_specs=spec, check_vma=False)
    return np.asarray(jax.jit(fn)(x, plan.send_idx))


def _compact(plan, x, f):
    """Receiver `f`'s compact table of the state `x` `[fnum, vp]`: its
    own block, then what every sender's table names for it."""
    return np.concatenate(
        [x[f]] + [x[g][plan.send_idx[g, f]] for g in range(plan.fnum)])


@pytest.mark.parametrize("dtype,took", [
    ("float32", "kernel"), ("int32", "kernel"),
    # what the kernel does not take keeps `x_local[send_idx]`
    ("float64", "xla"), ("int64", "xla"),
])
def test_pack_chooses_by_the_state(pull_kernel, dtype, took):
    """`exchange_mirrors` alone, armed: a 32-bit state is packed by the
    kernel, a 64-bit one by XLA's gather, and each shard's compact
    table is its own block, then what every sender's table names."""
    from libgrape_lite_tpu.parallel.mirror import build_mirror_plan

    fnum = 4
    frag = _rand_frag(fnum, n=700, e=5000, seed=23)
    plan = build_mirror_plan(frag, "ie")
    rng = np.random.default_rng(3)
    x = rng.integers(0, 1 << 20, (fnum, frag.vp)).astype(dtype)
    calls = pull_kernel("stand_in")
    out = []
    moved = gather_took(lambda: out.append(_exchange(frag, plan, x)))
    assert moved == {"kernel": 0, "xla": 0, took: 1}
    assert calls == ([(dtype, (frag.vp,), (fnum * plan.m,))]
                     if took == "kernel" else [])
    assert out[0].dtype == x.dtype
    for f in range(fnum):
        np.testing.assert_array_equal(out[0][f], _compact(plan, x, f))


def test_pack_of_query_lanes(pull_kernel):
    """Query lanes under `jax.vmap` share the send table: a lane's pack
    is its single call's, the kernel handed one lane's 1-D state, and
    the send buffer gets its `[fnum, m]` shape back lane by lane."""
    from libgrape_lite_tpu.parallel.mirror import build_mirror_plan

    fnum, lanes = 2, 3
    frag = _rand_frag(fnum, n=700, e=5000, seed=23)
    plan = build_mirror_plan(frag, "ie")
    x = np.random.default_rng(4).random(
        (lanes, fnum, frag.vp)).astype(np.float32)
    calls = pull_kernel("stand_in")
    out = []
    moved = gather_took(lambda: out.append(_exchange(frag, plan, x)))
    assert moved == {"kernel": 1, "xla": 0}
    assert set(calls) == {("float32", (frag.vp,), (fnum * plan.m,))}
    for b in range(lanes):
        for f in range(fnum):
            np.testing.assert_array_equal(
                out[0][b, f], _compact(plan, x[b], f))
