"""BC as a pull: a level of either sweep is one masked V-wide table, one
gather and one scan fold (`models/bc.py`), held to the benchmark's plain
reference (`benchmarks/references/bc.py`), in this lane's float64 and in the
chip's float32, and the reference to the loops of tests/test_bc.py.
"""

import functools
import types

import numpy as np
import pytest

from benchmarks.compare import mismatches
from benchmarks.graphs.csr import symmetric_csr
from benchmarks.references import bc as bc_reference
from benchmarks.run import by_vertex as by_id
from tests.bc_oracles import brandes_rounded
from tests.conftest import gather_took
from tests.test_bc import numpy_brandes_single_source
from tests.test_worker import build_fragment

SENT = np.iinfo(np.int32).max
EPS = 1e-3  # benchmarks/configs/g500-bc.json guarantees.bc


@functools.cache
def drawn(kind):
    """(n, src, dst, the references' matrices, {root kind: root}).  Ids
    0..9 have no edge; ids 10..14 are a path joined to nothing else."""
    if kind == "simple":
        n, e, seed = 1500, 4000, 5
    else:
        n, e, seed = 400, 3000, 9  # a pair is drawn 1.9 times over
    rng = np.random.default_rng(seed)
    src, dst = rng.integers(15, n, e), rng.integers(15, n, e)
    if kind == "simple":
        lo, hi = np.minimum(src, dst), np.maximum(src, dst)
        keep = np.unique(lo * n + hi, return_index=True)[1]
        keep = keep[(lo != hi)[keep]]
        src, dst = src[keep], dst[keep]
    src, dst = np.r_[src, 10:14], np.r_[dst, 11:15]
    minw, mult = symmetric_csr(n, src, dst, np.ones(len(src)))
    assert (mult.data.max() == 1) == (kind == "simple")
    degree = np.diff(minw.indptr)
    roots = {"edge": int(np.flatnonzero(degree[15:] > 1)[0]) + 15,
             "isolated": 3, "small": 12}
    assert degree[roots["isolated"]] == 0
    return n, src, dst, types.SimpleNamespace(minw=minw, mult=mult), roots


def narrow_bc():
    """The registry's `bc` with the state the chip holds, under this
    lane's x64."""
    from libgrape_lite_tpu.models import APP_REGISTRY

    class Narrow(APP_REGISTRY["bc"]):
        def init_state(self, frag, source=0):
            state = super().init_state(frag, source=source)
            return {k: v.astype(np.float32) if v.dtype == np.float64 else v
                    for k, v in state.items()}

    return Narrow


@functools.cache
def worker(kind, fnum, narrow):
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.worker.worker import Worker

    n, src, dst, _, _ = drawn(kind)
    frag = build_fragment(src, dst, None, n, fnum)
    return Worker((narrow_bc() if narrow else APP_REGISTRY["bc"])(), frag), frag


CASES = [(kind, fnum, root) for kind in ("simple", "parallel")
         for fnum in (1, 4) for root in ("edge", "isolated", "small")]


@pytest.mark.parametrize("kind,fnum,root", CASES)
@pytest.mark.parametrize("narrow", [False, True], ids=["f64", "f32"])
def test_the_app_answers_as_the_reference(kind, fnum, root, narrow):
    """Dependencies, depth and path counts against the plain reference: to
    1e-9 in float64, and within the configuration's eps on every vertex
    (zero where the reference is zero) from a float32 state."""
    from libgrape_lite_tpu.models.bc import BC_STATS

    n, _, _, graph, roots = drawn(kind)
    w, frag = worker(kind, fnum, narrow)
    state = w.query(source=roots[root])
    got = by_id(frag, w.result_values())
    delta, sigma, depth, levels = bc_reference.brandes(graph, roots[root])
    assert got.dtype == (np.float32 if narrow else np.float64)
    got_depth = by_id(frag, np.asarray(state["depth"]))
    assert (np.where(got_depth == SENT, -1, got_depth) == depth).all()
    got_pn = by_id(frag, np.asarray(state["pn"]))
    assert got_pn.dtype == got.dtype
    if narrow:
        assert mismatches("eps", got, delta, EPS) == 0
        assert mismatches("eps", got_pn, sigma, EPS) == 0
    else:
        np.testing.assert_allclose(got, delta, rtol=1e-9, atol=1e-12)
        np.testing.assert_allclose(got_pn, sigma, rtol=1e-9, atol=0)
    assert (got[delta == 0] == 0).all()
    want = {"edge": None, "isolated": 1, "small": 5}[root]
    assert want is None or len(np.concatenate(levels)) == want
    deep = len(levels) - 1
    stats = BC_STATS.snapshot()
    assert stats["pulls"] + stats["pushes"] == 2 * deep + 1
    # under the dense floor the program has no push arm
    # (tests/test_bc_push.py brings the budgets down and counts both)
    assert stats == {
        "levels": deep, "reached": int((depth >= 0).sum()),
        "pulls": 2 * deep + 1, "pushes": 0}
    assert int(w.rounds) == 0  # both sweeps are loops inside PEval
    if root == "edge":
        assert deep >= 3
    if kind == "simple":  # the root's own dependency: all it reaches
        assert got[roots[root]] == pytest.approx((depth >= 0).sum() - 1)


@pytest.mark.parametrize("kind", ["simple", "parallel"])
@pytest.mark.parametrize("root", ["edge", "isolated", "small"])
def test_the_reference_is_brandes_by_loops(kind, root):
    """The benchmark's reference against tests/test_bc.py's vertex-by-vertex
    loops (parallel edges as paths of their own, self-loops on no path)."""
    n, src, dst, graph, roots = drawn(kind)
    adj = [[] for _ in range(n)]
    for a, b in zip(src.tolist(), dst.tolist()):
        adj[a].append(b)
        adj[b].append(a)
    want, sigma, depth = numpy_brandes_single_source(n, adj, roots[root])
    delta, got_sigma, got_depth, _ = bc_reference.brandes(graph, roots[root])
    np.testing.assert_allclose(delta, want, rtol=1e-12, atol=0)
    np.testing.assert_allclose(got_sigma, sigma, rtol=1e-12, atol=0)
    assert (got_depth == depth).all()
    assert (bc_reference.reference(graph, {"source": roots[root]}) == delta).all()
    assert bc_reference.to_reference_form(delta) is delta


@pytest.mark.parametrize("kind", ["simple", "parallel"])
def test_a_narrower_float_fails_the_rule(kind):
    """The recurrences kept in float32 pass the configuration's eps with
    room; kept in bfloat16 they fail it."""
    import ml_dtypes

    _, _, _, graph, roots = drawn(kind)
    delta = bc_reference.brandes(graph, roots["edge"])[0]
    assert (brandes_rounded(graph, roots["edge"], np.float64) == delta).all()
    narrow = brandes_rounded(graph, roots["edge"], np.float32)
    assert mismatches("eps", narrow, delta, EPS / 30) == 0
    assert mismatches("eps", brandes_rounded(
        graph, roots["edge"], ml_dtypes.bfloat16), delta, EPS) > 10


@pytest.mark.parametrize("fnum", [1, 4])
def test_a_level_is_one_gather_and_one_scan_fold(fnum, pull_kernel):
    """What a traced BC program chooses: one `pull_gather` and one
    `segment_reduce` with the CSR's offsets a loop, so two of each a
    program and no scatter; armed as on the chip, a float32 state's tables
    go to the gather kernel (one 32-bit `[fnum * vp]` table, the `[Ep]`
    stream) and the scan's first level and row ends to theirs."""
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.ops import segment
    from libgrape_lite_tpu.worker.worker import Worker

    n, src, dst, graph, roots = drawn("simple")
    frag = build_fragment(src, dst, None, n, fnum)
    folds = segment.FOLD_STATS.snapshot()
    took = gather_took(
        lambda: Worker(APP_REGISTRY["bc"](), frag).query(source=roots["edge"]))
    assert took == {"kernel": 0, "xla": 2}  # float64: no kernel's kind
    assert segment.FOLD_STATS.snapshot() == {
        "scan": folds["scan"] + 2, "scatter": folds["scatter"]}

    calls = pull_kernel("stand_in")
    w, frag = worker("simple", fnum, True)
    folds, scans, ends = (s.snapshot() for s in (
        segment.FOLD_STATS, segment.SCAN_STATS, segment.ROW_END_STATS))
    fresh = Worker(w.app, frag)
    took = gather_took(lambda: fresh.query(source=roots["edge"]))
    assert took == {"kernel": 2, "xla": 0}
    ep = frag.dev.ie.edge_src.shape[1]
    assert calls and set(calls) == {("float32", (fnum * frag.vp,), (ep,))}
    assert segment.FOLD_STATS.snapshot() == {
        "scan": folds["scan"] + 2, "scatter": folds["scatter"]}
    assert segment.SCAN_STATS.snapshot()["kernel"] == scans["kernel"] + 2
    assert segment.ROW_END_STATS.snapshot()["kernel"] == ends["kernel"] + 2
    delta = bc_reference.brandes(graph, roots["edge"])[0]
    assert mismatches("eps", by_id(frag, fresh.result_values()), delta, EPS) == 0
