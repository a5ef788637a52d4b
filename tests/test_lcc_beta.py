"""LCCBeta (sorted-list intersection LCC) vs the golden and the bitmap LCC,
and its membership step against `np.isin` row by row."""

import numpy as np
import pytest

from tests.conftest import dataset_path
from tests.test_apps_golden import run_worker
from tests.verifiers import eps_verify, load_golden


@pytest.mark.parametrize("fnum", [1, 4])
def test_lcc_beta_golden(graph_cache, fnum):
    from libgrape_lite_tpu.models import LCCBeta

    frag = graph_cache(fnum)
    res = run_worker(LCCBeta(), frag)
    eps_verify(res, load_golden(dataset_path("p2p-31-LCC")))


def test_lcc_beta_tiny_sharded():
    from libgrape_lite_tpu.models import LCCBeta
    from libgrape_lite_tpu.worker.worker import Worker
    from tests.test_worker import build_fragment

    src = [0, 1, 0, 2]
    dst = [1, 2, 2, 3]
    frag = build_fragment(src, dst, None, 4, 4)
    w = Worker(LCCBeta(), frag)
    w.query()
    vals = np.concatenate(
        [w.result_values()[f, : frag.inner_vertices_num(f)] for f in range(4)]
    )
    np.testing.assert_allclose(vals, [1.0, 1.0, 1 / 3, 0.0], atol=1e-12)


@pytest.mark.parametrize("fnum", [1, 4])
def test_lcc_beta_tiered_golden(graph_cache, fnum, monkeypatch):
    """Force tiny tier widths so the tiered merge passes (eperm
    schedule + per-tier query widths) actually run on the test graph —
    the default ladder exceeds small-graph d_max and would silently
    disable tiering in CI."""
    monkeypatch.setenv("GRAPE_LCC_TIERS", "2,8")
    from libgrape_lite_tpu.models import LCCBeta

    frag = graph_cache(fnum)
    app = LCCBeta()
    res = run_worker(app, frag)
    assert app._tier_info is not None and len(app._tier_info) >= 2
    eps_verify(res, load_golden(dataset_path("p2p-31-LCC")))


@pytest.mark.parametrize("d", [1, 5, 251, 300])
@pytest.mark.parametrize("w", [1, 7, 64, 130])
def test_members_is_isin_row_by_row(w, d):
    """`_members` on a chunk that is no multiple of 128: empty and full
    rows on both sides, sentinel pads on both sides (a pad never hits a
    pad), edges that are not selected."""
    from libgrape_lite_tpu.models.lcc_beta import _members

    c, sent = 200, 1 << 10
    rng = np.random.default_rng(100 * w + d)

    def rows(width, first):
        """[c, width] sorted distinct ids < sent, the sentinel behind."""
        cnt = rng.integers(0, width + 1, size=c)
        cnt[:len(first)] = first
        ids = rng.random((c, sent)).argsort(axis=1)[:, :width]
        ids = np.where(np.arange(width)[None, :] < cnt[:, None], ids, sent)
        return np.sort(ids, axis=1).astype(np.int32), cnt

    # empty into empty, full into full, full into empty, empty into full
    q, qcnt = rows(w, [0, w, w, 0])
    tgt, tcnt = rows(d, [0, d, 0, d])
    sel = rng.random(c) < 0.8
    sel[:4] = True
    qv = np.arange(w)[None, :] < qcnt[:, None]
    got = np.asarray(_members(q.T, tgt.T, qv.T, sel)).T
    assert got.shape == (c, w) and got.dtype == bool
    for r in range(c):
        want = np.isin(q[r], tgt[r, :tcnt[r]]) & qv[r] & sel[r]
        assert np.array_equal(got[r], want), r
    assert (q == sent).any() and (tgt == sent).any() and not got[q == sent].any()
    assert (~sel).any() and not got[~sel].any()
    if w >= 64 and d >= 251:
        assert got.sum() > c  # the lists do meet
