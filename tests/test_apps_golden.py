"""Golden-file app tests, the analogue of `misc/app_tests.sh`:
every app × fragment counts {1,2,4,8} (the reference's `mpirun -n N`),
verified exact / eps / isomorphism against `dataset/p2p-31-*`.
"""

import numpy as np
import pytest

from tests.conftest import dataset_path
from tests.verifiers import (
    collect_worker_result as run_worker,
    eps_verify,
    exact_verify,
    load_golden,
    wcc_verify,
)

FNUMS = [1, 2, 4, 8]


@pytest.mark.parametrize("fnum", FNUMS)
def test_sssp(graph_cache, fnum):
    from libgrape_lite_tpu.models import SSSP

    frag = graph_cache(fnum)
    res = run_worker(SSSP(), frag, source=6)
    exact_verify(res, load_golden(dataset_path("p2p-31-SSSP")))


@pytest.mark.parametrize("fnum", FNUMS)
def test_bfs(graph_cache, fnum):
    from libgrape_lite_tpu.models import BFS

    frag = graph_cache(fnum)
    res = run_worker(BFS(), frag, source=6)
    exact_verify(res, load_golden(dataset_path("p2p-31-BFS")))


@pytest.mark.parametrize("fnum", FNUMS)
def test_pagerank(graph_cache, fnum):
    from libgrape_lite_tpu.models import PageRank

    frag = graph_cache(fnum)
    res = run_worker(PageRank(), frag, delta=0.85, max_round=10)
    eps_verify(res, load_golden(dataset_path("p2p-31-PR")))


@pytest.mark.parametrize("fnum", FNUMS)
def test_wcc(graph_cache, fnum):
    from libgrape_lite_tpu.models import WCC

    frag = graph_cache(fnum)
    res = run_worker(WCC(), frag)
    wcc_verify(res, load_golden(dataset_path("p2p-31-WCC")))


@pytest.mark.parametrize("fnum", FNUMS)
def test_cdlp(graph_cache, fnum):
    from libgrape_lite_tpu.models import CDLP

    frag = graph_cache(fnum)
    res = run_worker(CDLP(), frag, max_round=10)
    exact_verify(res, load_golden(dataset_path("p2p-31-CDLP")))


@pytest.mark.parametrize("fnum", [1, 4])
def test_lcc(graph_cache, fnum):
    from libgrape_lite_tpu.models import LCC

    frag = graph_cache(fnum)
    res = run_worker(LCC(), frag)
    eps_verify(res, load_golden(dataset_path("p2p-31-LCC")))


@pytest.mark.parametrize("fnum", [1, 4])
def test_cdlp_opt(graph_cache, fnum):
    """CDLPOpt's round-1 min shortcut must stay golden-identical
    (cdlp_opt.h's PEval exploits all-distinct initial labels)."""
    from libgrape_lite_tpu.models import CDLPOpt

    frag = graph_cache(fnum)
    res = run_worker(CDLPOpt(), frag, max_round=10)
    exact_verify(res, load_golden(dataset_path("p2p-31-CDLP")))


@pytest.mark.parametrize("fnum", [1, 4])
def test_cdlp_dynamic_compression(graph_cache, fnum):
    """Dynamic label-universe compression (the RMAT-20+ wide-path
    replacement): force the dynamic path; p2p-31's live universe fits
    the budget, so every round takes the packed-compressed branch of
    the in-jit lax.cond — must stay golden-exact."""
    from libgrape_lite_tpu.models import CDLP

    frag = graph_cache(fnum)
    app = CDLP()
    app._force_dynamic = True
    res = run_worker(app, frag, max_round=10)
    exact_verify(res, load_golden(dataset_path("p2p-31-CDLP")))


def test_cdlp_dynamic_wide_fallback(graph_cache):
    """Shrink the universe budget below the live label count so the
    lax.cond's runtime check routes every round to the wide branch —
    the fallback must also stay golden-exact."""
    from libgrape_lite_tpu.models import CDLP

    frag = graph_cache(4)
    app = CDLP()
    app._force_dynamic = True
    app._u_budget_override = 64  # << p2p-31's 62k live labels
    res = run_worker(app, frag, max_round=10)
    exact_verify(res, load_golden(dataset_path("p2p-31-CDLP")))


def test_cdlp_opt_single_round(graph_cache):
    """max_round=1 exercises exactly the shortcut round."""
    from libgrape_lite_tpu.models import CDLP, CDLPOpt

    frag = graph_cache(2)
    base = run_worker(CDLP(), frag, max_round=1)
    opt = run_worker(CDLPOpt(), frag, max_round=1)
    assert base == opt


# ---- the same goldens through the pull's kernel ---------------------------

_X32_RULES = {
    # app -> (query, golden, verifier, eps): tests/x32_check.py's rules
    # for 32-bit state against the f64 goldens
    "sssp": ({"source": 6}, "p2p-31-SSSP", eps_verify, 1e-3),
    "bfs": ({"source": 6}, "p2p-31-BFS", exact_verify, None),
    "pagerank": ({"delta": 0.85, "max_round": 10}, "p2p-31-PR",
                 eps_verify, 1e-3),
    "wcc": ({}, "p2p-31-WCC", wcc_verify, None),
}
# what compiles the kernel itself, interpreted (over a minute at this
# size); the others put `full[nbr]` behind the same choice
_INTERPRETED = {("pagerank", 1)}


@pytest.fixture(scope="module")
def graph_f32():
    """p2p-31 with f32 edge data, as a chip run loads it: SSSP's and
    PageRank's state is then 32-bit, which is what the kernel takes."""
    from libgrape_lite_tpu.fragment.loader import LoadGraph, LoadGraphSpec
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec

    cache = {}

    def get(fnum: int):
        if fnum not in cache:
            cache[fnum] = LoadGraph(
                dataset_path("p2p-31.e"), dataset_path("p2p-31.v"),
                CommSpec(fnum=fnum),
                LoadGraphSpec(directed=False, weighted=True,
                              edata_dtype=np.float32))
        return cache[fnum]

    return get


@pytest.mark.parametrize("fnum", [1, 4])
@pytest.mark.parametrize("app", sorted(_X32_RULES))
def test_golden_through_the_kernel(app, fnum, graph_f32, pull_kernel):
    from libgrape_lite_tpu.models import APP_REGISTRY
    from tests.conftest import gather_took

    query, golden, verify, eps = _X32_RULES[app]
    calls = pull_kernel("interpreted" if (app, fnum) in _INTERPRETED
                        else "stand_in")
    res = []
    moved = gather_took(lambda: res.append(
        run_worker(APP_REGISTRY[app](), graph_f32(fnum), **query)))
    assert moved["kernel"] == len(calls) > 0 and moved["xla"] == 0, moved
    verify(res[0], load_golden(dataset_path(golden)),
           **({} if eps is None else {"eps": eps}))
