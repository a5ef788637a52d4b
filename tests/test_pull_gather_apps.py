"""Every app the pull's kernel serves, a whole query through it.

`ops/segment.pull_gather` takes `pallas_kernels.vmem_gather` on the TPU
backend; here the choice is steered (`pull_kernel`, tests/conftest.py)
and each app's query, on one, two and four fragments under each
exchange, must take the kernel for every pull, hand it a table and a
stream it can take, and answer with the bytes of the same query through
XLA's gather, inside `shard_map(while_loop)`.  tests/test_pull_gather.py
pins the kernel's own bits and the choice; the kernel's other callers
(the dyn overlay, the batched runner's lanes) are pinned beside their
own tests.
"""

import numpy as np
import pytest

from libgrape_lite_tpu.models import APP_REGISTRY
from libgrape_lite_tpu.worker.worker import Worker
from tests.conftest import gather_took, rand_frag

# app -> (make the app, query, a graph with 32-bit state for it, the
# attribute that holds its mirror plan or None).  CDLP's labels are
# 64-bit by default in this lane (x64), which the kernel does not take.
APPS = {
    "pagerank": (lambda: APP_REGISTRY["pagerank"](), {"max_round": 4},
                 dict(weighted=False), "_mx"),
    "sssp": (lambda: APP_REGISTRY["sssp"](), {"source": 0}, {}, "_mx"),
    "bfs": (lambda: APP_REGISTRY["bfs"](), {"source": 0},
            dict(weighted=False), "_mx"),
    "wcc": (lambda: APP_REGISTRY["wcc"](), {}, dict(weighted=False),
            "_mx_ie"),
    # both pulls of the directed round: in- and out-neighbours
    "wcc_directed": (lambda: APP_REGISTRY["wcc"](), {},
                     dict(weighted=False, directed=True), "_mx_oe"),
    "cdlp": (lambda: APP_REGISTRY["cdlp"](label_dtype=np.int32),
             {"max_round": 4}, dict(weighted=False), None),
}
# the cases that compile the kernel itself, interpreted (30-75 s each,
# whatever the graph's size: tests/conftest.py); the others put
# `full[nbr]` behind the same choice.  One per kind of float table: a
# sum over the mirror exchange's compact table, a min with an add; BFS
# below has the int32 one
INTERPRETED = {("pagerank", 4, "mirror"), ("sssp", 2, "allgather")}
APP_CASES = [
    (app, fnum, exchange)
    for app in sorted(APPS)
    for fnum in (1, 2, 4)
    for exchange in ("allgather", "mirror")
    if exchange == "allgather" or (fnum > 1 and APPS[app][3])
]


def _through_the_kernel(make, query, frag, pull_kernel, kind,
                        every_pull=True):
    """One query through XLA's gather, the same through the kernel:
    the kernel was what every pull took, and the bytes are the same."""
    want = Worker(make(), frag)
    want.query(**query)
    calls = pull_kernel(kind)
    got = Worker(make(), frag)
    took = gather_took(lambda: got.query(**query))
    assert took["kernel"] > 0 and not (every_pull and took["xla"]), took
    assert len(calls) == took["kernel"]
    assert got.rounds == want.rounds
    a, b = got.result_values(), want.result_values()
    assert a.dtype == b.dtype
    assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    return got, calls


@pytest.mark.parametrize(
    "app,fnum,exchange", APP_CASES,
    ids=[f"{a}-{f}-{x}" + ("-interpreted" if (a, f, x) in INTERPRETED
                           else "") for a, f, x in APP_CASES])
def test_app_through_the_kernel(app, fnum, exchange, pull_kernel,
                                monkeypatch):
    make, query, graph, mx_attr = APPS[app]
    frag = rand_frag(fnum, seed=40 + fnum, **graph)
    if exchange == "mirror":
        monkeypatch.setenv("GRAPE_EXCHANGE", "mirror")
    else:
        monkeypatch.delenv("GRAPE_EXCHANGE", raising=False)
    kind = ("interpreted" if (app, fnum, exchange) in INTERPRETED
            else "stand_in")
    # under this lane's x64 CDLP's 32-bit labels widen to int64 in the
    # loop: PEval's pull alone is the kernel's (on the chip, x32, they
    # stay 32-bit and the loop's is too)
    got, calls = _through_the_kernel(
        make, query, frag, pull_kernel, kind, every_pull=app != "cdlp")
    if exchange == "mirror":
        plan = getattr(got.app, mx_attr)
        assert plan is not None, "mirror plan not engaged"
        # the pull's table is the exchange's compact one, not fnum *
        # vp wide, and the exchange packed it by the kernel too: the
        # shard's own state read by the plan's `[fnum * m]` stream
        assert {c[1] for c in calls} == {(plan.n_compact,), (frag.vp,)}
        assert {c[2] for c in calls if c[1] == (frag.vp,)} == {
            (plan.fnum * plan.m,)}
    else:
        assert {c[1] for c in calls} == {(frag.fnum * frag.vp,)}


@pytest.mark.parametrize("fnum", [1, 4])
def test_bfs_through_the_kernel(fnum, graph_cache, pull_kernel):
    """At p2p-31's size (several grid steps a round, the last ragged):
    BFS's round through the interpreted kernel, inside
    `shard_map(while_loop)`, answers with the bytes of the round
    through XLA's gather."""
    _through_the_kernel(APP_REGISTRY["bfs"], {"source": 6},
                        graph_cache(fnum), pull_kernel, "interpreted")
