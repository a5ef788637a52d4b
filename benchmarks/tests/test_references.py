"""The plain references, pinned to the repository's p2p-31 goldens.

    python -m pytest benchmarks/tests

Run by hand; not part of tier-1.
"""

import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.compare import mismatches  # noqa: E402
from benchmarks.graphs.csr import symmetric_csr  # noqa: E402
from benchmarks.references import bfs, pagerank, sssp, wcc  # noqa: E402

DATASET = os.path.join(ROOT, "dataset")
SOURCE = 6  # the goldens' source vertex


@pytest.fixture(scope="module")
def p2p():
    oids = np.loadtxt(os.path.join(DATASET, "p2p-31.v"), dtype=np.int64)[:, 0]
    e = np.loadtxt(os.path.join(DATASET, "p2p-31.e"))
    index = {int(o): i for i, o in enumerate(oids)}
    src = np.array([index[int(s)] for s in e[:, 0]])
    dst = np.array([index[int(d)] for d in e[:, 1]])
    minw, mult = symmetric_csr(len(oids), src, dst, e[:, 2])
    return types.SimpleNamespace(minw=minw, mult=mult, oids=oids, index=index)


def golden(p2p, suffix):
    rows = np.loadtxt(os.path.join(DATASET, f"p2p-31-{suffix}"), dtype=np.float64)
    out = np.empty(len(p2p.oids))
    out[[p2p.index[int(o)] for o in rows[:, 0]]] = rows[:, 1]
    return out


def test_pagerank_matches_golden(p2p):
    got = pagerank.reference(p2p, {"delta": 0.85, "max_round": 10})
    assert mismatches("eps", got, golden(p2p, "PR"), 1e-6) == 0


def test_sssp_matches_golden(p2p):
    want = golden(p2p, "SSSP")
    want[want > 1e300] = np.inf  # the golden's "unreached"
    got = sssp.reference(p2p, {"source": p2p.index[SOURCE]})
    assert mismatches("eps", got, want, 1e-9) == 0


def test_bfs_matches_golden(p2p):
    want = golden(p2p, "BFS")
    want = np.where(want > 1e18, -1, want).astype(np.int64)  # int64 max = unreached
    got = bfs.reference(p2p, {"source": p2p.index[SOURCE]})
    assert mismatches("exact", got, want) == 0


def test_wcc_matches_golden(p2p):
    got = wcc.reference(p2p, {})
    assert mismatches("partition", got, golden(p2p, "WCC").astype(np.int64)) == 0


@pytest.mark.parametrize("rule,got,want,eps,bad", [
    ("exact", [1, 2, 3], [1, 2, 4], None, 1),
    ("eps", [1.0, np.inf, 0.0], [1.0005, np.inf, 0.0], 1e-3, 0),
    ("eps", [1.0, 5.0], [1.002, np.inf], 1e-3, 2),
    ("partition", [0, 0, 1], [5, 5, 9], None, 0),
    ("partition", [0, 1, 1], [5, 5, 9], None, 1),
    ("exact", [1, 2], [1, 2, 3], None, 3),
])
def test_mismatches_rules(rule, got, want, eps, bad):
    assert mismatches(rule, np.array(got), np.array(want), eps) == bad
