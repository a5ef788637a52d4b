"""libgrape-lite's vertex-cut PageRank (`pagerank_vc.h`), plainly.

Written from the app's description, over the stored edge list as it stands
(no symmetrised copy, no matrix of multiplicities): a vertex's degree is the
number of times it appears as a source or as a destination, so a self-loop
counts twice; every round a stored edge (u, v) carries u's value to v and
v's to u; masters hold `rank / degree` between rounds,

    base     = (1 - d) / n + d * dangling / n
    next[v]  = (base + d * sum[v]) / degree[v]    (base where the degree is 0)
    dangling = base * (vertices of degree 0)

and the last round leaves `d * sum + base`, the rank itself.  float64, NumPy
and SciPy only; nothing of the library and nothing of `references/pagerank.py`.
"""

import numpy as np


def reference(graph, params: dict) -> np.ndarray:
    import scipy.sparse as sp

    src, dst, _ = graph.edges
    n = graph.n
    delta, rounds = float(params["delta"]), int(params["max_round"])
    degree = (np.bincount(src, minlength=n) + np.bincount(dst, minlength=n)).astype(np.float64)
    # into the destinations; its transpose carries the other direction
    along = sp.csr_matrix((np.ones(len(src)), (dst, src)), shape=(n, n))
    back = along.T.tocsr()
    linked = degree > 0
    isolated = float(n - linked.sum())
    held = np.where(linked, (1.0 / n) / np.maximum(degree, 1.0), 1.0 / n)
    dangling = isolated / n
    for step in range(1, rounds + 1):
        base = (1.0 - delta) / n + delta * dangling / n
        dangling = base * isolated
        total = along @ held + back @ held
        if step == rounds:
            held = delta * total + base
        else:
            held = np.where(linked, (base + delta * total) / np.maximum(degree, 1.0), base)
    return held


def to_reference_form(values: np.ndarray) -> np.ndarray:
    return values
