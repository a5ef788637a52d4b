"""Betweenness centrality from one root: the dependency of every vertex on
the root, by Brandes' algorithm (J. Math. Sociol. 25, 2001) as the GAP
Benchmark Suite states its kernel BC (arXiv:1508.03619): a breadth-first
search from the root that counts shortest paths a level at a time, then the
dependencies accumulated back a level at a time,

    sigma[v] = sum of sigma[u] over the entries (u, v) with depth[u] = depth[v] - 1
    delta[u] = sigma[u] * sum of (1 + delta[v]) / sigma[v]
               over the entries (u, v) with depth[v] = depth[u] + 1

in float64, each level one sparse product of the symmetric matrix with the
level's masked vector.  Levels follow the pattern of `graph.minw`; paths are
counted with `graph.mult`, an entry's multiplicity, because each of a pair's
parallel edges is a shortest path of its own (on a simple graph the two
matrices have one pattern and `mult` is all ones).  A self-loop joins a
level to itself and is on no shortest path.  The root's own dependency is
kept (the vertices it reaches, less itself, on a simple graph), as GAP's
accumulation and the reference's `centrality_value` (`bc.h`) both keep it;
an unreached vertex and a leaf of the search read 0.  Nothing from the
library.
"""

import numpy as np


def brandes(graph, source: int):
    """(delta, sigma, depth, levels) from `source`: depth -1 where unreached,
    `levels[d]` the ids at depth d."""
    mult = graph.mult
    n = mult.shape[0]
    reaches = graph.minw.indptr[1:] > graph.minw.indptr[:-1]  # rows with an entry
    depth = np.full(n, -1, dtype=np.int64)
    sigma = np.zeros(n, dtype=np.float64)
    depth[source], sigma[source] = 0, 1.0
    levels = [np.array([source], dtype=np.int64)]
    while reaches[levels[-1]].any():
        masked = np.zeros(n, dtype=np.float64)
        masked[levels[-1]] = sigma[levels[-1]]
        paths = mult @ masked
        new = np.flatnonzero((paths > 0) & (depth < 0))
        if not len(new):
            break
        depth[new], sigma[new] = len(levels), paths[new]
        levels.append(new)
    delta = np.zeros(n, dtype=np.float64)
    for d in range(len(levels) - 1, 0, -1):
        masked = np.zeros(n, dtype=np.float64)
        masked[levels[d]] = (1.0 + delta[levels[d]]) / sigma[levels[d]]
        above = levels[d - 1]
        delta[above] = sigma[above] * (mult @ masked)[above]
    return delta, sigma, depth, levels


def reference(graph, params: dict) -> np.ndarray:
    return brandes(graph, int(params["source"]))[0]


def to_reference_form(values: np.ndarray) -> np.ndarray:
    return values
