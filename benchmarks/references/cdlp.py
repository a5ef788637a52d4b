"""LDBC Graphalytics CDLP: synchronous label propagation, labels start as
vertex ids, every vertex takes the most frequent label among its neighbours,
ties to the smallest label, a fixed number of iterations.

Counting is a sparse matrix product, not a sort: with `onehot[j, l] = 1`
where vertex `j` holds label `l`, `counts = graph.mult @ onehot` has in row
`i`, column `l` the number of `i`'s adjacency entries whose far end holds
`l`.  Multiplicity counts: a doubled edge counts twice, as the
specification's neighbour scan has it.  A self-loop counts as `graph.mult`
holds it (twice: `graphs/csr.py` symmetrises the edge list, and so does the
program's undirected load; the configuration states it under `assumed`).
A vertex with no neighbour keeps its label.
"""

import numpy as np


def reference(graph, params: dict) -> np.ndarray:
    import scipy.sparse as sp

    mult = graph.mult.tocsr()
    n = mult.shape[0]
    ids = np.arange(n)
    labels = ids.copy()
    for _ in range(int(params["max_round"])):
        onehot = sp.csr_matrix((np.ones(n), (ids, labels)), shape=(n, n))
        counts = (mult @ onehot).tocsr()  # one entry per (row, label)
        rows = np.flatnonzero(np.diff(counts.indptr))  # those with a neighbour
        starts = counts.indptr[rows]
        most = np.maximum.reduceat(counts.data, starts)
        per_entry = np.repeat(most, np.diff(counts.indptr)[rows])
        # among the labels that reach the row's largest count, the smallest
        tied = np.where(counts.data == per_entry, counts.indices, n)
        labels[rows] = np.minimum.reduceat(tied, starts)
    return labels


def to_reference_form(values: np.ndarray) -> np.ndarray:
    """A label is a vertex id; the program's pad sentinel (the label type's
    largest value) is none."""
    return np.where(values >= len(values), -1, values).astype(np.int64)
