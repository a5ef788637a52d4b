"""LDBC Graphalytics LCC: for every vertex the share of the pairs of its
neighbours that are themselves joined, neighbours taken as a set; 0 for a
vertex with fewer than two.

The simple adjacency is the pattern of `graph.mult` less its diagonal, so a
doubled edge and a self-loop count for nothing and a vertex's degree is the
size of its neighbour set.  Triangles are counted by sparse products over a
degree-ordered orientation `L` (an edge points to the end of larger
(degree, id)), in which a triangle is u -> v -> w with u -> w:

    (L @ L) o L      holds at (u, w) the triangles between them: its row
                     sums credit the lowest corner, its column sums the highest
    (L.T @ L) o L    holds them at (v, w): its row sums credit the middle

`L @ L` multiplies sum_v in(v) out(v) pairs and `L.T @ L` sum_u out(u)^2, which
the orientation keeps near the number of wedges it leaves (`A @ A` would take
sum_v d(v)^2, a hub's square).  Both run in row blocks of bounded products, so
memory stays bounded at any scale.  The second product's column sums credit
the highest corner again and must agree with the first's.
"""

import numpy as np

BLOCK_PRODUCTS = 1 << 25  # multiplications one row block may take


def simple_adjacency(mult):
    """The 0/1 pattern of `mult` without its diagonal, CSR."""
    import scipy.sparse as sp

    a = sp.csr_matrix(mult, copy=True)
    a.data = np.ones(len(a.data), dtype=np.int64)
    a.setdiag(0)
    a.eliminate_zeros()
    return a


def oriented(a):
    """`a`'s edges from the end of smaller (degree, id) to the larger."""
    import scipy.sparse as sp

    deg = np.diff(a.indptr)
    coo = a.tocoo()
    r, c = coo.row, coo.col
    up = (deg[r] < deg[c]) | ((deg[r] == deg[c]) & (r < c))
    return sp.csr_matrix((np.ones(int(up.sum()), dtype=np.int64), (r[up], c[up])),
                         shape=a.shape)


def _masked_product_sums(left, right, mask):
    """Row and column sums of `(left @ right) o mask`, in row blocks of at
    most BLOCK_PRODUCTS multiplications (one row's at least)."""
    n = left.shape[0]
    per_row = left @ np.diff(right.indptr)  # multiplications each row takes
    ends = np.cumsum(per_row)
    rows, cols = np.zeros(n, dtype=np.int64), np.zeros(n, dtype=np.int64)
    lo = 0
    while lo < n:
        base = ends[lo - 1] if lo else 0
        hi = max(lo + 1, int(np.searchsorted(ends, base + BLOCK_PRODUCTS, side="right")))
        block = (left[lo:hi] @ right).multiply(mask[lo:hi]).tocsr()
        rows[lo:hi] = np.asarray(block.sum(axis=1)).ravel()
        cols += np.asarray(block.sum(axis=0)).ravel()
        lo = hi
    return rows, cols


def triangles(a) -> np.ndarray:
    """Triangles through each vertex of the simple undirected graph `a`."""
    fwd = oriented(a)
    lowest, highest = _masked_product_sums(fwd, fwd, fwd)
    middle, highest_again = _masked_product_sums(fwd.T.tocsr(), fwd, fwd)
    if (highest != highest_again).any():
        raise AssertionError("the two products disagree on the highest corners")
    return lowest + middle + highest


def reference(graph, params: dict) -> np.ndarray:
    a = simple_adjacency(graph.mult)
    d = np.diff(a.indptr).astype(np.float64)
    t = triangles(a).astype(np.float64)
    return np.where(d >= 2, 2.0 * t / np.maximum(d * (d - 1.0), 1.0), 0.0)


def to_reference_form(values: np.ndarray) -> np.ndarray:
    return np.asarray(values, dtype=np.float64)
