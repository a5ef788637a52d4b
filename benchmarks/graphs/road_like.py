"""A surrogate of a road network (GAP's graph Road, the 9th DIMACS
Challenge's `USA-road-d.USA`): bounded degree, mean degree 2.4, one
component, a hop diameter of the order of sqrt(vertices), as files
`LoadGraph` parses.

The published file is not here (no network), so what is kept is what makes
the family unlike Graph500's Kronecker graphs and Datagen's social graphs
(docs/ROAD_SURROGATE.md): no hub at all, most vertices of degree 2 or 3,
and a breadth-first search that needs a thousand rounds and more, each with
a few hundred vertices to do.

  * vertices: a `rows` x `cols` four-neighbour lattice of 2^scale points,
    `rows` = 2^ceil(scale / 2), `cols` = 2^floor(scale / 2);
  * one component: a spanning tree of the lattice is kept whole, the
    minimum one under a ranking of the edges drawn from the seed (SciPy's
    `minimum_spanning_tree`), so its paths wander as a random tree's do;
  * cycles: every other lattice edge is kept with the one probability that
    brings the expected mean degree to `mean_degree` (entries a vertex:
    both directions of an edge count);
  * ids: a permutation drawn from the seed, so no id order carries position
    on the lattice;
  * weights: `weights` = [low, high], integers low..high of `weight_dtype`.

Every parameter comes from the configuration's `generator` block and none
is fixed here.  One process, whole arrays, NumPy and SciPy.  The edge list
comes out ordered by (smaller id, larger id).  Nothing here imports JAX.
"""

from __future__ import annotations

import os

import numpy as np


def lattice_shape(scale: int) -> tuple:
    return 1 << (scale + 1) // 2, 1 << scale // 2


def lattice_edges(rows: int, cols: int):
    """Both ends of every edge of the four-neighbour lattice, by position
    (row-major): the horizontal edges, then the vertical ones."""
    at = np.arange(rows * cols, dtype=np.int64).reshape(rows, cols)
    a = np.concatenate([at[:, :-1].ravel(), at[:-1, :].ravel()])
    b = np.concatenate([at[:, 1:].ravel(), at[1:, :].ravel()])
    return a, b


def edges(gen: dict, scale: int):
    """The graph on 2^scale ids, whole and in memory: (src int32, dst int32,
    w), `src < dst`, each pair once."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import minimum_spanning_tree

    rng = np.random.default_rng(int(gen["generator_seed"]))
    n = 1 << scale
    a, b = lattice_edges(*lattice_shape(scale))
    # the edges ranked by a permutation: distinct keys, so the tree is the
    # seed's alone, and a kept key names its edge
    rank = rng.permutation(len(a))
    tree = minimum_spanning_tree(sp.csr_matrix((rank + 1.0, (a, b)), shape=(n, n)))
    by_rank = np.empty(len(a), dtype=np.int64)
    by_rank[rank] = np.arange(len(a))
    in_tree = np.zeros(len(a), dtype=bool)
    in_tree[by_rank[tree.data.astype(np.int64) - 1]] = True
    spare = len(a) - in_tree.sum()  # the lattice edges outside the tree
    want = float(gen["mean_degree"]) * n / 2 - in_tree.sum()
    keep = in_tree | (rng.random(len(a)) < want / max(spare, 1))
    perm = rng.permutation(n)
    pa, pb = perm[a[keep]], perm[b[keep]]
    lo, hi = np.minimum(pa, pb), np.maximum(pa, pb)
    order = np.argsort(lo * n + hi)
    lo_w, hi_w = gen["weights"]
    w = rng.integers(int(lo_w), int(hi_w) + 1, len(order),
                     dtype=np.dtype(gen["weight_dtype"]))
    return lo[order].astype(np.int32), hi[order].astype(np.int32), w


def write_files(gen: dict, scale: int, efile: str, vfile: str) -> dict:
    """Writes `efile` (`src dst w` lines) and `vfile` (every id 0..2^scale-1)
    and returns the counts.  Files appear under their final names only when
    whole."""
    import pandas as pd

    src, dst, w = edges(gen, scale)
    pd.DataFrame({"s": src, "d": dst, "w": w}).to_csv(
        efile + ".tmp", sep=" ", header=False, index=False)
    n = 1 << scale
    with open(vfile + ".tmp", "w") as f:
        f.write("\n".join(map(str, range(n))) + "\n")
    os.replace(vfile + ".tmp", vfile)
    os.replace(efile + ".tmp", efile)
    return {"vertices": n, "edges": len(src), "pull_entries": 2 * len(src),
            "efile_bytes": os.path.getsize(efile)}
