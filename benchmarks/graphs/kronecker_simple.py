"""The Graph500 Kronecker graph as the published graph500-* files hold it:
simple.  `kronecker`'s draws for the same `generator` block, less the
self-loops, one tuple of each undirected pair.

LDBC Graphalytics publishes the Graph500 family deduplicated and without
self-loops, and LCC is defined on a vertex's neighbours as a *set*; the
program's LCC, like the reference's (`lcc_context.h`), takes a vertex's
degree with multiplicity and so assumes such input.  Of the tuples that
join one pair, in either direction, the lightest is kept, the earliest
drawn among equals, as drawn (direction, weight, place in the order): so a
shortest path on this graph is the multigraph's.  The vertex file holds
every id, isolated ones included.  The edge list is made whole in one
process: at the scales an intersection can be timed it is a few million
tuples.  Nothing here imports JAX.
"""

from __future__ import annotations

import os

import numpy as np

from benchmarks.graphs import kronecker


def simplify(n: int, src, dst, w):
    """Indices, ascending, of the tuples a simple graph keeps."""
    vbits = max(1, (n - 1).bit_length())
    lo = np.minimum(src, dst).astype(np.int64)
    hi = np.maximum(src, dst).astype(np.int64)
    key = (((lo << vbits) | hi) << 8) | np.asarray(w, dtype=np.int64)
    order = np.argsort(key, kind="stable")  # by pair, weight, then as drawn
    order = order[(lo != hi)[order]]
    pair = key[order] >> 8
    first = np.r_[True, pair[1:] != pair[:-1]]
    return np.sort(order[first])


def edges(gen: dict, scale: int):
    """The whole edge list in memory: (src int32, dst int32, w uint8)."""
    src, dst, w = kronecker.edges(gen, scale)
    keep = simplify(1 << scale, src, dst, w)
    return src[keep], dst[keep], w[keep]


def write_files(gen: dict, scale: int, efile: str, vfile: str) -> dict:
    """Writes `efile` (`src dst w` lines) and `vfile` (every id 0..2^scale-1)
    and returns the counts.  Files appear under their final names only when
    whole."""
    import pandas as pd

    drawn = sum(kronecker.chunk_counts(gen, scale))
    src, dst, w = edges(gen, scale)
    pd.DataFrame({"s": src, "d": dst, "w": w}).to_csv(
        efile + ".tmp", sep=" ", header=False, index=False)
    n = 1 << scale
    with open(vfile + ".tmp", "w") as f:
        f.write("\n".join(map(str, range(n))) + "\n")
    os.replace(vfile + ".tmp", vfile)
    os.replace(efile + ".tmp", efile)
    return {"vertices": n, "edges": len(src), "pull_entries": 2 * len(src),
            "drawn": drawn, "efile_bytes": os.path.getsize(efile)}
