"""The least an LCC query must read, from the graph alone, and the lanes the
program pads it to.

Under a degree-ordered orientation (an edge points to the end of larger
(degree, id)) a triangle is found at its lowest edge (v, u) by intersecting
the two oriented lists N+(v) and N+(u).  Reading both lists once per oriented
edge is the least a list intersection moves: 4 B an id, 32-bit as the chip
holds them; per vertex the degree read and the coefficient written (8 B).  The
lists are short and the reads scattered, so this is a floor no gather reaches;
the share says how far the step is from streaming its own input.

The real lanes of the program's batched search are the wedges: one per member
of N+(v) per oriented edge (v, u), sum_v |N+(v)|^2.  Counted on the
benchmark's own copy of the graph, by original ids; the program breaks degree
ties by its own ids, which moves a few edges between lists and no total by more
than that.
"""

import numpy as np

BYTES = 4


def oriented_lists(n: int, src, dst):
    """(v, u, out): the oriented edges v -> u of the simple graph these tuples
    draw, and |N+| per vertex."""
    lo = np.minimum(src, dst).astype(np.int64)
    hi = np.maximum(src, dst).astype(np.int64)
    pair = np.unique(lo[lo != hi] * n + hi[lo != hi])
    a, b = pair // n, pair % n  # a < b
    deg = np.bincount(a, minlength=n) + np.bincount(b, minlength=n)
    up = deg[a] <= deg[b]
    v, u = np.where(up, a, b), np.where(up, b, a)
    return v, u, np.bincount(v, minlength=n)


def wedges(v, out) -> int:
    """Members of N+(v) over the oriented edges (v, u)."""
    return int(out[v].sum())


def lcc_list_bytes(n: int, v, u, out) -> int:
    """Both oriented lists once per oriented edge, and 8 B a vertex."""
    return int(BYTES * (out[v] + out[u]).sum() + 2 * BYTES * n)


def for_run(run) -> dict:
    """{"wedges", "list_bytes"} of the run's graph; counted once per run."""
    if "lcc_counts" not in run.__dict__:
        src, dst, _ = run.dataset.edges
        v, u, out = oriented_lists(run.dataset.n, src, dst)
        run.lcc_counts = {"wedges": wedges(v, out),
                          "list_bytes": lcc_list_bytes(run.dataset.n, v, u, out)}
        run.log(f"LCC on the benchmark's copy of the graph: {run.lcc_counts}")
    return run.lcc_counts


def read(run, spec):
    """`lcc_lane_pad_ratio`: the padded lanes all devices run over the wedges."""
    from benchmarks.layer_metrics.lcc_scope import lcc_stats

    stats = lcc_stats(run)
    if not stats or not stats.get("query_lanes"):
        return None
    return stats["query_lanes"] * run.chips / for_run(run)["wedges"]
