"""Hop depth per vertex, -1 where unreached: SciPy's BFS order and
predecessors, depths resolved one level per pass.  Copied from
`chip_smoke.py::ref_bfs`."""

import numpy as np


def reference(graph, params: dict) -> np.ndarray:
    from scipy.sparse.csgraph import breadth_first_order

    minw, source = graph.minw, int(params["source"])
    order, pred = breadth_first_order(minw, source, directed=True)
    depth = np.full(minw.shape[0], -1, dtype=np.int32)
    depth[source] = 0
    todo = order[1:]
    while len(todo):
        d = depth[pred[todo]]
        depth[todo[d >= 0]] = d[d >= 0] + 1
        todo = todo[d < 0]
    return depth


def to_reference_form(values: np.ndarray) -> np.ndarray:
    """The app's sentinel for an unreached vertex is no depth."""
    return np.where(values >= len(values), -1, values)
