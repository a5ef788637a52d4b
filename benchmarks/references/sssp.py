"""Single-source shortest paths: SciPy's Dijkstra on the lightest parallel
edge.  Copied from `chip_smoke.py::ref_sssp`.  Unreached is +inf."""

import numpy as np


def reference(graph, params: dict) -> np.ndarray:
    from scipy.sparse.csgraph import dijkstra

    return dijkstra(graph.minw, directed=True, indices=int(params["source"]))


def to_reference_form(values: np.ndarray) -> np.ndarray:
    return values
