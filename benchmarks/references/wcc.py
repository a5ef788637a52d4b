"""Weakly connected components: SciPy's labels.  Copied from
`chip_smoke.py::ref_wcc`.  Compared as a partition, labels arbitrary."""

import numpy as np


def reference(graph, params: dict) -> np.ndarray:
    from scipy.sparse.csgraph import connected_components

    return connected_components(graph.minw, directed=False)[1]


def to_reference_form(values: np.ndarray) -> np.ndarray:
    return values
