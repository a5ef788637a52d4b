"""LDBC Graphalytics PageRank: power iteration, dangling mass spread evenly.

Copied from `chip_smoke.py::ref_pagerank`.  f64 throughout.
"""

import numpy as np


def reference(graph, params: dict) -> np.ndarray:
    mult = graph.mult
    delta, rounds = float(params["delta"]), int(params["max_round"])
    n = mult.shape[0]
    deg = np.asarray(mult.sum(axis=1)).ravel()
    rank = np.full(n, 1.0 / n)
    for _ in range(rounds):
        base = (1.0 - delta) / n + delta * rank[deg == 0].sum() / n
        rank = base + delta * (mult @ (rank / np.maximum(deg, 1.0)))
    return rank


def to_reference_form(values: np.ndarray) -> np.ndarray:
    return values
