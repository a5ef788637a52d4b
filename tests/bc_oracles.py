"""Brandes' recurrences in a precision of the caller's choosing: the reading
that shows what a configuration's `eps` for BC holds and what it refuses.

`benchmarks/references/bc.py` is the float64 answer.  This is the same sweep
with every vector it keeps (path counts, the level's masked table, the row
sums, dependencies) rounded to `dtype` where it is written, the row sums
themselves taken by SciPy in float32 (in float64 for a float64 `dtype`): the
kindest reading of a narrow type, storage in it and accumulation above it.
Levels are the float64 reference's, so only the arithmetic differs.
"""

import numpy as np


def brandes_rounded(graph, source: int, dtype) -> np.ndarray:
    """Dependencies on `source` with every stored value rounded to `dtype`
    (`numpy.float32`, `ml_dtypes.bfloat16`, ...), as float64."""
    from benchmarks.references.bc import brandes

    wide = np.float64 if np.dtype(dtype) == np.float64 else np.float32
    mult = graph.mult.astype(wide)
    levels = brandes(graph, source)[3]

    def kept(x):
        return np.asarray(x).astype(dtype).astype(wide)

    n = mult.shape[0]
    sigma = np.zeros(n, dtype=wide)
    sigma[source] = 1
    for above, level in zip(levels, levels[1:]):
        masked = np.zeros(n, dtype=wide)
        masked[above] = sigma[above]
        sigma[level] = kept((mult @ masked)[level])
    delta = np.zeros(n, dtype=wide)
    for above, level in zip(levels[-2::-1], levels[:0:-1]):
        masked = np.zeros(n, dtype=wide)
        masked[level] = kept((1 + delta[level]) / sigma[level])
        delta[above] = kept(sigma[above] * kept((mult @ masked)[above]))
    return delta.astype(np.float64)
