"""The undirected multigraph as plain SciPy matrices, for the references.

Copied from `chip_smoke.py::symmetric_csr`: NumPy and SciPy only, nothing
from the library under test.
"""

from __future__ import annotations

import numpy as np


def symmetric_csr(n: int, src, dst, w):
    """Two CSR matrices with one entry per distinct (row, col): the
    lightest parallel weight, and the pair's multiplicity (a self-loop
    counts twice, as in a symmetrised edge list).  One sort of packed
    (row, col, weight) keys; weights must be integers below 256."""
    import scipy.sparse as sp

    wi = np.asarray(w).astype(np.int64)
    if not ((wi == w).all() and wi.min() >= 0 and wi.max() < 256):
        raise ValueError("reference needs integer weights in [0, 256)")
    vbits = max(1, (n - 1).bit_length())
    s = np.concatenate([src, dst]).astype(np.int64)
    t = np.concatenate([dst, src]).astype(np.int64)
    key = (s << (vbits + 8)) | (t << 8) | np.concatenate([wi, wi])
    del s, t
    key.sort()
    pair = key >> 8
    starts = np.flatnonzero(np.r_[True, pair[1:] != pair[:-1]])
    mult = np.diff(np.r_[starts, len(key)])
    key = key[starts]
    rc = (key >> (vbits + 8), (key >> 8) & ((1 << vbits) - 1))
    return (sp.csr_matrix(((key & 255).astype(np.float64), rc), shape=(n, n)),
            sp.csr_matrix((mult.astype(np.float64), rc), shape=(n, n)))


def degrees(n: int, src, dst) -> np.ndarray:
    """Entries per vertex of the symmetrised edge list."""
    return np.bincount(src, minlength=n) + np.bincount(dst, minlength=n)
