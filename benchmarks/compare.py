"""The comparison that decides `correct`.

`mismatches` is copied from `chip_smoke.py` (tests/verifiers.py's three
rules, vectorised for millions of rows).  The rule and its tolerance come
from the configuration's `guarantees`, never from the program.
"""

from __future__ import annotations

import numpy as np


def mismatches(rule: str, got, want, eps=None) -> int:
    """Vertices on which `got` breaks `rule` against `want`."""
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        return max(got.size, want.size)
    if rule == "partition":  # same grouping, arbitrary labels: 0 or 1
        a = np.unique(got, return_inverse=True)[1].astype(np.int64)
        b = np.unique(want, return_inverse=True)[1].astype(np.int64)
        pairs = len(np.unique(a * (b.max() + 1) + b))
        return int(not pairs == a.max() + 1 == b.max() + 1)
    if rule == "exact":
        return int((got != want).sum())
    if rule != "eps":
        raise ValueError(f"unknown rule {rule!r}")
    got = got.astype(np.float64)
    inf = np.isinf(want) | np.isinf(got)
    with np.errstate(invalid="ignore"):  # inf - inf, masked out below
        close = np.where(want == 0, np.abs(got) < max(1e-12, eps * 1e-8),
                         np.abs(got - want) <= eps * np.abs(want))
    return int((~np.where(inf, got == want, close)).sum())
