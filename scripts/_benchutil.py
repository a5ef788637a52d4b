"""Shared measurement core for the TPU probe scripts.

The sync convention for a local chip: JAX dispatch is asynchronous, so
every timing ends inside the timed region in a wait for the result —
here a real device_get of one element, which `block_until_ready` would
serve equally on a locally attached device.
"""

from __future__ import annotations

import time


def sync(x):
    """Force a real device->host readback of one element."""
    import jax
    import numpy as np

    leaf = jax.tree.leaves(x)[0]
    return np.asarray(leaf.ravel()[:1])


def timeit(fn, *args, iters=5):
    """Average seconds per call over `iters` dispatches, amortizing one
    readback at the end (the queue is FIFO, so the final sync waits for
    all dispatched iterations)."""
    out = fn(*args)
    sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    sync(out)
    return (time.perf_counter() - t0) / iters
