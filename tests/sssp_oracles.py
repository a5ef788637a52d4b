"""Plain NumPy shortest-path relaxations, the oracles for what the default
`sssp`'s rounds count (`ROUND_STATS`): a hop-synchronous Bellman-Ford with
its frontier made explicit, and the near/far discipline of
`worker._frontier_loop` under `models/sssp.py`'s offer, budgets and
fallbacks included.  Both take a CSR (`indptr`, `nbr`, `w`) whose rows push
along their entries, and distances as floats, inf where none.
"""

import numpy as np


def _push(indptr, nbr, w, dist, rows):
    """`dist` after the listed rows push `dist[row] + w` along their entries,
    every candidate from the values the round began with."""
    count = indptr[rows + 1] - indptr[rows]
    entry = np.repeat(indptr[rows] - np.r_[0, np.cumsum(count)[:-1]], count) + np.arange(count.sum())
    new = dist.copy()
    np.minimum.at(new, nbr[entry], np.repeat(dist[rows], count) + w[entry])
    return new


def bellman_ford(indptr, nbr, w, source):
    """Rounds in which the rows improved last round push: `(dist, rounds,
    pushed, widest)`, `pushed` the rows that pushed over the query and
    `widest` the longest list as (rows, entries).  The last round pushes and
    improves nothing, as the dense loop's does."""
    dist = np.full(len(indptr) - 1, np.inf, w.dtype)
    dist[source] = 0
    front, rounds, pushed, widest = np.array([source]), 0, 0, (0, 0)
    while len(front):
        rounds += 1
        pushed += len(front)
        widest = max(widest, (len(front), int((indptr[front + 1] - indptr[front]).sum())))
        new = _push(indptr, nbr, w, dist, front)
        front, dist = np.flatnonzero(new < dist), new
    return dist, rounds, pushed, widest


def near_far(indptr, nbr, w, dist, rows, entries, step):
    """`worker._frontier_loop` with a threshold, from the state `dist`:
    the record it would leave, `rounds` (every iteration: the pushes, the
    threshold's steps and the last look), `frontier_rounds`, `advances`,
    `pushed_sum`, the votes (`active`), and the distances."""
    dist = np.array(dist)
    everyone = np.arange(len(dist))

    def bucket_end(least):
        return max(np.floor(least / step) * step + step, np.nextafter(least, np.inf))

    front = np.flatnonzero(np.isfinite(dist))
    n = len(front) if len(front) <= 1 else max(len(front), rows + 1)
    below = bucket_end(dist[front].min()) if len(front) else np.inf
    out = {"rounds": 0, "frontier_rounds": 0, "advances": 0, "pushed_sum": n, "active": []}
    while True:
        out["rounds"] += 1
        if n:
            fits = n <= rows and (indptr[front + 1] - indptr[front]).sum() <= entries
            new = _push(indptr, nbr, w, dist, front if fits else everyone)
            near = np.flatnonzero((new < dist) & (new < below))
            out["frontier_rounds"] += int(fits)
            dist = new
        elif (dist[dist >= below] < np.inf).any():
            upto = bucket_end(dist[dist >= below].min())
            near, below = np.flatnonzero((dist >= below) & (dist < upto)), upto
            out["advances"] += 1
        else:
            out["active"].append(0)
            return dist, out
        n = len(near)
        front = near if n <= rows else front  # a list that is not whole is not read
        out["pushed_sum"] += n
        out["active"].append(n)
