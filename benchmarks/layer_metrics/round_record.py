"""What the rounds of the last query had to do, from the program's own record.

`ROUND_STATS` (`libgrape_lite_tpu/worker/worker.py`) is filled when a query's
answer is extracted: the fused loop carries, beside the state, the count of
rounds by the bit length of the `active` each voted, the largest vote and
their sum.  After the run it holds the last query's record; in a cell that
asks one key every query of a run reads the same.
"""


def round_stats(run) -> dict | None:
    """The program's `ROUND_STATS` after the run's queries (logged once); None
    from a program from before it existed, or before any answer of the fused
    loop was extracted."""
    try:
        from libgrape_lite_tpu.worker.worker import ROUND_STATS
    except ImportError:
        return None
    stats = ROUND_STATS.snapshot()
    if "round_stats_logged" not in run.__dict__:
        run.round_stats_logged = True
        run.log(f"ROUND_STATS: {stats}")
    return stats if stats["rounds"] else None


def read(run, spec):
    """With `stat`, that count of `ROUND_STATS`.  Without, the sum of `active`
    over the rounds, over rounds x vertices, in %: the rows that had anything
    to do among the rows the rounds folded."""
    stats = round_stats(run)
    if stats is None:
        return None
    if "stat" in spec:
        return stats[spec["stat"]]
    return 100.0 * stats["active_sum"] / (stats["rounds"] * run.dataset_info["vertices"])
