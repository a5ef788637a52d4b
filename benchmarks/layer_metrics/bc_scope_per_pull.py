"""What a level pull of the traced BC query costs, and the sweep's own count.

`BC_STATS` (`libgrape_lite_tpu/models/bc.py`) is filled when a query's answer
is extracted, so after the run it holds the last query's record: the `levels`
under the root, the vertices `reached`, and the level `pulls` the two loops
ran.  The cell asks one key, so every query of a run reads the same.  Both
sweeps are `while_loop`s inside PEval: `Worker.rounds` reads 0 and the
metrics that divide by traced rounds read nothing, so the scopes' time is
divided by the pulls the program says it made.
"""

from benchmarks import reduce_scopes


def bc_stats(run) -> dict | None:
    """The program's `BC_STATS` after the run's queries (logged once); None
    from a program from before it existed, or before any BC answer."""
    try:
        from libgrape_lite_tpu.models.bc import BC_STATS
    except ImportError:
        return None
    stats = BC_STATS.snapshot()
    if "bc_stats_logged" not in run.__dict__:
        run.bc_stats_logged = True
        run.log(f"BC_STATS: {stats}")
    return stats if stats["pulls"] else None


def read(run, spec):
    """With `stat`, that count of `BC_STATS`.  With `scopes`, self time under
    them in the traced pass per level pull, in us; with `per_entry`, in ns
    per padded pull entry one device gathers and folds.  None where the
    program keeps no such record or the trace's operations carry no scope."""
    stats = bc_stats(run)
    if stats is None:
        return None
    if "stat" in spec:
        return stats[spec["stat"]]
    s = reduce_scopes.scope_seconds(run, spec["scopes"])
    queries = len([j for j in run.traffic["jobs"] if j["app"] == "bc"])
    if s is None or not queries:
        return None
    per_pull = s / queries / stats["pulls"]
    if spec.get("per_entry"):
        return 1e9 * per_pull / run.frag.dev.ie.edge_src.shape[1]
    return 1e6 * per_pull
