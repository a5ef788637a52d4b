"""The dynamic branch's packed arm: how many passes of the last query took
it, and what its two named parts cost a pass that did.

`CDLP_STATS` (`libgrape_lite_tpu/models/cdlp.py`) is filled when a query's
answer is extracted, so after the run it holds the last query's record; CDLP
takes no input but the graph, so every query of a run reads the same.  The
scopes `grape.cdlp.live` and `grape.cdlp.rank` are inside the packed arm
alone: a pass that sorts two keys runs neither, so their time is divided by
the packed passes, not by all of them.
"""

from benchmarks import reduce_scopes


def cdlp_stats(run) -> dict | None:
    """The program's `CDLP_STATS` after the run's queries (logged once); None
    from a program from before it existed, or before any CDLP answer."""
    try:
        from libgrape_lite_tpu.models.cdlp import CDLP_STATS
    except ImportError:
        return None
    stats = CDLP_STATS.snapshot()
    if "cdlp_stats_logged" not in run.__dict__:
        run.cdlp_stats_logged = True
        run.log(f"CDLP_STATS: {stats}")
    return stats if stats["passes"] else None


def read(run, spec):
    """With `stat`, that count of `CDLP_STATS`.  With `scopes`, self time
    under them in the traced query per packed pass, in ms; with `per_entry`,
    in ns per padded pull entry one device ranks.  None where the program
    keeps no such record, the trace holds none of the scopes or no pass
    packed."""
    stats = cdlp_stats(run)
    if stats is None:
        return None
    if "stat" in spec:
        return stats[spec["stat"]]
    red = reduce_scopes.for_run(run)
    if not red or red["scope_s"] is None or not stats["packed_passes"]:
        return None
    found = [red["scope_s"][s] for s in spec["scopes"] if s in red["scope_s"]]
    if not found:
        return None
    per_pass = sum(found) / len(run.traffic["jobs"]) / stats["packed_passes"]
    if spec.get("per_entry"):
        return 1e9 * per_pass / run.frag.dev.oe.edge_src.shape[1]
    return 1e3 * per_pass
