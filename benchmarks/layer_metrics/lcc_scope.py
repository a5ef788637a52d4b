from benchmarks import reduce_scopes


def lcc_stats(run) -> dict | None:
    """The program's `LCC_STATS` after the run's queries (logged once); None
    from a program from before it existed."""
    try:
        from libgrape_lite_tpu.models.lcc_beta import LCC_STATS
    except ImportError:
        return None
    stats = LCC_STATS.snapshot()
    if "lcc_stats_logged" not in run.__dict__:
        run.lcc_stats_logged = True
        run.log(f"LCC_STATS: {stats}")
    return stats


def traced_queries(run) -> int:
    """Whole queries in the traced pass: one per job of the traffic mix."""
    return len(run.traffic["jobs"])


def read(run, spec):
    """Self time under the metric's scopes of one traced query, in ms; with
    `per_lane`, in ns per padded lane one device's step runs
    (`LCC_STATS["query_lanes"]`).  None where the trace holds none of the
    scopes or the program counts no lanes."""
    red = reduce_scopes.for_run(run)
    if not red or red["scope_s"] is None:
        return None
    found = [red["scope_s"][s] for s in spec["scopes"] if s in red["scope_s"]]
    if not found:
        return None
    per_query = sum(found) / traced_queries(run)
    if not spec.get("per_lane"):
        return 1e3 * per_query
    stats = lcc_stats(run)
    if not stats or not stats.get("query_lanes"):
        return None
    return 1e9 * per_query / stats["query_lanes"]
