from benchmarks import reduce_scopes


def read(run, spec):
    """Device idle time under the metric's host spans, per traced batch."""
    s = reduce_scopes.idle_seconds(run, spec["spans"])
    red = reduce_scopes.for_run(run)
    if s is None or not red["batches"]:
        return None
    return 1e3 * s / len(red["batches"])
