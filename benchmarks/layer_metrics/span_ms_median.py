import statistics

from benchmarks import reduce_scopes


def read(run, spec):
    found = reduce_scopes.span_seconds(run, spec["spans"])
    return 1e3 * statistics.median(found) if found else None
