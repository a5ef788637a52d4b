from benchmarks import reduce_scopes


def read(run, spec):
    """The metric's host spans, summed, per span named by `per`."""
    found = reduce_scopes.span_seconds(run, spec["spans"])
    per = reduce_scopes.span_seconds(run, [spec["per"]])
    if not found or not per:
        return None
    return 1e3 * sum(found) / len(per)
