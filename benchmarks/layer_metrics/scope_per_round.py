from benchmarks import reduce_scopes


def read(run, spec):
    """Self time under the metric's scopes per traced round, in ms; with
    `per_entry`, in ns per padded pull entry one device folds."""
    s = reduce_scopes.scope_seconds(run, spec["scopes"])
    rounds = run.readings.get("traced_rounds")
    if s is None or not rounds:
        return None
    if spec.get("per_entry"):
        return 1e9 * s / rounds / run.frag.dev.ie.edge_src.shape[1]
    return 1e3 * s / rounds
