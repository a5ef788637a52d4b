from benchmarks import reduce_scopes


def read(run, spec):
    """Self time under the pull's two scopes per traced round, in ns per
    padded entry a device folds in both directions (the width of its tile's
    pull stream).  A fragment without the tiles' CSRs gives nothing."""
    pull = getattr(run.frag.dev, "pull", None)
    s = reduce_scopes.scope_seconds(run, spec["scopes"])
    rounds = run.readings.get("traced_rounds")
    if pull is None or s is None or not rounds:
        return None
    return 1e9 * s / rounds / pull.edge_src.shape[1]
