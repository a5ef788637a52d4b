def read(run, spec):
    """fnum x the width of a tile's pull stream (both directions) over the
    real entries: each stored edge once a direction.  A fragment without the
    tiles' CSRs gives nothing."""
    pull = getattr(run.frag.dev, "pull", None)
    edges = run.dataset_info.get("edges")
    if pull is None or not edges:
        return None
    return pull.edge_src.shape[0] * pull.edge_src.shape[1] / (2 * edges)
