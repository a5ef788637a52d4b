def read(run, spec):
    """fnum x Ep of the resident pull (incoming) CSR over the real entries."""
    src = run.frag.dev.ie.edge_src
    return src.shape[0] * src.shape[1] / run.dataset_info["pull_entries"]
