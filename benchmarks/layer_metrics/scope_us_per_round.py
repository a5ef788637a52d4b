from benchmarks.layer_metrics import scope_per_round


def read(run, spec):
    """`scope_per_round`'s ms per traced round, in us."""
    ms = scope_per_round.read(run, spec)
    return None if ms is None else 1e3 * ms
