from benchmarks import reduce_scopes


def read(run, spec):
    red = reduce_scopes.for_run(run)
    if not red or red["scoped_share"] is None:
        return None
    return 100.0 * red["scoped_share"]
