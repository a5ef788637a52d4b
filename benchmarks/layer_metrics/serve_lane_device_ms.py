import statistics

from benchmarks import reduce_scopes


def read(run, spec):
    red = reduce_scopes.for_run(run)
    lanes = [1e3 * b["busy_s"] / b["lanes"]
             for b in (red["batches"] if red else ()) if b["lanes"]]
    return statistics.median(lanes) if lanes else None
