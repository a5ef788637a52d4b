from benchmarks import reduce_scopes


def passes(run) -> int:
    """Propagation passes in the traced pass: one query per job, each of the
    job's `max_round` passes.  Not `Worker.rounds`, which counts IncEvals
    only: CDLP's PEval is itself a pass, so 10 passes run in 9 rounds."""
    return sum(int(j.get("params", {}).get("max_round", 0))
               for j in run.traffic["jobs"])


def read(run, spec):
    """Self time under the metric's scopes per propagation pass of the traced
    query, in ms; with `per_entry`, in ns per padded pull entry one device
    sorts and folds.  None where the trace holds none of the scopes (a
    program from before they existed)."""
    red = reduce_scopes.for_run(run)
    n = passes(run)
    if not red or red["scope_s"] is None or not n:
        return None
    found = [red["scope_s"][s] for s in spec["scopes"] if s in red["scope_s"]]
    if not found:
        return None
    if spec.get("per_entry"):
        return 1e9 * sum(found) / n / run.frag.dev.oe.edge_src.shape[1]
    return 1e3 * sum(found) / n
