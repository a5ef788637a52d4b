def read(run, spec):
    return None if not run.trace else 100.0 * run.trace["idle_share"]
