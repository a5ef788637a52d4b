def read(run, spec):
    t = run.trace
    if not t or not t["collective_s"]:
        return None
    return 100.0 * t["collective_exposed_s"] / t["collective_s"]
