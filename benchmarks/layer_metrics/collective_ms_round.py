def read(run, spec):
    t, rounds = run.trace, run.readings.get("traced_rounds")
    if not t or not rounds:
        return None
    return 1e3 * t["collective_s"] / rounds
