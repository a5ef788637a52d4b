def read(run, spec):
    """Device busy time of the traced pass over its rounds, in us: what a
    round costs whatever its frontier."""
    rounds = run.readings.get("traced_rounds")
    if not run.trace or not rounds:
        return None
    return 1e6 * run.trace["busy_s"] / rounds
