import re


def read(run, spec):
    """Self time of the operations whose short name matches the patterns of
    the metric's file, over all operation self time."""
    t = run.trace
    total = sum(s for _, s in t["device_ops"]) if t else 0
    if not total:
        return None
    pat = re.compile("|".join(spec["name_patterns"]), re.I)
    return 100.0 * sum(s for n, s in t["device_ops"] if pat.search(n)) / total
