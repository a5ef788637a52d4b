"""A count of the program's round record, or its ratio to another count.

`ROUND_STATS[stat]` of the last query (`round_record.round_stats`); with
`over`, divided by that count of the record or, where the record has none of
that name, of the graph (`vertices`); with `percent`, times 100.  A program
whose record lacks `stat` (one from before the count existed) gives nothing.
"""

from benchmarks.layer_metrics import round_record


def read(run, spec):
    stats = round_record.round_stats(run)
    if stats is None or spec["stat"] not in stats:
        return None
    value = stats[spec["stat"]]
    if "over" in spec:
        below = stats.get(spec["over"], run.dataset_info.get(spec["over"]))
        if not below:
            return None
        value = value / below
    return 100.0 * value if spec.get("percent") else value
