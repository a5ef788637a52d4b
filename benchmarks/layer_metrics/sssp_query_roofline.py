"""The least an exact single-source shortest-path query must move, from shapes
alone, over the traced query's device busy time.

Whatever the algorithm, every edge is relaxed at least once in each direction
it can be used: per real pull entry the neighbour's index, the weight and the
neighbour's distance (12 B); per vertex its distance written and its row's
two offsets read (12 B).  32-bit throughout, as the chip computes.  Padding
is not counted, and neither is any entry read twice: a label-correcting
program reads more, which is what the share shows.
"""

BYTES = 4


def sssp_query_bytes(pull_entries: int, vertices: int) -> int:
    return pull_entries * 3 * BYTES + vertices * 3 * BYTES


def read(run, spec):
    t = run.trace
    peaks = run.peaks["devices"].get(run.devices[0].device_kind)
    queries = len([j for j in run.traffic["jobs"] if j["app"] == "sssp"])
    if not t or not t.get("busy_s") or not queries or peaks is None:
        return None  # no table of peaks for this device: no roofline
    floor = queries * sssp_query_bytes(
        run.dataset_info["pull_entries"], run.dataset_info["vertices"]
    ) / run.chips / peaks["hbm_bytes_per_s"]
    return 100.0 * floor / t["busy_s"]
