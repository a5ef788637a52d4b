"""The least an exact single-root betweenness query (Brandes) must move, from
shapes alone, over the traced query's device busy time.

Whatever the algorithm, every edge is looked at from both of its ends once on
the way out (is the neighbour a level up, and how many paths reach it) and
once on the way back (what the neighbour a level down hands up): per real pull
entry the neighbour's index twice (8 B); per vertex its depth, its path count
and its dependency written and its row's two offsets read (20 B).  32-bit
throughout, as the chip computes.  Padding is not counted, and neither is any
entry read a third time or any neighbour's value read at all: a
level-synchronous program reads the graph once a level, which is what the
share shows.
"""

BYTES = 4


def bc_query_bytes(pull_entries: int, vertices: int) -> int:
    return pull_entries * 2 * BYTES + vertices * 5 * BYTES


def read(run, spec):
    t = run.trace
    peaks = run.peaks["devices"].get(run.devices[0].device_kind)
    queries = len([j for j in run.traffic["jobs"] if j["app"] == "bc"])
    if not t or not t.get("busy_s") or not queries or peaks is None:
        return None  # no table of peaks for this device: no roofline
    floor = queries * bc_query_bytes(
        run.dataset_info["pull_entries"], run.dataset_info["vertices"]
    ) / run.chips / peaks["hbm_bytes_per_s"]
    return 100.0 * floor / t["busy_s"]
