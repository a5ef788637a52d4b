"""The least one CDLP propagation pass must move, from shapes alone.

Per real pull entry: the neighbour's index, the gathered label and the row
it counts for (12 B, a pull round's reading); one read and one write of the
(row, label) pair, which is the least any sort moves (16 B); one read of
the sorted pair for the count (8 B).  Per vertex the old label read and the
new one written (8 B).  32-bit throughout, as the chip computes.  Padding
is not counted: it is what the program adds, not what the algorithm needs.
A comparison sort of E pairs makes more than one pass over them; the floor
counts one, so the share says how far the whole round is from a single
streaming pass, not from the best sort.
"""

BYTES = 4


def cdlp_pass_bytes(pull_entries: int, vertices: int) -> int:
    gather = 3 * BYTES
    sort = 2 * 2 * BYTES
    count = 2 * BYTES
    return pull_entries * (gather + sort + count) + vertices * 2 * BYTES


def cdlp_pass_floor_s(pull_entries: int, vertices: int, chips: int,
                      hbm_bytes_per_s: float) -> float:
    """Seconds one pass takes at the peak HBM bandwidth, the graph split
    evenly over `chips`."""
    return cdlp_pass_bytes(pull_entries, vertices) / chips / hbm_bytes_per_s
