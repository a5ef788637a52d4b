"""The least a pull round must move, from shapes alone, and the time the
chip's memory would need for it.

A pull round reads, per pull entry (one direction of one edge), the
neighbour's index, the neighbour's gathered value and the row it folds into
(the segment id), and for a weighted fold the edge weight; per vertex it
reads the old state and writes the new.  The chip computes in 32 bits, so
each of these is 4 bytes.  Padding entries and padded rows are not counted:
they are what the program adds, not what the algorithm needs.  The work is
bound by bandwidth, not by arithmetic: one add or min per entry.
"""

from __future__ import annotations

BYTES = 4  # indices, values and weights are 32-bit on the chip
WEIGHTED_APPS = ("sssp",)  # fold reads the edge weight too


def pull_round_bytes(pull_entries: int, vertices: int, weighted: bool) -> int:
    per_entry = 3 * BYTES + (BYTES if weighted else 0)
    return pull_entries * per_entry + vertices * 2 * BYTES


def pull_round_floor_s(pull_entries: int, vertices: int, weighted: bool,
                       chips: int, hbm_bytes_per_s: float) -> float:
    """Seconds one round takes at the peak HBM bandwidth, the graph split
    evenly over `chips`."""
    return pull_round_bytes(pull_entries, vertices, weighted) / chips / hbm_bytes_per_s
