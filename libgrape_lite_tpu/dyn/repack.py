"""Repack policy: when accumulated deltas fold into a rebuilt CSR.

Below the threshold, staged additions ride the dense overlay side-path
(dyn/ingest.py) and queries pay a few hundred extra gather slots per
round — zero pack replanning, zero XLA recompiles.  Past the
threshold the amortized rebuild wins (SparseP's delta-ratio analysis,
arxiv 2201.05072: the overlay's unstructured slots lack the packed
CSR's locality, so their per-edge cost is a large constant multiple of
the planned streams'), and the buffer folds into the base arrays via
the existing mutation machinery: `BasicFragmentMutator.mutate` edits
the retained host edge list and rebuilds the padded shards, the next
`init_state` re-plans against the new content — a counted recompile
event, never a silent one.

Non-additive ops (removals, weight updates, vertex changes) force a
repack regardless of ratio: a tropical min-fold cannot "un-min" a
candidate, so the overlay cannot represent them consistently.

Env knobs (read by `RepackPolicy.from_env`):
  GRAPE_DYN_REPACK_RATIO   delta-ratio threshold (default 0.05)
  GRAPE_DYN_CAP            delta buffer / overlay capacity (default 4096)
"""

from __future__ import annotations

import os
from dataclasses import dataclass

from libgrape_lite_tpu.dyn.delta import DeltaBuffer

REPACK_RATIO_ENV = "GRAPE_DYN_REPACK_RATIO"
CAPACITY_ENV = "GRAPE_DYN_CAP"

DEFAULT_REPACK_RATIO = 0.05
DEFAULT_CAPACITY = 4096


@dataclass(frozen=True)
class RepackPolicy:
    """The fold-vs-accumulate trade-off in one place (the dyn/ analogue
    of serve/policy.BatchPolicy)."""

    # staged edge ops / base real edges above which apply() folds the
    # buffer into a rebuilt CSR; 0 repacks on every apply (useful to
    # force the rebuild path in tests), >= 1 effectively never (the
    # bounded buffer still forces a fold at capacity)
    threshold: float = DEFAULT_REPACK_RATIO
    # delta buffer bound == overlay slot capacity per fragment; fixed
    # per DynGraph so ingest never changes compiled state shapes
    capacity: int = DEFAULT_CAPACITY

    def __post_init__(self):
        if self.threshold < 0:
            raise ValueError(
                f"threshold must be >= 0, got {self.threshold}"
            )
        if self.capacity < 1:
            raise ValueError(
                f"capacity must be >= 1, got {self.capacity}"
            )

    @classmethod
    def from_env(cls) -> "RepackPolicy":
        return cls(
            threshold=float(
                os.environ.get(REPACK_RATIO_ENV, DEFAULT_REPACK_RATIO)
            ),
            capacity=int(os.environ.get(CAPACITY_ENV, DEFAULT_CAPACITY)),
        )

    def should_repack(self, buffer: DeltaBuffer, fragment) -> bool:
        """Ratio trigger only — structural triggers (non-additive ops,
        unknown endpoints, overlay slot overflow) are checked by
        DynGraph.apply, which can see the overlay build outcome."""
        return (
            buffer.delta_ratio(fragment.total_edges_num) > self.threshold
        )


def repack_fragment(fragment, buffer: DeltaBuffer):
    """Fold the staged buffer into a rebuilt sharded fragment.

    Reuses the rebuild-on-mutate machinery (`fragment/mutation.py`):
    host edge-list edit -> partition -> padded shard build, validated
    under GRAPE_VALIDATE_LOAD=1 like every other load path.  The
    caller owns cache/worker re-keying (serve/session adopts the new
    fragment into its resident workers; stale compiled runners miss
    naturally because the apps' plan uids change on re-init)."""
    if fragment.edge_list is None:
        raise ValueError(
            "repack needs the retained host edge list; build the base "
            "fragment with retain_edge_list=True (LoadGraphSpec"
            "(retain_edge_list=True) or LoadGraphAndMutate)"
        )
    return buffer.to_mutator(directed=fragment.directed).mutate(fragment)
