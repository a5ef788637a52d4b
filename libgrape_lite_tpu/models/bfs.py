"""BFS — breadth-first search levels.

Re-design of `examples/analytical_apps/bfs/bfs.h:30-150` (level-sync
frontier bitmaps).  TPU formulation: pull-mode unit-weight Bellman-Ford
over int32 depths, the same level assignment.  Two rounds give it.  The
dense round (`inceval`) relaxes every entry and folds every row
whatever the frontier: masked dense work that XLA keeps on the VPU, the
right round where a level holds much of the graph, and the only one of
the batched, chunked, stepwise and dyn-overlay runners.  The round
that follows its frontier (`inceval_frontier`,
`ops/segment.frontier_relax`) pushes from the rows that improved last
round alone, at static shapes: the fused serial loop carries their list
and takes that round wherever the list fits `_FRONTIER_ROWS` rows and
`_FRONTIER_ENTRIES` entries (`worker._frontier_loop`), one fragment's
unbatched query on a graph large enough for it to pay.  A road
network's search is a thousand such rounds and more; a Graph500
search's first and last.  Unreached vertices keep the int sentinel and
print as the reference's `std::numeric_limits<int64_t>::max()`
(`bfs_context.h:44`, golden `p2p-31-BFS`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from libgrape_lite_tpu.app.base import ParallelAppBase, StepContext
from libgrape_lite_tpu.ops.segment import frontier_relax, pull_gather
from libgrape_lite_tpu.utils.types import LoadStrategy, MessageStrategy

_SENTINEL = np.iinfo(np.int32).max
_OUT_SENTINEL = np.iinfo(np.int64).max  # printed for unreachable

# The room of a frontier round (ops/segment.frontier_relax): a list of
# B rows whose adjacency holds C entries, both paid in full by every
# such round, since shapes are static.  On the road-like graph at 2^20
# vertices 1,077 of a search's 1,686 rounds have 512-1,023 live rows,
# 26 have 1,024-2,047 and none more, and the largest degree is 4
# (ROUND_STATS["active_bits"], PERF.md section 6, PR 39): 2,048 rows
# and their 8,192 entries cover every round there; a round that
# outgrows either is a dense one.
_FRONTIER_ROWS = 2048
_FRONTIER_ENTRIES = 8192
# A budget-sized round costs what a dense round costs on a graph of a
# few tens of C entries (PERF.md section 6, PR 40), so below this many
# times C padded pull entries the dense round is the cheaper one at any
# frontier and the loop is built without the other arm
_DENSE_FLOOR = 16


class BFS(ParallelAppBase):
    load_strategy = LoadStrategy.kBothOutIn
    message_strategy = MessageStrategy.kSyncOnOuterVertex
    result_format = "int"
    batch_query_key = "source"  # serve/: [k]-source batched dispatch
    # dyn/: unit-weight tropical relax — additive deltas fold exactly,
    # and the previous depth vector seeds incremental IncEval
    dyn_overlay_support = True
    inc_mode = "monotone-min"
    inc_seed_keys = {"depth": "min"}

    def init_state(self, frag, source=0):
        from libgrape_lite_tpu.app.base import source_lane_array

        # a SEQUENCE of sources builds the batched [k, fnum, vp] carry
        # for the serve/ vmapped multi-source dispatch (ephemeral
        # streams below are built once and shared across lanes)
        batched, depth = source_lane_array(
            frag, source, "BFS", _SENTINEL, 0, np.int32
        )
        depth = depth if batched else depth[0]
        state = {"depth": depth}
        eph_entries = {}
        from libgrape_lite_tpu.parallel.mirror import resolve_mirror_plan

        # dyn/ overlay (see SSSP.init_state): pid-addressed side
        # arrays, mirror compaction off while attached
        self._dyn = getattr(frag, "dyn_overlay", None) is not None
        if self._dyn:
            from libgrape_lite_tpu.dyn.ingest import overlay_state_entries

            eph_entries.update(
                overlay_state_entries(frag, "ie", None, "dyn_ie_")
            )
            self._mx = None
        else:
            self._mx = resolve_mirror_plan(frag, "ie")
        if self._mx is not None:
            eph_entries.update(self._mx.state_entries("mx_"))
        self._mx_uid = self._mx.uid if self._mx is not None else -1
        # a round that follows its frontier (worker `_make_runner`): one
        # fragment's unbatched state read straight from `depth`, and a
        # graph on which a dense round costs more than a budget-sized one
        offered = (
            frag.fnum == 1 and not batched and not self._dyn
            and self._mx is None
            and frag.dev.ie.edge_nbr.shape[-1]
            >= _DENSE_FLOOR * _FRONTIER_ENTRIES
        )
        self.frontier_budget = (
            (_FRONTIER_ROWS, _FRONTIER_ENTRIES) if offered else None
        )
        if eph_entries:
            state.update(eph_entries)
            self.ephemeral_keys = frozenset(eph_entries)
        return state

    def peval(self, ctx: StepContext, frag, state):
        return state, jnp.int32(1)

    def inceval(self, ctx: StepContext, frag, state):
        depth = state["depth"]
        ie = frag.ie
        sent = jnp.int32(_SENTINEL)
        if self._mx is not None:
            full = ctx.exchange_mirrors(depth, state["mx_send"])
            nbr = state["mx_nbr"]
        else:
            full = ctx.gather_state(depth)
            nbr = ie.edge_nbr
        cand = pull_gather(full, nbr, ie.edge_mask, sent, add=1,
                           absent=sent)
        relaxed = self.segment_reduce(cand, ie.edge_src, frag.vp,
                                      "min", row_ptr=ie.indptr)
        if "dyn_ie_nbr" in state:
            # staged delta edges (dyn/): extra unit-weight candidates
            # merged at the fold; `full` is pid-addressed in overlay
            # mode (init_state disables mirror compaction)
            dcand = pull_gather(
                full, state["dyn_ie_nbr"], state["dyn_ie_mask"], sent,
                add=1, absent=sent,
            )
            relaxed = self.dyn_min_fold(
                relaxed, state, frag.vp, "dyn_ie_", dcand
            )
        with jax.named_scope("grape.app.update"):
            new = jnp.minimum(depth, relaxed)
            changed = jnp.logical_and(new < depth, frag.inner_mask)
            active = ctx.sum(changed.sum().astype(jnp.int32))
        return {"depth": new}, active

    # ---- the round that follows its frontier (app/base.py) ----

    def frontier_mask(self, state, new_state=None):
        if new_state is None:
            return state["depth"] != jnp.int32(_SENTINEL)
        return new_state["depth"] < state["depth"]

    def frontier_csr(self, frag):
        # an undirected fragment's `oe` is its `ie`, one set of buffers
        # under two names: read them under the name the dense round
        # does, so that the program has them once
        return frag.oe if frag.directed else frag.ie

    def inceval_frontier(self, frag, state, front, lo, count):
        """`inceval` from the listed rows alone: they push depth + 1
        along `oe` where the dense round pulls along `ie`.  The same
        depths and the same vote, since a row that did not improve last
        round has nothing to propose that it has not proposed."""
        depth, front, active = frontier_relax(
            state["depth"], front, lo, count,
            self.frontier_csr(frag).edge_nbr, self.frontier_budget[1],
            add=1, absent=jnp.int32(_SENTINEL),
        )
        return {"depth": depth}, active, front

    def invariants(self, frag, state):
        # levels live in [0, SENTINEL] and only ever improve (pull-mode
        # unit-weight relaxation is tropical-min, like SSSP)
        from libgrape_lite_tpu.guard.invariants import (
            in_range, monotone_non_increasing,
        )

        return [
            in_range("depth", lo=0, hi=_SENTINEL),
            monotone_non_increasing("depth"),
        ]

    def finalize(self, frag, state):
        d = np.asarray(state["depth"]).astype(np.int64)
        return np.where(d == _SENTINEL, _OUT_SENTINEL, d)
