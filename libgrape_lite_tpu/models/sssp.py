"""SSSP — single-source shortest paths.

Re-design of `examples/analytical_apps/sssp/sssp.h:36-170` (frontier
DenseVertexSet + atomic_min relax + SyncStateOnOuterVertex).

TPU formulation: Bellman-Ford over float distances, in two rounds.  The
dense round (`inceval`) gathers the global distance vector
(`all_gather` over ICI — the collective form of the reference's
outer-vertex sync) and relaxes *all* in-edges with one gather +
`segment_min`, whatever the frontier: vertices whose distance did not
change contribute no improvement.  It is the right round where a round
improves much of the graph (a Graph500 graph's few rounds), and the
only one of the batched, chunked, stepwise and dyn-overlay runners, of
several fragments and of directed ones.  Its weights and its mask are
the fragment's own `edge_w` and `edge_mask`: no query holds a second
copy of the weights, and a state is `dist` and what a mirror plan or a
dyn overlay adds.  `min` is associative, so the result is bit-exact
regardless of reduction order — matching the reference's atomic_min
semantics and golden outputs.  Termination:
`psum` of the per-shard changed-count (the reference's 2-int
MPI_Allreduce, `parallel_message_manager.h:123-138`).

The round that follows its frontier (`inceval_frontier`,
`ops/segment.frontier_relax` with a weight an entry) pushes
`dist[row] + w` from a list of rows alone, under a distance threshold:
near/far, as the reference's CUDA SSSP (`cuda/sssp/sssp.h:50-100`).
Weights that spread over a range drag a band of re-improving vertices
behind a hop-synchronous wavefront (on the road-like graph every
vertex is improved 39 times and a round's list runs to 48,036 rows);
with only the rows under the threshold pushing, and the threshold
stepping on by a bucket when none is left, a vertex pushes 1.0 to 1.6
times and a round's list stays in the low thousands.  The fused serial
loop carries list and threshold (`worker._frontier_loop`) for one
undirected fragment's unbatched query on a graph large enough for the
round to pay; the fixed point is the dense loop's, every distance the
same, reached in another order.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from libgrape_lite_tpu.app.base import ParallelAppBase, StepContext
from libgrape_lite_tpu.ops.segment import frontier_relax, pull_gather
from libgrape_lite_tpu.utils.types import LoadStrategy, MessageStrategy

# The room of a frontier round, paid in full by every such round
# (models/bfs.py has the reckoning).  On the road-like graph at 2^20
# vertices, weights 1..255, the widest list of a query under a
# threshold that steps by 16 largest weights is 2,011 rows (4,826
# entries at degree 2.4), so BFS's room holds every round there; at 24
# weights it is 2,590 rows and 181 of 2,182 rounds run dense, at 32
# 3,996 and 660 of 2,075 (ROUND_STATS on the chip, PERF.md section 6,
# PR 43; the NumPy oracle of tests/sssp_oracles.py reads the same to
# the unit).
_FRONTIER_ROWS = 2048
_FRONTIER_ENTRIES = 8192
# as models/bfs.py: under this many times C padded pull entries a dense
# round is the cheaper one at any frontier
_DENSE_FLOOR = 16
# A bucket's width in largest weights of the fragment.  A narrow bucket
# is label-setting (a vertex pushes once) and pays for it in rounds, a
# wide one has few steps and a band of re-improving rows that outgrows
# the list.  On that graph 4 / 8 / 12 / 16 / 24 / 32 weights give
# 3,046 / 2,557 / 2,399 / 2,289 / 2,182 / 2,075 rounds (175 / 87 / 58 /
# 43 / 29 / 21 of them steps) of 1.13 / 1.28 / 1.44 / 1.60 / 1.98 /
# 2.75 pushes a vertex under lists of at most 1,265 / 1,474 / 1,694 /
# 2,011 / 2,590 / 3,996 rows, and a warm query of 1.159 / 0.979 /
# 0.921 / 0.880 / 2.628 / 7.304 s on the chip (PERF.md section 6,
# PR 43): a round that fits costs 385 us whatever it lists and a dense
# one 9.7 ms, so the fewest rounds that still fit the list win, and 16
# is the widest that fits at this scale.
_BUCKET_WEIGHTS = 16


class SSSP(ParallelAppBase):
    load_strategy = LoadStrategy.kBothOutIn
    message_strategy = MessageStrategy.kSyncOnOuterVertex
    result_format = "sssp_infinity"
    needs_edata = True  # double edata (run_app.cc:48-52)
    batch_query_key = "source"  # serve/: [k]-source batched dispatch
    # dyn/: staged additive deltas fold exactly into the tropical min
    # relax, and the previous fixed point seeds incremental IncEval
    dyn_overlay_support = True
    inc_mode = "monotone-min"
    inc_seed_keys = {"dist": "min"}

    def init_state(self, frag, source=0):
        import jax

        if not frag.weighted or frag.host_ie[0].edge_w is None:
            # the reference SSSP requires double edata (run_app.cc:48-52);
            # fail at init instead of a tracer TypeError mid-superstep
            raise ValueError(
                "SSSP requires edge weights; load the graph with "
                "weighted=True (use BFS for unit-weight traversal)"
            )
        dtype = frag.host_ie[0].edge_w.dtype
        if not jax.config.jax_enable_x64:
            # honest TPU dtype: x64-off would downcast silently anyway
            dtype = np.float32
        from libgrape_lite_tpu.app.base import source_lane_array

        # a SEQUENCE of sources builds the batched [k, fnum, vp] carry
        # for the serve/ vmapped multi-source dispatch; the ephemeral
        # entries below (a mirror plan's, an overlay's) are shared
        # across lanes
        batched, dist = source_lane_array(
            frag, source, "SSSP", np.inf, 0.0, dtype
        )
        dist = dist if batched else dist[0]
        state = {"dist": dist}
        eph_entries = {}
        from libgrape_lite_tpu.parallel.mirror import resolve_mirror_plan

        # dyn/ overlay: staged delta edges ride as ephemeral side
        # arrays and fold into the relax below.  Their neighbor reads
        # index the pid-addressed full gather, so mirror compaction is
        # disabled while an overlay is attached (the entries are
        # present — possibly all-masked — whenever the fragment is
        # dyn-managed, keeping the compiled state structure stable
        # across ingests: zero recompiles below the repack threshold)
        self._dyn = getattr(frag, "dyn_overlay", None) is not None
        if self._dyn:
            from libgrape_lite_tpu.dyn.ingest import overlay_state_entries

            eph_entries.update(
                overlay_state_entries(frag, "ie", dtype, "dyn_ie_")
            )
            self._mx = None
        else:
            self._mx = resolve_mirror_plan(frag, "ie")
        if self._mx is not None:
            eph_entries.update(self._mx.state_entries("mx_"))
        self._mx_uid = self._mx.uid if self._mx is not None else -1
        # a round that follows its frontier under a threshold (worker
        # `_frontier_loop`): one undirected fragment (its `oe` is its
        # `ie`, so the push reads the buffers the pull does), an
        # unbatched state read straight from `dist`, and a graph on
        # which a dense round costs more than a budget-sized one.
        # Batched lanes, several fragments, directed fragments, the dyn
        # overlay and a mirror plan keep the dense round, as do the
        # chunked, guarded and stepwise runners, which never ask
        offered = (
            frag.fnum == 1 and not frag.directed and not batched
            and not self._dyn and self._mx is None
            and frag.dev.ie.edge_nbr.shape[-1]
            >= _DENSE_FLOOR * _FRONTIER_ENTRIES
        )
        heaviest = 0.0
        if offered:
            heaviest = float(np.max(
                frag.host_ie[0].edge_w, initial=0.0,
                where=frag.host_ie[0].edge_mask))
        offered = offered and 0.0 < heaviest < np.inf
        self.frontier_budget = (
            (_FRONTIER_ROWS, _FRONTIER_ENTRIES) if offered else None
        )
        # the bucket's width, from the fragment's own weights
        self.frontier_step = _BUCKET_WEIGHTS * heaviest if offered else None
        state.update(eph_entries)
        self.ephemeral_keys = frozenset(eph_entries)
        return state

    def peval(self, ctx: StepContext, frag, state):
        # The reference PEval relaxes only the source's out-edges
        # (sssp.h:68-83); the first pull round subsumes that.
        return state, jnp.int32(1)  # ForceContinue (sssp.h:90)

    def inceval(self, ctx: StepContext, frag, state):
        dist = state["dist"]
        ie = frag.ie
        if self._mx is not None:
            full = ctx.exchange_mirrors(dist, state["mx_send"])
            nbr = state["mx_nbr"]
        else:
            full = ctx.gather_state(dist)
            nbr = ie.edge_nbr
        # one gather pass a round, masked entries filled with inf; the
        # weights and the mask are the fragment's own, whatever the
        # query (under a mirror plan `mx_nbr` keeps `ie`'s layout)
        cand = pull_gather(full, nbr, ie.edge_mask,
                           jnp.asarray(jnp.inf, dist.dtype), add=ie.edge_w)
        relaxed = self.segment_reduce(cand, ie.edge_src, frag.vp, "min",
                                      row_ptr=ie.indptr)
        if "dyn_ie_nbr" in state:
            # staged delta edges (dyn/): one extra gather + segment_min
            # over the dense overlay slots, merged at the fold — `full`
            # is pid-addressed here (mirror compaction is off in
            # overlay mode, see init_state)
            inf = jnp.asarray(jnp.inf, dist.dtype)
            dcand = pull_gather(
                full, state["dyn_ie_nbr"], state["dyn_ie_mask"], inf,
                add=state["dyn_ie_w"],
            )
            relaxed = self.dyn_min_fold(
                relaxed, state, frag.vp, "dyn_ie_", dcand
            )
        with jax.named_scope("grape.app.update"):
            new = jnp.minimum(dist, relaxed)
            changed = jnp.logical_and(new < dist, frag.inner_mask)
            active = ctx.sum(changed.sum().astype(jnp.int32))
        return {"dist": new}, active

    # ---- the round that follows its frontier (app/base.py) ----

    def frontier_mask(self, state, new_state=None):
        if new_state is None:
            return jnp.isfinite(state["dist"])
        return new_state["dist"] < state["dist"]

    def frontier_values(self, state):
        return state["dist"]

    def frontier_csr(self, frag):
        # offered on an undirected fragment only, whose `oe` is its
        # `ie`: the buffers the dense round reads
        return frag.ie

    def inceval_frontier(self, frag, state, front, lo, count, below=None):
        """The listed rows push `dist + w` along their entries, where
        the dense round pulls along every row's."""
        ie = frag.ie
        dist, front, active = frontier_relax(
            state["dist"], front, lo, count, ie.edge_nbr,
            self.frontier_budget[1], add=ie.edge_w, below=below,
        )
        return {"dist": dist}, active, front

    def invariants(self, frag, state):
        # distances are tropical-min state: never negative, never NaN
        # (in_range(lo=0) rejects NaN — NaN >= 0 is False), and only
        # ever improving; +inf is the legitimate unreached sentinel
        from libgrape_lite_tpu.guard.invariants import (
            in_range, monotone_non_increasing,
        )

        return [
            in_range("dist", lo=0.0),
            monotone_non_increasing("dist"),
        ]

    def finalize(self, frag, state):
        return np.asarray(state["dist"])
