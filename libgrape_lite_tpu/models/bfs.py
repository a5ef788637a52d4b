"""BFS — breadth-first search levels.

Re-design of `examples/analytical_apps/bfs/bfs.h:30-150` (level-sync
frontier bitmaps).  TPU formulation: pull-mode unit-weight Bellman-Ford
over int32 depths — identical level assignment, no frontier compaction
needed (masked dense relaxation; XLA keeps it on the VPU).  Unreached
vertices keep the int sentinel and print as the reference's
`std::numeric_limits<int64_t>::max()` (`bfs_context.h:44`, golden
`p2p-31-BFS`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from libgrape_lite_tpu.app.base import ParallelAppBase, StepContext
from libgrape_lite_tpu.ops.segment import pull_gather
from libgrape_lite_tpu.utils.types import LoadStrategy, MessageStrategy

_SENTINEL = np.iinfo(np.int32).max
_OUT_SENTINEL = np.iinfo(np.int64).max  # printed for unreachable


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
    # r9: unit-weight tropical relax — min folds split bit-stably
    pipeline_state_key = "depth"

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
        # superstep pipelining (r9): after the exchange decision,
        # which the pipelined round reuses verbatim (see SSSP)
        self._pipeline = None
        if not batched and not self._dyn:
            from libgrape_lite_tpu.parallel.pipeline import resolve_pipeline

            self._pipeline = resolve_pipeline(
                frag, app_name="BFS", key="depth", direction="ie",
                mirror=self._mx, mx_prefix="mx_", with_weights=False,
            )
            if self._pipeline is not None:
                eph_entries.update(self._pipeline.host_entries)
        self._pipeline_uid = (
            self._pipeline.uid if self._pipeline is not None else -1
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

    def inceval_pipelined(self, ctx: StepContext, frag, state, xbuf):
        """Double-buffered round (parallel/pipeline.py; see SSSP):
        boundary relax, exchange kickoff, interior relax overlapping
        the collective, join at the boundary mask — bit-identical to
        the serial min relax."""
        pl = self._pipeline
        depth = state["depth"]
        sent = jnp.int32(_SENTINEL)
        full = pl.splice(ctx, depth, state, xbuf)
        bmask = state["pl_bmask"]
        cand_b = pull_gather(
            full, state["pl_b_nbr"], state["pl_b_val"], sent,
            add=1, absent=sent,
        )
        rel_b = self.segment_reduce(
            cand_b, state["pl_b_src"], frag.vp, "min"
        )
        new_b = jnp.minimum(depth, rel_b)
        xbuf2 = pl.kickoff(ctx, jnp.where(bmask, new_b, depth), state)
        # ---- pipelined window: carry reads below are named in
        # parallel/pipeline.PIPELINE_WINDOW_READS (grape-lint R6) ----
        cand_i = pull_gather(
            full, state["pl_i_nbr"], state["pl_i_val"], sent,
            add=1, absent=sent,
        )
        rel_i = self.segment_reduce(
            cand_i, state["pl_i_src"], frag.vp, "min"
        )
        with jax.named_scope("grape.app.update"):
            new_i = jnp.minimum(depth, rel_i)
            new = jnp.where(bmask, new_b, new_i)
            changed = jnp.logical_and(new < depth, frag.inner_mask)
            active = ctx.sum(changed.sum().astype(jnp.int32))
        return {"depth": new}, active, xbuf2

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
