"""SSSP — single-source shortest paths.

Re-design of `examples/analytical_apps/sssp/sssp.h:36-170` (frontier
DenseVertexSet + atomic_min relax + SyncStateOnOuterVertex).

TPU formulation: pull-mode Bellman-Ford.  Each superstep gathers the
global distance vector (`all_gather` over ICI — the collective form of
the reference's outer-vertex sync) and relaxes *all* in-edges with one
gather + `segment_min`; the frontier bitset becomes implicit (vertices
whose distance did not change contribute no improvement).  `min` is
associative, so the result is bit-exact regardless of reduction order —
matching the reference's atomic_min semantics and golden outputs.
Termination: `psum` of the per-shard changed-count (the reference's 2-int
MPI_Allreduce, `parallel_message_manager.h:123-138`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from libgrape_lite_tpu.app.base import ParallelAppBase, StepContext
from libgrape_lite_tpu.ops.segment import pull_gather
from libgrape_lite_tpu.utils.types import LoadStrategy, MessageStrategy


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
        # for the serve/ vmapped multi-source dispatch — the ephemeral
        # streams below are built once and shared across lanes
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
        # fused dense pull (r6): pre-mask the weight stream ONCE at init
        # (inf at masked edges), so the per-round relax is one gather +
        # one add with no separate edge_mask select pass (x + inf ==
        # inf; distances never reach -inf, so no NaN).  The host CSRs
        # are already padded to the device Ep, so the stream stacks
        # uniformly.
        eph_entries["wf_eff"] = np.stack([
            np.where(frag.host_ie[f].edge_mask,
                     frag.host_ie[f].edge_w,
                     np.asarray(np.inf, frag.host_ie[f].edge_w.dtype))
            for f in range(frag.fnum)
        ])
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
        # one gather pass: the pre-masked weight stream (wf_eff, inf
        # at masked edges) folds the relax-mask select into the add
        cand = pull_gather(full, nbr, add=state["wf_eff"])
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
