"""WCC — weakly connected components.

Re-design of `examples/analytical_apps/wcc/wcc.h` (min-gid label
propagation over both edge directions, atomic_min + outer-vertex sync).

TPU formulation: component ids are pids (bit-identical to the
reference's gids given the power-of-two padding); each superstep pulls
`min` over in- and out-neighborhoods via gather + `segment_min`.  For
undirected graphs the two CSRs hold the same symmetrised multiset, so a
single pull suffices.  Output labels are canonicalised to the component
representative's *oid* on the host (the LDBC WCC check is
partition-isomorphism, `misc/wcc_check.cc`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from libgrape_lite_tpu.app.base import ParallelAppBase, StepContext
from libgrape_lite_tpu.ops.segment import pull_gather
from libgrape_lite_tpu.utils.types import LoadStrategy, MessageStrategy


class WCC(ParallelAppBase):
    load_strategy = LoadStrategy.kBothOutIn
    message_strategy = MessageStrategy.kSyncOnOuterVertex
    result_format = "int"
    # dyn/: min-gid propagation is a tropical fold — additive deltas
    # merge exactly, and the previous labeling seeds incremental
    # IncEval (labels remapped across repacks via inc_value_map)
    dyn_overlay_support = True
    inc_mode = "monotone-min"
    inc_seed_keys = {"comp": "min"}

    def init_state(self, frag, **_):
        vp = frag.vp
        pids = np.arange(frag.fnum * vp, dtype=np.int32).reshape(frag.fnum, vp)
        # padded rows get a big sentinel so they never win a min
        comp = np.where(frag.host_inner_mask(), pids, np.iinfo(np.int32).max)
        state = {"comp": comp.astype(np.int32)}
        eph_entries = {}
        # mirror-compressed exchange (GRAPE_EXCHANGE=mirror), per pull
        # direction
        from libgrape_lite_tpu.parallel.mirror import resolve_mirror_plan

        self._mx_ie = self._mx_oe = None
        # dyn/ overlay (see SSSP.init_state): pid-addressed side
        # arrays for each pull direction, mirror compaction off
        self._dyn = getattr(frag, "dyn_overlay", None) is not None
        if self._dyn:
            from libgrape_lite_tpu.dyn.ingest import overlay_state_entries

            eph_entries.update(
                overlay_state_entries(frag, "ie", None, "dyn_ie_")
            )
            if frag.directed:
                eph_entries.update(
                    overlay_state_entries(frag, "oe", None, "dyn_oe_")
                )
        else:
            self._mx_ie = resolve_mirror_plan(frag, "ie")
        if self._mx_ie is not None:
            eph_entries.update(self._mx_ie.state_entries("mx_ie_"))
            if frag.directed:
                self._mx_oe = resolve_mirror_plan(frag, "oe")
                if self._mx_oe is not None:
                    eph_entries.update(self._mx_oe.state_entries("mx_oe_"))
        self._mx_uid = self._mx_ie.uid if self._mx_ie is not None else -1
        if eph_entries:
            state.update(eph_entries)
            self.ephemeral_keys = frozenset(eph_entries)
        return state

    def peval(self, ctx: StepContext, frag, state):
        return state, jnp.int32(1)

    def _pull(self, ctx, frag, comp, csr, state=None,
              mx=None, mx_prefix="mx_ie_", dyn_prefix=None):
        big = jnp.int32(np.iinfo(np.int32).max)
        if mx is not None:
            full = ctx.exchange_mirrors(comp, state[mx_prefix + "send"])
            nbr = state[mx_prefix + "nbr"]
        else:
            full = ctx.gather_state(comp)
            nbr = csr.edge_nbr
        cand = pull_gather(full, nbr, csr.edge_mask, big)
        red = self.segment_reduce(cand, csr.edge_src, frag.vp, "min",
                                  row_ptr=csr.indptr)
        if dyn_prefix is not None and dyn_prefix + "nbr" in state:
            # staged delta edges (dyn/): extra label candidates merged
            # at the fold; `full` is pid-addressed in overlay mode
            # (init_state disables mirror compaction)
            dcand = pull_gather(
                full, state[dyn_prefix + "nbr"],
                state[dyn_prefix + "mask"], big,
            )
            red = self.dyn_min_fold(red, state, frag.vp, dyn_prefix,
                                    dcand)
        return red

    def _post_pull(self, ctx, frag, new):
        """Hook between the neighbor pull and the change count —
        WCCOpt inserts pointer jumping here."""
        return new

    def inceval(self, ctx: StepContext, frag, state):
        comp = state["comp"]
        new = jnp.minimum(
            comp,
            self._pull(ctx, frag, comp, frag.ie, state,
                       self._mx_ie, "mx_ie_", dyn_prefix="dyn_ie_"),
        )
        if frag.directed:
            new = jnp.minimum(
                new,
                self._pull(ctx, frag, new, frag.oe, state,
                           self._mx_oe, "mx_oe_", dyn_prefix="dyn_oe_"),
            )
        with jax.named_scope("grape.app.update"):
            new = self._post_pull(ctx, frag, new)
            changed = jnp.logical_and(new < comp, frag.inner_mask)
            active = ctx.sum(changed.sum().astype(jnp.int32))
        return {"comp": new}, active

    def inc_value_map(self, key, values, old_frag, new_frag):
        """Component labels are PIDS, so a repack (which renumbers the
        pid space) must re-address the label VALUES, not just migrate
        rows: old representative pid -> its oid -> its new pid.  A
        representative absent from the new map (only possible for
        non-additive deltas, which never reach the seeded path) falls
        back to the sentinel — no information, the fresh init wins."""
        if old_frag is new_frag or key != "comp":
            return values
        sent = np.iinfo(np.int32).max
        flat = np.asarray(values).reshape(-1)
        valid = flat != sent
        if not valid.any():
            return values
        reps = np.unique(flat[valid])
        rep_oids = old_frag.pid_to_oid(reps)
        new_reps = new_frag.oid_to_pid(np.asarray(rep_oids))
        new_reps = np.where(new_reps < 0, sent, new_reps).astype(
            values.dtype
        )
        idx = np.searchsorted(reps, flat[valid])
        out = flat.copy()
        out[valid] = new_reps[idx]
        return out.reshape(np.asarray(values).shape)

    def invariants(self, frag, state):
        # min-gid propagation: labels are pids (or the pad sentinel)
        # and only ever shrink toward the component representative
        from libgrape_lite_tpu.guard.invariants import (
            in_range, monotone_non_increasing,
        )

        return [
            in_range("comp", lo=0, hi=np.iinfo(np.int32).max),
            monotone_non_increasing("comp"),
        ]

    def finalize(self, frag, state):
        comp = np.asarray(state["comp"]).astype(np.int64)
        # canonicalise: component id -> oid of representative pid
        # (oids may be str objects for --string_id graphs)
        flat = comp.reshape(-1)
        reps = np.unique(flat[flat != np.iinfo(np.int32).max])
        rep_oids = frag.pid_to_oid(reps)
        lut = {int(r): o for r, o in zip(reps, np.asarray(rep_oids).tolist())}
        otype = object if frag.is_string_keyed() else np.int64
        return np.vectorize(lambda c: lut.get(int(c), -1), otypes=[otype])(comp)
