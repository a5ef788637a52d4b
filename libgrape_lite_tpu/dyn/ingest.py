"""Live ingest: the delta overlay side-path + the DynGraph runtime.

`DynGraph` pairs a frozen packed fragment with a `DeltaBuffer` and
decides, at every apply boundary, between two representations of the
staged updates:

  * **overlay** — additive-only deltas between known vertices
    materialise as dense [fnum, capacity] side arrays
    (`DeltaOverlay`), attached to the fragment as `frag.dyn_overlay`.
    Overlay-contracted apps (SSSP/BFS/WCC — `AppBase.
    dyn_overlay_support`) ship them as ephemeral state and fold the
    extra edges into their pull reduction with one gather +
    `segment_min` per round, merged at the fold — `min` is
    associative and exact, so the query result is byte-identical to a
    cold run on the rebuilt graph while the plans, mirror
    tables, and compiled runners stay untouched (fixed shapes: the
    second query after an ingest is a cache hit, pinned by
    tests/test_dyn.py).
  * **repack** — everything else (ratio past the policy threshold,
    non-additive ops, unknown endpoints, overlay slot overflow) folds
    the buffer into a rebuilt CSR (dyn/repack.py).

Apply points are superstep boundaries by construction: the host pumps
queries and ingests between dispatches, so a delta never lands inside
a running while_loop — ft checkpoint cuts and guard digest semantics
carry over unchanged (a mid-query mutation goes through the
MutationContext path instead, which resets the watchdog history at the
boundary; see guard/monitor.GuardMonitor.on_mutation).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from libgrape_lite_tpu.dyn.delta import (
    DeltaBuffer,
    DeltaOverflowError,
    DeltaSummary,
)
from libgrape_lite_tpu.dyn.repack import RepackPolicy, repack_fragment
from libgrape_lite_tpu.utils import logging as glog


class _OverlaySide:
    """One pull direction's dense side arrays ([fnum, cap] each)."""

    def __init__(self, src, nbr, w, mask):
        self.src = src    # i32 local row (the vertex being relaxed); pad = vp
        self.nbr = nbr    # i32 pid of the contributing neighbor; pad = 0
        self.w = w        # f64 edge weight; pad = 0
        self.mask = mask  # bool


class DeltaOverlay:
    """Dense scatter/gather side-path for staged ADD edges.

    Rows are grouped by owner fragment and sorted by local row id, so
    the fold's `segment_reduce` keeps its sorted-segment lowering; pad
    slots route to the vp overflow row (the library-wide padding
    convention) with mask False."""

    def __init__(self, fnum: int, vp: int, capacity: int,
                 ie: _OverlaySide, oe: _OverlaySide, count: int):
        self.fnum = fnum
        self.vp = vp
        self.capacity = capacity
        self.ie = ie
        self.oe = oe
        self.count = count  # staged edges represented (0 = inert)

    @classmethod
    def empty(cls, frag, capacity: int) -> "DeltaOverlay":
        side = cls._blank(frag.fnum, frag.vp, capacity)
        return cls(frag.fnum, frag.vp, capacity, side, side, 0)

    @staticmethod
    def _blank(fnum: int, vp: int, cap: int) -> _OverlaySide:
        return _OverlaySide(
            src=np.full((fnum, cap), vp, dtype=np.int32),
            nbr=np.zeros((fnum, cap), dtype=np.int32),
            w=np.zeros((fnum, cap), dtype=np.float64),
            mask=np.zeros((fnum, cap), dtype=bool),
        )

    @classmethod
    def build(cls, frag, adds: List[Tuple], capacity: int):
        """(overlay, None) or (None, reason) when the buffer cannot
        ride the side-path and must repack instead."""
        if not adds:
            return cls.empty(frag, capacity), None
        src_oid = np.asarray([a[0] for a in adds])
        dst_oid = np.asarray([a[1] for a in adds])
        w = np.asarray([a[2] for a in adds], dtype=np.float64)
        sp = frag.oid_to_pid(src_oid)
        dp = frag.oid_to_pid(dst_oid)
        if (sp < 0).any() or (dp < 0).any():
            return None, "edge endpoint(s) outside the vertex map"

        # pull-mode orientations: the ie fold relaxes the DST row from
        # the SRC neighbor; undirected graphs symmetrise (both
        # orientations, mirroring the CSR build), and their oe aliases
        # ie — the same multiset either way
        if frag.directed:
            ie_rows, ie_nbr, ie_w = dp, sp, w
            oe_rows, oe_nbr, oe_w = sp, dp, w
        else:
            ie_rows = np.concatenate([dp, sp])
            ie_nbr = np.concatenate([sp, dp])
            ie_w = np.concatenate([w, w])
            oe_rows, oe_nbr, oe_w = ie_rows, ie_nbr, ie_w

        def fill(rows, nbr, ww):
            side = cls._blank(frag.fnum, frag.vp, capacity)
            fid = rows // frag.vp
            lid = rows % frag.vp
            for f in range(frag.fnum):
                m = fid == f
                n = int(m.sum())
                if n > capacity:
                    return None
                order = np.argsort(lid[m], kind="stable")
                side.src[f, :n] = lid[m][order]
                side.nbr[f, :n] = nbr[m][order]
                side.w[f, :n] = ww[m][order]
                side.mask[f, :n] = True
            return side

        ie = fill(ie_rows, ie_nbr, ie_w)
        if ie is None:
            return None, (
                f"overlay capacity ({capacity} slots/fragment) exceeded"
            )
        if frag.directed:
            oe = fill(oe_rows, oe_nbr, oe_w)
            if oe is None:
                return None, (
                    f"overlay capacity ({capacity} slots/fragment) "
                    "exceeded"
                )
        else:
            oe = ie
        return cls(frag.fnum, frag.vp, capacity, ie, oe, len(adds)), None

    def entries(self, direction: str, weight_dtype=None,
                prefix: Optional[str] = None) -> Dict[str, np.ndarray]:
        """Ephemeral state entries for one pull direction.  Keys are
        `dyn_<dir>_{src,nbr,mask[,w]}`; the weight column is included
        only when `weight_dtype` is given (BFS/WCC are unweighted
        folds).  Shapes are [fnum, capacity] — fixed per DynGraph, so
        ingest never perturbs the compiled state structure."""
        side = self.ie if direction == "ie" else self.oe
        prefix = prefix if prefix is not None else f"dyn_{direction}_"
        out = {
            prefix + "src": side.src,
            prefix + "nbr": side.nbr,
            prefix + "mask": side.mask,
        }
        if weight_dtype is not None:
            out[prefix + "w"] = side.w.astype(weight_dtype)
        return out


class DynGraph:
    """A packed fragment + its delta buffer + the apply policy — the
    dynamic-graph runtime a ServeSession (or a bare Worker test) drives.

    Typical use::

        dg = DynGraph(frag)                     # frag built retain_edge_list=True
        dg.ingest([("a", 3, 9, 1.5)])           # stage + apply at the boundary
        Worker(SSSP(), dg.fragment).query(source=0)   # sees the delta

    The overlay is attached to the fragment from construction on (an
    empty, fully-masked one), so overlay-contracted apps compile ONE
    state structure that stays valid across every ingest until a
    repack — the zero-recompile property ServeSession.ingest pins."""

    def __init__(self, fragment, policy: RepackPolicy | None = None):
        self.policy = policy or RepackPolicy.from_env()
        self.fragment = fragment
        self.buffer = DeltaBuffer(capacity=self.policy.capacity)
        self.stats = {
            "ingested": 0, "overlay_applies": 0, "repacks": 0,
            "folded_ops": 0,
        }
        # summary of the ops the last apply() acted on — a repack
        # CLEARS the buffer, so `summary()` alone would afterwards
        # describe an empty (vacuously additive) delta; incremental
        # seeding must use the snapshot that still names the folded
        # ops (rides in every report as "delta", kept here too)
        self.last_applied: Optional[DeltaSummary] = None
        self._attach(DeltaOverlay.empty(fragment, self.policy.capacity))

    def _attach(self, overlay: DeltaOverlay) -> None:
        self.fragment.dyn_overlay = overlay

    @property
    def overlay_count(self) -> int:
        ov = getattr(self.fragment, "dyn_overlay", None)
        return 0 if ov is None else ov.count

    def stage(self, ops) -> int:
        """Stage ops, folding at capacity: when a chunk would overflow
        the bounded buffer, the pending ops repack into the CSR (a
        counted fold) and staging continues — a delta stream longer
        than the buffer must degrade to amortized repacks, not raise
        DeltaOverflowError out of a live serve loop.  Batches larger
        than the capacity itself are split into capacity-sized chunks
        with a fold between each."""
        ops = list(ops)
        total = 0
        cap = self.policy.capacity
        for lo in range(0, len(ops), cap):
            chunk = ops[lo:lo + cap]
            try:
                total += self.buffer.stage(chunk)
            except DeltaOverflowError:
                # buffer.stage is atomic, so nothing half-staged:
                # fold the pending ops, then the chunk (<= capacity)
                # fits the emptied buffer
                self.apply(
                    force_repack=True,
                    reason="delta buffer at capacity",
                )
                total += self.buffer.stage(chunk)
        self.stats["ingested"] += total
        return total

    def ingest(self, ops, *, force_repack: bool = False) -> dict:
        """Stage `ops` and apply at this (between-dispatches) boundary."""
        staged = self.stage(ops)
        report = self.apply(force_repack=force_repack)
        report["staged"] = staged
        return report

    def summary(self) -> DeltaSummary:
        return self.buffer.summary()

    def fold_now(self, reason: str = "forced") -> dict:
        """Unconditional repack of the pending buffer (e.g. before a
        query by an app with no overlay contract)."""
        return self.apply(force_repack=True, reason=reason)

    def apply(self, *, force_repack: bool = False,
              reason: str = "") -> dict:
        """Apply the staged buffer at a superstep/dispatch boundary.

        Decision ladder: forced -> policy ratio -> overlay build
        feasibility (non-additive ops, unknown endpoints, slot
        overflow all fall through to repack).  Returns a report dict
        {mode, pending, delta_ratio, reason, repacked?}."""
        ratio = self.buffer.delta_ratio(self.fragment.total_edges_num)
        delta = self.buffer.summary()
        self.last_applied = delta
        why = reason
        repack = force_repack
        if not repack and self.policy.should_repack(
            self.buffer, self.fragment
        ):
            repack = True
            why = (
                f"delta ratio {ratio:.4f} > threshold "
                f"{self.policy.threshold:g}"
            )
        overlay = None
        if not repack:
            if not self.buffer.additive_only:
                repack = True
                why = "non-additive ops cannot ride the min-fold overlay"
            else:
                overlay, build_reason = DeltaOverlay.build(
                    self.fragment, self.buffer.add_edges,
                    self.policy.capacity,
                )
                if overlay is None:
                    repack = True
                    why = build_reason

        if repack:
            rep = self._repack(why or "forced")
            rep["delta"] = delta
            return rep
        self._attach(overlay)
        self.stats["overlay_applies"] += 1
        glog.vlog(
            1, "dyn: overlay apply — %d staged edge(s), ratio %.4f "
            "(threshold %g)", self.buffer.n_edge_ops, ratio,
            self.policy.threshold,
        )
        return {
            "mode": "overlay",
            "pending": self.buffer.n_ops,
            "delta_ratio": ratio,
            "delta": delta,
            "reason": "below repack threshold",
        }

    def _repack(self, why: str) -> dict:
        n = self.buffer.n_ops
        folded = repack_fragment(self.fragment, self.buffer)
        self.buffer.clear()
        self.fragment = folded
        self._attach(
            DeltaOverlay.empty(folded, self.policy.capacity)
        )
        self.stats["repacks"] += 1
        self.stats["folded_ops"] += n
        glog.log_info(
            f"dyn: repack — folded {n} staged op(s) into a rebuilt "
            f"CSR ({why}); plan cache re-keys on next init"
        )
        return {
            "mode": "repack",
            "pending": 0,
            "folded": n,
            "delta_ratio": 0.0,
            "reason": why,
        }


def broadcast_ingest(targets, ops, *, force_repack: bool = False) -> list:
    """Apply ONE delta chunk to every target (DynGraphs or dyn-enabled
    ServeSessions) in order — the fleet router's replica broadcast
    (fleet/router.py wraps this behind its graph-version fence).  The
    ops list is materialised once so a generator cannot feed replica
    0 a different stream than replica 1; per-target reports return in
    target order."""
    ops = list(ops)
    return [
        t.ingest(ops, force_repack=force_repack) for t in targets
    ]


def overlay_state_entries(frag, direction: str, weight_dtype=None,
                          prefix: Optional[str] = None) -> Dict:
    """Helper for app init_state: the fragment's overlay entries, or {}
    when no overlay is attached (static graphs compile exactly the
    state they always have)."""
    ov = getattr(frag, "dyn_overlay", None)
    if ov is None:
        return {}
    return ov.entries(direction, weight_dtype, prefix)
