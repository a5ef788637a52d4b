"""Mirror-compressed state exchange: sync outer vertices only.

Re-design of the reference's batch-shuffle mirror sync
(`grape/parallel/batch_shuffle_message_manager.h:237-264`, mirror lists
from `grape/fragment/edgecut_fragment_base.h:569-602`): instead of
all_gathering the FULL per-vertex state vector — O(fnum*vp) HBM per
device and O(N) ICI bytes per round regardless of cut quality — each
shard sends every neighbor shard exactly the state rows that shard's
edges reference (its outer-vertex mirrors).

TPU formulation (static shapes, one collective):

  host/prepare time: per (receiver f, sender g) the request list
  req[f][g] = sorted unique pids of shard g referenced by f's edges.
  M = max |req| padded to the lane width; the send table for shard g
  is `send_idx[g]` [fnum, M] (rows ordered by receiver), and every
  edge column is remapped into the COMPACT index space
  [vp local | g0 mirrors | g1 mirrors | ...] of length vp + fnum*M.

  per round (inside shard_map): one gather of x_local by send_idx ->
  [fnum, M] (`ops/segment.table_gather`: the VMEM gather kernel reads
  the table as its [fnum*M] stream where the shard's state is one it
  takes, XLA's x_local[send_idx] elsewhere; M is a whole number of
  128s for that), one `all_to_all`, one concat -> x_compact.  ICI
  bytes drop from fnum*vp to fnum*M per device per round; state never
  materialises at O(fnum*vp).
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field

import numpy as np

_UID = itertools.count(1)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def exchange_bytes_ledger(fnum: int, vp: int, m: int | None = None,
                          itemsize: int = 4) -> dict:
    """THE per-round exchange-bytes model, in one place.

    `resolve_mirror_plan`'s auto-mode engagement gate, `MirrorPlan`'s
    byte properties and the 1-D side of the partition planner
    (fragment/partition.py) read this function instead of keeping
    private copies of "exchange bytes" that could drift apart.

    Returns {"gather": per-device ICI bytes of the full-state
    all_gather, "mirror": bytes of the mirror all_to_all (None when no
    mirror plan exists/fits)}."""
    return {
        "gather": fnum * vp * itemsize,
        "mirror": None if m is None else fnum * m * itemsize,
    }


def vc2d_exchange_bytes(k: int, vc: int, itemsize: int = 4,
                        pulls: int = 1) -> int:
    """Per-round per-device ICI bytes of the 2-D vertex-cut round
    (fragment/partition.py's side of THE shared exchange model — the
    1-D side is `exchange_bytes_ledger`).  Per pull: one ring psum of
    the [vc] partials along k row peers (2*(k-1)/k * vc payload) plus
    one transpose ppermute ((1 - 1/k) * vc average — diagonal devices
    self-map).  The asymptotic point of SparseP's 2-D argument: this
    is O(N/k) per device where the 1-D gather is O(N)."""
    if k <= 1:
        return 0
    per_pull = (2 * (k - 1) / k + (1 - 1 / k)) * vc * itemsize
    return int(round(pulls * per_pull))


@dataclass
class MirrorPlan:
    """Static routing for the mirror exchange of one fragment+direction."""

    fnum: int
    vp: int
    m: int                     # mirror slots per (sender, receiver) pair
    n_compact: int             # vp + fnum * m
    send_idx: np.ndarray       # [fnum(sender), fnum(receiver), m] int32 lids
    nbr_compact: np.ndarray    # [fnum, Ep] int32 compact edge columns
    uid: int = field(default_factory=lambda: next(_UID))

    @property
    def bytes_all_gather(self) -> int:
        """Per-device ICI bytes per round of the full-state all_gather
        this plan replaces (f32 payload; shared ledger —
        exchange_bytes_ledger)."""
        return exchange_bytes_ledger(self.fnum, self.vp, self.m)["gather"]

    @property
    def bytes_mirror(self) -> int:
        """Per-device ICI bytes per round of the mirror all_to_all."""
        return exchange_bytes_ledger(self.fnum, self.vp, self.m)["mirror"]

    def state_entries(self, prefix: str) -> dict:
        """Ephemeral state leaves ([fnum, ...], sharded on dim 0)."""
        return {
            prefix + "send": self.send_idx,
            prefix + "nbr": self.nbr_compact,
        }


_FRAG_MIRROR_CACHE = None

# auto-mode engagement gate: mirror must at least halve the per-round
# ICI bytes AND the all_gather it replaces must be big enough for bytes
# (not collective latency) to dominate.  Below ~1 MiB of gathered state
# an all_gather is latency-bound and the extra gather + all_to_all hop
# of the mirror path buys nothing (decision recorded in
# docs/PERF_NOTES.md; revisit with a measured TPU crossover).
_AUTO_RATIO = 0.5
_AUTO_MIN_BYTES = 1 << 20


def resolve_mirror_plan(frag, direction: str = "ie"):
    """Resolve the exchange mode for an app's pull (the single entry
    point models call).  `GRAPE_EXCHANGE`:

      * "mirror" — always exchange mirrors (fnum > 1),
      * "gather" / "off" — always all_gather,
      * unset / "auto" — engage mirrors only when the static bytes
        model shows a clear ICI win (see _AUTO_RATIO/_AUTO_MIN_BYTES).

    Returns a MirrorPlan or None (= use gather_state)."""
    import os

    mode = os.environ.get("GRAPE_EXCHANGE", "auto") or "auto"
    if mode not in ("mirror", "gather", "off", "auto"):
        # an unrecognized value must not silently engage mirrors
        from libgrape_lite_tpu.utils import logging as glog

        glog.log_info(
            f"GRAPE_EXCHANGE={mode!r} is not one of "
            "mirror|gather|off|auto; using gather"
        )
        return None
    if mode in ("gather", "off") or frag.fnum == 1:
        return None
    gather_bytes = exchange_bytes_ledger(frag.fnum, frag.vp)["gather"]
    if mode != "mirror" and gather_bytes <= _AUTO_MIN_BYTES:
        return None  # too small for bytes to matter; skip the planner
    plan = build_mirror_plan(frag, direction)
    if plan is None or mode == "mirror":
        return plan
    if (
        plan.bytes_all_gather > _AUTO_MIN_BYTES
        and plan.bytes_mirror <= _AUTO_RATIO * plan.bytes_all_gather
    ):
        return plan
    return None


def build_mirror_plan(frag, direction: str = "ie") -> MirrorPlan | None:
    """Build (and cache per fragment) the mirror plan for `frag`'s
    pull over `direction` ("ie" | "oe").  Returns None for fnum == 1
    (nothing to exchange — apps use local state directly)."""
    global _FRAG_MIRROR_CACHE
    import weakref

    if frag.fnum == 1:
        return None
    if _FRAG_MIRROR_CACHE is None:
        _FRAG_MIRROR_CACHE = weakref.WeakKeyDictionary()
    per_frag = _FRAG_MIRROR_CACHE.setdefault(frag, {})
    if direction in per_frag:
        return per_frag[direction]
    from libgrape_lite_tpu import obs

    # the miss: once per fragment and direction, a set-up phase
    with obs.tracer().span("derived.mirror_plan", fnum=frag.fnum,
                           direction=direction) as sp:
        plan = _plan_mirrors(frag, direction)
        sp.set(m=plan.m)
    per_frag[direction] = plan
    return plan


def _plan_mirrors(frag, direction: str) -> MirrorPlan:
    """The host planner behind `build_mirror_plan`: request lists per
    (receiver, sender), the send indices and the compact columns."""
    fnum, vp = frag.fnum, frag.vp
    csrs = frag.host_ie if direction == "ie" else frag.host_oe

    # per (receiver f, sender g) sorted unique request lists
    reqs: list[list[np.ndarray]] = []
    m = 1
    for f in range(fnum):
        h = csrs[f]
        nbr = h.edge_nbr[h.edge_mask].astype(np.int64)
        row = []
        g_of = nbr // vp
        for g in range(fnum):
            if g == f:
                row.append(np.zeros(0, np.int64))
                continue
            r = np.unique(nbr[g_of == g])
            row.append(r)
            m = max(m, len(r))
        reqs.append(row)
    m = _round_up(m, 128)

    send_idx = np.zeros((fnum, fnum, m), dtype=np.int32)
    for g in range(fnum):
        for f in range(fnum):
            if f == g:
                continue
            r = reqs[f][g]
            send_idx[g, f, : len(r)] = (r % vp).astype(np.int32)

    ep = csrs[0].edge_nbr.shape[0]
    nbr_compact = np.zeros((fnum, ep), dtype=np.int32)
    for f in range(fnum):
        h = csrs[f]
        nbr = h.edge_nbr.astype(np.int64)
        g_of = nbr // vp
        out = np.where(g_of == f, nbr % vp, 0).astype(np.int64)
        for g in range(fnum):
            if g == f:
                continue
            sel = g_of == g
            if not sel.any():
                continue
            pos = np.searchsorted(reqs[f][g], nbr[sel])
            out[sel] = vp + g * m + pos
        nbr_compact[f] = np.where(h.edge_mask, out, 0).astype(np.int32)

    return MirrorPlan(
        fnum=fnum, vp=vp, m=m, n_compact=vp + fnum * m,
        send_idx=send_idx, nbr_compact=nbr_compact,
    )
