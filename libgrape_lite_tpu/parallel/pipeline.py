"""Superstep software pipelining — overlap the halo exchange with
interior compute (ROADMAP item 3, r9).

The fused superstep runs compute -> exchange strictly serially, so
every round the VPU idles for the full `all_gather`/`all_to_all` (obs/
measures it as the dispatch/device split; SparseP frames the same
compute/transfer balance).  This module restructures the round as a
double-buffered software pipeline over the boundary/interior vertex
split of `fragment/edgecut.boundary_split`:

  round k:   compute BOUNDARY slice   (reads the buffered exchange xbuf)
             kick off the exchange    (round k+1's inputs — only the
                                       boundary rows just computed)
             compute INTERIOR slice   (overlaps the in-flight collective)
             join at the fold         (per-row select on the boundary mask)

Byte-identity argument (the pinned contract, tests/test_pipeline.py):

  * every REMOTE read of fragment g's state touches only g's boundary
    rows (that is the definition of boundary), and the kickoff payload
    carries exactly those rows' NEW values;
  * every LOCAL read goes through `splice`, which overlays the live
    local block over the buffered table — bitwise the serial value;
  * the boundary and interior slices partition the output rows, and
    each row's fold consumes exactly its own edges in their original
    CSR order — so the joined state equals the serial state bit for
    bit, inductively over rounds.

The exchange buffer `xbuf` is an INTERNAL while-loop carry: it is
created after PEval and dropped at loop exit, and it is a pure
function of the query carry (the exchange of the current state).  The
observable cut therefore never moves: guard digests, checkpoint
snapshots and watchdog residuals all observe the post-join carry —
the same consistent cut as the serial loop (docs/PIPELINE.md).

Engagement (`GRAPE_PIPELINE`):

  * unset / "0" / ""  — off: the serial loop body compiles bit-for-bit
    unchanged (lowered-HLO pinned);
  * "1" / "auto"      — engage when the modeled per-round exchange
    bytes (`mirror.exchange_bytes_ledger` — the SAME ledger the
    mirror auto mode reads) clear GRAPE_PIPELINE_MIN_BYTES (default
    1 MiB): latency-bound exchanges lose to the extra dispatch, the
    `_AUTO_MIN_BYTES` discipline;
  * "force"           — engage whenever structurally possible (tests,
    small-graph A/Bs).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from libgrape_lite_tpu.parallel.mirror import (
    exchange_bytes_ledger,
    pipelined_round_s,
)

# auto-mode engagement floor, same discipline (and the same shared
# byte ledger) as mirror._AUTO_MIN_BYTES: below ~1 MiB the exchange is
# collective-latency-bound and the split's extra dispatch loses
_MIN_BYTES_DEFAULT = 1 << 20

# ---- the worker pipeline contract (grape-lint R6) -------------------------
#
# Inside the pipelined window — between the exchange kickoff and the
# join — the ONLY reads of the query carry (or of ephemeral streams
# standing in for it) permitted by grape-lint rule R6 are the names
# below.  Entries ending in "*" are prefixes.  Every name here is an
# AUDITED read: it is safe precisely because the kickoff writes into a
# fresh double buffer and never aliases the live carry — the aliasing
# bug class this contract fossilizes.  Adding a read to the window
# means auditing it and naming it here, in review.
PIPELINE_WINDOW_READS = frozenset({
    # live carry leaves the interior slice folds against
    "dist", "depth", "comp",
    # CDLP's carry label plane (the join selector of the mode fold)
    # and its replicated rank LUT (read by both part folds)
    "labels", "lut",
    # the boundary mask (the join selector) and the interior streams
    "pl_bmask", "pl_i_src", "pl_i_nbr", "pl_i_val", "pl_i_w",
    # the second-direction streams of the directed double-pull round
    # (WCC oe leg) — both parts fold inside the window, which opens at
    # the FIRST kickoff of the round
    "pl2_*",
})

# Callees AUDITED to receive the whole carry dict inside the window.
# R6 cannot see into another module's function body, so passing the
# full `state` to an un-named callee after the kickoff is flagged as a
# whole-carry escape; each name here was audited by hand:
#   kickoff       PipelinePlan.kickoff — reads only its send_key leaf
#                 (the mirror send table, a static host stream), never
#                 a live carry value; the directed double-pull round
#                 issues a SECOND kickoff inside the first's window
#   splice        PipelinePlan.splice — reads nothing from the carry
#                 dict at all (mirror mode concatenates its explicit
#                 args; gather mode reads only ctx.fid())
PIPELINE_WINDOW_CALLEES = frozenset({
    "kickoff", "splice",
})

# resolve-path registry: the last pipeline decision + split stats, so
# plan_stats()/trace_report can surface boundary-set sizes without
# holding fragment references.  Federated as "pipeline"
# (obs/federation.py); mutation sites unchanged.
from libgrape_lite_tpu.obs.federation import FederatedStats as _FedStats

PIPELINE_STATS = _FedStats("pipeline", {
    "resolved": 0,        # plans built (engaged)
    "declined": 0,        # structurally eligible but below threshold/off
    "last_decision": None,
    "last_stats": None,
})


def pipeline_mode() -> str:
    """off | auto | force, from GRAPE_PIPELINE (default off: the
    serial superstep stays the compiled program until an A/B on real
    hardware flips the default — docs/PIPELINE.md)."""
    v = os.environ.get("GRAPE_PIPELINE", "") or "0"
    if v in ("0", "", "off"):
        return "off"
    if v == "force":
        return "force"
    return "auto"  # "1", "auto", anything else truthy


def pipeline_min_bytes() -> int:
    v = os.environ.get("GRAPE_PIPELINE_MIN_BYTES", "")
    return int(v) if v else _MIN_BYTES_DEFAULT


# Modeled rates for the overlap term come from the shared RateProfile
# (ops/calibration.py) — the module-level names stay as the pinned
# default's values for importers (fragment/partition.py, the recount
# in scripts/pack_cost_model.py) but live pricing reads the ACTIVE
# profile, so a fitted profile re-prices the engage decision.
from libgrape_lite_tpu.ops.calibration import (
    active_profile as _active_profile,
    default_profile as _default_profile,
)

VPU_LANES_PER_CYCLE = _default_profile().vpu_lanes_per_cycle
CLOCK_HZ = _default_profile().clock_hz
ICI_BPS = _default_profile().ici_bps
DEFAULT_OPS_PER_EDGE = 30.0     # op COUNT per edge (XLA gather+segment
#                                 fold) — a counting convention, not a
#                                 rate; stays literal


def pipeline_min_hidden_us() -> float:
    """Priced engage floor (µs): in auto mode the overlap model must
    hide at least this much exchange per round or the pipeline
    declines.  Default 0 — the shipped byte threshold alone decides,
    bit-for-bit the pre-calibration behavior."""
    v = os.environ.get("GRAPE_PIPELINE_MIN_HIDDEN_US", "")
    return float(v) if v else 0.0


def overlap_model(boundary_edges: int, interior_edges: int,
                  exchange_bytes: int,
                  ops_per_edge: float | None = None,
                  profile=None) -> dict:
    """The exchange-overlap term of the op-budget ledger:

        t_serial    = compute_b + compute_i + exchange
        t_pipelined = max(compute_i, exchange) + compute_b

    (`mirror.pipelined_round_s` — max not sum).  Returns modeled round
    times plus `hidden_frac`, the fraction of the exchange hidden
    under interior compute (min(compute_i, exchange) / exchange) —
    the number the bench `pipeline` block and the obs query span
    report, and trace_report flags when it lands under 10%."""
    p = profile or _active_profile()
    ope = DEFAULT_OPS_PER_EDGE if ops_per_edge is None else ops_per_edge
    rate = p.vpu_lanes_per_cycle * p.clock_hz
    t_b = boundary_edges * ope / rate
    t_i = interior_edges * ope / rate
    t_x = exchange_bytes / p.ici_bps
    t_serial = t_b + t_i + t_x
    t_pipe = pipelined_round_s(t_i, t_x, t_b)
    hidden = min(t_i, t_x) / t_x if t_x > 0 else 0.0
    return {
        "t_serial_s": t_serial,
        "t_pipelined_s": t_pipe,
        "hidden_frac": round(hidden, 4),
        "round_speedup": round(t_serial / t_pipe, 4) if t_pipe > 0 else 1.0,
        "exchange_s": t_x,
        "compute_boundary_s": t_b,
        "compute_interior_s": t_i,
    }


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclass
class PipelinePlan:
    """One resolved boundary/interior pipeline for an app's pull.

    Host side: the split edge streams ride as ephemeral state leaves —
    `host_entries` merges into the app's init state exactly like
    mirror tables (closure capture would trip grape-lint R1 and
    replicate under shard_map).  Traced side:
    `exchange`/`kickoff`/`splice` are the three collective touchpoints
    of the pipelined round (see module docstring)."""

    mode: str                  # "mirror" | "gather"
    key: str                   # the exchanged carry leaf ("dist", ...)
    fnum: int
    vp: int
    m: int                     # mirror slots (0 in gather mode)
    send_key: str              # state key of the mirror send table
    prefix: str = "pl_"
    stats: dict = field(default_factory=dict)
    exchange_bytes: int = 0
    decision: dict = field(default_factory=dict)
    host_entries: dict = field(default_factory=dict)
    # second exchange leg of the directed double-pull round (WCC oe):
    # None when single-direction.  leg=2 on exchange/kickoff/splice
    # routes through these instead — same wiring, second direction.
    mode2: Optional[str] = None
    m2: int = 0
    send_key2: str = ""

    @property
    def uid(self) -> str:
        """STABLE content fingerprint of the compiled-trace-relevant
        plan shape — this rides in the app's `trace_key` (as
        `_pipeline_uid`) to keep serial and pipelined compiles in
        separate runner-cache entries.  It must be identical across
        re-resolves of the same plan: a per-resolve counter here made
        every query recompile (trace_key changed each init_state),
        which turned the bench A/B into a compile-time measurement.
        Stream SHAPES (split sizes) already key the runner cache via
        the state struct; this only needs the routing facts the struct
        cannot see."""
        return (
            f"{self.mode}:{self.fnum}:{self.vp}:{self.m}:"
            f"{self.mode2 or '-'}"
        )

    def _leg(self, leg: int):
        if leg == 2:
            if self.mode2 is None:
                raise ValueError("pipeline plan has no second leg")
            return self.mode2, self.send_key2
        return self.mode, self.send_key

    # ---- traced (inside shard_map) ----

    def exchange(self, ctx, x_local, state, leg: int = 1):
        """The halo exchange of `x_local`'s read rows — bitwise the
        payload of the serial round's exchange when the boundary rows
        of `x_local` are current (pad/interior rows are never read
        remotely).  Routed through the SAME StepContext collectives
        the serial round uses (one copy of the exchange wiring); the
        mirror form drops the helper's leading live-local block — the
        buffer must hold only remote rows, `splice` re-attaches the
        LIVE local block at read time.  `leg=2` is the second
        direction of the directed double-pull round."""
        mode, send_key = self._leg(leg)
        if mode == "mirror":
            compact = ctx.exchange_mirrors(
                x_local, state[send_key]
            )
            return compact[self.vp:]
        return ctx.gather_state(x_local)

    def kickoff(self, ctx, x_kick, state, leg: int = 1):
        """Kick off the NEXT pull's exchange from the boundary-merged
        carry (new values at boundary rows, stale elsewhere — the
        stale rows are never read).  Distinct name on purpose: this
        call opens the pipelined window grape-lint R6 audits."""
        return self.exchange(ctx, x_kick, state, leg=leg)

    def splice(self, ctx, x_local, state, xbuf, leg: int = 1):
        """The full pull table for this round: LIVE local rows overlaid
        on the buffered remote rows — local reads are bitwise the
        serial value, remote reads hit only (current) boundary rows."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        mode, _ = self._leg(leg)
        with jax.named_scope("grape.exchange.unpack"):
            if mode == "mirror":
                return jnp.concatenate([x_local, xbuf])
            fid = ctx.fid()
            return lax.dynamic_update_slice(
                xbuf, x_local, (fid * self.vp,)
            )

    # ---- host side ----

    def span_brief(self) -> dict:
        """The obs query-span attachment (and the bench `pipeline`
        block's modeled half)."""
        t = self.stats.get("totals", {})
        model = overlap_model(
            t.get("boundary_edges", 0), t.get("interior_edges", 0),
            self.exchange_bytes,
        )
        return {
            "engaged": True,
            "mode": self.mode,
            "plan_uid": self.uid,
            "exchange_bytes": self.exchange_bytes,
            "modeled_hidden_frac": model["hidden_frac"],
            "hidden_us_per_round": self.hidden_us_per_round(),
            "boundary_vertices": t.get("boundary_vertices", 0),
            "interior_vertices": t.get("interior_vertices", 0),
            "boundary_edges": t.get("boundary_edges", 0),
            "interior_edges": t.get("interior_edges", 0),
        }

    def hidden_us_per_round(self) -> float:
        """Modeled exchange time hidden under interior compute, per
        superstep, in µs: min(compute_interior, exchange).  The obs
        query span records `overlap_hidden_us` = this x rounds, and
        trace_report's overlap column prints it per superstep with a
        drift flag when the plan is armed but hides <10% of the
        exchange."""
        t = self.stats.get("totals", {})
        model = overlap_model(
            t.get("boundary_edges", 0), t.get("interior_edges", 0),
            self.exchange_bytes,
        )
        return round(
            min(model["compute_interior_s"], model["exchange_s"]) * 1e6,
            3,
        )


def _split_streams(frag, bmask: np.ndarray, direction: str, mirror,
                   with_weights: bool, prefix: str) -> dict:
    """Stable row-partitioned edge streams for the XLA fold path.

    Per part (b = boundary rows, i = interior rows) and per fragment:
    `src` (pad -> vp overflow row), `nbr` (compact columns under a
    mirror plan, pids otherwise; pad -> 0), `val` (validity), and `w`
    when weighted — each padded to the per-part max across shards
    (one traced program under shard_map).  Within a part the original
    CSR edge order is preserved, so every row's fold consumes its own
    candidates in the serial order (the byte-identity invariant; for
    float sums this additionally relies on XLA's order-deterministic
    sorted segment reduction, pinned by tests/test_pipeline.py)."""
    fnum, vp = frag.fnum, frag.vp
    csrs = frag.host_ie if direction == "ie" else frag.host_oe
    parts = {"b": [], "i": []}
    for f in range(fnum):
        h = csrs[f]
        mask = h.edge_mask
        src = h.edge_src.astype(np.int64)
        cols = (
            mirror.nbr_compact[f] if mirror is not None else h.edge_nbr
        ).astype(np.int64)
        safe_src = np.minimum(src, vp - 1)
        is_b = np.logical_and(mask, bmask[f][safe_src])
        is_i = np.logical_and(mask, ~bmask[f][safe_src])
        for part, sel in (("b", is_b), ("i", is_i)):
            idx = np.flatnonzero(sel)
            parts[part].append((
                src[idx].astype(np.int32),
                cols[idx].astype(np.int32),
                None if not with_weights else h.edge_w[idx],
            ))
    out = {prefix + "bmask": bmask}
    for part, shards in parts.items():
        cap = _round_up(max([len(s[0]) for s in shards] + [1]), 128)
        src_a = np.full((fnum, cap), vp, dtype=np.int32)
        nbr_a = np.zeros((fnum, cap), dtype=np.int32)
        val_a = np.zeros((fnum, cap), dtype=bool)
        w_a = (
            np.zeros((fnum, cap), dtype=csrs[0].edge_w.dtype)
            if with_weights else None
        )
        for f, (src, nbr, w) in enumerate(shards):
            n = len(src)
            src_a[f, :n] = src
            nbr_a[f, :n] = nbr
            val_a[f, :n] = True
            if w_a is not None:
                w_a[f, :n] = w
        p = f"{prefix}{part}_"
        out[p + "src"] = src_a
        out[p + "nbr"] = nbr_a
        out[p + "val"] = val_a
        if w_a is not None:
            out[p + "w"] = w_a
    return out


def resolve_pipeline(frag, *, app_name: str, key: str,
                     direction: str = "ie", mirror=None,
                     mx_prefix: str = "mx_",
                     with_weights: bool = False,
                     eligible: bool = True, reason: str = "",
                     direction2: str | None = None, mirror2=None,
                     mx2_prefix: str = "mx_oe_"):
    """Resolve the superstep pipeline for one app's pull, or None.

    `mirror` is the app's ALREADY-RESOLVED exchange — the pipelined
    round must use the same exchange mode as the serial one, or
    byte-identity is off the table.  Every fold that pipelines is
    exact under a split (min, CDLP's mode); a float sum is not, so
    PageRank passes `eligible=False` with its reason.  Decline reasons are recorded
    in PIPELINE_STATS["last_decision"] (and vlogged), never silent.

    `direction2` requests the directed DOUBLE-PULL round (WCC on a
    directed graph: an ie pull then an oe pull per superstep).  The
    boundary mask becomes the JOINT split over both directions — a row
    any remote fragment reads through either edge orientation is
    boundary — so each pull's kickoff payload is current at every
    remotely-read row, and the second leg's streams ride under the
    `pl2_` prefix with their own exchange mode (`mirror2`)."""
    from libgrape_lite_tpu.utils import logging as glog

    mode = pipeline_mode()
    prof = _active_profile()
    decision = {"app": app_name, "mode": mode, "engaged": False,
                "profile": prof.label()}

    def declined(why: str, count: bool = True):
        decision["reason"] = why
        PIPELINE_STATS["last_decision"] = decision
        if count:
            PIPELINE_STATS["declined"] += 1
            glog.vlog(1, "pipeline: declined for %s: %s", app_name, why)
        return None

    if mode == "off":
        return declined("GRAPE_PIPELINE off", count=False)
    if not eligible:
        return declined(reason or "app declared ineligible")
    if frag.fnum <= 1:
        return declined("fnum==1: no exchange to overlap")
    ov = getattr(frag, "dyn_overlay", None)
    if ov is not None:
        return declined("dyn overlay attached (pid-addressed reads)")
    xmode = "mirror" if mirror is not None else "gather"
    bytes_ledger = exchange_bytes_ledger(
        frag.fnum, frag.vp, mirror.m if mirror is not None else None
    )
    xbytes = bytes_ledger[xmode] or 0
    xmode2 = None
    if direction2 is not None:
        xmode2 = "mirror" if mirror2 is not None else "gather"
        ledger2 = exchange_bytes_ledger(
            frag.fnum, frag.vp,
            mirror2.m if mirror2 is not None else None,
        )
        xbytes += ledger2[xmode2] or 0
    decision["exchange_bytes"] = xbytes
    decision["min_bytes"] = pipeline_min_bytes()
    if mode == "auto" and xbytes < pipeline_min_bytes():
        return declined(
            f"modeled exchange bytes {xbytes} below threshold "
            f"{pipeline_min_bytes()} (latency-bound; set "
            "GRAPE_PIPELINE_MIN_BYTES or =force to override)"
        )

    from libgrape_lite_tpu.fragment.edgecut import (
        boundary_split, boundary_stats,
    )

    directions = (direction,) if direction2 is None \
        else (direction, direction2)
    bmask = boundary_split(frag, directions)
    stats = boundary_stats(frag, bmask, direction)
    if direction2 is not None:
        # both pulls fold inside the same round: edge totals sum, the
        # vertex split is shared (one joint mask)
        stats2 = boundary_stats(frag, bmask, direction2)
        for part in ("boundary_edges", "interior_edges"):
            stats["totals"][part] = (
                stats["totals"].get(part, 0)
                + stats2["totals"].get(part, 0)
            )

    min_hidden = pipeline_min_hidden_us()
    if mode == "auto" and min_hidden > 0:
        tot = stats["totals"]
        model = overlap_model(
            tot.get("boundary_edges", 0), tot.get("interior_edges", 0),
            xbytes, profile=prof,
        )
        hidden_us = min(model["compute_interior_s"],
                        model["exchange_s"]) * 1e6
        decision["modeled_hidden_us"] = round(hidden_us, 3)
        # grape-lint R12: a modeled claim must carry its trace
        # correlation key even on the declined path (same recipe as
        # PipelinePlan.uid; re-stamped authoritatively on engage)
        decision["plan_uid"] = (
            f"{xmode}:{frag.fnum}:{frag.vp}:"
            f"{mirror.m if mirror is not None else 0}:{xmode2 or '-'}"
        )
        if hidden_us < min_hidden:
            return declined(
                f"modeled hidden exchange {hidden_us:.2f}us under "
                f"profile {prof.label()} is below the "
                f"GRAPE_PIPELINE_MIN_HIDDEN_US={min_hidden:g} floor"
            )

    host_entries = _split_streams(
        frag, bmask, direction, mirror, with_weights, "pl_"
    )
    if direction2 is not None:
        h2 = _split_streams(
            frag, bmask, direction2, mirror2, with_weights, "pl2_"
        )
        h2.pop("pl2_bmask")  # one joint mask, already under pl_
        host_entries.update(h2)

    decision["engaged"] = True
    plan = PipelinePlan(
        mode=xmode, key=key, fnum=frag.fnum, vp=frag.vp,
        m=mirror.m if mirror is not None else 0,
        send_key=mx_prefix + "send",
        stats=stats, exchange_bytes=xbytes, decision=decision,
        host_entries=host_entries,
        mode2=xmode2,
        m2=mirror2.m if mirror2 is not None else 0,
        send_key2=mx2_prefix + "send",
    )
    decision["plan_uid"] = plan.uid  # the truth meter's join key
    PIPELINE_STATS["resolved"] += 1
    PIPELINE_STATS["last_decision"] = decision
    PIPELINE_STATS["last_stats"] = stats
    glog.vlog(
        1, "pipeline: engaged for %s (%s exchange, %d B/round, "
        "%d boundary / %d interior vertices)",
        app_name, xmode, xbytes,
        stats["totals"].get("boundary_vertices", 0),
        stats["totals"].get("interior_vertices", 0),
    )
    return plan


# ---- the 2-D vertex-cut (SUMMA) pipeline ----------------------------------


@dataclass
class VC2DPipelinePlan:
    """The pipelined SUMMA round: a two-phase split of each tile's COO
    edge ring so the row-axis `pmin` of the phase-0 partial overlaps
    the phase-1 tile-local fold (docs/PARTITION2D.md "Overlapped
    round").

      serial:     partial = fold(ALL edge slots); pmin(row); transpose
      pipelined:  p0 = fold(slots [:split]); r0 = pmin(p0)  <- kicked
                  p1 = fold(slots [split:])                 <- overlaps
                  r1 = pmin(p1); relax = min(r0, r1); transpose

    Byte-identity argument: min is associative/commutative and
    idempotent over any regrouping of the same candidate multiset, and
    both folds run the identical segment reduction over disjoint
    static slices of the SAME per-shard edge arrays — min(r0, r1)
    is elementwise equal, bit for bit, to the serial pmin of the
    unsplit fold (ints and IEEE floats alike; no float addition
    regroups).  The phase split is static slicing of the device COO —
    no extra host streams, so `host_entries` is empty and the
    exchange buffer is an inert scalar (the SUMMA round has no
    cross-round halo table to double-buffer).

    The split doubles the COLLECTIVE COUNT (two [vc] pmins instead of
    one) but only the first is hidden; `exchange_bytes` prices the
    hideable leg and the auto gate sees exactly that."""

    k: int
    vc: int
    split: int                  # phase-0 edge-slot count (per shard)
    stats: dict = field(default_factory=dict)
    exchange_bytes: int = 0
    decision: dict = field(default_factory=dict)
    host_entries: dict = field(default_factory=dict)
    mode: str = "vc2d"

    @property
    def uid(self) -> str:
        """Stable trace fingerprint (rides `_pipeline_uid` in the
        app's trace_key, same contract as PipelinePlan.uid)."""
        return f"vc2d:{self.k}:{self.vc}:{self.split}"

    def span_brief(self) -> dict:
        t = self.stats.get("totals", {})
        model = overlap_model(
            t.get("boundary_edges", 0), t.get("interior_edges", 0),
            self.exchange_bytes,
        )
        return {
            "engaged": True,
            "mode": self.mode,
            "plan_uid": self.uid,
            "exchange_bytes": self.exchange_bytes,
            "modeled_hidden_frac": model["hidden_frac"],
            "hidden_us_per_round": self.hidden_us_per_round(),
            "boundary_vertices": t.get("boundary_vertices", 0),
            "interior_vertices": t.get("interior_vertices", 0),
            "boundary_edges": t.get("boundary_edges", 0),
            "interior_edges": t.get("interior_edges", 0),
        }

    def hidden_us_per_round(self) -> float:
        t = self.stats.get("totals", {})
        model = overlap_model(
            t.get("boundary_edges", 0), t.get("interior_edges", 0),
            self.exchange_bytes,
        )
        return round(
            min(model["compute_interior_s"], model["exchange_s"]) * 1e6,
            3,
        )


def resolve_vc2d_pipeline(frag, *, app_name: str,
                          src_pull: bool = False,
                          dtype_bytes: int = 4):
    """Resolve the pipelined SUMMA round for a vc2d app, or None.

    Same engagement ladder as `resolve_pipeline` (GRAPE_PIPELINE
    off/auto/force, the byte and hidden-µs auto floors, declines
    recorded in PIPELINE_STATS — never silent), with the vc2d
    structural gates:

      * `src_pull` (directed WCC's column-axis pull) declines — the
        second pull folds the TRANSPOSED relax of the first, a
        dependent chain with no independent work to overlap;
      * a tile ring too small to split in two 128-multiple phases
        declines (nothing to overlap).

    The decision record always carries the rate-profile label and the
    modeled `hidden_us_per_round` (the bench `vc2d_pipeline` lane
    gates on both being present)."""
    from libgrape_lite_tpu.utils import logging as glog

    mode = pipeline_mode()
    prof = _active_profile()
    decision = {"app": app_name, "mode": mode, "engaged": False,
                "profile": prof.label(), "plan": "vc2d"}

    def declined(why: str, count: bool = True):
        decision["reason"] = why
        PIPELINE_STATS["last_decision"] = decision
        if count:
            PIPELINE_STATS["declined"] += 1
            glog.vlog(1, "pipeline: declined for %s: %s", app_name, why)
        return None

    if mode == "off":
        return declined("GRAPE_PIPELINE off", count=False)
    k = int(frag.k)
    if k <= 1:
        return declined("k==1: the row-axis pmin is a no-op")
    if src_pull:
        return declined(
            "directed src-pull round: the column-axis pull consumes "
            "the transposed row relax — a dependent chain with no "
            "independent fold to overlap"
        )
    _, _, _, m_arr = frag._host_tiles
    ep = int(m_arr.shape[1])
    split = min(_round_up(max(ep // 2, 1), 128), ep)
    if split >= ep:
        return declined(
            f"tile edge ring too small to split ({ep} slots): "
            "nothing to overlap"
        )

    # the hideable collective: ONE row-axis pmin of the [vc] partial
    # per device — ring all-reduce over the k row peers
    vc = int(frag.vc)
    xbytes = int(vc * dtype_bytes * 2 * (k - 1) / k)
    decision["exchange_bytes"] = xbytes
    decision["min_bytes"] = pipeline_min_bytes()

    # real (unpadded) edges per phase, summed over tiles — the phase-0
    # fold is the "boundary" (pre-kick) term of the overlap model, the
    # phase-1 fold the overlapped "interior" term
    e0 = int(m_arr[:, :split].sum())
    e1 = int(m_arr[:, split:].sum())
    stats = {"totals": {
        "boundary_edges": e0, "interior_edges": e1,
        "boundary_vertices": 0, "interior_vertices": 0,
        "phase_split": split, "edge_slots": ep,
    }}
    model = overlap_model(e0, e1, xbytes, profile=prof)
    hidden_us = min(model["compute_interior_s"],
                    model["exchange_s"]) * 1e6
    decision["modeled_hidden_us"] = round(hidden_us, 3)
    # grape-lint R12: the modeled claim carries its trace key even
    # when a later gate declines (same recipe as VC2DPipelinePlan.uid)
    decision["plan_uid"] = f"vc2d:{k}:{vc}:{split}"

    if mode == "auto" and xbytes < pipeline_min_bytes():
        return declined(
            f"modeled pmin bytes {xbytes} below threshold "
            f"{pipeline_min_bytes()} (latency-bound; set "
            "GRAPE_PIPELINE_MIN_BYTES or =force to override)"
        )
    min_hidden = pipeline_min_hidden_us()
    if mode == "auto" and min_hidden > 0 and hidden_us < min_hidden:
        return declined(
            f"modeled hidden pmin {hidden_us:.2f}us under profile "
            f"{prof.label()} is below the "
            f"GRAPE_PIPELINE_MIN_HIDDEN_US={min_hidden:g} floor"
        )

    decision["engaged"] = True
    plan = VC2DPipelinePlan(
        k=k, vc=vc, split=split, stats=stats,
        exchange_bytes=xbytes, decision=decision,
    )
    PIPELINE_STATS["resolved"] += 1
    PIPELINE_STATS["last_decision"] = decision
    PIPELINE_STATS["last_stats"] = stats
    glog.vlog(
        1, "pipeline: engaged vc2d for %s (k=%d, split %d/%d slots, "
        "%d B pmin/round)", app_name, k, split, ep, xbytes,
    )
    return plan
