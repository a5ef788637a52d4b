"""Pack-gather SpMV: sorted segment-sum at vector-unit rate on TPU.

Replaces the XLA gather + segment_sum pull (measured ~8.7 ns/element
EACH on real v5e hardware — docs/PERF_NOTES.md) with a fully static
Pallas pipeline in which every data movement is a lane gather, a
sublane gather, or a static 3-stage shuffle (ops/route3.py):

  per block [SUB, 128] of edge slots (host-planned, static):
    1. GATHER   x values: non-hub edges sit at a slot whose lane is
       the XOR-mixed `_lane_mix(col)` (plain col%128 is skewed on
       Kronecker ids), so ONE sublane dynamic_gather from the
       VMEM-resident, lane-mixed x-table [SUB, 128] (pass p holds
       x[p*SUB*128:(p+1)*SUB*128]) fetches x[col] for the whole block; hub columns (the top-HUB
       most referenced, which would overflow lane capacity) read a
       tiny [HUB/128, 128] register table via lane gathers + selects.
    2. ROUTE    gathered values back to CSR (row-sorted) slot order —
       a static 3-stage shuffle.
    3. SCAN     segmented sums over the flattened block.  Default
       (GRAPE_PACK_SCAN=mxu): MXU prefix sums — a [SUB,128] @
       tri[128,128] triangular matmul per row, a chained per-group
       inter-row tail prefix, and segment restoration through two
       static gathers against host-planned start planes (ps/bk) —
       flat 10 VPU ops/slot with the heavy lifting on the matrix
       unit.  Fallback (=shift, and always for min/max semirings):
       ceil(log2(max_seglen)) span-aware shift-add stages against a
       static segment-start flag stream.  Engagement is per level by
       modeled cost (see _decide_level_scan).
    4. EXTRACT  each row's last-slot scan value (= the row's partial
       sum within the block) into a compact [OUT_SUB, 128] stream —
       another static shuffle.
  fold levels: the per-block partial streams are grouped (<= SUB //
  OUT_SUB streams per group, bounded by output capacity), re-sorted by
  row with a static shuffle, and reduced by the same scan+extract
  kernel — recursively, until one block remains; the final level's
  extraction targets slot == row id, so the result lands as the dense
  [vp] output with no scatter of any kind.

The reference counterpart is the CUDA LB-kernel catalog
(`grape/cuda/parallel/parallel_engine.h:42-1444`) — the machinery that
makes per-edge work run at hardware rate.  On TPU that machinery is
this file: all irregularity is compiled into static routes at plan
time; the per-round dataflow is dense vector work.

Plans are built once per (fragment, dtype) and reused every round;
planning cost is O(E log) numpy (cacheable alongside the fragment
serialization cache).
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from libgrape_lite_tpu.ops.route3 import (
    Route3,
    plan_lane_aligned_rows,
    plan_route,
)

C = 128


def _compose_enabled() -> bool:
    """Route composition (upstream extraction lands directly in the
    downstream fold's sorted layout, collapsing the fold merge route to
    one sublane move) is on by default; GRAPE_PACK_COMPOSE=0 reverts to
    the generic 3-stage fold routes for A/B and debugging."""
    import os

    return os.environ.get("GRAPE_PACK_COMPOSE", "1") not in ("0", "")


def _scan_mode() -> str:
    """Segmented-scan backend: "mxu" (default) restores segment sums
    from MXU triangular-matmul prefix sums; "shift" is the log-stage
    shift-add ladder kept as the A/B fallback (GRAPE_PACK_SCAN=shift).
    Engagement is per LEVEL and only where the modeled VPU cost wins
    (see _decide_level_scan) — shallow-ladder blocks keep the shift
    form even in mxu mode, and min/max semirings always run the ladder
    (a matmul cannot evaluate a tropical prefix)."""
    import os

    mode = os.environ.get("GRAPE_PACK_SCAN", "mxu")
    if mode not in ("mxu", "shift"):
        raise ValueError(
            f"GRAPE_PACK_SCAN={mode!r}: expected 'mxu' or 'shift'"
        )
    return mode


def _scan_stages_for(rows_sorted: np.ndarray) -> int:
    """ceil(log2(max segment run)) — the number of shift-combine scan
    stages that provably reach every segment's start.  After S stages
    the flag window spans 2^S slots, so any position whose segment
    start lies within max_seglen-1 <= 2^S - 1 behind it is fully
    blocked; every further stage combines with the exact identity and
    is a bit-exact no-op.  Zero stages when every segment has length 1
    (degree-1 tails; the scan is the identity)."""
    e = len(rows_sorted)
    if e == 0:
        return 0
    ch = np.nonzero(np.diff(rows_sorted))[0]
    bounds = np.concatenate([[-1], ch, [e - 1]])
    max_run = int(np.diff(bounds).max())
    return max(0, int(np.ceil(np.log2(max(1, max_run)))))


def _mxu_group_rows(sub: int) -> int:
    """Sublane-group height of the MXU scan's inter-row carry: 128-row
    groups when they tile `sub` evenly (the [128, 128] matmul operand
    the MXU is built for), else one group spanning the whole block
    (tiny test geometries)."""
    return 128 if sub % 128 == 0 else sub


def _mxu_scan_meta(rows_sorted: np.ndarray, sub: int):
    """Static restoration planes for the MXU segmented scan of one
    block over CSR-sorted rows (see the mxu branch of _kernel_body for
    the device-side consumption):

      ps [sub, C] int8: per slot, the lane of its segment's start when
         the segment starts IN this row (the in-row restore subtracts
         the exclusive row prefix at that lane); 0 for slots whose
         segment carried in from an earlier row (exclusive prefix at
         lane 0 is exactly 0, so they subtract nothing); the slot's OWN
         lane for invalid slots (self-isolating: rseg degenerates to
         the slot's raw value, which nothing downstream reads).
      bk [sub, C] int: per slot, how many rows back its segment
         started (0 when it starts in-row); the carry restoration
         subtracts the exact full row-tail prefix W at row `r - bk`
         (W[r] - W[r] = 0 for in-row segments — no mask plane).

    The ladder-path flag `f0 = (ps == lane) & (bk == 0)` recovers the
    shift scan's segment-start-or-invalid flag exactly (min/max kinds
    run the ladder off these same planes), so mxu blocks ship ps/bk
    INSTEAD of the flag plane."""
    e = len(rows_sorted)
    slots = sub * C
    lane = (np.arange(slots, dtype=np.int64) % C)
    ps = lane.copy()
    bk = np.zeros(slots, dtype=np.int64)
    if e:
        i = np.arange(e, dtype=np.int64)
        s = np.ones(e, dtype=bool)
        s[1:] = rows_sorted[1:] != rows_sorted[:-1]
        start = np.maximum.accumulate(np.where(s, i, 0))
        srow, slane = start // C, start % C
        r = i // C
        same = srow == r
        ps[:e] = np.where(same, slane, 0)
        bk[:e] = np.where(same, 0, r - srow)
    return ps.reshape(sub, C).astype(np.int8), bk.reshape(sub, C)


# Modeled per-slot VPU ops of the MXU scan (matmuls priced in the
# separate mxu column): exclusive-rowcum subtract, ps gather, in-row
# subtract, the W group concat + chained base add, SR iota + subtract,
# W gather, carry subtract, final add.  Flat — the full-prefix carry
# has no span-dependent ladder.
_MXU_SCAN_VPU = 10
# MXU matmul output planes per block: the lane cumsum, the per-group
# tail broadcasts, and the per-group exclusive tail prefixes.
_MXU_SCAN_PLANES = 3


def _decide_level_scan(blocks) -> bool:
    """Engage the MXU scan for a level iff GRAPE_PACK_SCAN=mxu and the
    summed modeled VPU cost across the level's blocks beats the shift
    ladder's (3 ops per span-aware stage, plus the flag compare the
    mxu form drops).  Per level, not per block: a level's blocks share
    stacked streams and one kernel family, so the scan form must be
    uniform within it."""
    if _scan_mode() != "mxu" or not blocks:
        return False
    shift = sum(3 * b.scan_stages + 1 for b in blocks)
    return _MXU_SCAN_VPU * len(blocks) < shift


def _lane_mix(local: np.ndarray) -> np.ndarray:
    """Static lane assignment for a pass-local column id.

    Plain `col % 128` is pathologically skewed on Kronecker/RMAT
    graphs: high-degree ids have many trailing zero bits, so lane 0
    receives ~8x its share and blocks cut at ~12% fill.  XOR-folding
    the next id bits into the lane decorrelates degree from lane while
    staying a bijection per table row (a per-row constant XOR), so the
    kernel recovers the layout with one computed lane gather on the
    x-table (`tab[r, l] = x[r*128 + (l ^ mix(r))]`)."""
    r = local >> 7
    return (local ^ r ^ (r >> 7)) & (C - 1)


def _row_mix(r):
    """The per-table-row XOR constant of `_lane_mix` (kernel side)."""
    return (r ^ (r >> 7)) & (C - 1)


@dataclass(frozen=True)
class PackConfig:
    # sub=2048 keeps the worst gather-level VMEM residency (streams
    # double-buffered + x-table + f32 temps) within the ~16 MB/core
    # budget of v5e — see vmem_bytes(); sub=4096 overflows it
    sub: int = 2048        # sublane rows per block (block = sub*128 slots)
    out_sub: int = 512     # sublane rows per compact output block
    # hub=4096 (r7, was 1024): the padded-hub-table read costs two
    # shape-matched gathers REGARDLESS of hub size (the old register
    # loop scaled with hub//C, which is why 1024 was chosen), and a
    # 4x hub absorbs enough Kronecker skew to lift gather-block fill
    # from ~67% to ~87% at bench geometry (1.5 -> 1.15 slots/edge) —
    # every per-slot stream byte and VPU op scales down with it.
    # out_sub=1024 was probed and REJECTED: the distinct-rows cap is
    # not the binding cutter (block counts unchanged) and halving the
    # fold group_cap balloons the fold hierarchy (26.8 -> 32.9 B/edge).
    hub: int = 4096        # hub table size (multiple of 128)

    def __post_init__(self):
        # sub/hub index streams are int16 and hub rows split into
        # [hub/128, 128] register tiles — enforce the ranges the device
        # dtypes silently assume (ADVICE r2: a sub > 32767 would wrap
        # on astype(int16) with no error)
        if not (0 < self.sub <= 32767):
            raise ValueError(f"sub={self.sub} not in (0, 32767]")
        if not (0 < self.hub <= 32767) or self.hub % C:
            raise ValueError(
                f"hub={self.hub} must be a positive multiple of {C} "
                "<= 32767"
            )
        if self.hub // C > self.sub:
            # the hub read is two dynamic gathers from a hub table
            # padded to [sub, C] (Mosaic's sublane gather requires
            # table shape == index shape); a hub taller than the block
            # cannot pad down
            raise ValueError(
                f"hub={self.hub} needs {self.hub // C} register rows "
                f"> sub={self.sub}"
            )
        if not (0 < self.out_sub <= self.sub):
            raise ValueError(
                f"out_sub={self.out_sub} not in (0, sub={self.sub}]"
            )

    @property
    def slots(self) -> int:
        return self.sub * C

    @staticmethod
    def from_env() -> "PackConfig":
        """Default config, overridable via GRAPE_PACK_CFG
        ("sub=64,out_sub=16,hub=128").  Lets harnesses (dryrun, probes)
        shrink the plan geometry through the real call path instead of
        monkeypatching the planner (VERDICT r4 weak #5)."""
        import os

        spec = os.environ.get("GRAPE_PACK_CFG", "")
        if not spec:
            return PackConfig()
        parts = [p for p in spec.split(",") if p]
        if any("=" not in p for p in parts):
            raise ValueError(
                f"GRAPE_PACK_CFG={spec!r}: expected comma-separated "
                "key=value tokens (e.g. 'sub=64,out_sub=16,hub=128')"
            )
        kv = dict(p.split("=", 1) for p in parts)
        allowed = {"sub", "out_sub", "hub"}
        bad = set(kv) - allowed
        if bad:
            raise ValueError(f"GRAPE_PACK_CFG unknown keys: {sorted(bad)}")
        return PackConfig(**{k: int(v) for k, v in kv.items()})

    @property
    def max_distinct(self) -> int:
        return self.out_sub * C

    def vmem_bytes(self, has_gather: bool, has_w: bool,
                   out_sub: int | None = None) -> int:
        """Worst-case VMEM residency estimate for one level's kernel:
        grid-varying streams are double-buffered by the Pallas
        pipeline (x2); grid-invariant tables buffer once; plus the f32
        working set (routed block, scan value+flag planes, one int32
        upcast of an index stream at a time).  An estimate, not a
        Mosaic quote — plan_pack warns when it exceeds
        GRAPE_PACK_VMEM_BUDGET (default 14 MiB)."""
        o = self.out_sub if out_sub is None else out_sub
        ermid = max(self.sub, o)
        varying = (
            self.sub * C * (1 + 2 + 1)       # l1 i8, s2 i16, l3 i8
            # flags i8, or ps i8 + bk priced at its WIDENED i16 form
            # (deep segments value-widen bk; the estimate must cover
            # the worst engaged level, not the narrow best case)
            + self.sub * C * 3
            + ermid * C * (1 + 2)            # el1 i8, es2 i16
            + o * C * 1                      # el3 i8
            + o * C * 4                      # out f32
        )
        if has_gather:
            varying += self.sub * C * 2        # gidx i16
            if has_w:
                varying += self.sub * C * 4    # w f32
        else:
            varying += self.sub * C * 4        # fold input vals f32
        # x-table + hub table padded to [sub, C] (shape-matched gather)
        invariant = 2 * self.sub * C * 4 if has_gather else 0
        temps = (self.sub * C * 4) * 3 + ermid * C * 4
        return 2 * varying + invariant + temps


@dataclass
class BlockPlan:
    """Static arrays for one [sub, 128] kernel block."""

    # gather stage (None on fold levels)
    sub_idx: Optional[np.ndarray]  # [sub, C] int16: x-table row per slot
    hub_sel: Optional[np.ndarray]  # [sub, C] int16: hub idx, -1 if not hub
    # CSR-restore / merge route (pack slots -> row-sorted slots); None
    # when `route_rows` carries the composed lane-preserving form
    route: Optional[Route3]
    flags: np.ndarray              # [sub, C] int8: bit0 valid, bit1 seg start
    # extraction route (scanned slots -> compact out slots); None on
    # final blocks, which use per-row-range `tiles` instead
    eroute: Optional[Route3]
    out_rows: np.ndarray           # [out_slots] int64 row id per out slot
    out_valid: np.ndarray          # [out_slots] bool
    n_edges: int = 0
    n_inputs: int = 1              # fold levels: streams concatenated
    w: Optional[np.ndarray] = None  # [sub, C] f32 edge weights, CSR order
    # final blocks: one (Route3, valid[tile_sub*C]) per vp row-range
    # tile, so the extraction kernel touches <= tile_sub*C output rows
    # at a time (a monolithic [vp//128, 128] extraction blows VMEM at
    # bench vp)
    tiles: Optional[List] = None
    # span-aware scan: stages the kernel unrolls for this block
    # (= ceil(log2(max segment run)); further stages are exact no-ops)
    scan_stages: int = 0
    # MXU scan restoration planes (see _mxu_scan_meta); ps/bk ship in
    # place of `flags` when the level engages the mxu scan
    scan_mxu: bool = False
    ps: Optional[np.ndarray] = None   # [sub, C] int8 in-row start lane
    bk: Optional[np.ndarray] = None   # [sub, C] int row backspan
    # composed merge route: [sub, C] int source-row plane (one sublane
    # gather) replacing the generic 3-stage `route` on fold levels whose
    # upstream extractions were rewritten to land lane-aligned
    route_rows: Optional[np.ndarray] = None
    # planner-only: scan slots of this block's segment-last elements
    # (the extraction sources) — consumed when a downstream fold level
    # composes this block's eroute with its merge permutation
    e_src: Optional[np.ndarray] = None
    # static op-budget ledger: exact per-stage vector-ALU op counts
    # (see _LEDGER_CONVENTIONS in scripts/pack_cost_model.py)
    ledger: dict = field(default_factory=dict)


@dataclass
class LevelPlan:
    """One pallas_call: a list of equally-shaped blocks."""

    cfg: PackConfig
    blocks: List[BlockPlan]
    has_gather: bool
    pass_base: int = 0             # x-table offset (gather levels)
    out_sub: int = 0               # output rows per block
    tile_sub: int = 0              # final level: rows per extraction tile


def _block_op_ledger(cfg: PackConfig, *, gather: bool, scan_stages: int,
                     route_moves: int, out_sub: int = 0,
                     n_tiles: int = 0, tile_sub: int = 0,
                     scan_mxu: bool = False) -> dict:
    """Exact per-engine op counts for one block, by stage.  Counting
    conventions (shared with scripts/pack_cost_model.py, which verifies
    them independently from the shipped stream arrays):

      * one VPU op = one full-width vector operation over the
        operand's [rows, 128] plane, priced `rows * 128` lanes; the
        per-stage entries below are all VPU ops;
      * one MXU elem (`mxu` entry) = one element of a triangular /
        broadcast matmul OUTPUT plane ([B,128] @ [128,128], the one
        cumsum form Mosaic lowers — priced at the measured 0.008
        cyc/elem for B >= 512 in scripts/pack_cost_model.py);
      * gather overlay: 3 ops — the per-row hub-group lane reduce and
        the two shape-matched hub-table gathers (the x-table sublane
        dynamic_gather itself is priced separately as `gather_rows` —
        its rate is the hardware unknown the probe measures).  The
        merged gidx plane's hub decode and the final select ride
        inside this price, as the r6 register-loop selects did;
      * route: one op per take_along_axis stage, priced at that
        stage's operand height (generic Route3: l1/s2 at r_mid, l3 at
        r_dst; composed lane-aligned form: one sublane gather at sub);
      * flags: the one segment-flag compare (`flags != 1`) — shift
        levels only; mxu levels ship ps/bk restoration planes and run
        no flag pass in the sum semiring (min/max fall back to the
        ladder and pay a 3-op flag derivation NOT priced here: the
        ledger prices the sum pipeline the bench runs);
      * scan: shift levels: 3 ops (shift, select, combine) per
        span-aware unrolled stage; mxu levels: a FLAT `_MXU_SCAN_VPU`
        (= 10) restoration ops per slot — the full-prefix inter-row
        carry has no span-dependent ladder — with the matmuls landing
        in the `mxu` column as `_MXU_SCAN_PLANES` (= 3) output planes;
      * extract: the eroute stages (the out-validity select is gone:
        unrouted compact slots carry garbage that is its own flagged
        segment downstream, the same isolation proof that removed the
        scan's validity select in r6), or the per-row-range tile
        routes on final blocks (whose validity select SURVIVES — tile
        outputs are summed straight into the dense result);
      * fold-input assembly (concat / disjoint-slot merge) runs in XLA
        outside the kernels and is excluded, as it always was.
    """
    slots = cfg.sub * C
    led = {
        "overlay": 3 * slots if gather else 0,
        "route": route_moves * slots,
        "flags": 0 if scan_mxu else slots,
        "scan": (_MXU_SCAN_VPU if scan_mxu
                 else 3 * scan_stages) * slots,
        "mxu": _MXU_SCAN_PLANES * slots if scan_mxu else 0,
    }
    if n_tiles:
        led["extract"] = n_tiles * (2 * slots + 2 * tile_sub * C)
    elif out_sub:
        r_mid = max(cfg.sub, out_sub)
        led["extract"] = 2 * r_mid * C + out_sub * C
    else:
        led["extract"] = 0
    led["gather_rows"] = slots if gather else 0
    return led


def _reledger_block(cfg: PackConfig, blk: "BlockPlan") -> dict:
    """Recompute a block's ledger from its own planned structure —
    used when a post-pass changes scan parameters (level-wide mxu
    engagement, multi-shard stage unification)."""
    return _block_op_ledger(
        cfg,
        gather=blk.sub_idx is not None,
        scan_stages=blk.scan_stages,
        route_moves=1 if blk.route_rows is not None else 3,
        out_sub=(blk.eroute.l3.shape[0] if blk.eroute is not None
                 else 0),
        n_tiles=len(blk.tiles) if blk.tiles is not None else 0,
        tile_sub=(blk.tiles[0][1].shape[0] // C
                  if blk.tiles else 0),
        scan_mxu=blk.scan_mxu,
    )


def _apply_level_scan_mode(cfg: PackConfig, blocks) -> None:
    """Set the level-uniform scan form on `blocks` (mxu iff modeled
    cheaper under GRAPE_PACK_SCAN=mxu) and refresh their ledgers."""
    mxu = _decide_level_scan(blocks)
    for b in blocks:
        b.scan_mxu = mxu
        b.ledger = _reledger_block(cfg, b)


def _ledger_of_levels(shard_levels, n_cols: int, cfg: PackConfig) -> dict:
    """Aggregate the per-block op ledgers of a plan (list over shards
    of its ordered LevelPlans, final level last) into the static
    op-budget ledger: exact ALU op / gather-row / HBM-byte counts per
    level and in total, under the conventions of _block_op_ledger.
    HBM bytes are the shipped stream tables (post dtype-narrowing, from
    the real device stacks) plus one x pass-window load per gather
    level — the same accounting the r4 cost model used."""
    n_lv = len(shard_levels[0])
    out_levels = []
    totals = {"vpu_ops": 0, "mxu_ops": 0, "gather_rows": 0,
              "hbm_bytes": 0, "blocks": 0}
    per_stage_tot: dict = {}
    edges = 0
    for li in range(n_lv):
        per_stage: dict = {}
        gr = 0
        mxu = 0
        hbm = 0
        nbl = 0
        has_gather = shard_levels[0][li].has_gather
        for lvs in shard_levels:
            lv = lvs[li]
            nbl += len(lv.blocks)
            for b in lv.blocks:
                for k, v in b.ledger.items():
                    if k == "gather_rows":
                        gr += int(v)
                    elif k == "mxu":
                        mxu += int(v)
                    else:
                        per_stage[k] = per_stage.get(k, 0) + int(v)
                if lv.has_gather:
                    edges += int(b.n_edges)
            if lv.blocks:
                hbm += sum(
                    int(n) for n in
                    _stack_blocks(lv, nbytes_only=True).values()
                )
            if lv.has_gather:
                hbm += min(n_cols, cfg.slots * len(lv.blocks)) * 4
        vpu = sum(per_stage.values())
        out_levels.append({
            "level": li, "blocks": nbl, "has_gather": bool(has_gather),
            "vpu_ops": vpu, "mxu_ops": mxu, "gather_rows": gr,
            "hbm_bytes": hbm, "per_stage": per_stage,
        })
        totals["vpu_ops"] += vpu
        totals["mxu_ops"] += mxu
        totals["gather_rows"] += gr
        totals["hbm_bytes"] += hbm
        totals["blocks"] += nbl
        for k, v in per_stage.items():
            per_stage_tot[k] = per_stage_tot.get(k, 0) + v
    return {
        "edges": edges,
        "levels": out_levels,
        "totals": {**totals, "per_stage": per_stage_tot},
    }


def plan_ledger(plan) -> dict:
    """The static op-budget ledger of a PackPlan or MultiPackPlan."""
    if isinstance(plan, MultiPackPlan):
        if plan.ledger is None:
            raise ValueError("MultiPackPlan carries no ledger")
        return plan.ledger
    levels = list(plan.levels)
    if plan.final is not None and plan.final.blocks:
        levels = levels + [plan.final]
    return _ledger_of_levels([levels], plan.n_cols, plan.cfg)


_PLAN_COUNTER = itertools.count()


@dataclass
class PackPlan:
    vp: int                        # output length (padded, multiple of 128)
    n_cols: int                    # gather-table length
    cfg: PackConfig
    hub_cols: np.ndarray           # [hub] int64 column ids (padded with 0)
    levels: List[LevelPlan] = field(default_factory=list)
    final: Optional[LevelPlan] = None  # single-block level -> [vp]
    # unique id: apps bake it into trace keys so a cached runner is
    # never reused with a different fragment's closed-over plan
    uid: int = field(default_factory=lambda: next(_PLAN_COUNTER))

    # device-side constant streams, materialized lazily per backend
    _device: dict = field(default_factory=dict)


# --------------------------------------------------------------------------
# host planning
# --------------------------------------------------------------------------


def _hub_row_margin(cfg: PackConfig) -> int:
    """Slot capacity reserved for the row-aligned hub assignment: hub
    edges are placed group-sorted with each kernel row taking entries
    of a SINGLE 128-entry hub group (the lane-uniform row index the
    two-gather hub read requires — see _plan_gather_block); a group
    change mid-row skips the row's remaining holes, wasting at most
    (groups - 1) * (C - 1) slots per block.  hub // C <= sub by
    PackConfig validation, so the margin always leaves >= sub slots."""
    return (cfg.hub // C) * (C - 1)


def _cut_blocks(rows, local_cols, hub_mask, cfg: PackConfig):
    """Split CSR-ordered edges into block ranges such that per block:
    no mixed lane exceeds `sub` non-hub edges, slots (plus the hub
    row-alignment margin when hub edges are present) <= sub*128, and
    distinct rows <= max_distinct.  Returns list of (lo, hi).

    O(E): per-lane edge position lists + segment-start prefix counts
    give each cut point in O(1)."""
    e = len(rows)
    cap = cfg.slots - (_hub_row_margin(cfg) if hub_mask.any() else 0)
    lane = np.where(hub_mask, -1, _lane_mix(local_cols))
    # per-lane position lists: pos_by_lane[l] = sorted edge indices in l
    order = np.argsort(lane, kind="stable")
    lane_sorted = lane[order]
    lane_starts = np.searchsorted(lane_sorted, np.arange(C))
    lane_ends = np.searchsorted(lane_sorted, np.arange(C), side="right")
    pos_by_lane = [order[lane_starts[l]:lane_ends[l]] for l in range(C)]

    seg_start = np.ones(e, dtype=np.int64)
    seg_start[1:] = rows[1:] != rows[:-1]
    cum_start = np.concatenate([[0], np.cumsum(seg_start)])

    cuts = []
    lo = 0
    while lo < e:
        hi = min(e, lo + cap)
        # lane overflow: for each lane, the (rank_at_lo + sub)-th edge
        # of that lane is the first infeasible position
        for l in range(C):
            pl = pos_by_lane[l]
            r0 = np.searchsorted(pl, lo)
            if r0 + cfg.sub < len(pl):
                hi = min(hi, int(pl[r0 + cfg.sub]))
        # distinct-rows bound: distinct([lo,hi)) = 1 + cum_start[hi]
        # - cum_start[lo+1]  (the row at lo counts whether or not it is
        # a recorded segment start); keep the largest feasible hi
        target = cum_start[lo + 1] + cfg.max_distinct - 1
        hi_feas = int(np.searchsorted(cum_start, target, side="right")) - 1
        hi = min(hi, max(lo + 1, hi_feas))
        cuts.append((lo, hi))
        lo = hi
    return cuts


def _plan_gather_block(rows, cols, hub_idx, base, cfg: PackConfig,
                       w=None):
    """Plan one gather block from its CSR-ordered edge slice.

    hub_idx: int32 per edge, -1 if the edge reads the pass table,
    else its index into the hub table.  `base` is the pass's x offset.
    `w`: optional per-edge weights (same slice), stored in CSR slot
    order for post-route application.
    """
    e = len(rows)
    sub = cfg.sub
    is_hub = hub_idx >= 0

    # ---- slot assignment: non-hub lane = mixed lane; hub fills holes ----
    lane = np.where(is_hub, -1, _lane_mix(cols - base)).astype(np.int64)
    slot = np.full(e, -1, dtype=np.int64)
    # positions of non-hub edges within their lane column (stable)
    nh = np.nonzero(~is_hub)[0]
    order = np.argsort(lane[nh], kind="stable")
    lane_sorted = lane[nh][order]
    pos_in_lane = np.arange(len(nh)) - np.searchsorted(
        lane_sorted, lane_sorted
    )
    slot[nh[order]] = pos_in_lane * C + lane_sorted
    assert (pos_in_lane < sub).all(), "lane overflow despite block cut"
    # hub edges take remaining slots (any lane), GROUP-SORTED and
    # row-aligned: every kernel row's hub slots read entries of one
    # 128-entry hub group, so the kernel's hub-table row index is
    # lane-uniform per row and the two shape-matched gathers compose
    # correctly (a per-slot row plane would read the row index at the
    # POST-lane-gather position — wrong whenever rows mix groups).  A
    # group change mid-row skips the row's remaining holes; the block
    # cutter reserved capacity for exactly that (_hub_row_margin).
    hub_e = np.nonzero(is_hub)[0]
    if len(hub_e):
        used = np.zeros(sub * C, dtype=bool)
        used[slot[nh]] = True
        free = np.nonzero(~used)[0]
        order_h = np.argsort(hub_idx[hub_e] >> 7, kind="stable")
        hub_sorted = hub_e[order_h]
        grp = hub_idx[hub_sorted] >> 7
        bounds = np.concatenate(
            [[0], np.nonzero(np.diff(grp))[0] + 1, [len(grp)]]
        )
        fi = 0
        for gi in range(len(bounds) - 1):
            k, k2 = int(bounds[gi]), int(bounds[gi + 1])
            take = k2 - k
            assert fi + take <= len(free), \
                "hub row-alignment margin exhausted despite block cut"
            slot[hub_sorted[k:k2]] = free[fi:fi + take]
            fi += take
            # a group must not share a row with the next: skip the
            # last used row's remaining holes
            if fi and fi < len(free):
                last_row = free[fi - 1] // C
                while fi < len(free) and free[fi] // C == last_row:
                    fi += 1
        # the invariant the kernel's lane-uniform row index relies on
        hrows = slot[hub_sorted] // C
        gmin = np.full(sub, np.iinfo(np.int64).max)
        gmax = np.full(sub, -1, dtype=np.int64)
        np.minimum.at(gmin, hrows, grp)
        np.maximum.at(gmax, hrows, grp)
        occ = gmax >= 0
        assert (gmax[occ] == gmin[occ]).all(), \
            "a kernel row mixes hub groups"
    assert (slot >= 0).all()

    # ---- gather streams ----
    sub_idx = np.zeros((sub, C), dtype=np.int16)
    hub_sel = np.full((sub, C), -1, dtype=np.int16)
    srow, slane = slot // C, slot % C
    tab_row = np.where(is_hub, 0, (cols - base) >> 7)
    assert (tab_row >= 0).all() and (tab_row < sub).all()
    sub_idx[srow, slane] = tab_row.astype(np.int16)
    hub_sel[srow[is_hub], slane[is_hub]] = hub_idx[is_hub].astype(np.int16)

    # ---- CSR-restore route: pack slot -> CSR slot i ----
    route = plan_route(slot, np.arange(e, dtype=np.int64), sub, sub)

    # ---- flags for the segmented scan over CSR order ----
    flags = np.zeros((sub, C), dtype=np.int8)
    csr_r, csr_l = np.arange(e) // C, np.arange(e) % C
    seg_start = np.ones(e, dtype=bool)
    seg_start[1:] = rows[1:] != rows[:-1]
    flags[csr_r, csr_l] = 1 | (seg_start.astype(np.int8) << 1)

    # ---- extraction: each row's last CSR slot -> compact out slot ----
    last = np.ones(e, dtype=bool)
    last[:-1] = rows[1:] != rows[:-1]
    src = np.nonzero(last)[0]
    d = len(src)
    assert d <= cfg.max_distinct
    eroute = plan_route(
        src, np.arange(d, dtype=np.int64), sub, cfg.out_sub
    )
    out_rows = np.zeros(cfg.out_sub * C, dtype=np.int64)
    out_rows[:d] = rows[src]
    out_valid = np.zeros(cfg.out_sub * C, dtype=bool)
    out_valid[:d] = True

    w_block = None
    if w is not None:
        w_block = np.zeros((sub, C), dtype=np.float32)
        w_block[csr_r, csr_l] = w.astype(np.float32)

    stages = _scan_stages_for(rows)
    ps, bk = _mxu_scan_meta(rows, sub)
    return BlockPlan(
        sub_idx=sub_idx, hub_sel=hub_sel, route=route, flags=flags,
        eroute=eroute, out_rows=out_rows, out_valid=out_valid, n_edges=e,
        w=w_block, scan_stages=stages, e_src=src,
        ps=ps, bk=bk,
        ledger=_block_op_ledger(cfg, gather=True, scan_stages=stages,
                                route_moves=3, out_sub=cfg.out_sub),
    )


def _group_prep(grp):
    """Concatenate a group's stream metadata and compute the merge
    permutation (valid slots, stably sorted by row — the tie-break that
    keeps the fold's combine order, and hence every f32 bit,
    unchanged).  Computed ONCE per group and shared by the feasibility
    probe and the block planner (the argsort is the planner's unit of
    work; doubling it doubled cache-cold plan time for nothing)."""
    in_rows = np.concatenate([r for r, _, _ in grp])
    in_valid = np.concatenate([v for _, v, _ in grp])
    val = np.nonzero(in_valid)[0]
    order = val[np.argsort(in_rows[val], kind="stable")]
    return in_rows, in_valid, order


def _aligned_feasible(grp, cfg: PackConfig, prep=None) -> bool:
    """True when this group's upstream extractions can be rewritten so
    the merge route is lane-preserving: per input stream, no merged
    lane may receive more than out_sub of that stream's elements (each
    stream is an [out_sub, C] block — out_sub rows of sublane capacity
    per lane)."""
    sl = cfg.max_distinct
    _, _, order = prep if prep is not None else _group_prep(grp)
    e = len(order)
    if e == 0:
        return True
    lanes = np.arange(e, dtype=np.int64) % C
    stream_of = order // sl
    counts = np.bincount(stream_of * C + lanes,
                         minlength=len(grp) * C)
    return int(counts.max()) <= cfg.out_sub


def _rewrite_upstream_aligned(grp, order, cfg: PackConfig) -> np.ndarray:
    """Compose each producer's extraction route with this group's merge
    permutation: producers re-extract straight into lane-aligned
    compact slots (same lane as the element's final merged slot), so
    the merge itself collapses to ONE sublane gather.  Mutates the
    producer BlockPlans (fresh eroute/out_rows/out_valid) and returns
    the consumer's [sub, C] source-row plane.

    Bit-exactness: `order` (the merge permutation) is computed from the
    ORIGINAL compact layouts, so every element's final slot — and hence
    the scan tree and extracted values — is unchanged; only the
    intermediate compact placement moves."""
    sl = cfg.max_distinct
    e = len(order)
    i = np.arange(e, dtype=np.int64)
    j_of = order // sl
    q_old = order % sl
    lam = i % C
    # rank within (stream, lane), in final-slot order (i ascending)
    key = j_of * C + lam
    ord2 = np.argsort(key, kind="stable")
    sorted_key = key[ord2]
    starts = np.searchsorted(sorted_key, sorted_key)
    ranks = np.empty(e, dtype=np.int64)
    ranks[ord2] = np.arange(e, dtype=np.int64) - starts
    q_new = ranks * C + lam

    # the merged route is lane-preserving by construction; the helper
    # re-checks that invariant and emits the single-move row plane
    route_rows = plan_lane_aligned_rows(j_of * sl + q_new, i, cfg.sub)

    for j, (r, v, blk) in enumerate(grp):
        m = j_of == j
        d_j = int(m.sum())
        if d_j == 0:
            continue
        newq = np.empty(d_j, dtype=np.int64)
        # the producer's compact slots are the prefix 0..d_j-1, in the
        # same order as its e_src extraction sources
        newq[q_old[m]] = q_new[m]
        assert blk.e_src is not None and len(blk.e_src) == d_j
        blk.eroute = plan_route(blk.e_src, newq, cfg.sub, cfg.out_sub)
        nr = np.zeros(sl, dtype=np.int64)
        nv = np.zeros(sl, dtype=bool)
        nr[newq] = r[:d_j]
        nv[newq] = True
        blk.out_rows = nr
        blk.out_valid = nv
    return route_rows


def _plan_fold_block(grp, cfg: PackConfig, out_sub: int,
                     final_by_row: bool, tile_sub: int = 0,
                     aligned: bool = False, prep=None):
    """Plan one fold block over a group of input streams
    [(out_rows, out_valid, producer BlockPlan)]: the merge route sorts
    valid slots by (row, original position), scan folds them, and
    extraction emits one slot per distinct row (or slot==row when
    `final_by_row`, split into `tile_sub`-row range tiles so each
    extraction kernel program stays within VMEM).  With `aligned`, the
    producers' extractions are rewritten (route composition) and the
    merge route ships as a single sublane-gather plane instead of a
    3-stage Route3."""
    sub = cfg.sub
    in_rows, in_valid, order = (
        prep if prep is not None else _group_prep(grp)
    )
    pad = cfg.slots - len(in_rows)
    assert pad >= 0
    if pad:
        # pad slots are invalid and trailing, so `order` (computed on
        # the unpadded concat) indexes identically into the padded form
        in_rows = np.concatenate([in_rows, np.zeros(pad, np.int64)])
        in_valid = np.concatenate([in_valid, np.zeros(pad, bool)])
    e = len(order)
    if aligned:
        route = None
        route_rows = _rewrite_upstream_aligned(grp, order, cfg)
        route_moves = 1
    else:
        route = plan_route(order, np.arange(e, dtype=np.int64), sub, sub)
        route_rows = None
        route_moves = 3

    rows_sorted = in_rows[order]
    flags = np.zeros((sub, C), dtype=np.int8)
    csr_r, csr_l = np.arange(e) // C, np.arange(e) % C
    seg_start = np.ones(e, dtype=bool)
    seg_start[1:] = rows_sorted[1:] != rows_sorted[:-1]
    flags[csr_r, csr_l] = 1 | (seg_start.astype(np.int8) << 1)
    stages = _scan_stages_for(rows_sorted)
    ps, bk = _mxu_scan_meta(rows_sorted, sub)

    last = np.ones(e, dtype=bool)
    last[:-1] = rows_sorted[1:] != rows_sorted[:-1]
    src = np.nonzero(last)[0]
    d = len(src)
    if final_by_row:
        dst = rows_sorted[src]
        assert d == len(np.unique(dst))
        out_rows = np.arange(out_sub * C, dtype=np.int64)
        out_valid = np.zeros(out_sub * C, dtype=bool)
        out_valid[dst] = True
        # per-row-range extraction tiles (tile_sub rows each)
        tile_sub = tile_sub or out_sub
        n_tiles = -(-out_sub // tile_sub)
        tiles = []
        for t in range(n_tiles):
            lo = t * tile_sub * C
            hi = lo + tile_sub * C
            m = (dst >= lo) & (dst < hi)
            er = plan_route(src[m], dst[m] - lo, sub, tile_sub)
            ev = np.zeros(tile_sub * C, dtype=bool)
            ev[dst[m] - lo] = True
            tiles.append((er, ev))
        return BlockPlan(
            sub_idx=None, hub_sel=None, route=route, flags=flags,
            eroute=None, out_rows=out_rows, out_valid=out_valid,
            n_edges=e, tiles=tiles, scan_stages=stages,
            route_rows=route_rows, ps=ps, bk=bk,
            ledger=_block_op_ledger(cfg, gather=False, scan_stages=stages,
                                    route_moves=route_moves,
                                    n_tiles=n_tiles, tile_sub=tile_sub),
        )
    assert d <= out_sub * C
    dst = np.arange(d, dtype=np.int64)
    out_rows = np.zeros(out_sub * C, dtype=np.int64)
    out_rows[:d] = rows_sorted[src]
    out_valid = np.zeros(out_sub * C, dtype=bool)
    out_valid[:d] = True
    eroute = plan_route(src, dst, sub, out_sub)
    return BlockPlan(
        sub_idx=None, hub_sel=None, route=route, flags=flags,
        eroute=eroute, out_rows=out_rows, out_valid=out_valid, n_edges=e,
        scan_stages=stages, route_rows=route_rows, e_src=src,
        ps=ps, bk=bk,
        ledger=_block_op_ledger(cfg, gather=False, scan_stages=stages,
                                route_moves=route_moves, out_sub=out_sub),
    )


# final extraction runs in row-range tiles of this many sublane rows,
# so its VMEM residency is bounded regardless of vp; the vp ceiling is
# then set by HBM (per-final-block tile-route storage is O(vp)) rather
# than by one monolithic [vp//128, 128] extraction block
_FINAL_TILE_SUB = 2048
_MAX_VP_SUB = 65536  # vp <= 65536*128 (8.4M rows) per plan/shard


def _plan_shard_gather(edge_row, edge_col, vp, n_cols, cfg: PackConfig,
                       edge_w=None):
    """Gather levels + hub table for one shard's CSR-sorted edge list.
    Returns (levels: dict pass_idx -> LevelPlan, hub_cols_padded) —
    passes with no edges get no entry (plan_pack_multi pads them when
    another shard does populate the pass)."""
    # hub columns: the most-referenced ones (these overflow per-lane
    # capacity in the packed layout; they read a register table instead)
    counts = np.bincount(edge_col, minlength=n_cols)
    hub = min(cfg.hub, n_cols)
    hub_cols = np.argsort(-counts, kind="stable")[:hub].astype(np.int64)
    hub_lut = np.full(n_cols, -1, dtype=np.int32)
    hub_lut[hub_cols] = np.arange(hub, dtype=np.int32)
    hub_cols_padded = np.zeros(cfg.hub, dtype=np.int64)
    hub_cols_padded[:hub] = hub_cols

    hub_idx_all = hub_lut[edge_col]

    from concurrent.futures import ThreadPoolExecutor

    span = cfg.sub * C
    n_pass = max(1, -(-n_cols // span))
    levels: dict[int, LevelPlan] = {}
    # `with` guarantees worker threads are reaped even when block
    # planning raises (ADVICE r2: the bare shutdown leaked them)
    with ThreadPoolExecutor() as pool:
        for p in range(n_pass):
            base = p * span
            # hub edges join the pass of their column so every edge
            # lives in exactly one pass (their table entry is ignored
            # anyway)
            if n_pass > 1:
                in_pass = (edge_col >= base) & (edge_col < base + span)
            else:
                in_pass = np.ones(len(edge_col), dtype=bool)
            sel = np.nonzero(in_pass)[0]
            if len(sel) == 0:
                continue
            rows, cols = edge_row[sel], edge_col[sel]
            hub_idx = hub_idx_all[sel]
            w_sel = edge_w[sel] if edge_w is not None else None
            cuts = _cut_blocks(rows, cols - base, hub_idx >= 0, cfg)
            # block planning is route-heavy numpy (argsort-dominated,
            # GIL-friendly): thread it
            blocks = list(pool.map(
                lambda lohi, rows=rows, cols=cols, hub_idx=hub_idx,
                       w_sel=w_sel, base=base: _plan_gather_block(
                    rows[lohi[0]:lohi[1]], cols[lohi[0]:lohi[1]],
                    hub_idx[lohi[0]:lohi[1]], base, cfg,
                    w_sel[lohi[0]:lohi[1]] if w_sel is not None else None,
                ),
                cuts,
            ))
            levels[p] = LevelPlan(
                cfg=cfg, blocks=blocks, has_gather=True, pass_base=base,
                out_sub=cfg.out_sub,
            )
    return levels, hub_cols_padded


def _empty_gather_block(cfg: PackConfig, base: int, has_w: bool):
    """A no-edge gather block (pads shards to uniform block counts
    under shard_map: all flags invalid, all outputs masked)."""
    z = np.zeros(0, dtype=np.int64)
    return _plan_gather_block(
        z, z, np.zeros(0, dtype=np.int32), base, cfg,
        np.zeros(0, dtype=np.float32) if has_w else None,
    )


def _level_streams(levels):
    out = []
    for lv in levels:
        for b in lv.blocks:
            out.append((b.out_rows, b.out_valid, b))
    return out


def _plan_mid_folds(streams, cfg: PackConfig):
    """Contract streams with fold levels while they help (data-dependent
    grouping — single-shard plans only).  Returns (levels, streams)."""
    group_cap = cfg.sub // cfg.out_sub
    levels = []
    depth = 0
    compose = _compose_enabled()
    # mid folds: contract while they help (already-compact streams,
    # e.g. degree-1 tails, cannot contract — the multi-block final
    # level absorbs them instead, having no distinct-rows limit)
    while sum(len(r) for r, _, _ in streams) > cfg.slots:
        grps = []
        i = 0
        while i < len(streams):
            grp = []
            slots = 0
            distinct = set()
            while (i < len(streams) and len(grp) < group_cap
                   and slots + len(streams[i][0]) <= cfg.slots):
                r, v, _ = streams[i]
                u = set(np.unique(r[v]).tolist())
                if grp and len(distinct | u) > cfg.max_distinct:
                    break
                distinct |= u
                grp.append(streams[i])
                slots += len(r)
                i += 1
            grps.append(grp)
        if 2 * len(grps) > len(streams):
            # weak contraction (< 2x — overlapping row ranges hit the
            # distinct-rows cap): a further fold level would ship a
            # full set of merge/extraction streams for almost no
            # reduction, while the final level absorbs the same
            # streams at the same block count (r7: the bench chain
            # spent two levels shrinking 50 -> 34 -> 33 blocks, ~3.3
            # HBM B/edge for nothing) — hand over to the final level
            break
        # route composition engages per level (kernel structure must be
        # uniform across a level's blocks)
        preps = [_group_prep(g) for g in grps]
        aligned = compose and all(
            _aligned_feasible(g, cfg, p) for g, p in zip(grps, preps)
        )
        blocks = []
        nxt = []
        for grp, prep in zip(grps, preps):
            blk = _plan_fold_block(grp, cfg, cfg.out_sub,
                                   final_by_row=False, aligned=aligned,
                                   prep=prep)
            blk.n_inputs = len(grp)
            blocks.append(blk)
            nxt.append((blk.out_rows, blk.out_valid, blk))
        levels.append(LevelPlan(cfg=cfg, blocks=blocks, has_gather=False,
                                out_sub=cfg.out_sub))
        streams = nxt
        depth += 1
        assert depth < 8, "fold recursion failed to converge"
    return levels, streams


def _final_groups(streams, cfg: PackConfig):
    """Capacity-only grouping of the final level's input streams —
    data-independent, so multi-shard plans built from uniform stream
    counts get uniform structure."""
    grps = []
    i = 0
    while i < len(streams):
        grp = []
        slots = 0
        while i < len(streams) and slots + len(streams[i][0]) <= cfg.slots:
            grp.append(streams[i])
            slots += len(streams[i][0])
            i += 1
        if not grp:  # single stream larger than a block cannot happen
            raise AssertionError("stream exceeds block capacity")
        grps.append(grp)
    return grps


def _plan_final_level(streams, vp, cfg: PackConfig,
                      aligned: bool | None = None,
                      preps=None) -> LevelPlan:
    """Final level: multi-block, each block scan-folds its streams and
    extracts straight into the dense [vp] layout (slot == row id) in
    row-range tiles; block outputs are summed by the caller, so
    overlapping rows across final blocks are fine.  `aligned=None`
    decides route composition from this stream set alone; multi-shard
    planning passes the all-shard AND so the skeleton stays uniform."""
    vp_sub = vp // C
    tile_sub = min(vp_sub, _FINAL_TILE_SUB)
    from concurrent.futures import ThreadPoolExecutor

    grps = _final_groups(streams, cfg)
    if preps is None:
        preps = [_group_prep(g) for g in grps]
    if aligned is None:
        aligned = _compose_enabled() and all(
            _aligned_feasible(g, cfg, p) for g, p in zip(grps, preps)
        )

    def build(grp_prep):
        grp, prep = grp_prep
        blk = _plan_fold_block(grp, cfg, vp_sub, final_by_row=True,
                               tile_sub=tile_sub, aligned=aligned,
                               prep=prep)
        blk.n_inputs = len(grp)
        return blk

    with ThreadPoolExecutor() as pool:
        fblocks = list(pool.map(build, list(zip(grps, preps))))
    return LevelPlan(cfg=cfg, blocks=fblocks, has_gather=False,
                     out_sub=vp_sub, tile_sub=tile_sub)


def plan_pack(edge_row: np.ndarray, edge_col: np.ndarray, vp: int,
              n_cols: int, cfg: PackConfig = PackConfig(),
              edge_w: np.ndarray | None = None) -> PackPlan:
    """Build the full static plan for `y[r] = sum_e x[col[e]]` over
    CSR-sorted edges with `vp` output rows and `n_cols` x entries.

    `vp` must be a multiple of 128 and <= 65536*128 rows per plan
    (the per-final-block tile-route storage is O(vp) in HBM; shard
    larger graphs)."""
    edge_row = np.asarray(edge_row, dtype=np.int64)
    edge_col = np.asarray(edge_col, dtype=np.int64)
    assert vp % C == 0
    if vp // C > _MAX_VP_SUB:
        raise ValueError(
            f"vp={vp} exceeds {_MAX_VP_SUB * C} rows per plan; "
            "shard the graph"
        )
    assert (np.diff(edge_row) >= 0).all(), "edges must be row-sorted"

    glevels, hub_cols_padded = _plan_shard_gather(
        edge_row, edge_col, vp, n_cols, cfg, edge_w
    )
    plan = PackPlan(vp=vp, n_cols=n_cols, cfg=cfg,
                    hub_cols=hub_cols_padded)
    plan.levels = [glevels[p] for p in sorted(glevels)]

    streams = _level_streams(plan.levels)
    fold_levels, streams = _plan_mid_folds(streams, cfg)
    plan.levels += fold_levels
    plan.final = _plan_final_level(streams, vp, cfg)
    for lv in list(plan.levels) + [plan.final]:
        _apply_level_scan_mode(cfg, lv.blocks)
    _warn_vmem(cfg, has_w=edge_w is not None,
               final_out_sub=plan.final.tile_sub)
    return plan


def _warn_vmem(cfg: PackConfig, has_w: bool, final_out_sub: int = 0):
    """Warn once per (cfg, shape class) when the estimated per-kernel
    VMEM residency exceeds the budget (GRAPE_PACK_VMEM_BUDGET bytes,
    default 14 MiB of the ~16 MiB/core on v5e)."""
    import os
    import warnings

    budget = int(os.environ.get("GRAPE_PACK_VMEM_BUDGET", 14 << 20))
    worst = max(
        cfg.vmem_bytes(has_gather=True, has_w=has_w),
        cfg.vmem_bytes(has_gather=False, has_w=False,
                       out_sub=final_out_sub or cfg.out_sub),
    )
    if worst > budget:
        key = (cfg.sub, cfg.out_sub, cfg.hub, has_w, final_out_sub)
        if key not in _VMEM_WARNED:
            _VMEM_WARNED.add(key)
            warnings.warn(
                f"pack plan estimated VMEM {worst / 2**20:.1f} MiB exceeds "
                f"budget {budget / 2**20:.1f} MiB (sub={cfg.sub}, "
                f"final_out_sub={final_out_sub}); the kernel may fail "
                "Mosaic VMEM allocation — shrink PackConfig.sub or shard "
                "the graph",
                stacklevel=3,
            )


_VMEM_WARNED: set = set()


# --------------------------------------------------------------------------
# numpy reference executor (the kernel's semantics, stage for stage)
# --------------------------------------------------------------------------


# reduction semirings: (combine, identity, weight-combine).  `min`/`max`
# pair with ADDITIVE edge weights (the tropical semiring SSSP/BFS
# relaxation x[nbr] + w); `sum` pairs with multiplicative weights.
_KINDS = {
    "sum": (np.add, 0.0, np.multiply),
    "min": (np.minimum, np.inf, np.add),
    "max": (np.maximum, -np.inf, np.add),
}


def _jnp_kind(kind):
    """The jnp (combine, identity, weight-combine) triple, mirroring
    _KINDS so the kernel and numpy reference cannot drift."""
    import jax.numpy as jnp

    return {
        "sum": (jnp.add, 0.0, jnp.multiply),
        "min": (jnp.minimum, np.inf, jnp.add),
        "max": (jnp.maximum, -np.inf, jnp.add),
    }[kind]


def _scan_np(v, f, kind, stages: int | None = None):
    """Segmented inclusive scan over flattened [sub, C] row-major order
    via shift-combine stages — mirrors the kernel exactly.  `stages`
    truncates the unroll (span-aware scans: beyond
    ceil(log2(max_seglen)) every stage combines with the identity, so
    truncation is bit-exact); None runs the full log2(n) ladder."""
    op, ident, _ = _KINDS[kind]
    sub = v.shape[0]
    n = sub * C
    vf = v.reshape(n).copy()
    ff = f.reshape(n).copy().astype(bool)
    s = 1
    done = 0
    while s < n and (stages is None or done < stages):
        carry = np.where(ff[s:], ident, vf[:-s])
        vf[s:] = op(vf[s:], carry)
        ff[s:] = ff[s:] | ff[:-s]
        s *= 2
        done += 1
    return vf.reshape(sub, C)


def _scan_np_mxu(v, ps, bk):
    """Numpy mirror of the kernel's MXU segmented scan (sum semiring
    only — min/max cannot ride a matmul prefix and fall back to the
    shift ladder with flags derived from ps/bk).  Stage for stage:

      1. per-row inclusive lane cumsum `rowcum = v @ tri` (the ONE
         cumsum form that lowers in Pallas TPU; exclusive form by
         subtracting v), then the in-row restore subtracts the
         exclusive prefix at each slot's static start lane `ps` —
         exactly 0 for slots whose segment carried in from an earlier
         row (ps = 0 → exclusive prefix at lane 0) — giving `rseg`,
         each slot's sum back to its segment start within the row;
      2. the FULL exclusive row prefix W of the per-row trailing
         -segment totals (`tail = rseg @ E127`, a lane-127 broadcast
         matmul): per 128-row sublane group, `Lexc @ tail` on the MXU
         plus a [1, C] running base chained across groups — full
         prefixes NEST, so W[r] - W[r'] is the exact tail sum over
         rows [r', r) with no span-dependent ladder;
      3. restoration: every slot adds `W[r] - W[r - bk]` — the
         carried-in part of its segment (bk = 0 slots subtract W at
         their own row and add exactly 0, so no mask plane exists).

    NOT bit-identical to the shift ladder on arbitrary floats (a
    prefix difference rounds differently from a direct tree sum —
    both are valid f32 segment sums); identical on integer-valued
    data below the mantissa (any summation order is exact), which is
    what the parity pin in tests/test_pack_budget.py uses.

    NON-FINITE CAVEAT: prefix differences propagate non-finite values
    ACROSS segments — one +/-inf or NaN element poisons every later
    segment of its block with NaN (inf - inf), where the ladder
    isolates it to its own segment.  Sum-kind callers with possibly
    non-finite inputs must use GRAPE_PACK_SCAN=shift; the min/max
    tropical kinds (the ones that legitimately carry inf sentinels —
    SSSP/BFS/WCC) always run the ladder and are unaffected.  Pinned
    by tests/test_pack_budget.py::test_mxu_nonfinite_caveat."""
    sub = v.shape[0]
    dt = v.dtype
    tri = np.triu(np.ones((C, C), dtype=dt))
    rowcum = v @ tri
    rowcum_exc = rowcum - v
    sub1 = np.take_along_axis(rowcum_exc, ps.astype(np.int64), axis=1)
    rseg = rowcum - sub1
    gr = _mxu_group_rows(sub)
    e_last = np.zeros((C, C), dtype=dt)
    e_last[C - 1, :] = 1
    lexc = np.tril(np.ones((gr, gr), dtype=dt), -1)
    w = np.empty_like(v)
    base = np.zeros((1, v.shape[1]), dtype=dt)
    for g in range(sub // gr):
        sl = slice(g * gr, (g + 1) * gr)
        tail_g = rseg[sl] @ e_last
        s_exc_g = lexc @ tail_g
        w[sl] = s_exc_g + base
        base = base + (s_exc_g[gr - 1:gr] + tail_g[gr - 1:gr])
    srrow = np.arange(sub, dtype=np.int64)[:, None] - bk.astype(np.int64)
    return rseg + (w - np.take_along_axis(w, srrow, axis=0))


def _mxu_f0_np(ps, bk):
    """The shift ladder's segment-start-or-invalid flag, recovered
    from the mxu planes (min/max kinds on an mxu level): a slot is a
    start iff its in-row restore points at itself with no row carry;
    invalid slots encode ps = own lane, bk = 0 — also starts."""
    lane = np.arange(C, dtype=np.int64)[None, :]
    return ((ps.astype(np.int64) == lane)
            & (bk.astype(np.int64) == 0)).astype(np.float64)


def _exec_block_np(plan: PackPlan, lv: LevelPlan, blk: BlockPlan, x,
                   x_hub, in_vals, kind="sum"):
    from libgrape_lite_tpu.ops.route3 import apply_route3_np

    op, ident, wop = _KINDS[kind]
    cfg = lv.cfg
    if lv.has_gather:
        tab = np.zeros((cfg.sub, C), dtype=x.dtype)
        src = x[lv.pass_base: lv.pass_base + cfg.slots]
        tab.reshape(-1)[: len(src)] = src
        # lane-mix shuffle: tab_mixed[r, l] = tab[r, l ^ mix(r)]
        rr = np.arange(cfg.sub)[:, None]
        ll = np.arange(C)[None, :]
        tab = np.take_along_axis(
            tab, (ll ^ _row_mix(rr)).astype(np.int64), axis=1
        )
        v_tab = np.take_along_axis(
            tab, blk.sub_idx.astype(np.int64), axis=0
        )
        hub_tab = x_hub.reshape(cfg.hub // C, C)
        hs = blk.hub_sel.astype(np.int64)
        hs_c = np.maximum(hs, 0)
        v_hub = hub_tab[hs_c >> 7, hs_c & (C - 1)]
        vals = np.where(hs >= 0, v_hub, v_tab)
    else:
        vals = in_vals
    # route to row-sorted order (composed plans ship the fold merge as
    # a single sublane-gather plane; values at invalid slots are
    # arbitrary but each is its own flagged segment, so they can never
    # combine into — or be extracted as — a real row's value)
    if blk.route_rows is not None:
        routed = np.take_along_axis(
            vals.astype(np.float64),
            blk.route_rows.astype(np.int64), axis=0,
        )
    else:
        routed = apply_route3_np(vals.astype(np.float64), blk.route)
    if lv.has_gather and blk.w is not None:
        routed = wop(routed, blk.w.astype(np.float64))
    if blk.scan_mxu and kind == "sum":
        cs = _scan_np_mxu(routed, blk.ps, blk.bk)
    else:
        f0 = (_mxu_f0_np(blk.ps, blk.bk) if blk.scan_mxu
              else (blk.flags != 1).astype(np.float64))
        cs = _scan_np(routed, f0, kind, blk.scan_stages)
    if blk.tiles is not None:
        # final block: per-row-range extraction tiles concatenate into
        # the dense [vp] layout
        parts = []
        for er, ev in blk.tiles:
            ex = apply_route3_np(cs, er)
            tsub = ev.shape[0] // C
            parts.append(
                np.where(ev.reshape(tsub, C), ex, ident)
            )
        return np.concatenate(parts, axis=0)
    out = apply_route3_np(cs, blk.eroute)
    ovalid = blk.out_valid.reshape(lv.out_sub, C)
    return np.where(ovalid, out, ident)


def exec_plan_np(plan: PackPlan, x: np.ndarray, kind="sum") -> np.ndarray:
    """Numpy reference of the whole pipeline."""
    op, ident, _ = _KINDS[kind]
    x_hub = x[plan.hub_cols]
    streams = []
    lvls = list(plan.levels)
    gather_levels = [lv for lv in lvls if lv.has_gather]
    fold_levels = [lv for lv in lvls if not lv.has_gather]
    for lv in gather_levels:
        for blk in lv.blocks:
            streams.append(
                _exec_block_np(plan, lv, blk, x, x_hub, None,
                               kind).reshape(-1)
            )
    for lv in fold_levels:
        nxt = []
        i = 0
        for blk in lv.blocks:
            k = blk.n_inputs
            vals = np.concatenate(streams[i:i + k])
            i += k
            pad = lv.cfg.slots - len(vals)
            if pad:
                vals = np.concatenate([vals, np.full(pad, ident)])
            nxt.append(
                _exec_block_np(
                    plan, lv, blk, None, None,
                    vals.reshape(lv.cfg.sub, C), kind,
                ).reshape(-1)
            )
        streams = nxt
    y = np.full(plan.vp, ident, dtype=np.float64)
    i = 0
    for blk in plan.final.blocks:
        k = blk.n_inputs
        vals = np.concatenate(streams[i:i + k])
        i += k
        pad = plan.cfg.slots - len(vals)
        if pad:
            vals = np.concatenate([vals, np.full(pad, ident)])
        out = _exec_block_np(plan, plan.final, blk, None, None,
                             vals.reshape(plan.cfg.sub, C), kind)
        y = op(y, out.reshape(-1)[: plan.vp])
    return y


# --------------------------------------------------------------------------
# device executor (Pallas TPU kernels; interpret mode off-TPU)
# --------------------------------------------------------------------------


def _kernel_body(lv_has_gather: bool, sub: int, out_sub: int,
                 n_stages: int, kind: str = "sum", has_w: bool = False,
                 extract: bool = True, aligned: bool = False,
                 scan_mxu: bool = False):
    """Build the kernel function for one scan group (shapes static).

    `n_stages` is the group's span-aware scan unroll — blocks are
    batched into pallas_calls by their planned stage count, so a
    degree-1 tail block runs 0 shift-combine stages while a hub-heavy
    block runs the full ladder.  `aligned` selects the composed fold
    path: the merge route arrives as ONE sublane-gather plane (rr)
    instead of a 3-stage Route3.  `scan_mxu` selects the MXU scan
    level form: the segment restoration planes (ps, bk) arrive in
    place of the flag plane; the sum semiring rides the triangular
    -matmul prefix (see _scan_np_mxu for the math), min/max run the
    shift ladder with the flag derived as `(ps == lane) & (bk == 0)`
    (a matmul cannot evaluate a tropical prefix)."""
    import jax
    import jax.numpy as jnp

    op, ident, wop = _jnp_kind(kind)
    use_mxu = scan_mxu and kind == "sum"

    def scan_segmented(v, f):
        s = 1
        for _ in range(n_stages):
            if s < C:
                rolled_v = jnp.roll(v, s, axis=1)
                rolled_f = jnp.roll(f, s, axis=1)
                prev_v = jnp.concatenate(
                    [jnp.full((1, C), ident, v.dtype), rolled_v[:-1]],
                    axis=0,
                )
                prev_f = jnp.concatenate(
                    [jnp.ones((1, C), f.dtype), rolled_f[:-1]], axis=0
                )
                lane = jax.lax.broadcasted_iota(jnp.int32, (sub, C), 1)
                sh_v = jnp.where(lane < s, prev_v, rolled_v)
                sh_f = jnp.where(lane < s, prev_f, rolled_f)
            else:
                k = s // C
                sh_v = jnp.concatenate(
                    [jnp.full((k, C), ident, v.dtype), v[:-k]], axis=0
                )
                sh_f = jnp.concatenate(
                    [jnp.ones((k, C), f.dtype), f[:-k]], axis=0
                )
            v = op(v, jnp.where(f > 0, jnp.full_like(v, ident), sh_v))
            f = jnp.maximum(f, sh_f)
            s *= 2
        return v

    def scan_mxu_sum(v, ps, bk):
        """Segment sums from MXU prefix sums (see _scan_np_mxu)."""
        tri = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
               <= jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
               ).astype(v.dtype)
        rowcum = jnp.dot(v, tri, preferred_element_type=v.dtype)
        rowcum_exc = rowcum - v
        sub1 = jnp.take_along_axis(rowcum_exc, ps, axis=1)
        rseg = rowcum - sub1
        gr = _mxu_group_rows(sub)
        e_last = (jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
                  == (C - 1)).astype(v.dtype)
        lexc = (jax.lax.broadcasted_iota(jnp.int32, (gr, gr), 1)
                < jax.lax.broadcasted_iota(jnp.int32, (gr, gr), 0)
                ).astype(v.dtype)
        parts = []
        base = jnp.zeros((1, C), v.dtype)
        for g in range(sub // gr):
            rg = rseg[g * gr:(g + 1) * gr]
            tail_g = jnp.dot(rg, e_last,
                             preferred_element_type=v.dtype)
            s_exc_g = jnp.dot(lexc, tail_g,
                              preferred_element_type=v.dtype)
            parts.append(s_exc_g + base)
            base = base + (s_exc_g[gr - 1:gr] + tail_g[gr - 1:gr])
        w_pref = (jnp.concatenate(parts, axis=0) if len(parts) > 1
                  else parts[0])
        row = jax.lax.broadcasted_iota(jnp.int32, (sub, C), 0)
        g_w = jnp.take_along_axis(w_pref, row - bk, axis=0)
        return rseg + (w_pref - g_w)

    from libgrape_lite_tpu.ops.route3 import apply_route3

    def scan_part(vals, w_ref, route_refs, scan_refs):
        """Shared route -> segmented scan.  Values at invalid slots are
        left unmasked: every invalid slot is its own flagged segment
        (flags==0 -> f0=1; mxu planes encode ps=lane, bk=0 -> same),
        so garbage there can neither combine into a real segment nor
        be extracted — the old per-slot validity select was a no-op on
        every observable output."""
        if aligned:
            (rr_ref,) = route_refs
            routed = jnp.take_along_axis(
                vals, rr_ref[0].astype(jnp.int32), axis=0
            )
        else:
            l1_ref, s2_ref, l3_ref = route_refs
            routed = apply_route3(vals, l1_ref[0], s2_ref[0], l3_ref[0])
        if w_ref is not None:
            routed = wop(routed, w_ref[0])
        if scan_mxu:
            ps_ref, bk_ref = scan_refs
            ps = ps_ref[0].astype(jnp.int32)
            bk = bk_ref[0].astype(jnp.int32)
            if use_mxu:
                return scan_mxu_sum(routed, ps, bk)
            lane = jax.lax.broadcasted_iota(jnp.int32, (sub, C), 1)
            f0 = jnp.logical_and(ps == lane, bk == 0)
            return scan_segmented(routed, f0.astype(routed.dtype))
        (flags_ref,) = scan_refs
        f0 = (flags_ref[0].astype(jnp.int32) != 1).astype(vals.dtype)
        return scan_segmented(routed, f0)

    def tail(vals, w_ref, route_refs, scan_refs,
             el1_ref, es2_ref, el3_ref, out_ref):
        """Shared route -> segmented scan -> extraction epilogue.
        No out-validity select: unrouted compact slots carry garbage
        that stays its own flagged segment downstream."""
        cs = scan_part(vals, w_ref, route_refs, scan_refs)
        out_ref[0] = apply_route3(cs, el1_ref[0], es2_ref[0],
                                  el3_ref[0])

    def gather_vals(tab_ref, hubtab_ref, gidx_ref):
        tab = tab_ref[...]
        # undo the lane mix: tab_mixed[r, l] = tab[r, l ^ mix(r)]
        rr = jax.lax.broadcasted_iota(jnp.int32, (sub, C), 0)
        ll = jax.lax.broadcasted_iota(jnp.int32, (sub, C), 1)
        tab = jnp.take_along_axis(tab, ll ^ _row_mix(rr), axis=1)
        idx = gidx_ref[0].astype(jnp.int32)
        v_tab = jnp.take_along_axis(tab, jnp.maximum(idx, 0), axis=0)
        # hub slots encode -1 - hub_idx; the hub table is padded to
        # [sub, C] so its read is two shape-matched dynamic gathers
        # instead of a hub//C register loop.  The sublane gather's row
        # index MUST be lane-uniform (the subsequent lane gather would
        # otherwise read the row plane at post-permutation positions);
        # the planner guarantees each kernel row holds hub entries of
        # ONE 128-entry group, recovered here with a lane-wise max
        # (non-hub slots carry hs < 0 and never win; all-non-hub rows
        # read group 0 garbage that the final select discards).
        hs = -1 - idx
        hs_c = jnp.maximum(hs, 0)
        grp_row = jnp.max(hs, axis=1, keepdims=True)
        rowp = jnp.broadcast_to(
            jnp.maximum(grp_row, 0) >> 7, (sub, C)
        )
        ht = jnp.take_along_axis(hubtab_ref[...], rowp, axis=0)
        v_hub = jnp.take_along_axis(ht, hs_c & (C - 1), axis=1)
        return jnp.where(hs >= 0, v_hub, v_tab)

    if not extract:
        # final-level phase A: fold-scan only; phase B extracts per
        # row-range tile from the scanned plane
        if aligned:
            def kernel(vals_ref, rr_ref, *scan_refs):
                out_ref = scan_refs[-1]
                out_ref[0] = scan_part(vals_ref[0], None, (rr_ref,),
                                       scan_refs[:-1])
        else:
            def kernel(vals_ref, l1_ref, s2_ref, l3_ref, *scan_refs):
                out_ref = scan_refs[-1]
                out_ref[0] = scan_part(vals_ref[0], None,
                                       (l1_ref, s2_ref, l3_ref),
                                       scan_refs[:-1])

        return kernel

    if lv_has_gather and has_w:
        def kernel(tab_ref, hubtab_ref, gidx_ref, w_ref, *rest):
            route_refs, scan_refs, ext = _split_refs(rest, aligned,
                                                     scan_mxu)
            tail(gather_vals(tab_ref, hubtab_ref, gidx_ref), w_ref,
                 route_refs, scan_refs, *ext)
    elif lv_has_gather:
        def kernel(tab_ref, hubtab_ref, gidx_ref, *rest):
            route_refs, scan_refs, ext = _split_refs(rest, aligned,
                                                     scan_mxu)
            tail(gather_vals(tab_ref, hubtab_ref, gidx_ref), None,
                 route_refs, scan_refs, *ext)
    else:
        def kernel(vals_ref, *rest):
            route_refs, scan_refs, ext = _split_refs(rest, aligned,
                                                     scan_mxu)
            tail(vals_ref[0], None, route_refs, scan_refs, *ext)

    return kernel


def _split_refs(rest, aligned: bool, scan_mxu: bool):
    """Split a kernel's trailing positional refs into (route_refs,
    scan_refs, extraction refs + out_ref) per the level form."""
    n_route = 1 if aligned else 3
    n_scan = 2 if scan_mxu else 1
    return (
        tuple(rest[:n_route]),
        tuple(rest[n_route:n_route + n_scan]),
        tuple(rest[n_route + n_scan:]),
    )


def _extract_kernel_body(kind: str = "sum"):
    """Final-level phase B: extract one row-range tile from a scanned
    block (grid (block, tile); the scanned plane stays resident across
    the tile dimension)."""
    _, ident, _ = _jnp_kind(kind)

    def kernel(cs_ref, el1_ref, es2_ref, el3_ref, eval_ref, out_ref):
        import jax.numpy as jnp
        from libgrape_lite_tpu.ops.route3 import apply_route3

        ex = apply_route3(cs_ref[0], el1_ref[0, 0], es2_ref[0, 0],
                          el3_ref[0, 0])
        out_ref[0, 0] = jnp.where(eval_ref[0, 0] > 0, ex,
                                  jnp.full_like(ex, ident))

    return kernel


def _stage_order(blocks):
    """Stable ordering of a level's blocks by scan stage count — the
    device executor batches same-stage blocks into one pallas_call, so
    the stacked streams ship in this order (skel.order maps back)."""
    return np.argsort([b.scan_stages for b in blocks], kind="stable")


def _narrowed_dtype(arrs, dtype):
    """Widen rather than wrap when a stream outgrows its narrow dtype
    (the final level's es2 rows scale with vp//128, which PackConfig
    cannot bound; mxu bk planes scale with segment row span).  Widens
    to the NARROWEST integer type that holds the level's actual value
    range — the ledger prices every shipped table at this dtype."""
    if np.issubdtype(dtype, np.integer):
        lo = min(int(a.min()) for a in arrs)
        hi = max(int(a.max()) for a in arrs)
        for cand in (dtype, np.dtype(np.int16), np.dtype(np.int32)):
            info = np.iinfo(cand)
            if lo >= info.min and hi <= info.max:
                return np.dtype(cand)
        return np.dtype(np.int64)
    return np.dtype(dtype)


def _stack_blocks(lv: LevelPlan, nbytes_only: bool = False):
    """Stack a level's static block arrays into device-ready numpy, in
    scan-stage-sorted block order (see _stage_order).

    Index streams stay narrow on device (lane ids int8, row ids int16 —
    ADVICE r2: int32 streams double the VMEM bill for nothing); the
    kernel upcasts to int32 at each use site.  Lane ids are < 128 and
    block row ids < 32768 by PackConfig validation; widening is decided
    by _narrowed_dtype.

    `nbytes_only` returns each stream's exact shipped byte count
    instead of the arrays — the op-budget ledger prices HBM from the
    same dtype decisions without paying for a second full copy of
    hundreds of MB of stream tables."""
    import numpy as np

    blocks = [lv.blocks[i] for i in _stage_order(lv.blocks)]

    def st(name, get):
        arrs = [np.asarray(get(b)) for b in blocks]
        dtype = _narrowed_dtype(arrs, np.dtype(_STREAM_DTYPES[name]))
        if nbytes_only:
            return sum(a.size for a in arrs) * dtype.itemsize
        return np.stack(arrs).astype(dtype)

    if blocks[0].route_rows is not None:
        # composed fold level: the merge route is one sublane-gather
        # row plane — 3x fewer index streams than a generic Route3
        d = {"rr": st("rr", lambda b: b.route_rows)}
    else:
        d = {
            "l1": st("l1", lambda b: b.route.l1),
            "s2": st("s2", lambda b: b.route.s2),
            "l3": st("l3", lambda b: b.route.l3),
        }
    if blocks[0].scan_mxu:
        # mxu scan levels ship the restoration planes instead of the
        # flag plane (the ladder flag is derivable: ps==lane & bk==0)
        d["ps"] = st("ps", lambda b: b.ps)
        d["bk"] = st("bk", lambda b: b.bk)
    else:
        d["flags"] = st("flags", lambda b: b.flags)
    if lv.blocks[0].tiles is not None:
        # final level: per-row-range tile extraction routes
        def tst(name, get):
            arrs = [np.asarray(get(t)) for b in blocks for t in b.tiles]
            dtype = _narrowed_dtype(arrs, np.dtype(_STREAM_DTYPES[name]))
            if nbytes_only:
                return sum(a.size for a in arrs) * dtype.itemsize
            nt = len(blocks[0].tiles)
            out = np.stack(arrs).reshape(
                (len(blocks), nt) + arrs[0].shape
            )
            return out.astype(dtype)

        d["tel1"] = tst("tel1", lambda t: t[0].l1)
        d["tes2"] = tst("tes2", lambda t: t[0].s2)
        d["tel3"] = tst("tel3", lambda t: t[0].l3)
        d["teval"] = tst(
            "teval", lambda t: t[1].reshape(lv.tile_sub, C)
        )
    else:
        # no out-validity plane: unrouted compact slots carry garbage
        # that downstream levels isolate as its own flagged segment
        # (same proof that removed the scan's validity select in r6)
        d["el1"] = st("el1", lambda b: b.eroute.l1)
        d["es2"] = st("es2", lambda b: b.eroute.s2)
        d["el3"] = st("el3", lambda b: b.eroute.l3)
    if lv.has_gather:
        # one merged index plane: >= 0 is the x-table row, < 0 encodes
        # the hub slot as -1 - hub_idx (halves the gather index bytes
        # vs the old separate sub_idx/hub_sel pair)
        d["gidx"] = st(
            "gidx",
            lambda b: np.where(
                b.hub_sel >= 0,
                -1 - b.hub_sel.astype(np.int32),
                b.sub_idx.astype(np.int32),
            ),
        )
        if lv.blocks[0].w is not None:
            d["w"] = st("w", lambda b: b.w)
    return d


@dataclass(frozen=True)
class LevelSkel:
    """The static structure of one level — everything the executor
    needs besides the stream arrays themselves.  Under shard_map every
    shard runs the SAME skeleton (plan_pack_multi pads shards and
    unifies per-block scan stages to make that true); the streams
    arrive as per-shard inputs."""

    has_gather: bool
    is_final: bool
    nb: int
    out_sub: int            # compact out rows (vp//128 on the final)
    tile_sub: int           # final: rows per extraction tile (else 0)
    pass_idx: int           # gather: index into the x pass stack
    has_w: bool
    n_inputs: tuple         # per block: input streams consumed
    # span-aware scan batching: ((stages, nblocks), ...) over the
    # stage-sorted block order the streams ship in, and the map from
    # sorted position back to original block index
    scan_groups: tuple = ()
    order: tuple = ()
    # composed fold level: merge route ships as one sublane-gather
    # plane ("rr") instead of a 3-stage Route3
    aligned: bool = False
    # MXU scan level: ps/bk restoration planes ship instead of flags;
    # kind=="sum" rides the triangular-matmul prefix, min/max the
    # ladder with the derived flag
    mxu: bool = False


def _skel_of(lv: LevelPlan, span: int) -> LevelSkel:
    order = tuple(int(i) for i in _stage_order(lv.blocks))
    groups: list[list[int]] = []
    for pos in order:
        s = int(lv.blocks[pos].scan_stages)
        if groups and groups[-1][0] == s:
            groups[-1][1] += 1
        else:
            groups.append([s, 1])
    return LevelSkel(
        has_gather=lv.has_gather,
        is_final=lv.blocks[0].tiles is not None if lv.blocks else False,
        nb=len(lv.blocks),
        out_sub=lv.out_sub,
        tile_sub=lv.tile_sub,
        pass_idx=lv.pass_base // span if lv.has_gather else 0,
        has_w=lv.has_gather and lv.blocks[0].w is not None,
        n_inputs=tuple(b.n_inputs for b in lv.blocks),
        scan_groups=tuple((s, c) for s, c in groups),
        order=order,
        aligned=bool(lv.blocks
                     and lv.blocks[0].route_rows is not None),
        mxu=bool(lv.blocks and lv.blocks[0].scan_mxu),
    )


def _level_device(plan: PackPlan, key, lv: LevelPlan):
    import jax.numpy as jnp

    if key not in plan._device:
        plan._device[key] = {
            k: jnp.asarray(v) for k, v in _stack_blocks(lv).items()
        }
    return plan._device[key]


def _run_level_dev(cfg: PackConfig, skel: LevelSkel, dev, x_tab, hub_tab,
                   in_streams, interpret: bool, kind: str = "sum"):
    """Run one level's pallas_call(s) from its skeleton + stream dict;
    returns list of per-block flat output streams (traced jnp arrays)
    in ORIGINAL block order (downstream consumption order and the
    final summation order are bit-load-bearing).

    Blocks are batched by their span-aware scan stage count — one
    pallas_call per (stages, count) group over the stage-sorted stream
    stacks, so each group unrolls exactly the stages its segments need.
    Final levels run two phases: a fold-scan over each block, then a
    (block, row-tile) extraction grid whose VMEM residency is bounded
    by tile_sub regardless of vp."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    nb = skel.nb
    sub, out_sub = cfg.sub, skel.out_sub
    has_w = skel.has_gather and skel.has_w
    max_stages = max(1, int(np.ceil(np.log2(sub * C))))
    groups = skel.scan_groups or ((max_stages, nb),)
    order = skel.order or tuple(range(nb))

    def bspec(shape_sub):
        return pl.BlockSpec((1, shape_sub, C), lambda i: (i, 0, 0))

    def fold_input_list():
        # assemble the ragged fold inputs into per-block [sub, C]
        # planes, original block order (all offsets static; these are
        # plain XLA concats/reshapes)
        parts = []
        off = 0
        for k in skel.n_inputs:
            segs = in_streams[off:off + k]
            ln = sum(s.shape[0] for s in segs)
            pad = cfg.slots - ln
            if pad:
                ident = _KINDS[kind][1]
                segs = segs + [
                    jnp.full((pad,), ident, segs[0].dtype)
                ]
            parts.append(jnp.concatenate(segs).reshape(sub, C))
            off += k
        return parts

    if skel.aligned:
        route_in = [dev["rr"]]
        route_specs = [bspec(sub)]
    else:
        rmid = dev["s2"].shape[-2]
        route_in = [dev["l1"], dev["s2"], dev["l3"]]
        route_specs = [bspec(rmid), bspec(rmid), bspec(sub)]
    if skel.mxu:
        route_in += [dev["ps"], dev["bk"]]
        route_specs += [bspec(sub), bspec(sub)]
    else:
        route_in.append(dev["flags"])
        route_specs.append(bspec(sub))

    def unsort(outs_sorted):
        outs = [None] * nb
        for spos, o in enumerate(outs_sorted):
            outs[order[spos]] = o
        return outs

    if skel.is_final:
        # ---- phase A: fold-scan each block to its scanned plane ----
        parts = fold_input_list()
        parts_sorted = [parts[i] for i in order]
        cs_sorted = []
        off = 0
        for stages, cnt in groups:
            scan_kernel = _kernel_body(False, sub, sub, stages,
                                       kind, False, extract=False,
                                       aligned=skel.aligned,
                                       scan_mxu=skel.mxu)
            cs = pl.pallas_call(
                scan_kernel,
                grid=(cnt,),
                in_specs=[bspec(sub)] + route_specs,
                out_specs=bspec(sub),
                out_shape=jax.ShapeDtypeStruct((cnt, sub, C),
                                               jnp.float32),
                interpret=interpret,
            )(jnp.stack(parts_sorted[off:off + cnt]),
              *[a[off:off + cnt] for a in route_in])
            cs_sorted.extend(cs[b] for b in range(cnt))
            off += cnt

        # ---- phase B: extract row-range tiles (tile streams are
        # stacked in the same stage-sorted order) ----
        nt = dev["tel1"].shape[1]
        tile_sub = skel.tile_sub
        ermid = dev["tes2"].shape[-2]
        ex_kernel = _extract_kernel_body(kind)

        def tspec(shape_sub):
            return pl.BlockSpec(
                (1, 1, shape_sub, C), lambda i, j: (i, j, 0, 0)
            )

        out = pl.pallas_call(
            ex_kernel,
            grid=(nb, nt),
            in_specs=[
                pl.BlockSpec((1, sub, C), lambda i, j: (i, 0, 0)),
                tspec(ermid), tspec(ermid), tspec(tile_sub),
                tspec(tile_sub),
            ],
            out_specs=tspec(tile_sub),
            out_shape=jax.ShapeDtypeStruct(
                (nb, nt, tile_sub, C), jnp.float32
            ),
            interpret=interpret,
        )(jnp.stack(cs_sorted), dev["tel1"], dev["tes2"], dev["tel3"],
          dev["teval"])
        return unsort([out[b].reshape(-1) for b in range(nb)])

    ermid = dev["es2"].shape[-2]
    common_in = route_in + [
        dev["el1"], dev["es2"], dev["el3"],
    ]
    common_specs = route_specs + [
        bspec(ermid), bspec(ermid), bspec(out_sub),
    ]

    if skel.has_gather:
        stacked = [dev["gidx"]]
        stacked_specs = [bspec(sub)]
        if has_w:
            stacked.append(dev["w"])
            stacked_specs.append(bspec(sub))
        stacked += common_in
        stacked_specs += common_specs
        invariant = [x_tab, hub_tab]
        inv_specs = [
            pl.BlockSpec((sub, C), lambda i: (0, 0)),
            pl.BlockSpec((sub, C), lambda i: (0, 0)),
        ]
        parts_sorted = None
    else:
        stacked = common_in
        stacked_specs = common_specs
        invariant = []
        inv_specs = []
        parts = fold_input_list()
        parts_sorted = [parts[i] for i in order]

    outs_sorted = []
    off = 0
    for stages, cnt in groups:
        kernel = _kernel_body(skel.has_gather, sub, out_sub,
                              stages, kind, has_w, aligned=skel.aligned,
                              scan_mxu=skel.mxu)
        args = list(invariant)
        specs = list(inv_specs)
        if parts_sorted is not None:
            args.append(jnp.stack(parts_sorted[off:off + cnt]))
            specs.append(bspec(sub))
        args += [a[off:off + cnt] for a in stacked]
        specs += stacked_specs
        out = pl.pallas_call(
            kernel,
            grid=(cnt,),
            in_specs=specs,
            out_specs=bspec(out_sub),
            out_shape=jax.ShapeDtypeStruct((cnt, out_sub, C),
                                           jnp.float32),
            interpret=interpret,
        )(*args)
        outs_sorted.extend(out[b].reshape(-1) for b in range(cnt))
        off += cnt
    return unsort(outs_sorted)


def _exec_levels(x, cfg: PackConfig, vp: int, n_cols: int, level_list,
                 hub_cols, kind: str, interpret: bool | None):
    """Run the whole pipeline given [(LevelSkel, stream dict)] with the
    final level last.  `hub_cols` is a [cfg.hub] index array (traced or
    constant).  This is the shared engine behind the closed-over
    single-shard path and the streams-from-state multi-shard path."""
    import jax.numpy as jnp

    if interpret is None:
        from libgrape_lite_tpu.ops.pallas_kernels import use_pallas

        interpret = not use_pallas()

    x = jnp.asarray(x, jnp.float32)
    if not level_list:
        # zero-edge plan: nothing to gather or fold
        return jnp.full((vp,), _KINDS[kind][1], jnp.float32)

    span = cfg.slots
    n_pass = max(1, -(-n_cols // span))
    x_pad = jnp.concatenate(
        [x, jnp.zeros((n_pass * span - n_cols,), x.dtype)]
    ) if n_pass * span != n_cols else x
    x_passes = x_pad.reshape(n_pass, cfg.sub, C)
    # hub table padded to [sub, C]: Mosaic's sublane dynamic gather
    # requires table shape == index shape, so the kernel reads hubs
    # with two shape-matched gathers instead of a register loop
    hub_tab = jnp.concatenate([
        x[hub_cols].reshape(cfg.hub // C, C),
        jnp.zeros((cfg.sub - cfg.hub // C, C), x.dtype),
    ]) if cfg.sub > cfg.hub // C else x[hub_cols].reshape(cfg.sub, C)

    streams = []
    for skel, dev in level_list[:-1]:
        if skel.has_gather:
            streams += _run_level_dev(
                cfg, skel, dev, x_passes[skel.pass_idx], hub_tab, None,
                interpret, kind,
            )
        else:
            streams = _run_level_dev(cfg, skel, dev, None, None,
                                     streams, interpret, kind)
    fskel, fdev = level_list[-1]
    outs = _run_level_dev(cfg, fskel, fdev, None, None, streams,
                          interpret, kind)
    op, _, _ = _jnp_kind(kind)
    y = outs[0]
    for o in outs[1:]:
        y = op(y, o)
    return y[:vp]


def segment_reduce_pack(x, plan: PackPlan, kind: str = "sum",
                        interpret: bool | None = None):
    """Run the full pack-gather segment-reduce pipeline: y[vp] f32.

    kind selects the semiring: "sum" (weights multiply — classic
    SpMV), "min"/"max" (weights add — the tropical relaxation of
    SSSP/BFS; rows with no edges yield the identity, matching
    jax.ops.segment_min).  One plan serves every kind.  "sum" under
    the default MXU scan assumes FINITE inputs (prefix differences
    spread a non-finite value across its block — see _scan_np_mxu);
    min/max carry inf sentinels safely (they always run the ladder).

    Usable inside jit; all static structure is closed over as device
    constants.  `interpret=None` auto-selects compiled-on-TPU.
    """
    import jax.numpy as jnp

    if not plan.final or not plan.final.blocks:
        return jnp.full((plan.vp,), _KINDS[kind][1], jnp.float32)

    span = plan.cfg.slots
    level_list = []
    for li, lv in enumerate(plan.levels):
        key = ("g" if lv.has_gather else "f", li)
        level_list.append((_skel_of(lv, span), _level_device(plan, key, lv)))
    level_list.append((
        _skel_of(plan.final, span),
        _level_device(plan, ("final",), plan.final),
    ))
    return _exec_levels(x, plan.cfg, plan.vp, plan.n_cols, level_list,
                        jnp.asarray(plan.hub_cols), kind, interpret)


def segment_sum_pack(x, plan: PackPlan, interpret: bool | None = None):
    """Back-compat alias: segment_reduce_pack(kind="sum")."""
    return segment_reduce_pack(x, plan, "sum", interpret)


# --------------------------------------------------------------------------
# multi-shard plans: uniform structure + per-shard streams
# --------------------------------------------------------------------------


@dataclass
class MultiPackPlan:
    """Per-shard pack plans with one shared skeleton.

    Under `shard_map` every device runs the same traced program, so
    the level/block structure must be identical across shards; the
    shard-specific stream arrays are stacked `[fnum, ...]` and flow in
    as sharded state inputs (the app declares them `ephemeral_keys`).
    The reference analogue: the CUDA LB kernels run the same grid on
    every GPU of the mesh (`cuda/parallel/parallel_engine.h:989-1013`)
    with per-GPU data."""

    vp: int
    n_cols: int
    cfg: PackConfig
    fnum: int
    skels: List[LevelSkel]               # ordered; final level last
    host_streams: dict                   # name -> [fnum, ...] numpy
    uid: int = field(default_factory=lambda: next(_PLAN_COUNTER))
    # static op-budget ledger (summed across shards; see plan_ledger)
    ledger: Optional[dict] = None

    def state_entries(self, prefix: str) -> dict:
        """Numpy state entries ([fnum, ...] leaves) to merge into the
        app's init state; list them in the app's `ephemeral_keys`."""
        return {prefix + k: v for k, v in self.host_streams.items()}

    def state_keys(self, prefix: str):
        return [prefix + k for k in self.host_streams]


def plan_pack_multi(shards, vp: int, n_cols: int,
                    cfg: PackConfig = PackConfig()) -> MultiPackPlan:
    """Build per-shard plans with a uniform skeleton.

    shards: per fragment (rows, cols, w-or-None) CSR-sorted edge lists
    (rows are shard-local in [0, vp); cols index the gathered
    [n_cols] state).  Gather-level block counts are padded to the
    per-pass maximum with empty blocks; mid folds are skipped (their
    grouping is data-dependent) — the capacity-grouped final level
    absorbs the streams uniformly."""
    assert vp % C == 0
    if vp // C > _MAX_VP_SUB:
        raise ValueError(
            f"vp={vp} exceeds {_MAX_VP_SUB * C} rows per shard plan"
        )
    fnum = len(shards)
    has_w = shards[0][2] is not None
    span = cfg.slots

    per_gather = []
    hubs = []
    for rows, cols, w in shards:
        rows = np.asarray(rows, dtype=np.int64)
        cols = np.asarray(cols, dtype=np.int64)
        assert (np.diff(rows) >= 0).all(), "edges must be row-sorted"
        assert (w is None) == (not has_w), "weighted-ness must be uniform"
        glv, hub = _plan_shard_gather(rows, cols, vp, n_cols, cfg, w)
        per_gather.append(glv)
        hubs.append(hub)

    pass_idxs = sorted({p for glv in per_gather for p in glv})
    levels_per_shard: list[list[LevelPlan]] = [[] for _ in range(fnum)]
    for p in pass_idxs:
        nb = max(
            len(glv[p].blocks) if p in glv else 0 for glv in per_gather
        )
        for f, glv in enumerate(per_gather):
            lv = glv.get(p)
            if lv is None:
                lv = LevelPlan(cfg=cfg, blocks=[], has_gather=True,
                               pass_base=p * span, out_sub=cfg.out_sub)
            while len(lv.blocks) < nb:
                lv.blocks.append(_empty_gather_block(cfg, p * span,
                                                     has_w))
            levels_per_shard[f].append(lv)

    # route composition must produce ONE skeleton: engage the aligned
    # final level only when every shard's stream set is feasible.
    # Group preps (the per-group merge argsort) are computed once per
    # shard and shared with the final-level planner below.
    per_shard_streams = [
        _level_streams(levels_per_shard[f]) for f in range(fnum)
    ]
    per_shard_groups = [
        _final_groups(s, cfg) for s in per_shard_streams
    ]
    per_shard_preps = [
        [_group_prep(g) for g in grps] for grps in per_shard_groups
    ]
    aligned_final = _compose_enabled() and all(
        _aligned_feasible(g, cfg, p)
        for grps, preps in zip(per_shard_groups, per_shard_preps)
        for g, p in zip(grps, preps)
    )
    all_levels: list[list[LevelPlan]] = []
    for f in range(fnum):
        final = _plan_final_level(per_shard_streams[f], vp, cfg,
                                  aligned=aligned_final,
                                  preps=per_shard_preps[f])
        all_levels.append(levels_per_shard[f] + [final])
    # span-aware scans unroll a static stage count; under shard_map all
    # shards run one traced program, so unify each block's stages to
    # the per-block max across shards (extra stages are bit-exact
    # no-ops for the shard that needed fewer), then decide the
    # level-wide scan form from the ALL-shard block set so every
    # shard's skeleton engages identically
    for li in range(len(all_levels[0])):
        for bj in range(len(all_levels[0][li].blocks)):
            s = max(all_levels[f][li].blocks[bj].scan_stages
                    for f in range(fnum))
            for f in range(fnum):
                all_levels[f][li].blocks[bj].scan_stages = s
        blocks_all = [b for f in range(fnum)
                      for b in all_levels[f][li].blocks]
        mxu = _decide_level_scan(blocks_all)
        for b in blocks_all:
            b.scan_mxu = mxu
            b.ledger = _reledger_block(cfg, b)

    if not pass_idxs:
        # zero edges on every shard
        return MultiPackPlan(
            vp=vp, n_cols=n_cols, cfg=cfg, fnum=fnum, skels=[],
            host_streams={"hub_cols": np.stack(hubs)},
        )

    skels = [_skel_of(lv, span) for lv in all_levels[0]]
    for f in range(1, fnum):
        got = [_skel_of(lv, span) for lv in all_levels[f]]
        assert got == skels, (
            f"shard {f} skeleton diverged from shard 0 — "
            "plan_pack_multi padding is broken"
        )

    host_streams = {}
    for i in range(len(skels)):
        per_shard = [_stack_blocks(all_levels[f][i]) for f in range(fnum)]
        for name in per_shard[0]:
            arrs = [d[name] for d in per_shard]
            dt = np.result_type(*[a.dtype for a in arrs])
            host_streams[f"L{i}_{name}"] = np.stack(
                [a.astype(dt) for a in arrs]
            )
    host_streams["hub_cols"] = np.stack(hubs)
    _warn_vmem(cfg, has_w=has_w, final_out_sub=all_levels[0][-1].tile_sub)
    return MultiPackPlan(
        vp=vp, n_cols=n_cols, cfg=cfg, fnum=fnum, skels=skels,
        host_streams=host_streams,
        ledger=_ledger_of_levels(all_levels, n_cols, cfg),
    )


def segment_reduce_pack_sharded(x, mplan: MultiPackPlan, streams: dict,
                                kind: str = "sum",
                                interpret: bool | None = None,
                                prefix: str = ""):
    """The multi-shard executor: runs inside shard_map with this
    shard's squeezed stream arrays (pulled from the app state by the
    caller, keys as produced by `state_entries(prefix)`)."""
    level_list = []
    for i, skel in enumerate(mplan.skels):
        dev = {}
        want = f"{prefix}L{i}_"
        for k, v in streams.items():
            if k.startswith(want):
                dev[k[len(want):]] = v
        level_list.append((skel, dev))
    return _exec_levels(
        x, mplan.cfg, mplan.vp, mplan.n_cols, level_list,
        streams[prefix + "hub_cols"], kind, interpret,
    )


# --------------------------------------------------------------------------
# fragment-level entry point
# --------------------------------------------------------------------------

_FRAG_PLAN_CACHE = None
_INELIGIBLE_WARNED: set = set()


def warn_pack_ineligible(app_name: str, reason: str):
    """GRAPE_SPMV=pack was requested but the app fell back to XLA —
    say so once (ADVICE r2: a silent fallback lets an explicit pack
    A/B quietly measure the wrong path).  GRAPE_SPMV_STRICT=1 turns
    the fallback into an error for benchmark harnesses."""
    import os
    import warnings

    key = (app_name, reason)
    if os.environ.get("GRAPE_SPMV_STRICT"):
        raise RuntimeError(
            f"GRAPE_SPMV=pack requested but {app_name} is ineligible: "
            f"{reason} (GRAPE_SPMV_STRICT=1)"
        )
    if key not in _INELIGIBLE_WARNED:
        _INELIGIBLE_WARNED.add(key)
        warnings.warn(
            f"GRAPE_SPMV=pack requested but {app_name} falls back to the "
            f"XLA path: {reason}",
            stacklevel=3,
        )


def _frag_cache(frag):
    global _FRAG_PLAN_CACHE
    import weakref

    if _FRAG_PLAN_CACHE is None:
        _FRAG_PLAN_CACHE = weakref.WeakKeyDictionary()
    return _FRAG_PLAN_CACHE.setdefault(frag, {})


def _shard_edges(frag, fid: int, with_weights: bool, direction: str,
                 cols_override=None, row_mask=None):
    csrs = frag.host_ie if direction == "ie" else frag.host_oe
    h = csrs[fid] if csrs else (frag.host_oe[fid])
    mask = h.edge_mask
    if row_mask is not None:
        # boundary/interior sub-plan (superstep pipelining, r9): keep
        # only edges whose destination row is in this partition — the
        # original CSR order is preserved, so each surviving row's
        # fold sees its candidates in the serial order
        safe_src = np.minimum(h.edge_src.astype(np.int64), frag.vp - 1)
        mask = np.logical_and(mask, np.asarray(row_mask[fid])[safe_src])
    rows = h.edge_src[mask].astype(np.int64)
    if cols_override is not None:
        cols = np.asarray(cols_override[fid])[mask].astype(np.int64)
    else:
        cols = h.edge_nbr[mask].astype(np.int64)
    w = None
    if with_weights:
        if h.edge_w is None:
            return None
        w = h.edge_w[mask]
    return rows, cols, w


def plan_pack_for_fragment(frag, cfg: PackConfig = PackConfig(),
                           with_weights: bool = False,
                           direction: str = "ie"):
    """Build (and cache per fragment) the single-shard pack plan for
    `frag`'s dense pull: rows = local edge_src, cols = pid edge_nbr
    into the gathered [fnum*vp] state; `with_weights` bakes the f32
    edge-weight stream in (the tropical SSSP relaxation).  Multi-shard
    fragments use `plan_pack_multi_for_fragment` (uniform skeleton +
    per-shard streams) instead."""
    if frag.fnum != 1:
        return None
    per_frag = _frag_cache(frag)
    key = (cfg, with_weights, direction, "single", _scan_mode())
    if key in per_frag:
        return per_frag[key]
    shard = _shard_edges(frag, 0, with_weights, direction)
    if shard is None:
        return None
    rows, cols, w = shard
    plan = plan_pack(rows, cols, frag.vp, frag.fnum * frag.vp, cfg,
                     edge_w=w)
    per_frag[key] = plan
    return plan


def plan_pack_multi_for_fragment(frag, cfg: PackConfig = PackConfig(),
                                 with_weights: bool = False,
                                 direction: str = "ie"):
    """Build (and cache per fragment) the MultiPackPlan covering every
    shard of `frag` — the pack path's multi-chip form (VERDICT r2
    missing #2: the perf path and the mesh must compose)."""
    per_frag = _frag_cache(frag)
    key = (cfg, with_weights, direction, "multi", _scan_mode())
    if key in per_frag:
        return per_frag[key]
    shards = []
    for f in range(frag.fnum):
        shard = _shard_edges(frag, f, with_weights, direction)
        if shard is None:
            return None
        shards.append(shard)
    mplan = plan_pack_multi(shards, frag.vp, frag.fnum * frag.vp, cfg)
    per_frag[key] = mplan
    return mplan


def pack_plan_to_multi(plan: PackPlan) -> MultiPackPlan:
    """Convert a single-shard PackPlan into the skeleton + streams form
    (fnum=1), which is what PackDispatch executes and the plan cache
    persists — the mid-fold levels the single-shard planner builds
    carry over as ordinary fold skeleton entries."""
    span = plan.cfg.slots
    if not plan.final or not plan.final.blocks:
        return MultiPackPlan(
            vp=plan.vp, n_cols=plan.n_cols, cfg=plan.cfg, fnum=1,
            skels=[], host_streams={"hub_cols": plan.hub_cols[None]},
        )
    skels, streams = [], {}
    for i, lv in enumerate(list(plan.levels) + [plan.final]):
        skels.append(_skel_of(lv, span))
        for k, v in _stack_blocks(lv).items():
            streams[f"L{i}_{k}"] = v[None]
    streams["hub_cols"] = plan.hub_cols[None]
    return MultiPackPlan(
        vp=plan.vp, n_cols=plan.n_cols, cfg=plan.cfg, fnum=1,
        skels=skels, host_streams=streams, ledger=plan_ledger(plan),
    )


class PackDispatch:
    """One resolved pack backend for a (fragment, direction) pull, so
    apps dispatch through one object instead of duplicating the fnum
    branch (PageRank/SSSP/WCC/BFS all share this).

    mode "const": single-shard — stream tables close over the trace as
    device constants (cached here), no state plumbing.
    mode "state": multi-shard — per-shard streams ride in as sharded
    ephemeral state leaves (closing over them under shard_map would
    replicate every shard's tables to every device)."""

    def __init__(self, mplan: MultiPackPlan, mode: str, prefix: str):
        assert mode in ("const", "state")
        self.mplan = mplan
        self.mode = mode
        self.prefix = prefix
        self._const = None

    @property
    def uid(self) -> int:
        return self.mplan.uid

    def ledger(self) -> Optional[dict]:
        """The plan's static op-budget ledger (None for plans loaded
        from a pre-ledger cache entry — impossible under the current
        schema, kept for safety)."""
        return self.mplan.ledger

    def state_entries(self) -> dict:
        """Ephemeral state leaves ([fnum, ...] numpy) the app must merge
        into its init state (empty on the const path)."""
        if self.mode == "const":
            return {}
        return self.mplan.state_entries(self.prefix)

    def reduce(self, x, state, kind: str = "sum",
               interpret: bool | None = None):
        """y[vp] = segment-reduce of x over the planned edges."""
        import jax

        if self.mode == "const":
            import jax.numpy as jnp

            if self._const is None:
                self._const = {
                    k: jnp.asarray(v[0])
                    for k, v in self.mplan.host_streams.items()
                }
            streams, prefix = self._const, ""
        else:
            streams = {
                k: state[k] for k in self.mplan.state_keys(self.prefix)
            }
            prefix = self.prefix
        with jax.named_scope("grape.pull.pack"):
            return segment_reduce_pack_sharded(
                x, self.mplan, streams, kind, interpret, prefix=prefix
            )


# resolve-path counters: how often a pack resolve was served from the
# per-fragment cache vs the on-disk plan cache vs the O(E log E)
# planner.  serve/ pins "a session's second query performs ZERO pack
# planning" on `planned` staying flat (tests/test_serve.py).
# Federated as "plan" (obs/federation.py): a dict subclass, so the
# hot-path `PLAN_STATS[...] += 1` sites below are unchanged.
from libgrape_lite_tpu.obs.federation import FederatedStats as _FedStats

PLAN_STATS = _FedStats("plan", {
    "frag_cache_hits": 0, "disk_cache_hits": 0, "planned": 0,
})


def plan_stats() -> dict:
    """Snapshot of the resolve-path counters (copy — mutation-safe).
    When a superstep pipeline has been resolved (GRAPE_PIPELINE,
    parallel/pipeline.py), the snapshot additionally carries its
    boundary/interior vertex+edge counts per fragment under
    "pipeline" — the boundary-set stats surfaced everywhere the plan
    is (Worker.pack_ledger, trace_report)."""
    out = dict(PLAN_STATS)
    try:
        from libgrape_lite_tpu.parallel.pipeline import PIPELINE_STATS

        if PIPELINE_STATS["last_stats"] is not None:
            out["pipeline"] = {
                "resolved": PIPELINE_STATS["resolved"],
                "declined": PIPELINE_STATS["declined"],
                **PIPELINE_STATS["last_stats"],
            }
    except ImportError:  # pragma: no cover — circular-import safety
        pass
    return out


def resolve_pack_dispatch(frag, cfg: PackConfig | None = None,
                          with_weights: bool = False,
                          direction: str = "ie",
                          prefix: str = "pk_",
                          mirror=None,
                          role: str = "full",
                          row_mask=None):
    """Resolve the pack backend for `frag`: a PackDispatch, or None if
    no plan is buildable (caller should warn_pack_ineligible).  Checks
    the persistent plan cache (GRAPE_PACK_PLAN_CACHE) before running
    the O(E log E) host planner, and saves fresh plans into it.

    `mirror` (a parallel.mirror.MirrorPlan for the same direction)
    composes the plan with the mirror-compressed exchange: columns are
    the compact remapped ones and the gather table covers only
    vp + fnum*m entries instead of fnum*vp.

    `role`/`row_mask` (superstep pipelining, r9): "boundary" /
    "interior" sub-plans cover only edges whose destination row is in
    `row_mask` [fnum, vp], so the SpMV can run the boundary slice
    first and overlap the exchange with the interior slice.  The role
    is part of BOTH the per-fragment cache key and the v3 plan-cache
    digest — the disk cache must never serve a serial (full) plan to
    a pipelined run or vice versa, even if a future filter made their
    edge streams collide."""
    cfg = cfg or PackConfig.from_env()
    per_frag = _frag_cache(frag)
    key = (cfg, with_weights, direction, "dispatch",
           mirror.uid if mirror is not None else 0, _scan_mode(), role)
    if key in per_frag:
        mplan = per_frag[key]
        PLAN_STATS["frag_cache_hits"] += 1
        return PackDispatch(
            mplan, "const" if frag.fnum == 1 else "state", prefix
        )

    cols_override = mirror.nbr_compact if mirror is not None else None
    # 2-D vertex-cut tiles (fragment/vertexcut.py) gather from the
    # LOCAL [vc] column-broadcast chunk, not the [fnum*vp] all-gather
    # table — the fragment declares its pass-table width
    tile_cols = getattr(frag, "pack_n_cols", None)
    n_cols = (
        mirror.n_compact if mirror is not None
        else tile_cols if tile_cols is not None
        else frag.fnum * frag.vp
    )
    shards = []
    for f in range(frag.fnum):
        shard = _shard_edges(frag, f, with_weights, direction,
                             cols_override, row_mask)
        if shard is None:
            return None
        shards.append(shard)

    mplan = _load_cached_mplan(shards, frag.vp, n_cols, cfg, role)
    if mplan is not None:
        PLAN_STATS["disk_cache_hits"] += 1
    else:
        PLAN_STATS["planned"] += 1
        if row_mask is not None or tile_cols is not None:
            # sub-plans and per-tile plans always take the multi
            # planner (uniform skeleton over the per-shard streams)
            mplan = plan_pack_multi(shards, frag.vp, n_cols, cfg)
        elif mirror is not None:
            mplan = plan_pack_multi(shards, frag.vp, n_cols, cfg)
        elif frag.fnum == 1:
            plan = plan_pack_for_fragment(frag, cfg, with_weights,
                                          direction)
            if plan is None:
                return None
            mplan = pack_plan_to_multi(plan)
        else:
            mplan = plan_pack_multi_for_fragment(frag, cfg, with_weights,
                                                 direction)
            if mplan is None:
                return None
        _save_cached_mplan(mplan, shards, role)
    per_frag[key] = mplan
    return PackDispatch(
        mplan, "const" if frag.fnum == 1 else "state", prefix
    )


# ---- persistent plan cache (VERDICT r2 next #5) --------------------------
#
# The reference amortises load-time work with a content-addressed
# fragment cache (`basic_fragment_loader_base.h:127-242`); pack plans
# are the analogous load-time product here.  Keyed by a digest of the
# exact edge streams + geometry + schema version, stored as one .npz of
# the stacked stream tables under $GRAPE_PACK_PLAN_CACHE.

_PLAN_SCHEMA_VERSION = 3

# the narrow target dtype of every shipped stream table, in one place
# so the plan-cache digest fingerprints the dtype layout a plan was
# built with — widening beyond the target is value-driven
# (_narrowed_dtype) and thus already a function of the digested edge
# streams
_STREAM_DTYPES = {
    "rr": "int16", "l1": "int8", "s2": "int16", "l3": "int8",
    "flags": "int8", "ps": "int8", "bk": "int8",
    "el1": "int8", "es2": "int16", "el3": "int8",
    "tel1": "int8", "tes2": "int16", "tel3": "int8", "teval": "int8",
    "gidx": "int16", "w": "float32",
}


def _shards_digest(shards, vp: int, n_cols: int, cfg: PackConfig,
                   role: str = "full") -> str:
    """Content key for cached plans.  The config prefix fingerprints
    the FULL PackConfig (every dataclass field, so a future knob can't
    silently alias two configs), the input stream dtypes, the shipped
    stream dtype table, the schema version and the planner modes —
    including GRAPE_PACK_SCAN, so a scan-mode flip invalidates stale
    cached plans instead of loading ones whose shipped planes belong
    to the other kernel family, and the pipeline `role`
    (full/boundary/interior), so the cache can never hand a serial
    plan to a pipelined run even if the filtered edge streams were to
    coincide (r9; the threshold decision IS the role)."""
    import dataclasses
    import hashlib

    from libgrape_lite_tpu.ft.fingerprint import stable_config_digest

    cfg_fp = stable_config_digest({
        "schema": _PLAN_SCHEMA_VERSION,
        "cfg": dataclasses.asdict(cfg),
        "final_tile_sub": _FINAL_TILE_SUB,
        "compose": _compose_enabled(),
        "scan": _scan_mode(),
        "role": role,
        "stream_dtypes": _STREAM_DTYPES,
        "vp": vp,
        "n_cols": n_cols,
        "dtypes": [
            [str(np.asarray(r).dtype), str(np.asarray(c).dtype),
             None if w is None else str(np.asarray(w).dtype)]
            for r, c, w in shards
        ],
    })
    h = hashlib.sha256()
    h.update(cfg_fp.encode())
    for rows, cols, w in shards:
        h.update(np.ascontiguousarray(rows, np.int64).tobytes())
        h.update(np.ascontiguousarray(cols, np.int64).tobytes())
        h.update(b"w" if w is not None else b"-")
        if w is not None:
            h.update(np.ascontiguousarray(w, np.float32).tobytes())
    return h.hexdigest()[:24]


def _plan_cache_path(shards, vp, n_cols, cfg, role: str = "full"):
    import os

    root = os.environ.get("GRAPE_PACK_PLAN_CACHE")
    if not root:
        return None
    return os.path.join(
        root,
        f"packplan_{_shards_digest(shards, vp, n_cols, cfg, role)}.npz",
    )


def _save_cached_mplan(mplan: MultiPackPlan, shards, role: str = "full"):
    import dataclasses
    import json
    import os

    path = _plan_cache_path(shards, mplan.vp, mplan.n_cols, mplan.cfg,
                            role)
    if path is None:
        return
    os.makedirs(os.path.dirname(path), exist_ok=True)
    meta = {
        "vp": mplan.vp,
        "n_cols": mplan.n_cols,
        "fnum": mplan.fnum,
        "cfg": [mplan.cfg.sub, mplan.cfg.out_sub, mplan.cfg.hub],
        "skels": [dataclasses.asdict(s) for s in mplan.skels],
        "ledger": mplan.ledger,
    }
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(
            f,
            __meta=np.frombuffer(
                json.dumps(meta).encode(), dtype=np.uint8
            ).copy(),
            **mplan.host_streams,
        )
    os.replace(tmp, path)


def _load_cached_mplan(shards, vp, n_cols, cfg, role: str = "full"):
    import json
    import os

    path = _plan_cache_path(shards, vp, n_cols, cfg, role)
    if path is None or not os.path.exists(path):
        return None
    try:
        z = np.load(path)
        meta = json.loads(bytes(z["__meta"]))
        if (meta["vp"], meta["n_cols"]) != (vp, n_cols):
            return None
        skels = [
            LevelSkel(**{
                **d,
                "n_inputs": tuple(d["n_inputs"]),
                "scan_groups": tuple(
                    (int(s), int(c)) for s, c in d.get("scan_groups", ())
                ),
                "order": tuple(int(i) for i in d.get("order", ())),
            })
            for d in meta["skels"]
        ]
        streams = {k: z[k] for k in z.files if k != "__meta"}
        return MultiPackPlan(
            vp=vp, n_cols=n_cols, cfg=cfg, fnum=meta["fnum"],
            skels=skels, host_streams=streams,
            ledger=meta.get("ledger"),
        )
    except Exception:
        return None  # corrupt/stale cache entries are rebuilt
