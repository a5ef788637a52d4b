#!/usr/bin/env python
"""Analytic cycle/byte model of the pack-gather SpMV pipeline — the
no-hardware fallback for pricing `ops/spmv_pack.py` (VERDICT r3 next
#1: without a chip, ship cycle estimates derived from the real plan,
not hand-waved constants).

r6: the model CONSUMES the planner's static op-budget ledger
(`spmv_pack.plan_ledger` — exact per-stage op counts annotated on
every BlockPlan at plan time) instead of re-deriving its own
estimates, and independently RECOUNTS the same quantities from the
shipped device stream arrays (segment runs decoded from the flag or
ps/bk planes, route stage heights from the actual index-block shapes).
A ledger/recount disagreement > 5% on either engine column fails the
script — and bench.py, which embeds the ledger totals in the BENCH
json, fails the same way.

r7: the ledger carries separate `vpu_ops` / `mxu_ops` / `hbm_bytes`
columns.  MXU-scan levels (GRAPE_PACK_SCAN=mxu, the default) replace
the 3-ops-per-stage shift ladder with triangular-matmul prefix sums:
a flat 10 VPU restoration ops per slot plus 3 matmul output planes
priced at the MXU's measured cumsum rate.

Counting conventions are documented on `spmv_pack._block_op_ledger`;
the ledger prices, per block: the 3-op hub overlay (the per-row hub
-group reduce + two shape-matched gathers from the padded hub table;
the planner row-aligns hub slots so the sublane gather's row index is
lane-uniform), route moves at their true operand
heights (a composed lane-aligned fold route is ONE sublane move, a
generic Route3 is three), the `flags != 1` compare on shift levels,
the span-aware shift ladder or the flat mxu restoration, and the
extraction stages (validity select dropped on non-final levels).
Cycle rates are explicit v5e assumptions:

  * vector ALU: 1024 f32 lanes/cycle (one (8,128) vreg op/cycle),
  * MXU: 0.008 cyc per matmul output element at B >= 512 (the
    verified [B,128] @ tri[128,128] Mosaic lowering),
  * sublane dynamic_gather: bounded between 1 row/cycle and ~8
    cycles/row (Mosaic unroll) — THE unknown the probe measures,
  * HBM: 819 GB/s, stream bytes counted from the plan's real dtypes.

    python scripts/pack_cost_model.py [--scale 20] [--ef 16]

Prints one JSON line per level plus a summary with optimistic /
pessimistic wall-clock and MTEPS bounds for the bench PageRank round.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

C = 128                       # lane width
# Pricing rates come from the shared RateProfile (ops/calibration.py)
# — the pinned default carries exactly the hand-measured v5e numbers
# this script used to inline, and a fitted profile (GRAPE_RATE_PROFILE)
# re-prices every surface here without touching the recount
# CONVENTIONS below (the recounts compare op COUNTS; rates cancel in
# the mismatch, so sharing rates keeps the gate honest).
from libgrape_lite_tpu.ops.calibration import (  # noqa: E402
    active_profile,
    default_profile,
)

VPU_LANES_PER_CYCLE = default_profile().vpu_lanes_per_cycle
CLOCK_HZ = default_profile().clock_hz
HBM_BPS = default_profile().hbm_bps
BASELINE_MTEPS = 3500.0       # reference 8xV100 PageRank, per chip
GATHER_RATES = default_profile().gather_rates
MXU_CYC_PER_ELEM = default_profile().mxu_cyc_per_elem
MISMATCH_TOLERANCE = 0.05


def build_bench_plan(scale: int, ef: int):
    """The ACTUAL multi-level plan for the bench RMAT shard (undirected
    pull: symmetrised CSR-sorted edge list, like bench.py)."""
    from bench import rmat_edges
    from libgrape_lite_tpu.ops.spmv_pack import PackConfig, plan_pack

    n, src, dst = rmat_edges(scale, ef)
    rows = np.concatenate([src, dst])
    cols = np.concatenate([dst, src])
    order = np.argsort(rows, kind="stable")
    rows, cols = rows[order], cols[order]
    vp = 1 << scale
    # from_env, not PackConfig(): the engaged backend resolves
    # GRAPE_PACK_CFG the same way, so the priced plan IS the plan that
    # would run
    return plan_pack(rows, cols, vp, vp, PackConfig.from_env())


def _decode_shift_stages(fl: np.ndarray) -> int:
    """Span-aware scan stage count, re-derived from one block's flag
    plane (the independent decode both recounts share)."""
    e = int(((fl & 1) > 0).sum())
    if not e:
        return 0
    starts = np.flatnonzero((fl & 2) > 0)
    runs = np.diff(np.concatenate([starts, [e]]))
    mx = int(runs.max()) if len(runs) else 1
    return max(0, math.ceil(math.log2(max(1, mx))))


def _recount_level(d: dict, nb: int, sub: int, tot: dict,
                   stage_override=None) -> None:
    """Recount ONE level's blocks from its stacked stream dict
    ([nb, ...] leading block axis) into `tot` — the shared core of the
    single-plan and multi-plan (2-D tile) recounts, so the two gates
    can never codify different conventions.

    `stage_override[b]`, when given, replaces the per-block flag
    decode for shift-scan stages: under shard_map every shard runs ONE
    traced program, so plan_pack_multi unifies each block's stages to
    the cross-shard max before ledgering — the multi recount must
    price the unified count (decoded independently per shard, then
    maxed by the caller), not each shard's own."""
    slots = sub * C
    for b in range(nb):
        ops = 0
        # merge/restore route: one sublane move when composed
        # lane-aligned, else the three stages at their heights
        if "rr" in d:
            ops += slots
        else:
            ops += (d["l1"].shape[-2] + d["s2"].shape[-2]
                    + d["l3"].shape[-2]) * C
        if "ps" in d:
            # mxu level: flat restoration cost — 10 VPU ops and 3
            # matmul output planes per slot, HARDCODED here as the
            # independent codification of the documented
            # convention (importing spmv_pack's constants would
            # make this gate tautological: a planner-side constant
            # drift must trip the 5% mismatch, not follow it).
            # The ps/bk planes are also decoded for consistency:
            # the derived start flag (ps == lane & bk == 0) must
            # mark at least one start per block that ships edges.
            ops += 10 * slots
            tot["mxu_ops"] += 3 * slots
            ps = d["ps"][b].astype(np.int64)
            bk = d["bk"][b].astype(np.int64)
            lane = np.arange(C, dtype=np.int64)[None, :]
            f0 = (ps == lane) & (bk == 0)
            assert f0.any(), (
                "mxu restoration planes decode to zero segment "
                "starts — ps/bk are corrupt"
            )
        else:
            fl = d["flags"][b].reshape(-1).astype(np.int64)
            ops += slots  # the flags != 1 compare
            # span-aware scan stages, re-derived from the flags (or
            # the caller's cross-shard unified count — see docstring)
            if stage_override is not None:
                stages = stage_override[b]
            else:
                stages = _decode_shift_stages(fl)
            ops += 3 * stages * slots
        # extraction: compact eroute (no validity select) or
        # final row-range tiles (select survives: tile outputs
        # sum straight into the dense result)
        if "el1" in d:
            ops += (d["el1"].shape[-2] + d["es2"].shape[-2]
                    + d["el3"].shape[-2]) * C
        elif "tel1" in d:
            nt = d["tel1"].shape[1]
            ops += nt * (d["tel1"].shape[-2] + d["tes2"].shape[-2]
                         + 2 * d["teval"].shape[-2]) * C
        if "gidx" in d:
            # hub-group reduce + the two hub-table gathers
            ops += 3 * slots
            tot["gather_rows"] += slots
        tot["vpu_ops"] += ops


def independent_op_estimate(plan) -> dict:
    """Recount VPU ops, MXU elems and gather rows from the SHIPPED
    device stream arrays, independently of the planner's BlockPlan
    annotations: segment runs are decoded from the flag planes (or,
    on mxu levels, from the ps/bk restoration planes via the derived
    start flag `ps == lane & bk == 0`), route/extraction stage costs
    from the actual index-block shapes.  This is the cross-check that
    keeps `plan_ledger` honest."""
    from libgrape_lite_tpu.ops.spmv_pack import _stack_blocks

    levels = list(plan.levels)
    if plan.final is not None and plan.final.blocks:
        levels.append(plan.final)
    tot = {"vpu_ops": 0, "mxu_ops": 0, "gather_rows": 0}
    for lv in levels:
        if not lv.blocks:
            continue
        _recount_level(_stack_blocks(lv), len(lv.blocks), lv.cfg.sub,
                       tot)
    return tot


def independent_multi_estimate(mplan) -> dict:
    """`independent_op_estimate` for a MultiPackPlan — the form every
    per-tile (2-D vertex-cut) and per-shard plan ships in.  The level
    streams ride stacked as `L{i}_{name}` [fnum, nb, ...] host arrays;
    the recount decodes every shard's slice with the SAME per-level
    core as the single-plan gate (r10)."""
    tot = {"vpu_ops": 0, "mxu_ops": 0, "gather_rows": 0}
    for i, skel in enumerate(mplan.skels):
        prefix = f"L{i}_"
        names = [
            k[len(prefix):] for k in mplan.host_streams
            if k.startswith(prefix)
        ]
        if not names:
            continue
        shards = [
            {n: mplan.host_streams[prefix + n][f] for n in names}
            for f in range(mplan.fnum)
        ]
        # shift-scan levels: every shard runs ONE traced program, so
        # the planner unifies each block's stage count to the
        # cross-shard max (spmv_pack.plan_pack_multi) — decode each
        # shard's stages independently, then price the unified max
        # (extra stages are bit-exact no-ops for the shard that
        # needed fewer, but they execute and the ledger bills them)
        stage_override = None
        if "flags" in shards[0]:
            stage_override = [
                max(
                    _decode_shift_stages(
                        d["flags"][b].reshape(-1).astype(np.int64)
                    )
                    for d in shards
                )
                for b in range(skel.nb)
            ]
        for d in shards:
            _recount_level(d, skel.nb, mplan.cfg.sub, tot,
                           stage_override=stage_override)
    return tot


def tile_plan_recount(mplan) -> dict:
    """The 2-D tile-plan gate (bench `partition2d` lane): the per-tile
    MultiPackPlan's ledger totals vs the independent recount from its
    shipped streams, mismatch gated at MISMATCH_TOLERANCE exactly like
    the 1-D op-budget ledger."""
    rec = independent_multi_estimate(mplan)
    totals = (mplan.ledger or {}).get("totals")
    if not totals:
        return {"tile_recount_mismatch": 1.0,
                "reason": "tile plan ships no ledger"}
    mismatch = max(
        abs(totals[k] - rec[k]) / max(1, totals[k])
        for k in ("vpu_ops", "mxu_ops")
    )
    return {
        "tile_recount_mismatch": round(mismatch, 4),
        "ledger_vpu_ops": totals["vpu_ops"],
        "recount_vpu_ops": rec["vpu_ops"],
        "ledger_mxu_ops": totals["mxu_ops"],
        "recount_mxu_ops": rec["mxu_ops"],
    }


def spgemm_recount(plan) -> dict:
    """The r11 masked-SpGEMM gate (bench `spgemm` lane): the plan's
    op-budget ledger vs an independent recount from the SHIPPED device
    streams.  The real item count is decoded from the `valid` planes
    (never from `plan.items` — that is a planner annotation), the
    per-item plane costs are HARDCODED here as the independent
    codification of the documented conventions (importing
    spgemm_pack's constants would make the gate tautological: 10 VPU
    planes of 128 lanes, one 128-elem MXU count-reduce row and two
    bitmap row fetches per item), and HBM bytes come from the actual
    array sizes.  Mismatch gated at MISMATCH_TOLERANCE by bench.py
    exactly like the SpMV op-budget ledger."""
    st = plan.host_streams
    if st is None:
        return {"spgemm_recount_mismatch": 1.0,
                "reason": "plan_only plan ships no streams"}
    valid = np.asarray(st["valid"]).astype(np.int64)
    items = int(valid.sum())
    # consistency decode: every valid item's rows/tile must be
    # addressable in the shipped sub-bitmap — corrupt streams must
    # fail loudly, not price as zero
    bm = np.asarray(st["bm"])
    kt = np.asarray(st["kt"])
    for f in range(valid.shape[0]):
        sel = valid[f] > 0
        if not sel.any():
            continue
        assert int(np.asarray(st["vrow"])[f, sel].max()) < bm.shape[1], \
            "spgemm item references a row beyond the shipped bitmap"
        assert int(kt[f, sel].max()) * 4 < bm.shape[2], \
            "spgemm item references a K-tile beyond the shipped bitmap"
    rec = {
        "vpu_ops": 10 * 128 * items,
        "mxu_ops": 128 * items,
        "gather_rows": 2 * items,
        "hbm_bytes": sum(int(np.asarray(a).nbytes) for a in st.values()),
    }
    totals = (plan.ledger or {}).get("totals")
    if not totals:
        return {"spgemm_recount_mismatch": 1.0,
                "reason": "plan ships no ledger"}
    mismatch = max(
        abs(totals[k] - rec[k]) / max(1, totals[k])
        for k in ("vpu_ops", "mxu_ops", "hbm_bytes")
    )
    return {
        "spgemm_recount_mismatch": round(mismatch, 4),
        "items_recounted": items,
        "ledger_vpu_ops": totals["vpu_ops"],
        "recount_vpu_ops": rec["vpu_ops"],
        "ledger_mxu_ops": totals["mxu_ops"],
        "recount_mxu_ops": rec["mxu_ops"],
        "ledger_hbm_bytes": totals["hbm_bytes"],
        "recount_hbm_bytes": rec["hbm_bytes"],
    }


def price(totals: dict, edges: int, profile=None) -> dict:
    """Wall-clock + MTEPS bracket from ledger totals under the shared
    profile rates (default: the active RateProfile); the gather rate
    is bracketed (the probe's unknown).  VPU, MXU and gather time are
    summed (no overlap assumed — the conservative bound); HBM streams
    concurrently."""
    p = profile or active_profile()
    vpu_s = totals["vpu_ops"] / p.vpu_lanes_per_cycle / p.clock_hz
    mxu_s = totals["mxu_ops"] * p.mxu_cyc_per_elem / p.clock_hz
    hbm_s = totals["hbm_bytes"] / p.hbm_bps
    scenarios = {}
    for name, rate in p.gather_rates.items():
        g_s = totals["gather_rows"] / rate / p.clock_hz
        t = max(vpu_s + mxu_s + g_s, hbm_s)
        scenarios[name] = dict(
            gather_ms=round(g_s * 1e3, 2),
            round_ms=round(t * 1e3, 2),
            mteps=round(edges / t / 1e6, 0),
            vs_baseline_3500=round(edges / t / 1e6 / BASELINE_MTEPS, 2),
        )
    return dict(t_vpu_ms=round(vpu_s * 1e3, 2),
                t_mxu_ms=round(mxu_s * 1e3, 2),
                t_hbm_ms=round(hbm_s * 1e3, 2),
                scenarios=scenarios)


def model(scale: int, ef: int) -> dict:
    """Build the bench plan, read its ledger, recount independently,
    and price the round.  Returns the full report dict."""
    from libgrape_lite_tpu.ops.spmv_pack import plan_ledger

    plan = build_bench_plan(scale, ef)
    ledger = plan_ledger(plan)
    recount = independent_op_estimate(plan)
    totals = ledger["totals"]
    e = ledger["edges"]
    mismatch = max(
        abs(totals[k] - recount[k]) / max(1, totals[k])
        for k in ("vpu_ops", "mxu_ops")
    )
    summary = dict(
        edges=e,
        bytes_per_edge=round(totals["hbm_bytes"] / e, 1),
        vpu_ops_per_edge=round(totals["vpu_ops"] / e, 1),
        mxu_elems_per_edge=round(totals["mxu_ops"] / e, 1),
        gather_slots_per_edge=round(totals["gather_rows"] / e, 2),
        per_stage_ops_per_edge={
            k: round(v / e, 1)
            for k, v in sorted(totals["per_stage"].items())
        },
        ledger_vpu_ops=totals["vpu_ops"],
        recount_vpu_ops=recount["vpu_ops"],
        ledger_mxu_ops=totals["mxu_ops"],
        recount_mxu_ops=recount["mxu_ops"],
        ledger_recount_mismatch=round(mismatch, 4),
        **price(totals, e),
    )
    return dict(levels=ledger["levels"], summary=summary)


def bench_ledger_summary(scale: int, ef: int,
                         cache_dir: str | None = None) -> dict:
    """The summary dict bench.py embeds in the BENCH json, cached on
    disk keyed by (geometry, PackConfig, schema, compose mode) so
    repeated bench runs skip the O(E log E) planner."""
    import dataclasses

    from libgrape_lite_tpu.ft.fingerprint import stable_config_digest
    from libgrape_lite_tpu.ops.spmv_pack import (
        _PLAN_SCHEMA_VERSION,
        PackConfig,
        _compose_enabled,
        _scan_mode,
    )

    import hashlib

    import libgrape_lite_tpu.ops.route3 as _route3
    import libgrape_lite_tpu.ops.spmv_pack as _spmv_pack

    # the cache must be invalidated by the very drift the 5% gate
    # polices: key it by the planner/kernel/model SOURCE as well as the
    # geometry, so a code change recomputes the recount instead of
    # serving a stale green verdict forever
    code_fp = hashlib.sha256()
    for mod_file in (_spmv_pack.__file__, _route3.__file__, __file__):
        with open(mod_file, "rb") as f:
            code_fp.update(f.read())
    key = stable_config_digest({
        "scale": scale, "ef": ef,
        "cfg": dataclasses.asdict(PackConfig.from_env()),
        "schema": _PLAN_SCHEMA_VERSION,
        "compose": _compose_enabled(),
        "scan": _scan_mode(),
        "code": code_fp.hexdigest(),
    })[:16]
    path = (os.path.join(cache_dir, f"ledger_{key}.json")
            if cache_dir else None)
    if path and os.path.exists(path):
        try:
            with open(path) as f:
                return json.load(f)
        except Exception:
            pass  # corrupt cache entries are recomputed
    summary = model(scale, ef)["summary"]
    if path:
        os.makedirs(cache_dir, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(summary, f)
        os.replace(tmp, path)
    return summary


def overlap_recount(plan) -> dict:
    """The exchange-overlap term (r9, parallel/pipeline.py), recounted
    from the SHIPPED pipeline plan — the same discipline as
    `independent_op_estimate`: the planner's boundary/interior stats
    are annotations, so the boundary/interior edge counts are re-read
    from the arrays that actually dispatch (the `pl_{b,i}_val`
    validity planes on the XLA path, the sub-plan ledgers on the pack
    path) and the exchange bytes from the plan's mode + geometry, NOT
    from `plan.stats`.  Returns the recounted overlap model plus
    `overlap_recount_mismatch`, gated at MISMATCH_TOLERANCE by
    bench.py exactly like the op-budget ledger."""
    from libgrape_lite_tpu.parallel.pipeline import overlap_model

    if plan.pack_b is not None:
        led_b = plan.pack_b.ledger()
        led_i = plan.pack_i.ledger()
        b_edges = int(led_b["edges"]) if led_b else 0
        i_edges = int(led_i["edges"]) if led_i else 0
    else:
        b_edges = int(np.asarray(
            plan.host_entries["pl_b_val"]).sum())
        i_edges = int(np.asarray(
            plan.host_entries["pl_i_val"]).sum())
    # exchange bytes from mode + geometry (f32 payload convention,
    # the same itemsize the shared mirror ledger prices)
    if plan.mode == "mirror":
        xbytes = plan.fnum * plan.m * 4
    else:
        xbytes = plan.fnum * plan.vp * 4
    modeled = overlap_model(b_edges, i_edges, xbytes, plan.ops_per_edge)
    t = plan.stats.get("totals", {})
    planned = overlap_model(
        t.get("boundary_edges", 0), t.get("interior_edges", 0),
        plan.exchange_bytes, plan.ops_per_edge,
    )
    mismatch = max(
        abs(b_edges - t.get("boundary_edges", 0))
        / max(1, t.get("boundary_edges", 0)),
        abs(i_edges - t.get("interior_edges", 0))
        / max(1, t.get("interior_edges", 0)),
        abs(xbytes - plan.exchange_bytes)
        / max(1, plan.exchange_bytes),
        abs(modeled["hidden_frac"] - planned["hidden_frac"])
        / max(1e-9, planned["hidden_frac"] or 1.0),
    )
    return {
        "boundary_edges": b_edges,
        "interior_edges": i_edges,
        "exchange_bytes": xbytes,
        "modeled_hidden_frac": modeled["hidden_frac"],
        "modeled_round_speedup": modeled["round_speedup"],
        "overlap_recount_mismatch": round(mismatch, 4),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=20)
    ap.add_argument("--ef", type=int, default=16)
    args = ap.parse_args(argv)

    report = model(args.scale, args.ef)
    for lv in report["levels"]:
        print(json.dumps(lv))
    print(json.dumps({"summary": report["summary"]}))
    mismatch = report["summary"]["ledger_recount_mismatch"]
    if mismatch > MISMATCH_TOLERANCE:
        print(
            f"FATAL: planner ledger and independent recount disagree by "
            f"{mismatch:.1%} (> {MISMATCH_TOLERANCE:.0%}) — the op-budget "
            "annotations have drifted from the shipped kernels",
            file=sys.stderr,
        )
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
