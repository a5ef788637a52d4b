#!/usr/bin/env python
"""Independent recounts of the planners' static ledgers.

What is left of the analytic cost model after the pack pipeline went
(PR 28): the two gates a bench lane or a test still imports, and the
pricing they share.

  * `spgemm_recount` — the masked-SpGEMM plan's op-budget ledger
    (ops/spgemm_pack.py) against a recount from the SHIPPED device
    streams;
  * `overlap_recount` — the superstep pipeline's boundary/interior
    split (parallel/pipeline.py) against the arrays that dispatch;
  * `price` — ledger totals to seconds under the shared RateProfile
    (ops/calibration.py), the same rates `price_backends` reads.

A ledger/recount disagreement above `MISMATCH_TOLERANCE` fails the
bench lane that embeds it.  The rest of the modeled-pricing stack is
ROADMAP D5's.
"""

from __future__ import annotations

import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Pricing rates come from the shared RateProfile (ops/calibration.py);
# the recounts compare op COUNTS (rates cancel in the mismatch), so
# sharing rates keeps the gate honest.
from libgrape_lite_tpu.ops.calibration import active_profile  # noqa: E402

MISMATCH_TOLERANCE = 0.05


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
    array sizes.  Mismatch gated at MISMATCH_TOLERANCE by bench.py."""
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


def price(totals: dict, profile=None) -> dict:
    """Per-engine milliseconds of ledger totals under the shared
    profile rates (default: the active RateProfile)."""
    p = profile or active_profile()
    vpu_s = totals["vpu_ops"] / p.vpu_lanes_per_cycle / p.clock_hz
    mxu_s = totals["mxu_ops"] * p.mxu_cyc_per_elem / p.clock_hz
    hbm_s = totals["hbm_bytes"] / p.hbm_bps
    return dict(t_vpu_ms=round(vpu_s * 1e3, 2),
                t_mxu_ms=round(mxu_s * 1e3, 2),
                t_hbm_ms=round(hbm_s * 1e3, 2))


def overlap_recount(plan) -> dict:
    """The exchange-overlap term (r9, parallel/pipeline.py), recounted
    from the SHIPPED pipeline plan: the planner's boundary/interior
    stats are annotations, so the boundary/interior edge counts are
    re-read from the arrays that actually dispatch (the `pl_{b,i}_val`
    validity planes) and the exchange bytes from the plan's mode +
    geometry, NOT from `plan.stats`.  Returns the recounted overlap
    model plus `overlap_recount_mismatch`, gated at MISMATCH_TOLERANCE
    by bench.py."""
    from libgrape_lite_tpu.parallel.pipeline import overlap_model

    b_edges = int(np.asarray(plan.host_entries["pl_b_val"]).sum())
    i_edges = int(np.asarray(plan.host_entries["pl_i_val"]).sum())
    # exchange bytes from mode + geometry (f32 payload convention,
    # the same itemsize the shared mirror ledger prices)
    if plan.mode == "mirror":
        xbytes = plan.fnum * plan.m * 4
    else:
        xbytes = plan.fnum * plan.vp * 4
    modeled = overlap_model(b_edges, i_edges, xbytes)
    t = plan.stats.get("totals", {})
    planned = overlap_model(
        t.get("boundary_edges", 0), t.get("interior_edges", 0),
        plan.exchange_bytes,
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
