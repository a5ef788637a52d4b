#!/usr/bin/env python
"""Independent recounts of the planners' static ledgers.

What is left of the analytic cost model after the pack pipeline went
(PR 28) and the superstep pipeline (PR 42): the gate a bench lane and
a test still import, and the pricing beside it.

  * `spgemm_recount` — the masked-SpGEMM plan's op-budget ledger
    (ops/spgemm_pack.py) against a recount from the SHIPPED device
    streams;
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
