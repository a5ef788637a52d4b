#!/usr/bin/env python
"""Validate BENCH json records against the declared schema.

The bench record is a cross-session contract: the driver reads the
LAST json line, PERF_NOTES tables are built from the fields, and the
r7 ledger gate compares split engine columns — a silently renamed or
mistyped field corrupts every downstream comparison without failing
anything.  This script makes the record shape a pinned artifact:

* `SCHEMA` declares every block bench.py may emit (top-level metric,
  `sssp`, `guard`, `pack_ledger` with the r7 vpu/mxu split fields,
  the r8 `obs` rollup block);
* `validate_record(record)` returns a list of human-readable errors
  (empty = valid) — bench.py self-checks each record with it BEFORE
  printing, and scripts/app_tests.sh validates a fresh small-scale
  bench line end-to-end;
* unknown top-level / block keys are errors: a new field must be
  declared here (one line) or it is a typo.

CLI: `python scripts/check_bench_schema.py FILE...` where FILE is a
json record, a BENCH_r*.json driver wrapper (validated via its
`parsed` field), or `-` for the last json line on stdin.
"""

from __future__ import annotations

import json
import sys

_NUM = (int, float)

# field -> (type tuple, required).  Top-level SCALAR fields only —
# every nested block is declared once in _BLOCKS below and wired into
# _TOP / SCHEMA / validate_record / the CLI listing BY CONSTRUCTION
# (the PR 9/11/12 wiring-gap class: a block declared here but
# forgotten in one of the four consumers silently validated nothing).
_TOP_SCALARS = {
    "metric": (str, True),
    "value": (_NUM, True),
    "unit": (str, True),
    "vs_baseline": (_NUM, True),
    "load_avg_1m": (_NUM, False),
}

_SSSP = {
    "metric": (str, True),
    "value": (_NUM, True),
    "unit": (str, True),
    "variant": (str, True),
    "vs_baseline": (_NUM, True),
    "fused_pull": (bool, False),
}

_GUARD = {
    "fused_off_s": (_NUM, True),
    "guarded_s": (_NUM, True),
    "guarded_overhead_pct": (_NUM, True),
    "policy": (str, True),
    "cadence": (int, True),
    "probes": (int, True),
}

# bench.py stopped emitting this block with the pack pipeline (PR 28);
# the archived BENCH_r* records still carry it.  The r7 split-engine
# columns are REQUIRED whenever the block appears
_PACK_LEDGER = {
    "vpu_ops_per_edge": (_NUM, True),
    "mxu_elems_per_edge": (_NUM, True),
    "gather_slots_per_edge": (_NUM, True),
    "bytes_per_edge": (_NUM, True),
    "per_stage_ops_per_edge": (dict, True),
    "scan_mode": (str, True),
    "modeled": (dict, True),
    "ledger_recount_mismatch": (_NUM, True),
}

_OBS = {
    "trace_id": ((str, type(None)), False),
    "spans": (dict, True),
}

# the r9 serving-throughput lane: per app, per batch size (keys b<k>),
# qps at fixed p99 over the scripted stream; batch_hist is the
# admission queue's batch-size histogram (digit-string keys)
_SERVE = {
    "scale": (int, True),
    "queries_per_app": (int, True),
    "sssp": (dict, False),
    "bfs": (dict, False),
    "batch_hist": (dict, True),
}

_SERVE_POINT = {
    "qps": (_NUM, True),
    "p50_ms": (_NUM, True),
    "p99_ms": (_NUM, True),
    "n": (int, True),
    "ok": (int, True),
}

# the r12 async-pump lane (serve/pipeline.py, docs/SERVING.md): the
# dispatch-window A/B — W in {1, 4} at batch sizes {1, 8, 32} over the
# serve-scale twin WITH a concurrent delta-ingest stream.  `window_ab`
# holds w<k> -> b<k> -> point maps; each point is a _SERVE_POINT plus
# the sustained updates/s of its run.  `identical` is the per-query
# byte-identity verdict W=4 vs W=1 (bench exits 2 when it breaks),
# `overlay_recompiles` counts XLA compiles during the measured
# overlay-only ingests (must be 0 — compile_events), and qps_win_b8
# is the headline: measured W=4 / W=1 qps at b=8.  Verdict fields are
# DECLARED bool.
_SERVE_ASYNC = {
    "scale": (int, True),
    "app": (str, True),
    "queries": (int, True),
    "window_ab": (dict, True),
    "identical": (bool, True),
    "qps_win_b8": (_NUM, True),
    "updates_per_chunk": (int, True),
    "overlay_recompiles": (int, True),
    "admission_wait_ms": (dict, True),
    "declines": (dict, False),
}

_SERVE_ASYNC_POINT = dict(_SERVE_POINT)
_SERVE_ASYNC_POINT["updates_per_s"] = (_NUM, True)

# the r10 dynamic-graph lane (dyn/, docs/DYNAMIC_GRAPHS.md): updates
# ingested per second while a query stream stays live, repack vs
# overlay counts, and the incremental-vs-cold round/wall comparison
_DYN = {
    "updates_per_s": (_NUM, True),
    "ingested": (int, True),
    "repack_count": (int, True),
    "overlay_applies": (int, True),
    "queries": (int, True),
    "queries_ok": (int, True),
    "inc_cold_rounds": (int, False),
    "inc_seeded_rounds": (int, False),
    "inc_speedup": (_NUM, False),
}

# the r10 2-D vertex-cut partition lane (fragment/partition.py,
# models/vc2d.py, docs/PARTITION2D.md): hub-heavy RMAT A/B at fnum 4
# (k=2) — max-tile vs the raw 1-D hub fragment, modeled exchange
# bytes under the shared ledgers, serial-vs-2D wall, byte/eps
# identity verdicts, and the planner's recorded auto decision vs the
# measured winner.  Verdict fields are DECLARED bool.
_PARTITION2D = {
    "scale": (int, True),
    "fnum": (int, True),
    "k": (int, True),
    "app": (str, True),
    "hub_1d_edges": (int, True),
    "max_1d_edges": (int, True),
    "max_tile_edges": (int, True),
    "tile_skew": (_NUM, True),
    "tile_ratio_vs_hub": (_NUM, True),
    "tile_bound_ok": (bool, True),
    "exchange_bytes_1d": (int, True),
    "exchange_bytes_2d": (int, True),
    "exchange_reduced": (bool, True),
    "serial_1d_s": (_NUM, True),
    "vc2d_s": (_NUM, True),
    "sssp_byte_identical": (bool, True),
    "pagerank_max_rel_err": (_NUM, True),
    "pagerank_eps_identical": (bool, True),
    "planner_choice": (str, True),
    "planner_t1d_s": (_NUM, True),
    "planner_t2d_s": (_NUM, True),
    "measured_winner": (str, True),
    "decision_matches": (bool, True),
    # emitted until the pack pipeline went (PR 28); archived records
    # still carry them
    "tile_plan_ok": (bool, False),
    "tile_recount_mismatch": (_NUM, False),
}

# the r11 masked-SpGEMM lane (ops/spgemm_pack.py, docs/SPGEMM.md):
# LCC intersect-vs-spgemm wall A/B at the lane geometry with the
# bit-exactness verdict and the shipped-plan ledger recount (the 5%
# gate), plus the modeled ops/edge A/B at full bench geometry —
# spgemm MXU elems + VPU lanes per oriented mask edge against the
# popcount sweep's word-ops, priced into modeled seconds with the
# win verdict and the ledger-auto decision.  Verdict fields are
# DECLARED bool.
_SPGEMM = {
    "scale": (int, True),
    "bench_scale": (int, True),
    "intersect_s": (_NUM, True),
    "spgemm_s": (_NUM, True),
    "byte_identical": (bool, True),
    "items": (int, True),
    "items_per_edge": (_NUM, True),
    "mask_edges": (int, True),
    "ledger_recount_mismatch": (_NUM, True),
    "bench_mask_edges": (int, True),
    "bench_items_per_edge": (_NUM, True),
    "mxu_elems_per_edge": (_NUM, True),
    "vpu_ops_per_edge": (_NUM, True),
    "intersect_word_ops_per_edge": (_NUM, True),
    "modeled_spgemm_s": (_NUM, True),
    "modeled_intersect_s": (_NUM, True),
    "modeled_win": (bool, True),
    "auto_backend": (str, True),
}

_SPAN_ROLLUP = {
    "count": (int, True),
    "total_s": (_NUM, True),
    "mean_s": (_NUM, True),
    "max_s": (_NUM, True),
}

# the r13 serving-fleet lane (fleet/, docs/FLEET.md): the drain drill
# — R=2 replicas serving the query stream with concurrent barrier
# ingest, one replica drained mid-run — with per-replica qps@p99 (the
# ROADMAP's stated target bench), the byte-identity verdict vs the
# undrained R=1 run (bench exits 2 when it breaks), the
# dropped-query count (must be 0), and the budget/eviction counters.
# Verdict fields are DECLARED bool.
_FLEET = {
    "scale": (int, True),
    "replicas": (int, True),
    "tenants": (int, True),
    "queries": (int, True),
    "ok": (int, True),
    "dropped": (int, True),
    "drain_at": (int, True),
    "drained_replica": (int, True),
    "drain_wall_s": (_NUM, True),
    "catchup_ops": (int, True),
    "updates": (int, True),
    "updates_per_s": (_NUM, True),
    "fence": (int, True),
    "byte_identical": (bool, True),
    "per_replica": (dict, True),
    "evictions": (int, True),
    "readmit_compiles": (int, False),
}

_FLEET_REPLICA = {
    "qps": (_NUM, True),
    "p50_ms": (_NUM, True),
    "p99_ms": (_NUM, True),
    "served": (int, True),
    "ok": (int, True),
}

# the r15 telemetry lane (obs/, docs/OBSERVABILITY.md): the serve
# stream's per-stage latency decomposition (stage -> {p50_ms, p99_ms}
# from ServeResult.stages), the stats-federation census (registered
# namespace count + the self_check verdict), the SLO burn and the
# flight-recorder counters.  `scrape_ok` is the live-exporter smoke:
# an in-process scrape of /metrics named every federated namespace.
_TELEMETRY = {
    "namespaces": (int, True),
    "federation_ok": (bool, True),
    "scrape_ok": (bool, False),
    "stages": (dict, True),
    "slo_observed": (int, True),
    "slo_breaches": (int, True),
    "slo_max_burn": (_NUM, True),
    "recorder_recorded": (int, True),
    "recorder_dropped": (int, True),
    "recorder_triggers": (int, True),
}

_STAGE_POINT = {
    "p50": (_NUM, True),
    "p99": (_NUM, True),
}

# the r16 autopilot lane (autopilot/, docs/AUTOPILOT.md): the
# closed-loop drill — the feeder's arrival rate steps up mid-stream
# (rate_spec, serve/feeder.py) and the scaler must answer with at
# least one zero-drop scale-up through the drain/rejoin/replicate
# machinery while every answer stays byte-identical to a static-R
# scripted run; plus the result-cache sub-drill: repeated sources
# answered from the cache with ZERO XLA compiles, then one
# fence-bumping ingest invalidates the epoch and the post-ingest
# answers are byte-identical to a cold run on the mutated graph.
# Verdict fields are DECLARED bool.
_AUTOPILOT = {
    "scale": (int, True),
    "queries": (int, True),
    "ok": (int, True),
    "dropped": (int, True),
    "rate_spec": (str, True),
    "min_replicas": (int, True),
    "max_replicas": (int, True),
    "replicas_final": (int, True),
    "scale_ups": (int, True),
    "scale_downs": (int, True),
    "ticks": (int, True),
    "p99_ms": (_NUM, True),
    "p99_bound_ms": (_NUM, True),
    "p99_ok": (bool, True),
    "byte_identical": (bool, True),
    "cache_hits": (int, True),
    "cache_misses": (int, True),
    "cache_hit_compiles": (int, True),
    "cache_invalidations": (int, True),
    "post_ingest_identical": (bool, True),
}

# the r17 calibration lane (ops/calibration.py, docs/CALIBRATION.md):
# the fitted-rate record — the ACTIVE profile's label/fingerprint, the
# fit's sample count and RMS residual, the per-surface aggregate
# modeled-vs-measured drift (the 5% gate bench exits 2 on when an
# explicit GRAPE_RATE_PROFILE drifts), and the fitted rate values
# themselves so PERF_NOTES can table pinned-vs-fitted.  Verdict
# fields are DECLARED bool; every rate is numeric with bool rejected
# (the R5 class) via the extra rates-dict walk in validate_record.
_CALIBRATION = {
    "profile": (str, True),
    "fingerprint": (str, True),
    "source": (str, True),
    "fitted": (bool, True),
    "samples": (int, True),
    "residual_pct": (_NUM, True),
    "drift_pct": (_NUM, True),
    "max_sample_drift_pct": (_NUM, True),
    "drift_ok": (bool, True),
    "rates": (dict, True),
    "unfitted": (list, False),
    "fallback_notes": (list, False),
    "surfaces": (dict, False),
}

_CALIB_SURFACE = {
    "modeled_s": (_NUM, True),
    "measured_s": (_NUM, True),
    "samples": (int, True),
    "drift_pct": (_NUM, True),
}

# the distributed resilience drill (scripts/fault_drill.py
# --kill_rank, docs/FAULT_TOLERANCE.md "Distributed resilience"): a
# 2-process gang loses a rank at kill_round, and the survivors'
# sharded two-phase snapshot is reshard-restored onto a smaller mesh;
# byte_identical is the drill's verdict (the drill itself exits 2 on
# divergence — this block makes the record auditable after the fact)
_FT_DRILL = {
    "ranks": (int, True),
    "kill_round": (int, True),
    "kill_rank": (int, True),
    "old_fnum": (int, True),
    "new_fnum": (int, True),
    "checkpoint_rounds": (int, True),
    "restore_wall_s": (_NUM, True),
    "byte_identical": (bool, True),
    # the PR 20 gang-telemetry leg (tracer armed across the kill):
    # merged-trace completeness, the vote's cross-rank flow count,
    # and the byte-verified gang postmortem under one incident id
    "gang_trace_events": (int, False),
    "gang_trace_complete": (bool, False),
    "gang_cross_rank_flows": (int, False),
    "gang_incident": (str, False),
    "gang_bundle_verified": (bool, False),
}

# the PR 20 bench gang-telemetry self-drill (bench.py obs_gang_lane):
# two in-process fake-rank tracers federate sidecars through the real
# assembler (completeness / alignment / monotonicity / cross-rank
# flow verdicts), plus the armed-vs-disarmed fused-HLO byte-identity
# re-proof.  Verdict fields are DECLARED bool.
_OBS_GANG = {
    "ranks": (int, True),
    "events": (int, True),
    "flow_events": (int, True),
    "cross_rank_flows": (int, True),
    "aligned": (bool, True),
    "monotonic": (bool, True),
    "complete": (bool, True),
    "hlo_identical": (bool, True),
}

#: every nested block bench.py may emit — THE single declaration
#: point; _TOP, SCHEMA, validate_record and the CLI listing all
#: derive from it (self_check() pins the derivation)
_BLOCKS = {
    "sssp": _SSSP,
    "guard": _GUARD,
    "pack_ledger": _PACK_LEDGER,
    "obs": _OBS,
    "serve": _SERVE,
    "serve_async": _SERVE_ASYNC,
    "dyn": _DYN,
    "partition2d": _PARTITION2D,
    "spgemm": _SPGEMM,
    "fleet": _FLEET,
    "telemetry": _TELEMETRY,
    "autopilot": _AUTOPILOT,
    "calibration": _CALIBRATION,
    "ft_drill": _FT_DRILL,
    "obs_gang": _OBS_GANG,
}

_TOP = {**_TOP_SCALARS, **{k: (dict, False) for k in _BLOCKS}}

SCHEMA = {"": _TOP, **_BLOCKS}


def self_check() -> list:
    """The wiring-gap gate: every DECLARED block must be wired into
    _TOP, SCHEMA and validate_record — which all derive from _BLOCKS,
    so the only way to regress is to bypass the derivation; this
    check fails the CLI (exit 2) and tests/test_fleet.py if anyone
    does.  Returns a list of inconsistencies (empty = wired)."""
    errors = []
    top_blocks = {
        k for k, (types, _) in _TOP.items()
        if (types if isinstance(types, tuple) else (types,)) == (dict,)
    }
    if top_blocks != set(_BLOCKS):
        errors.append(
            f"_TOP dict-typed fields {sorted(top_blocks)} != declared "
            f"blocks {sorted(_BLOCKS)}"
        )
    if set(SCHEMA) != {""} | set(_BLOCKS):
        errors.append(
            f"SCHEMA keys {sorted(SCHEMA)} != '' + declared blocks"
        )
    for name, spec in _BLOCKS.items():
        if SCHEMA.get(name) is not spec:
            errors.append(f"SCHEMA[{name!r}] is not the declared spec")
    # validate_record must actually CHECK every declared block: feed
    # it a record where every block violates its spec and demand one
    # error per block
    probe = {k: {"__not_a_field__": 1} for k in _BLOCKS}
    probe.update({"metric": "x", "value": 1, "unit": "u",
                  "vs_baseline": 1.0})
    found = validate_record(probe)
    for name in _BLOCKS:
        if not any(e.startswith(f"{name}.") or e.startswith(f"{name}:")
                   for e in found):
            errors.append(
                f"validate_record never checked block {name!r}"
            )
    return errors


def _check_block(block: dict, spec: dict, where: str, errors: list,
                 allow_unknown: bool = False) -> None:
    for field, (types, required) in spec.items():
        if field not in block:
            if required:
                errors.append(f"{where}: missing required field {field!r}")
            continue
        v = block[field]
        accepted = types if isinstance(types, tuple) else (types,)
        # bool is an int subclass: every numeric field (int OR the
        # (int, float) number tuple) must reject it explicitly
        if isinstance(v, bool) and bool not in accepted:
            errors.append(
                f"{where}.{field}: expected "
                f"{getattr(types, '__name__', types)}, got bool"
            )
        elif not isinstance(v, types):
            errors.append(
                f"{where}.{field}: expected "
                f"{getattr(types, '__name__', types)}, got "
                f"{type(v).__name__} ({v!r})"
            )
    if not allow_unknown:
        for k in block:
            if k not in spec:
                errors.append(
                    f"{where}: unknown field {k!r} — declare it in "
                    "scripts/check_bench_schema.py or fix the typo"
                )


def validate_record(record) -> list:
    """Every schema violation in one BENCH record (empty = valid)."""
    errors: list = []
    if not isinstance(record, dict):
        return [f"record is {type(record).__name__}, expected object"]
    _check_block(record, _TOP, "record", errors)
    for key, spec in _BLOCKS.items():
        block = record.get(key)
        if isinstance(block, dict):
            _check_block(block, spec, key, errors)
    led = record.get("pack_ledger")
    if isinstance(led, dict):
        stages = led.get("per_stage_ops_per_edge")
        if isinstance(stages, dict):
            for k, v in stages.items():
                if not isinstance(v, _NUM) or isinstance(v, bool):
                    errors.append(
                        f"pack_ledger.per_stage_ops_per_edge[{k!r}]: "
                        f"expected number, got {type(v).__name__}"
                    )
        if led.get("scan_mode") not in (None, "mxu", "shift"):
            errors.append(
                f"pack_ledger.scan_mode: {led.get('scan_mode')!r} not in "
                "('mxu', 'shift')"
            )
    p2 = record.get("partition2d")
    if isinstance(p2, dict):
        for f in ("planner_choice", "measured_winner"):
            if p2.get(f) not in (None, "1d", "2d"):
                errors.append(
                    f"partition2d.{f}: {p2.get(f)!r} not in "
                    "('1d', '2d')"
                )
    sg = record.get("spgemm")
    if isinstance(sg, dict):
        if sg.get("auto_backend") not in (None, "intersect", "spgemm"):
            errors.append(
                f"spgemm.auto_backend: {sg.get('auto_backend')!r} not "
                "in ('intersect', 'spgemm')"
            )
    ob = record.get("obs")
    if isinstance(ob, dict) and isinstance(ob.get("spans"), dict):
        for name, r in ob["spans"].items():
            if not isinstance(r, dict):
                errors.append(f"obs.spans[{name!r}]: expected object")
                continue
            _check_block(r, _SPAN_ROLLUP, f"obs.spans[{name!r}]", errors)
    sv = record.get("serve")
    if isinstance(sv, dict):
        for app in ("sssp", "bfs"):
            blk = sv.get(app)
            if not isinstance(blk, dict):
                continue
            for bkey, point in blk.items():
                where = f"serve.{app}[{bkey!r}]"
                if not (bkey.startswith("b") and bkey[1:].isdigit()):
                    errors.append(
                        f"{where}: batch keys must look like b<k>"
                    )
                    continue
                if not isinstance(point, dict):
                    errors.append(f"{where}: expected object")
                    continue
                _check_block(point, _SERVE_POINT, where, errors)
        bh = sv.get("batch_hist")
        if isinstance(bh, dict):
            for k, v in bh.items():
                if not (isinstance(k, str) and k.isdigit()):
                    errors.append(
                        f"serve.batch_hist[{k!r}]: keys are decimal "
                        "batch sizes"
                    )
                if not isinstance(v, int) or isinstance(v, bool):
                    errors.append(
                        f"serve.batch_hist[{k!r}]: expected int count, "
                        f"got {type(v).__name__}"
                    )
    sa = record.get("serve_async")
    if isinstance(sa, dict):
        wab = sa.get("window_ab")
        if isinstance(wab, dict):
            for wkey, points in wab.items():
                where = f"serve_async.window_ab[{wkey!r}]"
                if not (wkey.startswith("w") and wkey[1:].isdigit()):
                    errors.append(f"{where}: window keys look like w<k>")
                    continue
                if not isinstance(points, dict):
                    errors.append(f"{where}: expected object")
                    continue
                for bkey, point in points.items():
                    pwhere = f"{where}[{bkey!r}]"
                    if not (bkey.startswith("b") and bkey[1:].isdigit()):
                        errors.append(
                            f"{pwhere}: batch keys look like b<k>"
                        )
                        continue
                    if not isinstance(point, dict):
                        errors.append(f"{pwhere}: expected object")
                        continue
                    _check_block(point, _SERVE_ASYNC_POINT, pwhere,
                                 errors)
        aw = sa.get("admission_wait_ms")
        if isinstance(aw, dict):
            for q in ("p50", "p99"):
                v = aw.get(q)
                if not isinstance(v, _NUM) or isinstance(v, bool):
                    errors.append(
                        f"serve_async.admission_wait_ms.{q}: expected "
                        f"number, got {type(v).__name__}"
                    )
    tl = record.get("telemetry")
    if isinstance(tl, dict) and isinstance(tl.get("stages"), dict):
        for sname, point in tl["stages"].items():
            where = f"telemetry.stages[{sname!r}]"
            if not isinstance(point, dict):
                errors.append(f"{where}: expected object")
                continue
            _check_block(point, _STAGE_POINT, where, errors)
    cb = record.get("calibration")
    if isinstance(cb, dict):
        rates = cb.get("rates")
        if isinstance(rates, dict):
            for k, v in rates.items():
                if not isinstance(v, _NUM) or isinstance(v, bool):
                    errors.append(
                        f"calibration.rates[{k!r}]: expected number, "
                        f"got {type(v).__name__}"
                    )
        for lf in ("unfitted", "fallback_notes"):
            seq = cb.get(lf)
            if isinstance(seq, list):
                for i, v in enumerate(seq):
                    if not isinstance(v, str):
                        errors.append(
                            f"calibration.{lf}[{i}]: expected str, "
                            f"got {type(v).__name__}"
                        )
        surfs = cb.get("surfaces")
        if isinstance(surfs, dict):
            for sname, point in surfs.items():
                where = f"calibration.surfaces[{sname!r}]"
                if not isinstance(point, dict):
                    errors.append(f"{where}: expected object")
                    continue
                _check_block(point, _CALIB_SURFACE, where, errors)
    fl = record.get("fleet")
    if isinstance(fl, dict):
        pr = fl.get("per_replica")
        if isinstance(pr, dict):
            for rkey, point in pr.items():
                where = f"fleet.per_replica[{rkey!r}]"
                if not (rkey.startswith("r") and rkey[1:].isdigit()):
                    errors.append(
                        f"{where}: replica keys look like r<k>"
                    )
                    continue
                if not isinstance(point, dict):
                    errors.append(f"{where}: expected object")
                    continue
                _check_block(point, _FLEET_REPLICA, where, errors)
    return errors


def _records_from_text(text: str, where: str):
    """(record, label) pairs from a file's content: a driver wrapper
    (validated via `parsed`), a bare record, or line-delimited output
    where the LAST json object line wins (the driver's convention)."""
    text = text.strip()
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None
    if isinstance(doc, dict):
        if "parsed" in doc and isinstance(doc["parsed"], dict):
            return [(doc["parsed"], f"{where}:parsed")]
        return [(doc, where)]
    # stream mode: last parseable json-object line (bench stdout)
    last = None
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                last = json.loads(line)
            except json.JSONDecodeError:
                continue
    if last is None:
        raise ValueError(f"{where}: no json record found")
    return [(last, f"{where}:last-line")]


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    # the self-consistency gate runs FIRST: a declared-but-unwired
    # block must fail the tool itself, not quietly validate nothing
    wiring = self_check()
    if wiring:
        print("FAIL schema self-check:", file=sys.stderr)
        for e in wiring:
            print(f"  - {e}", file=sys.stderr)
        return 2
    if not argv:
        print(__doc__.strip().splitlines()[0], file=sys.stderr)
        print("usage: check_bench_schema.py FILE... (or - for stdin)",
              file=sys.stderr)
        return 64
    failed = False
    for path in argv:
        text = sys.stdin.read() if path == "-" else open(path).read()
        try:
            pairs = _records_from_text(text, path)
        except ValueError as e:
            print(f"FAIL {e}")
            failed = True
            continue
        for record, label in pairs:
            errors = validate_record(record)
            if errors:
                failed = True
                print(f"FAIL {label}: {len(errors)} schema error(s)")
                for e in errors:
                    print(f"  - {e}")
            else:
                blocks = [k for k in _BLOCKS if k in record]
                print(f"OK {label} ({record.get('metric')}"
                      + (f"; blocks: {', '.join(blocks)}" if blocks
                         else "") + ")")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
