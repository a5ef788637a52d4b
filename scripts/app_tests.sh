#!/bin/bash -e
# End-to-end CLI test harness — the analogue of the reference's
# misc/app_tests.sh: every app via the real CLI at several fragment
# counts, outputs verified against dataset/p2p-31-* goldens.
# (pytest tests/ covers the same matrix in-process; this script drives
# the user-facing surface.)

REPO="$( cd "$(dirname "$0")/.." >/dev/null 2>&1 ; pwd -P )"
cd "$REPO"
OUT=$(mktemp -d)
trap 'rm -rf "$OUT"' EXIT

PLATFORM_ARGS="--platform cpu --cpu_devices 8"
DS="$REPO/dataset"

run() {
  local np=$1; shift
  local app=$1; shift
  rm -rf "$OUT/res"
  python -m libgrape_lite_tpu.cli --application "$app" \
    --efile "$DS/p2p-31.e" --vfile "$DS/p2p-31.v" \
    --out_prefix "$OUT/res" $PLATFORM_ARGS --fnum "$np" "$@" >/dev/null
  cat "$OUT/res"/* | sort -k1n > "$OUT/merged.res"
}

verify() {  # verify <kind:exact|eps|wcc> <golden>
  python - "$1" "$DS/$2" "$OUT/merged.res" <<'EOF'
import sys
sys.path.insert(0, ".")
from tests.verifiers import (load_golden, load_result_lines,
                             exact_verify, eps_verify, wcc_verify)
kind, golden_path, res_path = sys.argv[1:4]
res = load_result_lines(open(res_path).read())
gold = load_golden(golden_path)
{"exact": exact_verify, "eps": eps_verify, "wcc": wcc_verify}[kind](res, gold)
print(f"  OK ({kind}, {len(res)} vertices)")
EOF
}

for np in 1 2 4 8; do
  echo "== fnum=$np =="
  echo "sssp";          run $np sssp --sssp_source=6;        verify exact p2p-31-SSSP
  echo "sssp_auto";     run $np sssp_auto --sssp_source=6;   verify exact p2p-31-SSSP
  echo "bfs";           run $np bfs --bfs_source=6;          verify exact p2p-31-BFS
  echo "pagerank";      run $np pagerank --pr_mr=10;         verify eps p2p-31-PR
  echo "cdlp";          run $np cdlp --cdlp_mr=10;           verify exact p2p-31-CDLP
  echo "wcc";           run $np wcc;                         verify wcc p2p-31-WCC
done

echo "== strategy variants (fnum=4) =="
echo "sssp_msg";  run 4 sssp_msg --sssp_source=6;  verify exact p2p-31-SSSP
echo "wcc_opt";   run 4 wcc_opt;                   verify wcc p2p-31-WCC
echo "pagerank_push"; run 4 pagerank_push --pr_mr=10; verify eps p2p-31-PR

echo "== extra apps smoke (fnum=2, no goldens ship) =="
for app in bc kcore core_decomposition kclique; do
  echo "$app"
  run 2 $app --bc_source=6 --kcore_k=4 --kclique_k=3
done
echo "lcc_directed"
run 2 lcc_directed --directed

echo "== directed (fnum=4) =="
echo "sssp --directed"; run 4 sssp --sssp_source=6 --directed; verify exact p2p-31-SSSP-directed
echo "bfs --directed";  run 4 bfs --bfs_source=6 --directed;   verify exact p2p-31-BFS-directed
echo "pagerank --directed"; run 4 pagerank --pr_mr=10 --directed; verify eps p2p-31-PR-directed

echo "== lcc (fnum=4) =="
run 4 lcc; verify eps p2p-31-LCC

echo "== lcc backend A/B: spgemm cmp-identical to intersect (fnum=4) =="
# GRAPE_LCC_BACKEND=spgemm routes the bitmap LCC's triangle credits
# through the tiled masked SpGEMM (ops/spgemm_pack.py); the credit
# algebra is integer-identical, so the merged result files must be
# bit-identical to the intersect run's (docs/SPGEMM.md)
( export GRAPE_LCC_BACKEND=intersect; run 4 lcc_opt )
cp "$OUT/merged.res" "$OUT/lcc_intersect.res"
( export GRAPE_LCC_BACKEND=spgemm; run 4 lcc_opt )
cmp "$OUT/lcc_intersect.res" "$OUT/merged.res" \
  || { echo "SPGEMM LCC DIVERGED FROM INTERSECT" >&2; exit 1; }
verify eps p2p-31-LCC
echo "  OK (byte-identical across backends)"

echo "== vertex-cut pagerank (fnum=4) =="
run 4 pagerank --vc --pr_mr=10; verify eps p2p-31-PR

echo "== mutation (fnum=4) =="
rm -rf "$OUT/res"
python -m libgrape_lite_tpu.cli --application sssp \
  --efile "$DS/p2p-31.e.mutable_base" --vfile "$DS/p2p-31.v" \
  --delta_efile "$DS/p2p-31.e.mutable_delta" --sssp_source=6 \
  --out_prefix "$OUT/res" $PLATFORM_ARGS --fnum 4 >/dev/null
cat "$OUT/res"/* | sort -k1n > "$OUT/merged.res"
verify exact p2p-31-SSSP

echo "== serialization roundtrip (fnum=2) =="
SER="$OUT/serial"
run 2 pagerank --pr_mr=10 --serialize --serialization_prefix "$SER"; verify eps p2p-31-PR
run 2 pagerank --pr_mr=10 --deserialize --serialization_prefix "$SER"; verify eps p2p-31-PR

echo "== load validation gate (fnum=2) =="
# subshell: `VAR=x fn` would leak past the bash function call
( export GRAPE_VALIDATE_LOAD=1; run 2 wcc ); verify wcc p2p-31-WCC

echo "== guarded run, goldens unchanged (fnum=2) =="
run 2 sssp --sssp_source=6 --guard=halt; verify exact p2p-31-SSSP

echo "== 2-D vertex-cut partition: cmp-identical to 1-D (sssp, fnum=4) =="
# GRAPE_PARTITION=2d routes sssp through the k x k vertex-cut mesh
# (fragment/partition.py + models/vc2d.py); min folds regroup exactly
# across tiles, so the merged result files must be bit-identical to
# the serial 1-D run's (docs/PARTITION2D.md)
run 4 sssp --sssp_source=6
cp "$OUT/merged.res" "$OUT/serial_1d.res"
( export GRAPE_PARTITION=2d; run 4 sssp --sssp_source=6 )
cmp "$OUT/serial_1d.res" "$OUT/merged.res" \
  || { echo "2-D VERTEX-CUT RESULT DIVERGED FROM 1-D" >&2; exit 1; }
echo "  OK (byte-identical to the 1-D edge-cut)"
# declined geometry (fnum=2 is not a square) must fall back to 1-D
# with the reason recorded, never error out
( export GRAPE_PARTITION=2d; run 2 sssp --sssp_source=6 ); verify exact p2p-31-SSSP

echo "== guard self-heal drill (corrupt_carry + rollback-replay) =="
python scripts/fault_drill.py --self-heal --apps sssp,pagerank,wcc

echo "== flight-recorder drill (fleet breach -> bundle byte-matches trace) =="
# obs/recorder.py end-to-end: guard breaches under a 2-replica fleet
# dump postmortem bundles; the newest bundle's serve_query span rows
# must byte-match the Chrome trace's rows for the same query ids
python scripts/fault_drill.py --postmortem

echo "== distributed resilience (2-proc gang: sharded 2PC + kill_rank + reshard) =="
# docs/FAULT_TOLERANCE.md "Distributed resilience", through the real
# CLI: (1) the 2-process jax.distributed dryrun, now growing a
# checkpointed query lane that commits per-rank shard files under the
# two-phase barrier; (2) the kill_rank drill — rank 1 of 2 dies at
# superstep 4, and the survivors' fnum-4 sharded snapshot is
# reshard-restored onto a single-process fnum-2 mesh, byte-identical
# to a fault-free run (the drill exits 2 on divergence); the emitted
# ft_drill record must pass the bench schema gate
timeout 600 python scripts/multihost_dryrun.py > "$OUT/dryrun.txt" \
  || { cat "$OUT/dryrun.txt"; exit 1; }
grep -q "sharded ckpt" "$OUT/dryrun.txt" \
  || { echo "DRYRUN CHECKPOINT LANE MISSING" >&2; cat "$OUT/dryrun.txt"; exit 1; }
python scripts/fault_drill.py --kill_rank --workdir "$OUT/killrank" \
  > "$OUT/killrank.txt" \
  || { DRILL_RC=$?; cat "$OUT/killrank.txt";
       echo "KILL_RANK DRILL FAILED (rc=$DRILL_RC)" >&2; exit $DRILL_RC; }
cat "$OUT/killrank.txt"
grep '"ft_drill"' "$OUT/killrank.txt" | tail -1 > "$OUT/ft_drill.json"
python scripts/check_bench_schema.py "$OUT/ft_drill.json"
rm -rf "$OUT/killrank"
echo "  OK (dryrun ckpt lane, kill_rank reshard byte-identical, schema'd record)"

echo "== obs trace + per-superstep report (stepwise SSSP, fnum=2) =="
run 2 sssp --sssp_source=6 --profile \
  --trace "$OUT/trace.json" --metrics "$OUT/metrics"
verify exact p2p-31-SSSP
python scripts/trace_report.py "$OUT/trace.json" >/dev/null
test -s "$OUT/trace.jsonl" && test -s "$OUT/metrics.prom"
echo "  OK (trace + jsonl + metrics written, report rendered)"

echo "== serve: scripted 32-query stream through the CLI (fnum=2) =="
# mixed stream: 24 sssp + 8 bfs queries coalesce per-app under
# max_batch=8 — exercises admission, coalescing, and the vmapped
# batched dispatch through the real user-facing surface
python - > "$OUT/serve_stream.txt" <<'EOF'
for i in range(24):
    print("sssp", 6 + i)
for i in range(8):
    print("bfs", 6 + i)
EOF
python -m libgrape_lite_tpu.cli serve \
  --efile "$DS/p2p-31.e" --vfile "$DS/p2p-31.v" $PLATFORM_ARGS --fnum 2 \
  --stream "$OUT/serve_stream.txt" --max_batch 8 > "$OUT/serve.json"
python - "$OUT/serve.json" <<'EOF'
import json, sys
rec = json.loads(
    [l for l in open(sys.argv[1]) if l.startswith("{")][-1])
assert rec["queries"] == 32 and rec["failed"] == 0, rec
assert rec["apps"] == {"sssp": 24, "bfs": 8}, rec["apps"]
assert sum(rec["batch_hist"].values()) >= 4, rec["batch_hist"]
print(f"  OK (32 queries, {rec['qps']} q/s, hist {rec['batch_hist']})")
EOF

echo "== telemetry: live OpenMetrics scrape mid-serve + stages + SLO (fnum=2) =="
# the obs/ plane through the real CLI: --metrics_port 0 binds an
# ephemeral exporter (URL on stderr); the scrape runs WHILE the stream
# is live and must name every federated namespace in OpenMetrics text
# (docs/OBSERVABILITY.md); the summary must carry the per-stage
# p50/p99 decomposition and the SLO error-budget block
python -m libgrape_lite_tpu.cli serve \
  --efile "$DS/p2p-31.e" --vfile "$DS/p2p-31.v" $PLATFORM_ARGS --fnum 2 \
  --stream "$OUT/serve_stream.txt" --max_batch 8 --inflight 2 \
  --metrics_port 0 --slo 'sssp=5000,*=5000' \
  > "$OUT/tele_serve.json" 2> "$OUT/tele_serve.err" &
TELE_PID=$!
URL=""
for _ in $(seq 1 200); do
  URL=$(sed -n 's/.*metrics exporter: \(http[^ ]*\).*/\1/p' "$OUT/tele_serve.err" | head -1)
  [ -n "$URL" ] && break
  sleep 0.05
done
[ -n "$URL" ] || { echo "EXPORTER URL NEVER PRINTED" >&2; kill "$TELE_PID"; exit 1; }
python - "$URL" "$TELE_PID" <<'EOF'
import json, os, sys, time, urllib.request
url, pid = sys.argv[1], int(sys.argv[2])
# poll until the SLO ledger shows deliveries, so the scrape is a
# genuine mid-serve one (the all-8-namespace scrape is bench.py's
# telemetry lane; HERE the contract is consistency: everything the
# process has federated so far must be named in the OpenMetrics text)
fed, observed = {}, 0
for _ in range(600):
    try:
        fed = json.load(
            urllib.request.urlopen(url + "/federation", timeout=10))
    except OSError:
        break
    observed = (fed.get("slo") or {}).get("observed", 0)
    if observed >= 1 or not os.path.exists(f"/proc/{pid}"):
        break
    time.sleep(0.05)
assert observed >= 1, \
    f"serve ended before a delivery was ever scraped: {sorted(fed)}"
assert {"pump", "recorder", "slo"} <= set(fed), sorted(fed)
text = urllib.request.urlopen(url + "/metrics", timeout=10).read().decode()
live = os.path.exists(f"/proc/{pid}")
missing = [ns for ns in fed
           if f'grape_stats_registry{{namespace="{ns}"}}' not in text]
assert not missing, f"scrape missing namespaces: {missing}"
assert "grape_stats_slo_observed" in text, text[-400:]
assert text.endswith("# EOF\n"), "scrape is not OpenMetrics-terminated"
print(f"  OK ({'mid' if live else 'post'}-serve scrape at "
      f"{observed} deliveries named all {len(fed)} live namespace(s): "
      f"{sorted(fed)})")
EOF
wait "$TELE_PID"
python - "$OUT/tele_serve.json" <<'EOF'
import json, sys
rec = json.loads(
    [l for l in open(sys.argv[1]) if l.startswith("{")][-1])
assert rec["queries"] == 32 and rec["failed"] == 0, rec
st = rec["stages"]
assert {"queue_wait_us", "dispatch_us", "device_us",
        "harvest_us"} <= set(st), st
assert all(set(v) == {"p50", "p99"} for v in st.values()), st
slo = rec["slo"]
assert slo["observed"] == 32 and slo["breaches"] == 0, slo
print(f"  OK (stages {sorted(st)}; slo {slo['observed']} observed, "
      f"{slo['breaches']} breach(es))")
EOF

echo "== dyn: ingest a delta stream while a mixed query stream runs (fnum=2) =="
# streaming smoke (dyn/): 10 additive delta ops ingested in chunks
# between query batches — they ride the overlay side-path (no repack
# below the threshold) while 16 sssp + 8 bfs queries stay live
python - > "$OUT/dyn_delta.txt" <<'EOF'
for i in range(10):
    print("a 6", 200 + 17 * i, "0.5")
EOF
python - > "$OUT/dyn_stream.txt" <<'EOF'
for i in range(16):
    print("sssp", 6 + i)
for i in range(8):
    print("bfs", 6 + i)
EOF
python -m libgrape_lite_tpu.cli serve \
  --efile "$DS/p2p-31.e" --vfile "$DS/p2p-31.v" $PLATFORM_ARGS --fnum 2 \
  --stream "$OUT/dyn_stream.txt" --max_batch 8 \
  --delta_stream "$OUT/dyn_delta.txt" --ingest_every 8 \
  --dyn_repack_ratio 0.5 > "$OUT/dyn_serve.json"
python - "$OUT/dyn_serve.json" <<'EOF'
import json, sys
rec = json.loads(
    [l for l in open(sys.argv[1]) if l.startswith("{")][-1])
assert rec["queries"] == 24 and rec["failed"] == 0, rec
d = rec["dyn"]
assert d["ingested"] == 10 and d["repack_count"] == 0, d
assert d["overlay_applies"] >= 1 and d["updates_per_s"] > 0, d
assert d["queries_ok"] == 24, d
print(f"  OK (24 queries live, {d['ingested']} ops ingested at "
      f"{d['updates_per_s']} upd/s, {d['overlay_applies']} overlay "
      "applies, 0 repacks)")
EOF

echo "== async serve pump: --inflight 4 cmp-identical to --inflight 1 (fnum=2) =="
# the dispatch-window smoke (serve/pipeline.py): the SAME mixed query
# stream + 10-op delta stream through the CLI at window depth 1 and 4
# — per-query value digests (--dump_results, submit order) must be
# byte-identical, the ingest stays overlay-only (zero repacks), and
# the W=4 run must actually engage the window (pump block present,
# batches overlapped).  max_batch 4 with ingest_every 16 keeps TWO
# batches per ingest group, so the window genuinely overlaps.
for w in 1 4; do
  python -m libgrape_lite_tpu.cli serve \
    --efile "$DS/p2p-31.e" --vfile "$DS/p2p-31.v" $PLATFORM_ARGS --fnum 2 \
    --stream "$OUT/dyn_stream.txt" --max_batch 4 \
    --delta_stream "$OUT/dyn_delta.txt" --ingest_every 16 \
    --dyn_repack_ratio 0.5 --inflight $w \
    --dump_results "$OUT/async_w$w.res" > "$OUT/async_w$w.json"
done
cmp "$OUT/async_w1.res" "$OUT/async_w4.res" \
  || { echo "ASYNC PUMP (W=4) DIVERGED FROM THE SYNC LOOP (W=1)" >&2; exit 1; }
python - "$OUT/async_w4.json" <<'EOF'
import json, sys
rec = json.loads(
    [l for l in open(sys.argv[1]) if l.startswith("{")][-1])
assert rec["queries"] == 24 and rec["failed"] == 0, rec
assert rec["dyn"]["ingested"] == 10 and rec["dyn"]["repack_count"] == 0, rec["dyn"]
p = rec["pump"]
assert p["window"] == 4 and p["engaged"] >= 1, p
assert p["max_inflight"] >= 2, p  # the window genuinely held >1 batch
print(f"  OK (cmp-identical across windows; engaged={p['engaged']}, "
      f"max_inflight={p['max_inflight']}, "
      f"overlapped={p['overlapped_harvests']})")
EOF

echo "== fleet: 2 tenants x 2 replicas + drain, cmp-identical to single-replica (fnum=2) =="
# the serving-fleet smoke (fleet/, docs/FLEET.md): the SAME mixed
# stream + 10-op delta stream through the CLI, once plain and once as
# a 2-replica router with a by_app tenant split and replica 0 drained
# mid-stream (it rejoins through its catch-up log after the next
# ingest barrier) — per-query value digests must be byte-identical
# (zero-downtime drain, version-fenced ingest), zero queries dropped,
# and both replicas must have genuinely served traffic
python -m libgrape_lite_tpu.cli serve \
  --efile "$DS/p2p-31.e" --vfile "$DS/p2p-31.v" $PLATFORM_ARGS --fnum 2 \
  --stream "$OUT/dyn_stream.txt" --max_batch 4 \
  --delta_stream "$OUT/dyn_delta.txt" --ingest_every 8 \
  --dyn_repack_ratio 0.5 \
  --dump_results "$OUT/fleet_r1.res" > "$OUT/fleet_r1.json"
python -m libgrape_lite_tpu.cli serve \
  --efile "$DS/p2p-31.e" --vfile "$DS/p2p-31.v" $PLATFORM_ARGS --fnum 2 \
  --stream "$OUT/dyn_stream.txt" --max_batch 4 \
  --delta_stream "$OUT/dyn_delta.txt" --ingest_every 8 \
  --dyn_repack_ratio 0.5 --replicas 2 --tenants by_app --drain_at 12 \
  --dump_results "$OUT/fleet_r2.res" > "$OUT/fleet_r2.json"
cmp "$OUT/fleet_r1.res" "$OUT/fleet_r2.res" \
  || { echo "FLEET (R=2, drained) DIVERGED FROM THE SINGLE-REPLICA RUN" >&2; exit 1; }
python - "$OUT/fleet_r2.json" <<'EOF'
import json, sys
rec = json.loads(
    [l for l in open(sys.argv[1]) if l.startswith("{")][-1])
assert rec["queries"] == 24 and rec["failed"] == 0, rec
fl = rec["fleet"]
assert fl["replicas"] == 2 and fl["tenants"] == 2, fl
assert fl["dropped"] == 0 and fl["drains"] == 1, fl
reps = fl["router"]["replicas"]
assert all(r["served"] > 0 for r in reps.values()), reps
assert len({r["version"] for r in reps.values()}) == 1, reps
print(f"  OK (cmp-identical; fence={fl['router']['fence']}, "
      + ", ".join(f"{k} served {v['served']}" for k, v in reps.items())
      + ")")
EOF

echo "== autopilot: closed-loop serve with a repeated-source stream (fnum=2) =="
# the control-plane smoke (autopilot/, docs/AUTOPILOT.md): a
# repeated-source stream (4 sources x 6 cycles) through
# `serve --autopilot` — repeats of an already-answered (app, source)
# pair must come out of the fence-epoch result cache instead of the
# device (cache_hits asserted), every query must still succeed, and
# the summary must carry the autopilot block (ticks, scale counters,
# cache snapshot)
python - > "$OUT/ap_stream.txt" <<'EOF'
for cycle in range(6):
    for s in (6, 7, 8, 9):
        print("sssp", s)
EOF
python -m libgrape_lite_tpu.cli serve \
  --efile "$DS/p2p-31.e" --vfile "$DS/p2p-31.v" $PLATFORM_ARGS --fnum 2 \
  --stream "$OUT/ap_stream.txt" --max_batch 4 \
  --autopilot --min_replicas 1 --max_replicas 2 \
  > "$OUT/ap_serve.json"
python - "$OUT/ap_serve.json" <<'EOF'
import json, sys
rec = json.loads(
    [l for l in open(sys.argv[1]) if l.startswith("{")][-1])
assert rec["queries"] == 24 and rec["failed"] == 0, rec
ap = rec["autopilot"]
assert ap["ticks"] >= 24, ap
assert ap["cache_hits"] >= 8, ap  # repeats answered off-device
assert ap["cache"]["entries"] >= 4, ap["cache"]
assert ap["replicas_final"] >= ap["min_replicas"], ap
assert rec["fleet"]["dropped"] == 0, rec["fleet"]
print(f"  OK (24 queries, {ap['cache_hits']} cache hit(s) of "
      f"{ap['cache_hits'] + ap['cache_misses']} probes, "
      f"{ap['ticks']} control ticks, "
      f"{ap['replicas_final']} replica(s))")
EOF

echo "== grape-lint: static contract rules, zero unsuppressed findings =="
# the AST gate (R1-R9, analysis/): exits 1 on any finding the
# baseline does not name, 3 if the --json record drifts from its own
# declared schema — both fail this harness (set -e)
python scripts/grape_lint.py --json > "$OUT/lint.json"
python - "$OUT/lint.json" <<'EOF'
import json, sys
rec = json.load(open(sys.argv[1]))
assert rec["ok"], rec["findings"]
live = [f for f in rec["findings"] if not f["suppressed"]]
assert live == [], live
print(f"  OK (clean; {rec['suppressed']} named suppression(s))")
EOF

echo "== BENCH record schema (fresh small-scale bench incl. serve block + archived r05) =="
GRAPE_BENCH_SCALE=10 GRAPE_BENCH_NO_PROBE=1 \
  GRAPE_BENCH_NO_GUARD=1 python bench.py > "$OUT/bench.json" 2>/dev/null
python scripts/check_bench_schema.py "$OUT/bench.json" BENCH_r05.json
python - "$OUT/bench.json" <<'EOF'
import json, sys
rec = json.loads(
    [l for l in open(sys.argv[1]) if l.startswith("{")][-1])
sv = rec["serve"]
for app in ("sssp", "bfs"):
    qps = {k: v["qps"] for k, v in sv[app].items()}
    assert all(v["ok"] == v["n"] for v in sv[app].values()), sv[app]
    print(f"  serve {app}: qps {qps}")
tel = rec["telemetry"]
assert tel["federation_ok"] and tel["scrape_ok"], tel
assert tel["namespaces"] >= 6, tel
assert {"queue_wait_us", "dispatch_us", "device_us",
        "harvest_us"} <= set(tel["stages"]), tel
print(f"  telemetry: {tel['namespaces']} namespaces federated, "
      f"live scrape ok, {len(tel['stages'])} stages")
EOF

echo "== bench_compare: declaration-driven regression gate =="
# satellite of the schema gate (scripts/bench_compare.py): identical
# records gate zero regressions, the archived full-scale r05 record
# SKIPS (config guards) instead of false-failing against a scale-10
# run, and a seeded 2x regression must exit 2
python scripts/bench_compare.py "$OUT/bench.json" "$OUT/bench.json" > /dev/null
python scripts/bench_compare.py "$OUT/bench.json" BENCH_r05.json > /dev/null
python - "$OUT/bench.json" > "$OUT/bench_regressed.json" <<'EOF'
import json, sys
rec = json.loads(
    [l for l in open(sys.argv[1]) if l.startswith("{")][-1])
rec["value"] *= 0.5                            # halve the headline MTEPS
rec["telemetry"]["stages"]["device_us"]["p99"] *= 10.0
json.dump(rec, sys.stdout)
EOF
set +e
python scripts/bench_compare.py "$OUT/bench.json" "$OUT/bench_regressed.json" \
  > "$OUT/bench_cmp.txt" 2>&1
BC_RC=$?
set -e
test "$BC_RC" -eq 2 \
  || { echo "SEEDED REGRESSION NOT GATED (rc=$BC_RC)" >&2; cat "$OUT/bench_cmp.txt"; exit 1; }
grep -q "REGRESSION" "$OUT/bench_cmp.txt"
grep -q "telemetry.stages.device_us.p99" "$OUT/bench_cmp.txt"
echo "  OK (self-compare clean, archived r05 skipped-not-failed, seeded 2x regression exits 2)"

echo "== calibration: CPU rate fit + drift gate (ops/calibration.py) =="
# the r17 self-calibrating cost-ledger loop end to end on the CPU
# backend: fit a profile from a measured sweep (persisting sweep +
# profile), re-gate the RECORDED samples under the fitted profile
# (deterministic — no scheduler re-race), then prove a deliberately
# corrupted profile trips the 5% drift gate with exit 2, standalone
# AND through the bench calibration lane
timeout 900 python scripts/calibrate.py --scales 11,12 --repeats 3 \
  --out "$OUT/rates.json" --samples-out "$OUT/rate_samples.json" \
  > "$OUT/calibrate.txt"
timeout 300 python scripts/calibrate.py --check \
  --samples "$OUT/rate_samples.json" --profile "$OUT/rates.json" > /dev/null
python - "$OUT/rates.json" "$OUT/rates_bad.json" <<'EOF'
import json, sys
d = json.load(open(sys.argv[1]))
d["vpu_lanes_per_cycle"] *= 20            # a deliberately wrong rate
json.dump(d, open(sys.argv[2], "w"))
EOF
set +e
timeout 300 python scripts/calibrate.py --check \
  --samples "$OUT/rate_samples.json" --profile "$OUT/rates_bad.json" \
  > "$OUT/calibrate_bad.txt" 2>&1
CAL_RC=$?
set -e
test "$CAL_RC" -eq 2 \
  || { echo "CORRUPTED PROFILE NOT GATED (rc=$CAL_RC)" >&2; cat "$OUT/calibrate_bad.txt"; exit 1; }
# the bench lane under the same profile/samples: fitted passes, the
# corrupted profile exits 2 (every other lane skipped — this tests
# the gate, not the measurements)
BENCH_CAL="GRAPE_BENCH_SCALE=10 GRAPE_BENCH_NO_PROBE=1 \
  GRAPE_BENCH_NO_GUARD=1 GRAPE_BENCH_NO_SERVE=1 \
  GRAPE_BENCH_NO_SERVE_ASYNC=1 GRAPE_BENCH_NO_DYN=1 \
  GRAPE_BENCH_NO_PIPELINE=1 GRAPE_BENCH_NO_P2D=1 GRAPE_BENCH_NO_SPGEMM=1 \
  GRAPE_BENCH_NO_FLEET=1 GRAPE_BENCH_NO_AUTOPILOT=1 \
  GRAPE_BENCH_NO_TELEMETRY=1 GRAPE_CALIBRATION_SAMPLES=$OUT/rate_samples.json"
env $BENCH_CAL GRAPE_RATE_PROFILE="$OUT/rates.json" \
  python bench.py > "$OUT/bench_calibrated.json" 2>/dev/null
set +e
env $BENCH_CAL GRAPE_RATE_PROFILE="$OUT/rates_bad.json" \
  python bench.py > /dev/null 2> "$OUT/bench_calibrated_bad.err"
BCAL_RC=$?
set -e
test "$BCAL_RC" -eq 2 \
  || { echo "BENCH DRIFT GATE NOT TRIPPED (rc=$BCAL_RC)" >&2; cat "$OUT/bench_calibrated_bad.err"; exit 1; }
echo "  OK (fit within gate, corrupted profile exits 2 standalone + via bench)"

echo "ALL APP TESTS PASSED"
