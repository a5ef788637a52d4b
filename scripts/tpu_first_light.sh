#!/bin/bash
# First-chip playbook (VERDICT r3 next #1): the hardware measurement
# sequence, for a machine that holds a TPU.  chip_smoke.py is the
# quicker proof that the program starts there; run it first.
# Usage:  bash scripts/tpu_first_light.sh [outdir]
set -eo pipefail
cd "$(dirname "$0")/.."
OUT=${1:-scratch/first_light}
mkdir -p "$OUT"
# spgemm plans persist across every step below AND later bench re-runs
export GRAPE_PACK_PLAN_CACHE="$PWD/scratch/pack_plans"

echo "== probe =="
# must see a REAL accelerator: a CPU wall is not a measurement
if ! timeout 120 python -c "
import jax
d = jax.devices()
print(d)
assert d and d[0].platform != 'cpu', f'no accelerator: {d}'
"; then
  echo "no accelerator; aborting" >&2
  exit 1
fi

echo "== grape-lint artifact audit (no baked constants / surprise
compiles ON DEVICE — the A1/A3 contracts proven against real TPU
lowering, not the CPU fallback; docs/STATIC_ANALYSIS.md) =="
if ! timeout 900 python scripts/grape_lint.py --artifact --json \
    > "$OUT/lint_artifact.json" 2> "$OUT/lint_artifact.err"; then
  echo "GRAPE-LINT ARTIFACT AUDIT FAILED (see $OUT/lint_artifact.json" \
       "— a baked constant or surprise compile on device)" >&2
  tail -5 "$OUT/lint_artifact.err" >&2
  exit 1
fi

echo "== primitive rates (prices the sublane dynamic_gather — the
cost-model unknown; see docs/PERF_NOTES.md r4 section) =="
timeout 900 python scripts/pallas_probe.py 2> "$OUT/probe.err" | tee "$OUT/probe.json" || true

echo "== bench (PageRank + SSSP) =="
timeout 3600 python bench.py \
  2> "$OUT/bench.err" | tee "$OUT/bench.json" \
  || { tail -20 "$OUT/bench.err" >&2; exit 1; }

echo "== lcc backend A/B (GRAPE_LCC_BACKEND=intersect vs spgemm —
tiled masked SpGEMM on the MXU, ops/spgemm_pack.py; the bench's own
spgemm lane runs the pair at lane geometry and gates on bit-identity
+ the ledger recount; docs/SPGEMM.md) =="
GRAPE_LCC_BACKEND=intersect \
  timeout 3600 python bench.py \
  2> "$OUT/bench_lcc_int.err" | tee "$OUT/bench_lcc_int.json" || true
GRAPE_LCC_BACKEND=spgemm \
  timeout 3600 python bench.py \
  2> "$OUT/bench_lcc_sp.err" | tee "$OUT/bench_lcc_sp.json" || true
grep -h "\[bench\] spgemm" "$OUT/bench_lcc_int.err" \
  "$OUT/bench_lcc_sp.err" | tail -4 || true

echo "== serve async-pump A/B (dispatch window, serve/pipeline.py —
the bench's own serve_async lane interleaves W=1 vs W=4 at b in
{1,8,32} with concurrent barrier ingest and gates on per-query byte
identity + zero overlay recompiles; on TPU the launch cap defaults to
the full window because the device queue serialises programs without
stealing host cores — the overlap the CPU fallback could not show;
docs/SERVING.md \"The async pump\") =="
timeout 3600 python bench.py \
  2> "$OUT/bench_serve_async.err" | tee "$OUT/bench_serve_async.json" \
  || true
grep -h "\[bench\] serve_async" "$OUT/bench_serve_async.err" \
  | tail -8 || true

echo "== per-stage profile (stepwise mode, per-round wall clock) =="
GRAPE_TPU_VLOG=1 timeout 1200 python - <<'EOF' 2>&1 | tee "$OUT/profile.log" || true
import sys
sys.path.insert(0, ".")
import numpy as np
from bench import rmat_edges
from libgrape_lite_tpu.fragment.edgecut import ShardedEdgecutFragment
from libgrape_lite_tpu.parallel.comm_spec import CommSpec
from libgrape_lite_tpu.utils.id_parser import IdParser
from libgrape_lite_tpu.utils.types import LoadStrategy
from libgrape_lite_tpu.vertex_map.idxer import HashMapIdxer
from libgrape_lite_tpu.vertex_map.partitioner import SegmentedPartitioner
from libgrape_lite_tpu.vertex_map.vertex_map import VertexMap
from libgrape_lite_tpu.models import PageRank
from libgrape_lite_tpu.worker.worker import Worker

n, src, dst = rmat_edges(20, 16)
oids = np.arange(n, dtype=np.int64)
part = SegmentedPartitioner(1, oids)
vm = VertexMap(part, [HashMapIdxer(oids)], IdParser(1, n))
frag = ShardedEdgecutFragment.build(
    CommSpec(fnum=1), vm, src, dst, None, directed=False,
    load_strategy=LoadStrategy.kBothOutIn)
app = PageRank(delta=0.85, max_round=10)
w = Worker(app, frag)
w.query_stepwise(max_rounds=10)   # logs per-round wall clock
EOF

echo "== calibrate-then-recheck (r17, ops/calibration.py,
docs/CALIBRATION.md): fit the FIRST real-TPU rate profile from
measured device walls, persist profile + sweep, then re-run the
bench drift lane UNDER the fitted profile — exit 2 there means the
fit does not model the hardware it just measured =="
timeout 1800 python scripts/calibrate.py \
  --out "$OUT/rates.json" --samples-out "$OUT/rate_samples.json" \
  2> "$OUT/calibrate.err" | tee "$OUT/calibrate.txt" || {
  echo "CALIBRATION FIT/GATE FAILED (see $OUT/calibrate.err)" >&2
}
if [ -f "$OUT/rates.json" ]; then
  GRAPE_RATE_PROFILE="$OUT/rates.json" \
  GRAPE_CALIBRATION_SAMPLES="$OUT/rate_samples.json" \
  GRAPE_BENCH_SCALE=16 \
  GRAPE_BENCH_NO_GUARD=1 GRAPE_BENCH_NO_SERVE=1 \
  GRAPE_BENCH_NO_SERVE_ASYNC=1 GRAPE_BENCH_NO_DYN=1 \
  GRAPE_BENCH_NO_PIPELINE=1 GRAPE_BENCH_NO_P2D=1 \
  GRAPE_BENCH_NO_SPGEMM=1 GRAPE_BENCH_NO_FLEET=1 \
  GRAPE_BENCH_NO_AUTOPILOT=1 GRAPE_BENCH_NO_TELEMETRY=1 \
  timeout 1800 python bench.py \
    > "$OUT/bench_calibrated.json" 2> "$OUT/bench_calibrated.err" || {
    echo "CALIBRATED DRIFT GATE FAILED — the fitted profile drifts" \
         ">5% from its own measurement (see $OUT/bench_calibrated.err)" >&2
  }
fi

echo "== done; results in $OUT =="
