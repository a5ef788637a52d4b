"""Benchmark driver: PageRank + SSSP throughput on one TPU chip.

Prints ONE JSON line.  Primary metric: PageRank MTEPS/chip (edges
traversed per second across the 10 pull rounds, symmetrised edge
count) on an RMAT-style power-law graph.  The same line carries the
second north star as a nested object under "sssp" (VERDICT r3 next
#5): SSSP MTEPS/chip = single-pass edge count / query wall-clock on
the same graph with uniform(0.1,10) weights.

The backend is initialised once, in this process; on a CPU the bench
exits non-zero unless GRAPE_BENCH_NO_PROBE=1 asked for the CPU by name
— then the metric says `_cpu`.
Env knobs:
  GRAPE_BENCH_SCALE=N          RMAT scale (default 20)
  GRAPE_BENCH_NO_PROBE=1       run on the CPU (the schema checks of
                               scripts/app_tests.sh)

BENCH-json obs fields (r8): `obs` carries the per-phase span rollup
from the in-memory tracer armed for the whole bench — `spans` maps
span name (query/peval/superstep/chunk/...) to {count, total_s,
mean_s, max_s}, `trace_id` ties the record to a GRAPE_TRACE file when
one was requested.  Every record is self-checked against
scripts/check_bench_schema.py before printing; schema drift exits 3
AFTER all measurements are out (ledger drift keeps exit 2).

BENCH-json serve fields (r9): `serve` carries the serving-runtime
throughput lane (serve/, docs/SERVING.md) — per app (sssp, bfs) and
per batch size (b1/b8/b32), queries/sec with p50/p99 latency over a
32-query single-source stream on the serve-scale RMAT twin, plus the
admission queue's batch-size histogram.  Env knobs:
GRAPE_BENCH_NO_SERVE=1 skips, GRAPE_BENCH_SERVE_SCALE /
GRAPE_BENCH_SERVE_QUERIES size the lane.

BENCH-json serve_async fields (r12): `serve_async` carries the
async-pump dispatch-window A/B (serve/pipeline.py, docs/SERVING.md
"The async pump") — `window_ab` maps w1/w4 to per-batch-size
(b1/b8/b32) points of qps/p50/p99/updates_per_s over a 32-query SSSP
stream WITH a concurrent barrier-ingested delta stream, `identical`
is the per-query byte-identity verdict W=4 vs W=1 (a break exits 2),
`overlay_recompiles` counts XLA compiles during the measured
overlay-only ingests (non-zero exits 2), `qps_win_b8` is the headline
measured ratio, and `admission_wait_ms` carries the submit->dispatch
p50/p99 of the W=4 b=8 run.  Unlike the 2-D lane this win is
MEASURED on CPU fallback, not modeled.  Env knobs:
GRAPE_BENCH_NO_SERVE_ASYNC=1 skips, GRAPE_BENCH_SERVE_ASYNC_QUERIES /
_UPDATES size the lane (scale follows GRAPE_BENCH_SERVE_SCALE).

BENCH-json fleet fields (r13): `fleet` carries the serving-fleet
drain drill (fleet/, docs/FLEET.md) — R=2 replica sessions behind a
version-fenced least-outstanding router serving a mixed sssp+khop
stream with concurrent barrier ingest, replica 0 drained mid-run for
an offline forced repack and rejoined through its catch-up log.
`per_replica` maps r0/r1 to sustained qps with p50/p99 (the ROADMAP
target bench: qps@p99 PER REPLICA), `byte_identical` is the
per-query verdict vs the undrained R=1 run, `dropped` must be 0
(zero-downtime), and `readmit_compiles` counts XLA compiles after an
evict -> re-admit of a replica session (must be 0 — warm host
artifacts); any verdict failure exits 2.  Env knobs:
GRAPE_BENCH_NO_FLEET=1 skips, GRAPE_BENCH_FLEET_QUERIES / _UPDATES
size the lane (scale follows GRAPE_BENCH_SERVE_SCALE).

BENCH-json autopilot fields (r16): `autopilot` carries the
closed-loop drill (autopilot/, docs/AUTOPILOT.md) — the feeder's
arrival rate is calibrated to 0.8x the measured service rate and
DOUBLED a third of the way in (`rate_spec`, serve/feeder.py step
schedule); the Autoscaler must answer with >= 1 scale-up through the
zero-drop drain/rejoin/replicate machinery (`scale_ups`, `dropped`
must be 0, `byte_identical` vs the static R=1 scripted run, `p99_ok`
under GRAPE_BENCH_AUTOPILOT_P99_MS), and the result-cache sub-drill
pins a repeated source answered with ZERO XLA compiles
(`cache_hit_compiles`), a fence-bumping ingest reaping the epoch
(`cache_invalidations` > 0), and the post-ingest answer
byte-identical to a cache-less run on the same mutated graph
(`post_ingest_identical`); any verdict failure exits 2.  Env knobs:
GRAPE_BENCH_NO_AUTOPILOT=1 skips, GRAPE_BENCH_AUTOPILOT_QUERIES /
_P99_MS size the lane (scale follows GRAPE_BENCH_SERVE_SCALE).

BENCH-json telemetry fields (r15): `telemetry` carries the
observability plane's own lane (obs/, docs/OBSERVABILITY.md) — the
stats-federation census (`namespaces` registered + the
`federation_ok` self_check verdict), `scrape_ok` from a LIVE
mid-process scrape of the OpenMetrics exporter (the text must name
every federated namespace and end with `# EOF`), `stages` with the
per-stage p50/p99 latency decomposition from ServeResult.stages
(queue_wait/window_wait/dispatch/device/harvest), the SLO burn under
a generous objective, and the flight-recorder counters.  Env knobs:
GRAPE_BENCH_NO_TELEMETRY=1 skips, GRAPE_BENCH_TELEMETRY_SCALE /
_QUERIES size the lane.

BENCH-json dyn fields (r10): `dyn` carries the dynamic-graph lane
(dyn/, docs/DYNAMIC_GRAPHS.md) — `updates_per_s` ingested through
ServeSession.ingest while an SSSP query stream stays live (overlay
side-path below the repack threshold: zero replanning/recompiles),
`repack_count` / `overlay_applies`, live-query ok counts, and the
incremental-IncEval point: `inc_seeded_rounds` vs `inc_cold_rounds`
and the `inc_speedup` wall ratio of `Worker.query_incremental` seeded
from the pre-delta fixed point against a cold recompute.  Env knobs:
GRAPE_BENCH_NO_DYN=1 skips, GRAPE_BENCH_DYN_SCALE /
GRAPE_BENCH_DYN_UPDATES size the lane.

BENCH-json partition2d fields (r10): `partition2d` carries the 1-D
edge-cut vs 2-D vertex-cut A/B (fragment/partition.py, models/
vc2d.py, docs/PARTITION2D.md) on a hub-heavy RMAT at fnum 4 (k=2) —
max-tile edge count vs the raw 1-D hub fragment (the SCALE_NOTES
pathology), modeled exchange bytes under the shared ledgers,
serial-vs-2D wall, SSSP byte-identity / PageRank eps-identity
verdicts, and the planner's recorded auto decision against the
measured winner.
Env knobs: GRAPE_BENCH_NO_P2D=1 skips, GRAPE_BENCH_P2D_SCALE sizes
the twin (default 12 regardless of GRAPE_BENCH_SCALE — hub
statistics under-develop below that).

BENCH-json spgemm fields (r11): `spgemm` carries the masked-SpGEMM
lane (ops/spgemm_pack.py, docs/SPGEMM.md) — LCC intersect-vs-spgemm
wall A/B at GRAPE_BENCH_SPGEMM_SCALE (default min(SCALE, 10)) with
the bit-exactness verdict, the shipped-plan ledger recount (the 5%
gate), plan-time pruning stats (items / items_per_edge over the
oriented mask edges), and the MODELED ops/edge A/B at full bench
geometry: `mxu_elems_per_edge` + `vpu_ops_per_edge` for the spgemm
pipeline vs `intersect_word_ops_per_edge` for the popcount sweep
(per mask edge; the intersect bitmap is O(N²/8) bytes at scale 20 —
physically unbuildable, which is the breadth ceiling the primitive
lifts), priced into `modeled_*_s` with the `modeled_win` verdict and
the ledger-auto decision at lane geometry.  Env knobs:
GRAPE_BENCH_NO_SPGEMM=1 skips, GRAPE_BENCH_SPGEMM_SCALE sizes the
executed A/B.

The `calibration` lane (r17, ops/calibration.py, docs/CALIBRATION.md)
re-prices a measured sample set under the ACTIVE RateProfile and
exits 2 when an explicitly installed GRAPE_RATE_PROFILE has drifted
more than 5% from measurement on any priced surface; it also reports
a fresh fit (rates, RMS residual, fallback notes) for the
pinned-vs-fitted PERF_NOTES table.  Env knobs:
GRAPE_BENCH_NO_CALIBRATION=1 skips, GRAPE_CALIBRATION_SAMPLES points
at a recorded sweep (deterministic in CI) instead of re-measuring.

Baseline derivation (BASELINE.md): the reference GPU backend runs
PageRank on soc-LiveJournal1 (68.99M directed edges) in 24.65 ms on
8× V100 (`Performance.md:94`), i.e. 68.99e6 * 10 rounds / 0.02465 s
/ 8 chips ≈ 3500 MTEPS per chip.  SSSP: 32.3 ms on the same graph
(`Performance.md:82`) ≈ 68.99e6 / 0.0323 / 8 ≈ 267 MTEPS per chip
(single-pass convention — SSSP round counts are graph-dependent, so
TEPS counts each edge once per query).  vs_baseline = ours / theirs.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

import numpy as np


BASELINE_MTEPS_PER_CHIP = 3500.0
PLAN_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "scratch", "pack_plans"
)
SSSP_BASELINE_MTEPS_PER_CHIP = 267.0
SCALE = int(os.environ.get("GRAPE_BENCH_SCALE", 20))  # 2^20 vertices
EDGE_FACTOR = 16


def rmat_edges(scale: int, edge_factor: int, seed: int = 7):
    """Vectorised RMAT (a=0.57,b=0.19,c=0.19,d=0.05)."""
    n = 1 << scale
    e = n * edge_factor
    rng = np.random.default_rng(seed)
    src = np.zeros(e, dtype=np.int64)
    dst = np.zeros(e, dtype=np.int64)
    a, b, c = 0.57, 0.19, 0.19
    for bit in range(scale):
        r = rng.random(e)
        src_bit = r >= a + b
        dst_bit = ((r >= a) & (r < a + b)) | (r >= a + b + c)
        src = (src << 1) | src_bit
        dst = (dst << 1) | dst_bit
    return n, src, dst


def build_bench_inputs(scale: int | None = None):
    """(n, src, dst, comm_spec, vm): the bench graph's host-side
    inputs — shared by every lane so RMAT draws and the vertex map
    stay bit-identical by construction.  Lanes that only build a
    WEIGHTED twin (the dyn lane) stop here and skip the unweighted
    shard build + device upload."""
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec
    from libgrape_lite_tpu.utils.id_parser import IdParser
    from libgrape_lite_tpu.vertex_map.idxer import HashMapIdxer
    from libgrape_lite_tpu.vertex_map.partitioner import (
        SegmentedPartitioner,
    )
    from libgrape_lite_tpu.vertex_map.vertex_map import VertexMap

    n, src, dst = rmat_edges(SCALE if scale is None else scale,
                             EDGE_FACTOR)
    comm_spec = CommSpec(fnum=1)
    oids = np.arange(n, dtype=np.int64)
    part = SegmentedPartitioner(1, oids)
    vm = VertexMap(part, [HashMapIdxer(oids)], IdParser(1, n))
    return n, src, dst, comm_spec, vm


def build_bench_fragment(scale: int | None = None):
    """The bench graph + fragment.  The real load path: hash-partitioned
    vertex map over the native open-addressing idxer (round 1 bypassed
    VertexMap with an identity idxer because the dict path was
    load-bound; the native table is ~30x faster, so the bench exercises
    the honest path).
    `scale` overrides GRAPE_BENCH_SCALE (the serve lane runs a smaller
    twin of the same construction)."""
    from libgrape_lite_tpu.fragment.edgecut import ShardedEdgecutFragment
    from libgrape_lite_tpu.utils.types import LoadStrategy

    n, src, dst, comm_spec, vm = build_bench_inputs(scale)
    frag = ShardedEdgecutFragment.build(
        comm_spec, vm, src, dst, None,
        directed=False,
        load_strategy=LoadStrategy.kBothOutIn,
    )
    return n, src, dst, comm_spec, vm, frag


def build_bench_weighted_fragment(src, dst, comm_spec, vm,
                                  retain_edge_list=False):
    """The SSSP lane's weighted twin (seed-11 uniform(0.1,10) f32).
    The dyn lane builds its twin with retain_edge_list=True (the repack
    path edits the host edge list)."""
    from libgrape_lite_tpu.fragment.edgecut import ShardedEdgecutFragment
    from libgrape_lite_tpu.utils.types import LoadStrategy

    rng_w = np.random.default_rng(11)
    w = rng_w.uniform(0.1, 10.0, size=len(src)).astype(np.float32)
    return ShardedEdgecutFragment.build(
        comm_spec, vm, src, dst, w,
        directed=False,
        load_strategy=LoadStrategy.kBothOutIn,
        retain_edge_list=retain_edge_list,
    )


def spgemm_lane(scale: int, bench_scale: int, ef: int) -> dict:
    """The r11 masked-SpGEMM lane (ops/spgemm_pack.py, ROADMAP 5a):
    LCC intersect-vs-spgemm wall A/B at the lane geometry with the
    bit-exactness verdict and the shipped-plan recount, plus the
    MODELED ops/edge A/B at full bench geometry (plan_only — the
    intersect bitmap is O(N^2/8) bytes there, physically unbuildable,
    which is exactly the ceiling the primitive lifts)."""
    import libgrape_lite_tpu.ops.spgemm_pack as sg
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.worker.worker import Worker

    n, src, dst, comm_spec, vm, frag = build_bench_fragment(scale)
    prev_backend = os.environ.get("GRAPE_LCC_BACKEND")

    def restore():
        if prev_backend is None:
            os.environ.pop("GRAPE_LCC_BACKEND", None)
        else:
            os.environ["GRAPE_LCC_BACKEND"] = prev_backend

    def best_of(backend: str, n_meas: int = 2):
        os.environ["GRAPE_LCC_BACKEND"] = backend
        try:
            app = APP_REGISTRY["lcc_bitmap"]()
            wk = Worker(app, frag)
            wk.query()  # compile + plan
            best = math.inf
            for _ in range(n_meas):
                t0 = time.perf_counter()
                wk.query()
                best = min(best, time.perf_counter() - t0)
            return best, wk.result_values()
        finally:
            restore()

    t_int, r_int = best_of("intersect")
    t_sp, r_sp = best_of("spgemm")
    byte_identical = bool(np.array_equal(r_int, r_sp))

    # recount gate on the EXECUTED plan's shipped streams
    scripts = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    from pack_cost_model import spgemm_recount

    plan = sg.resolve_spgemm_dispatch(frag).plan
    rec = spgemm_recount(plan)

    # modeled A/B at FULL bench geometry (plan_only: counts + ledger,
    # no stream materialization)
    from libgrape_lite_tpu.models.lcc import _lcc_chunk

    lcc_chunk = _lcc_chunk()  # the intersect model must price the
    # chunk a real query would run (GRAPE_LCC_CHUNK), not a literal
    bn, bsrc, bdst = rmat_edges(bench_scale, ef)
    bplan = sg.plan_spgemm_edges(bsrc, bdst, bn, plan_only=True)
    n_pad_b = bplan.n_pad
    ep_sym = 2 * len(bsrc)
    b_int = sg.intersect_ledger_geom(
        n_pad_b, ep_sym, ep_sym, 1, n_pad_b, lcc_chunk)
    prices = sg.price_backends(bplan.ledger, b_int)
    me = max(1, bplan.mask_edges)
    # the auto decision AT LANE GEOMETRY, recorded like any query's
    os.environ["GRAPE_LCC_BACKEND"] = "auto"
    try:
        auto_backend = sg.resolve_lcc_backend("LCC", frag,
                                              chunk=lcc_chunk)
    finally:
        restore()
    return {
        "scale": scale,
        "bench_scale": bench_scale,
        "intersect_s": round(t_int, 4),
        "spgemm_s": round(t_sp, 4),
        "byte_identical": byte_identical,
        "items": int(plan.items),
        "items_per_edge": float(plan.stats["items_per_edge"]),
        "mask_edges": int(plan.mask_edges),
        "ledger_recount_mismatch": rec["spgemm_recount_mismatch"],
        # per MASK (oriented dedup) edge, at bench geometry
        "bench_mask_edges": int(bplan.mask_edges),
        "bench_items_per_edge": float(bplan.stats["items_per_edge"]),
        "mxu_elems_per_edge": round(
            bplan.ledger["totals"]["mxu_ops"] / me, 1),
        "vpu_ops_per_edge": round(
            bplan.ledger["totals"]["vpu_ops"] / me, 1),
        "intersect_word_ops_per_edge": round(b_int["word_ops"] / me, 1),
        "modeled_spgemm_s": round(prices["t_spgemm_s"], 6),
        "modeled_intersect_s": round(prices["t_intersect_s"], 6),
        "modeled_win": bool(prices["spgemm_wins"]),
        "auto_backend": auto_backend,
    }


def partition2d_lane(scale: int) -> dict:
    """The 1-D edge-cut vs 2-D vertex-cut A/B (r10, ROADMAP item 2;
    fragment/partition.py, models/vc2d.py, docs/PARTITION2D.md) on a
    hub-heavy RMAT at fnum 4 (k=2):

      * `hub_1d_edges` — the max 1-D shard edge count on the RAW
        degree-correlated id space: the recorded pathology
        (docs/SCALE_NOTES.md) every shard's padding pays;
      * the WALL A/B runs on the SHUFFLED id space (gen_rmat
        shuffle_perm — the honest best-case 1-D baseline, satellite
        of this PR): SSSP serial-1-D vs 2-D best-of-3, byte-identity
        of per-oid results, PageRank 1-D vs PageRankVC eps-identity;
      * the planner's recorded auto decision (modeled costs from the
        shared rate/byte ledgers) against the measured winner — walls
        within PARTITION_TIE_BAND count as agreeing with the planner:
        the model prices TPU rates, and a CPU-fallback wall split
        finer than the band is collective-dispatch noise, not signal.
    """
    import jax

    from libgrape_lite_tpu.fragment.edgecut import ShardedEdgecutFragment
    from libgrape_lite_tpu.fragment.partition import resolve_partition
    from libgrape_lite_tpu.fragment.vertexcut import (
        ImmutableVertexcutFragment,
    )
    from libgrape_lite_tpu.models import (
        PageRank,
        PageRankVC,
        SSSP,
        SSSPVC2D,
    )
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec
    from libgrape_lite_tpu.utils.types import LoadStrategy
    from libgrape_lite_tpu.vertex_map.partitioner import (
        SegmentedPartitioner,
    )
    from libgrape_lite_tpu.vertex_map.vertex_map import VertexMap
    from libgrape_lite_tpu.worker.worker import Worker

    fnum, k = 4, 2
    if jax.device_count() < fnum:
        raise RuntimeError("partition2d lane needs >= 4 devices")
    scripts = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    from gen_rmat import shuffle_perm

    n, src_raw, dst_raw = rmat_edges(scale, EDGE_FACTOR)
    # the recorded pathology: max 1-D shard ie-edge count on the raw
    # degree-correlated ids (contiguous-range partitioner convention)
    shard_w = max(1, -(-n // fnum))
    d_sym = np.concatenate([dst_raw, src_raw])
    hub_1d = int(np.bincount(
        np.minimum(d_sym // shard_w, fnum - 1), minlength=fnum
    ).max())

    perm = shuffle_perm(n)
    src, dst = perm[src_raw], perm[dst_raw]
    rng_w = np.random.default_rng(11)
    w = rng_w.uniform(0.1, 10.0, size=len(src)).astype(np.float32)
    oids = np.arange(n, dtype=np.int64)

    comm = CommSpec(fnum=fnum)
    vm = VertexMap.build(oids, SegmentedPartitioner(fnum, oids))
    frag_1d = ShardedEdgecutFragment.build(
        comm, vm, src, dst, w, directed=False,
        load_strategy=LoadStrategy.kBothOutIn,
    )
    max_1d = int(np.bincount(
        np.minimum(np.concatenate([dst, src]) // shard_w, fnum - 1),
        minlength=fnum,
    ).max())
    frag_2d = ImmutableVertexcutFragment.build(
        comm, oids, src, dst, w, directed=False, symmetrize=True,
    )
    tiles = frag_2d.tile_stats()

    def assembled(worker, frag):
        vals = worker.result_values()
        out = np.full(n, np.nan, dtype=vals.dtype)
        for f in range(frag.fnum):
            m = frag.inner_vertices_num(f)
            if m:
                out[np.asarray(frag.inner_oids(f))] = vals[f, :m]
        return out

    def best_of(app, frag, n_meas=3, **kw):
        worker = Worker(app, frag)
        worker.query(**kw)  # warm (compile + plan)
        best = float("inf")
        for _ in range(n_meas):
            t0 = time.perf_counter()
            worker.query(**kw)
            best = min(best, time.perf_counter() - t0)
        return best, assembled(worker, frag)

    t_1d, res_1d = best_of(SSSP(), frag_1d, source=0)
    t_2d, res_2d = best_of(SSSPVC2D(), frag_2d, source=0)
    byte_identical = res_1d.tobytes() == res_2d.tobytes()

    # PageRank: sum folds regroup across tiles -> eps, not bytes
    _, pr_1d = best_of(PageRank(delta=0.85, max_round=10), frag_1d,
                       n_meas=1, max_round=10)
    frag_2d_raw = ImmutableVertexcutFragment.build(
        comm, oids, src, dst, None, directed=False,
    )
    _, pr_2d = best_of(PageRankVC(), frag_2d_raw, n_meas=1,
                       delta=0.85, max_round=10)
    # the repo's eps convention (tests/verifiers.py eps_verify, from
    # the reference's eps_check.cc): 1e-4 relative — the bench runs
    # f32 (x64 off), so f64-tight bounds would misread f32 epsilon
    # accumulation as divergence
    pr_rel = float(np.max(
        np.abs(pr_1d - pr_2d) / np.maximum(np.abs(pr_1d), 1e-300)
    ))

    decision = resolve_partition(
        "sssp", fnum, src, dst, oids, directed=False, mode="auto"
    )
    costs = decision["costs"]
    planner_choice = decision["mode"]
    measured_winner = "2d" if t_2d < t_1d else "1d"
    tie = abs(t_2d - t_1d) / max(min(t_2d, t_1d), 1e-9) \
        <= PARTITION_TIE_BAND
    decision_matches = (planner_choice == measured_winner) or tie

    return {
        "scale": scale,
        "fnum": fnum,
        "k": k,
        "app": "sssp",
        "hub_1d_edges": hub_1d,
        "max_1d_edges": max_1d,
        "max_tile_edges": tiles["max_tile_edges"],
        "tile_skew": tiles["tile_skew"],
        "tile_ratio_vs_hub": round(
            tiles["max_tile_edges"] / max(1, hub_1d), 4),
        "tile_bound_ok": tiles["max_tile_edges"] <= 0.5 * hub_1d,
        "exchange_bytes_1d": costs["1d"]["exchange_bytes"],
        "exchange_bytes_2d": costs["2d"]["exchange_bytes"],
        "exchange_reduced": (
            costs["2d"]["exchange_bytes"] < costs["1d"]["exchange_bytes"]
        ),
        "serial_1d_s": round(t_1d, 4),
        "vc2d_s": round(t_2d, 4),
        "sssp_byte_identical": byte_identical,
        "pagerank_max_rel_err": pr_rel,
        "pagerank_eps_identical": pr_rel < 1e-4,
        "planner_choice": planner_choice,
        "planner_t1d_s": costs["1d"]["t_round_s"],
        "planner_t2d_s": costs["2d"]["t_round_s"],
        "measured_winner": measured_winner,
        "decision_matches": decision_matches,
    }


def obs_gang_lane() -> dict:
    """The gang-telemetry self-drill (PR 20; obs/gang.py,
    docs/OBSERVABILITY.md "Gang-wide telemetry").  The bench is a
    single process, so the lane builds the gang in-process: two fake
    rank tracers (the constructor rank/nprocs fallback) each record a
    superstep span and one leg of a breach-vote flow, write real
    sidecars into a scratch `.gang` dir with an injected clock
    handshake (rank 1's clock deliberately skewed), and the rank-0
    assembler must merge them into one complete, aligned, monotonic
    timeline with the vote arrow crossing both rank tracks — the same
    code path `trace_report --gang` and the fault drill run.

    The second leg re-proves the PR 15 invariant at bench time: the
    fused runner's lowered HLO must be byte-identical armed vs
    disarmed (tracing is a host-side decision; gang stamping is gated
    on nprocs > 1 and must never reach the compiled program)."""
    import shutil
    import tempfile

    import jax

    from libgrape_lite_tpu import obs
    from libgrape_lite_tpu.obs import gang
    from libgrape_lite_tpu.obs.tracer import Tracer

    # -- two-rank sidecar federation ----------------------------------
    tracers = [Tracer(enabled=True, rank=r, nprocs=2) for r in (0, 1)]
    # rank 1's monotonic clock reads 2.5ms ahead of rank 0's: the
    # assembler must shift it back or the merged order interleaves
    offsets = {"0": 0, "1": -2_500_000}
    hs = {"nprocs": 2, "offsets_ns": offsets, "allgather_wall_ns": 0}
    for r, t in enumerate(tracers):
        with t.span("superstep", round=1):
            pass
        t.flow("breach_vote", flow_id=1, cat="gang-vote",
               phase="s" if r == 0 else "f", round=1)
    wd = tempfile.mkdtemp(prefix="grape_obs_gang_")
    try:
        gdir = os.path.join(wd, "trace.gang")
        for r, t in enumerate(tracers):
            gang.write_sidecar(
                tracer=t, handshake=dict(hs, rank=r),
                path=os.path.join(gdir, f"rank_{r}.json"),
                events=t.events(),
            )
        summary = gang.assemble(
            gdir, out_path=os.path.join(wd, "merged.json"))
    finally:
        shutil.rmtree(wd, ignore_errors=True)

    # -- armed-vs-disarmed fused-HLO identity -------------------------
    from libgrape_lite_tpu.fragment.edgecut import ShardedEdgecutFragment
    from libgrape_lite_tpu.models import SSSP
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec
    from libgrape_lite_tpu.utils.types import LoadStrategy
    from libgrape_lite_tpu.vertex_map.partitioner import (
        SegmentedPartitioner,
    )
    from libgrape_lite_tpu.vertex_map.vertex_map import VertexMap
    from libgrape_lite_tpu.worker.worker import Worker

    n = 32
    src = np.arange(n - 1, dtype=np.int64)
    dst = src + 1
    wts = np.ones(n - 1, np.float32)
    oids = np.arange(n, dtype=np.int64)
    fnum = min(jax.device_count(), 2)
    vm = VertexMap.build(oids, SegmentedPartitioner(fnum, oids))
    frag = ShardedEdgecutFragment.build(
        CommSpec(fnum=fnum), vm, src, dst, wts, directed=False,
        load_strategy=LoadStrategy.kBothOutIn,
    )

    def lowered_text():
        w = Worker(SSSP(), frag)
        state = w._place_state(w.app.init_state(frag, source=0))
        eph = frozenset(getattr(w.app, "ephemeral_keys", ()) or ())
        carry = {k: v for k, v in state.items() if k not in eph}
        eph_part = {k: v for k, v in state.items() if k in eph}
        runner = w._make_runner(0)(state)
        return jax.jit(runner).lower(frag.dev, carry, eph_part).as_text()

    armed_txt = lowered_text()  # main() armed obs at the top
    obs.reset()
    disarmed_txt = lowered_text()
    # re-arm: env sinks re-resolve lazily, else back to in-memory
    if os.environ.get(obs.TRACE_ENV) or os.environ.get(obs.METRICS_ENV):
        obs.tracer()
    else:
        obs.configure(in_memory=True)

    return {
        "ranks": len(summary["ranks"]),
        "events": int(summary["events"]),
        "flow_events": int(summary["flow_events"]),
        "cross_rank_flows": int(summary["cross_rank_flows"]),
        "aligned": bool(summary["aligned"]),
        "monotonic": bool(summary["monotonic"]),
        "complete": bool(summary["complete"]),
        "hlo_identical": armed_txt == disarmed_txt,
    }


# measured walls within this band of each other count as agreeing
# with the planner's modeled choice: the model prices TPU VPU/ICI
# rates, and a CPU-fallback split finer than this is dispatch noise
PARTITION_TIE_BAND = 0.25


def _partition2d_lane_subprocess(scale: int) -> dict:
    """Run the lane in a fresh CPU process with a forced 4-device
    host platform (the CPU-fallback bench holds a 1-device backend,
    frozen at init)."""
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=4"
        ).strip()
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--partition2d-lane", str(scale)],
        capture_output=True, text=True, timeout=900, env=env,
    )
    if r.returncode != 0:
        raise RuntimeError(
            f"partition2d-lane subprocess failed: "
            f"{r.stderr.strip()[-500:]}"
        )
    return json.loads(r.stdout.strip().splitlines()[-1])


_SCHEMA_ERRORS: list = []
_VALIDATE_RECORD = None


def _validator():
    """One-time import of the schema checker (the scripts dir goes on
    sys.path once, not per emitted record)."""
    global _VALIDATE_RECORD
    if _VALIDATE_RECORD is None:
        scripts = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts")
        if scripts not in sys.path:
            sys.path.insert(0, scripts)
        from check_bench_schema import validate_record

        _VALIDATE_RECORD = validate_record
    return _VALIDATE_RECORD


def _emit_record(record) -> None:
    """Print one BENCH json line, self-checked against the declared
    schema first (scripts/check_bench_schema.py).  A schema breach is
    loud on stderr but must never cost a measurement — the line still
    prints, and main() exits nonzero at the end instead."""
    try:
        errs = _validator()(record)
    except Exception as e:  # checker bugs must not kill the bench
        errs = [f"schema checker unavailable: {type(e).__name__}: {e}"]
    if errs:
        for err in errs:
            print(f"[bench] SCHEMA: {err}", file=sys.stderr)
        _SCHEMA_ERRORS.extend(errs)
    print(json.dumps(record), flush=True)


def main():
    import jax

    from libgrape_lite_tpu.utils.compile_cache import place_compile_cache

    # the backend is initialised once, here.  GRAPE_BENCH_NO_PROBE=1
    # asks for the CPU by name; a CPU nobody asked for is a failure,
    # never a quieter measurement.
    cpu_asked = bool(os.environ.get("GRAPE_BENCH_NO_PROBE"))
    if cpu_asked:
        jax.config.update("jax_platforms", "cpu")
    place_compile_cache()
    platform = jax.devices()[0].platform
    if platform == "cpu" and not cpu_asked:
        print("[bench] JAX found platform 'cpu', no accelerator — nothing "
              "was measured (GRAPE_BENCH_NO_PROBE=1 asks for the CPU)",
              file=sys.stderr)
        sys.exit(2)
    alive = platform != "cpu"
    suffix = "" if alive else "_cpu"

    from libgrape_lite_tpu import obs
    from libgrape_lite_tpu.models import PageRank
    from libgrape_lite_tpu.worker.worker import Worker

    # obs/: the BENCH record carries per-phase span rollups.  With
    # GRAPE_TRACE set the env arms the file-backed tracer itself (its
    # history feeds the same rollup); otherwise arm in-memory —
    # keeping any GRAPE_METRICS file sink, which alone would drop
    # drained events and leave the rollup empty.  The spans are a few
    # host events per measured query (the fused path is ONE dispatch),
    # so the rollup costs the measurement nothing observable.
    if not os.environ.get(obs.TRACE_ENV):
        obs.configure(
            in_memory=True,
            metrics_path=os.environ.get(obs.METRICS_ENV) or None,
        )

    # persist spgemm plans across bench invocations (explicit
    # GRAPE_PACK_PLAN_CACHE wins)
    os.environ.setdefault("GRAPE_PACK_PLAN_CACHE", PLAN_CACHE_DIR)

    t_load0 = time.perf_counter()
    n, src, dst, comm_spec, vm, frag = build_bench_fragment()
    t_load = time.perf_counter() - t_load0
    e_sym = 2 * len(src)  # undirected pull touches each edge twice per round

    rounds = 10

    def measure(name: str, app_factory, bench_frag, kwargs):
        """Time one app; returns the best seconds or None on failure."""
        try:
            worker = Worker(app_factory(), bench_frag)
            t_c0 = time.perf_counter()
            worker.query(**kwargs)  # warmup (compile + plan)
            t_compile = time.perf_counter() - t_c0
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                worker.query(**kwargs)
                best = min(best, time.perf_counter() - t0)
            print(
                f"[bench] {name}: best={best:.4f}s "
                f"warm+compile={t_compile:.1f}s rounds={worker.rounds}",
                file=sys.stderr,
            )
            return best
        except Exception as e:  # a failed app must not kill the bench
            print(
                f"[bench] {name}: failed: {type(e).__name__}: {e}",
                file=sys.stderr,
            )
            return None

    best_time = measure(
        "pagerank", lambda: PageRank(delta=0.85, max_round=rounds),
        frag, {"max_round": rounds})
    if best_time is None:
        raise RuntimeError("PageRank produced no measurement")
    mteps = e_sym * rounds / best_time / 1e6
    record = {
        "metric": f"pagerank_rmat{SCALE}_mteps_per_chip{suffix}",
        "value": round(mteps, 1),
        "unit": "MTEPS/chip",
        "vs_baseline": round(mteps / BASELINE_MTEPS_PER_CHIP, 3),
        # occupancy context (VERDICT r4 weak #2): fallback numbers on a
        # shared 1-core box wobble with box load; a reader comparing
        # rounds must be able to see whether the box was contended
        "load_avg_1m": round(os.getloadavg()[0], 2),
    }

    # the primary measurement goes out BEFORE the SSSP lane: a chip
    # death mid-SSSP (the documented r1/r2 failure mode) hangs
    # uninterruptibly, and the driver reads the LAST JSON line — so a
    # completed SSSP lane supersedes this line with the combined record
    _emit_record(record)

    # second north star: SSSP on the same graph, weighted (best-effort —
    # a failure must not cost the PageRank measurement)
    try:
        from libgrape_lite_tpu.models import APP_REGISTRY
        from libgrape_lite_tpu.models.sssp_select import select_sssp_variant

        frag_w = build_bench_weighted_fragment(src, dst, comm_spec, vm)
        # probe-and-pick (VERDICT r4 next #4): the bench runs whichever
        # variant the evidence picks for this graph — RMAT is
        # low-diameter, so this resolves to the dense pull, but the
        # decision is now measured, not assumed
        picked, reason = select_sssp_variant(frag_w, 0)
        print(f"[bench] sssp_select -> {picked}: {reason}", file=sys.stderr)
        ss_time = measure("sssp", APP_REGISTRY[picked], frag_w,
                          {"source": 0})
        if ss_time is not None:
            ss_mteps = e_sym / ss_time / 1e6
            record["sssp"] = {
                "metric":
                    f"sssp_rmat{SCALE}_mteps_per_chip{suffix}",
                "value": round(ss_mteps, 1),
                "unit": "MTEPS/chip",
                "variant": picked,
                # the dense pull is one gather pass a round (weights
                # and mask read from the fragment); sssp_delta has no
                # fused form
                "fused_pull": picked == "sssp",
                "vs_baseline":
                    round(ss_mteps / SSSP_BASELINE_MTEPS_PER_CHIP, 3),
            }
    except Exception as e:
        print(f"[bench] sssp lane failed: {type(e).__name__}: {e}",
              file=sys.stderr)
    else:
        if "sssp" in record:
            _emit_record(record)

    # guard overhead lane (r7): guards OFF take literally the same code
    # path as the primary measurement above (Worker.query consults only
    # a host-side env read before compiling the untouched fused runner
    # — tests/test_guard.py pins trace identity), so the off-delta is
    # re-measured here only to put a number next to the structural
    # claim; guards ON pay chunked-fused execution + a probe per chunk,
    # and that overhead is the honest cost of online validation.
    # GRAPE_BENCH_NO_GUARD=1 skips the lane.
    if not os.environ.get("GRAPE_BENCH_NO_GUARD"):
        try:
            from libgrape_lite_tpu.guard import GuardConfig

            def best_of(worker, n=3, **kw):
                b = float("inf")
                for _ in range(n):
                    t0 = time.perf_counter()
                    worker.query(**kw)
                    b = min(b, time.perf_counter() - t0)
                return b

            w_off = Worker(PageRank(delta=0.85, max_round=rounds), frag)
            w_off.query(max_round=rounds)  # warm
            t_off = best_of(w_off, max_round=rounds)
            cfg = GuardConfig(policy="warn", every=2)
            w_on = Worker(PageRank(delta=0.85, max_round=rounds), frag)
            w_on.query(max_round=rounds, guard=cfg)  # warm
            t_on = best_of(w_on, max_round=rounds, guard=cfg)
            record["guard"] = {
                # guards-off IS the fused fast path (trace-identical by
                # construction; pinned in tests/test_guard.py) — the
                # number is here so a reader sees the same wall clock,
                # not a near-zero delta to squint at
                "fused_off_s": round(t_off, 4),
                "guarded_s": round(t_on, 4),
                "guarded_overhead_pct": round((t_on / t_off - 1) * 100, 1),
                "policy": cfg.policy,
                "cadence": cfg.every,
                "probes": (w_on.guard_report or {}).get("probes", 0),
            }
            _emit_record(record)
            print(
                f"[bench] guard: off={t_off:.4f}s on={t_on:.4f}s "
                f"(+{record['guard']['guarded_overhead_pct']}%)",
                file=sys.stderr,
            )
        except Exception as e:  # the guard lane must not cost the bench
            print(
                f"[bench] guard lane failed: {type(e).__name__}: {e}",
                file=sys.stderr,
            )

    # serving throughput lane (r9, ROADMAP item 1): queries/sec at
    # fixed p99 next to MTEPS.  A session pins the graph once; a
    # 32-query single-source stream runs at batch sizes {1, 8, 32} for
    # SSSP and BFS — b=1 is today's one-query-at-a-time dispatch
    # sequence, larger batches share one vmapped dispatch, and the qps
    # ratio IS the amortization win the obs traces predicted (dispatch
    # overhead dominates small queries).  Point queries are a
    # small-graph story, so the lane runs its own smaller RMAT twin
    # (GRAPE_BENCH_SERVE_SCALE, default min(SCALE, 12)): a serving
    # fleet shards many resident graphs rather than one planet-scale
    # one, and a b=32 lane at RMAT-20 would not fit the CPU-fallback
    # heap.  GRAPE_BENCH_NO_SERVE=1 skips.
    if not os.environ.get("GRAPE_BENCH_NO_SERVE"):
        try:
            from libgrape_lite_tpu.serve import BatchPolicy, ServeSession

            serve_scale = int(os.environ.get(
                "GRAPE_BENCH_SERVE_SCALE", min(SCALE, 12)))
            n_q = int(os.environ.get("GRAPE_BENCH_SERVE_QUERIES", 32))
            sn, ssrc, sdst, scomm, svm, sfrag = build_bench_fragment(
                serve_scale
            )
            sfrag_w = build_bench_weighted_fragment(
                ssrc, sdst, scomm, svm
            )
            rng_q = np.random.default_rng(5)
            sources = [int(x) for x in rng_q.integers(0, sn, size=n_q)]
            serve_block = {
                "scale": serve_scale, "queries_per_app": n_q,
            }
            hist: dict = {}
            for app_key, sf in (("sssp", sfrag_w), ("bfs", sfrag)):
                app_block = {}
                for bsz in (1, 8, 32):
                    sess = ServeSession(
                        sf, policy=BatchPolicy(max_batch=bsz)
                    )
                    # warm: compile this (app, batch-shape) runner once
                    for s in sources[:min(bsz, n_q)]:
                        sess.submit(app_key, {"source": s})
                    sess.drain()
                    sess.queue.batch_hist = {}  # hist counts measured work
                    t0 = time.perf_counter()
                    for s in sources:
                        sess.submit(app_key, {"source": s})
                    res = sess.drain()
                    wall = time.perf_counter() - t0
                    lat = sorted(r.latency_s for r in res)
                    point = {
                        "qps": round(len(res) / wall, 2),
                        "p50_ms": round(1e3 * lat[len(lat) // 2], 3),
                        "p99_ms": round(1e3 * lat[
                            min(len(lat) - 1, int(len(lat) * 0.99))
                        ], 3),
                        "n": len(res),
                        "ok": sum(1 for r in res if r.ok),
                    }
                    app_block[f"b{bsz}"] = point
                    for k, v in sess.queue.batch_hist.items():
                        hist[k] = hist.get(k, 0) + v
                    print(
                        f"[bench] serve {app_key} b{bsz}: "
                        f"{point['qps']} q/s p99={point['p99_ms']}ms "
                        f"({point['ok']}/{point['n']} ok)",
                        file=sys.stderr,
                    )
                serve_block[app_key] = app_block
            serve_block["batch_hist"] = {
                str(k): v for k, v in sorted(hist.items())
            }
            record["serve"] = serve_block
            _emit_record(record)
        except Exception as e:  # the serve lane must not cost the bench
            print(f"[bench] serve lane failed: {type(e).__name__}: {e}",
                  file=sys.stderr)

    # telemetry lane (r15, obs/, docs/OBSERVABILITY.md): the stats-
    # federation census (registered namespaces + self_check verdict),
    # a LIVE scrape of the OpenMetrics exporter taken mid-serve (the
    # text must name every federated namespace), the per-stage
    # latency decomposition from ServeResult.stages, the SLO burn
    # under a generous objective, and the flight-recorder counters.
    # GRAPE_BENCH_NO_TELEMETRY=1 skips.
    if not os.environ.get("GRAPE_BENCH_NO_TELEMETRY"):
        try:
            import urllib.request

            from libgrape_lite_tpu.obs import exporter, federation, slo
            from libgrape_lite_tpu.obs.recorder import REC_STATS
            from libgrape_lite_tpu.serve import BatchPolicy, ServeSession
            from libgrape_lite_tpu.serve.queue import latency_summary_ms

            tel_scale = int(os.environ.get(
                "GRAPE_BENCH_TELEMETRY_SCALE", min(SCALE, 10)))
            n_q = int(os.environ.get(
                "GRAPE_BENCH_TELEMETRY_QUERIES", 16))
            tn, tsrc, tdst, tcomm, tvm, tfrag = build_bench_fragment(
                tel_scale
            )
            # a generous objective: observed counters move per query,
            # burn stays 0 unless something is genuinely pathological
            slo.configure("*=60000")
            exp = exporter.start_exporter(0)
            sess = ServeSession(tfrag, policy=BatchPolicy(max_batch=8))
            pump = sess.async_pump(window=2)
            rng_t = np.random.default_rng(6)
            for s in (int(x) for x in rng_t.integers(0, tn, size=n_q)):
                sess.submit("bfs", {"source": s})
            results = []
            while sess.queue.pending() or pump.inflight():
                results.extend(pump.pump(force=True, block=True))
            results.extend(pump.drain())
            # the live mid-process scrape: every federated namespace
            # must be named in the OpenMetrics text
            scrape_ok = False
            try:
                with urllib.request.urlopen(
                    exp.url + "/metrics", timeout=5
                ) as resp:
                    text = resp.read().decode("utf-8")
                scrape_ok = all(
                    f'grape_stats_registry{{namespace="{ns}"}}' in text
                    for ns in federation.registered()
                ) and text.endswith("# EOF\n")
            finally:
                exporter.stop_exporter()
            stage_lists: dict = {}
            for r in results:
                for k, v in (r.stages or {}).items():
                    stage_lists.setdefault(k, []).append(v / 1e6)
            stages_block = {}
            for k, v in sorted(stage_lists.items()):
                s = latency_summary_ms(v)
                stages_block[k] = {"p50": s["p50_ms"],
                                   "p99": s["p99_ms"]}
            fed_errors = federation.self_check()
            slo_snap = slo.SLO_STATS.snapshot()
            telemetry_block = {
                "namespaces": len(federation.registered()),
                "federation_ok": not fed_errors,
                "scrape_ok": scrape_ok,
                "stages": stages_block,
                "slo_observed": int(slo_snap["observed"]),
                "slo_breaches": int(slo_snap["breaches"]),
                "slo_max_burn": float(slo_snap["max_burn"]),
                "recorder_recorded": int(REC_STATS["recorded"]),
                "recorder_dropped": int(REC_STATS["dropped"]),
                "recorder_triggers": int(REC_STATS["triggers"]),
            }
            print(
                f"[bench] telemetry: {telemetry_block['namespaces']} "
                f"namespace(s), scrape_ok={scrape_ok}, "
                f"federation_ok={telemetry_block['federation_ok']}, "
                f"stages={sorted(stages_block)}",
                file=sys.stderr,
            )
            record["telemetry"] = telemetry_block
            _emit_record(record)
        except Exception as e:  # the telemetry lane must not cost the bench
            print(
                f"[bench] telemetry lane failed: {type(e).__name__}: {e}",
                file=sys.stderr,
            )

    # async-pump serving lane (r12, ROADMAP item 2a): the dispatch-
    # window A/B — W in {1, 4} at batch sizes {1, 8, 32} over the
    # serve-scale twin WITH a concurrent barrier-ingested delta stream
    # (serve/pipeline.py, docs/SERVING.md).  Unlike the modeled
    # 2-D win, this one is MEASURED even on CPU fallback:
    # the window overlaps host admission/state-build/extraction with
    # device execution (JAX async dispatch runs XLA on its own
    # threads), so qps@p99 moves without a TPU in the loop.  Gated on
    # per-query byte identity W=4 vs W=1 (exit 2 on a break) and on
    # zero XLA compiles during the measured overlay-only ingests.
    # GRAPE_BENCH_NO_SERVE_ASYNC=1 skips;
    # GRAPE_BENCH_SERVE_ASYNC_QUERIES / _UPDATES size the lane.
    serve_async_mismatch = None
    if not os.environ.get("GRAPE_BENCH_NO_SERVE_ASYNC"):
        try:
            from libgrape_lite_tpu.analysis import compile_events
            from libgrape_lite_tpu.dyn import RepackPolicy
            from libgrape_lite_tpu.serve import (
                PUMP_STATS,
                BatchPolicy,
                ServeSession,
            )

            sys.path.insert(
                0, os.path.join(os.path.dirname(os.path.abspath(
                    __file__)), "scripts"))
            from gen_rmat import delta_edges

            sa_scale = int(os.environ.get(
                "GRAPE_BENCH_SERVE_SCALE", min(SCALE, 12)))
            # 64 queries = 8 b8-batches: the pipeline needs depth to
            # amortize its boundary (the first batch's prepare and the
            # last batch's extraction overlap nothing)
            sa_q = int(os.environ.get(
                "GRAPE_BENCH_SERVE_ASYNC_QUERIES", 64))
            sa_upd = int(os.environ.get(
                "GRAPE_BENCH_SERVE_ASYNC_UPDATES", 256))
            an, asrc, adst, acomm, avm = build_bench_inputs(sa_scale)
            rng_q = np.random.default_rng(5)
            sa_sources = [
                int(x) for x in rng_q.integers(0, an, size=sa_q)
            ]
            u_src, u_dst = delta_edges(sa_scale, sa_upd, seed=37)
            rng_uw = np.random.default_rng(41)
            u_w = rng_uw.uniform(0.1, 10.0, sa_upd)
            sa_ops = [("a", int(s), int(d), float(x)) for s, d, x in
                      zip(u_src, u_dst, u_w)]
            # two ingest groups: at b=8 each group holds MULTIPLE
            # batches, so the window genuinely overlaps between
            # barriers (one batch per group would let the barrier
            # serialise the window and measure nothing)
            n_groups = 2
            sa_chunk = -(-sa_upd // n_groups)
            sa_group = -(-sa_q // n_groups)

            def serve_async_run(window, bsz):
                """One measured (W, b) run: sa_q queries dispatched in
                n_groups groups with a barrier-ingested delta chunk
                between groups (ingest points pinned by DISPATCH
                count, so the batch <-> graph-version interleave is
                identical at every window depth).  Warm covers every
                shape the run touches: the batched runner pre- and
                post-overlay and a chunk-sized overlay apply.  Returns
                (point, per-query digests, measured XLA compiles)."""
                afrag = build_bench_weighted_fragment(
                    asrc, adst, acomm, avm, retain_edge_list=True
                )
                sess = ServeSession(
                    afrag, policy=BatchPolicy(max_batch=bsz),
                    dyn=RepackPolicy(capacity=max(4096, 4 * sa_upd)),
                )
                pump = sess.async_pump(window=window)
                for s in sa_sources[:min(bsz, sa_q)]:
                    sess.submit("sssp", {"source": s})
                pump.drain()
                pump.ingest(sa_ops[:sa_chunk])  # warm the overlay shape
                for s in sa_sources[:min(bsz, sa_q)]:
                    sess.submit("sssp", {"source": s})
                pump.drain()
                # one measured pass — the caller interleaves (w1, w4)
                # reps and keeps the best, so de-noising lives where
                # the drift does
                sess.queue.batch_hist = {}
                sess.queue.admission_waits = []
                oi = sa_chunk
                n_meas_ops = len(sa_ops) - oi
                t0 = time.perf_counter()
                with compile_events() as ev:
                    reqs = [
                        sess.submit("sssp", {"source": s})
                        for s in sa_sources
                    ]
                    while (sess.queue.pending() or pump.inflight()
                           or oi < len(sa_ops)):
                        target = pump.dispatched_queries + sa_group
                        while (sess.queue.pending()
                               and pump.dispatched_queries < target):
                            pump.pump(force=True, block=True,
                                      max_dispatch=target)
                        if oi < len(sa_ops):
                            pump.ingest(sa_ops[oi:oi + sa_chunk])
                            oi += sa_chunk
                        else:
                            pump.drain()
                wall = time.perf_counter() - t0
                res = [q.result for q in reqs]
                lat = sorted(r.latency_s for r in res)
                digests = [
                    r.values.tobytes() if r.ok else b"" for r in res
                ]
                point = {
                    "qps": round(len(res) / wall, 2),
                    "p50_ms": round(1e3 * lat[len(lat) // 2], 3),
                    "p99_ms": round(1e3 * lat[
                        min(len(lat) - 1, int(len(lat) * 0.99))
                    ], 3),
                    "n": len(res),
                    "ok": sum(1 for r in res if r.ok),
                    "updates_per_s": (
                        round(n_meas_ops / wall, 1) if wall > 0
                        else 0.0
                    ),
                }
                waits = sess.queue.admission_wait_summary()
                pump.close()
                return point, digests, ev.compiles, waits

            PUMP_STATS.reset()
            window_ab: dict = {"w1": {}, "w4": {}}
            digests_ab: dict = {}
            sa_compiles = 0
            sa_waits = {"p50_ms": 0.0, "p99_ms": 0.0}
            # interleaved (w1, w4, w1, w4) reps per batch size, best
            # qps kept per arm: process-global warmth (disk plan
            # cache, XLA code paths, allocator arenas) drifts run to
            # run, and a one-shot A/B would attribute that drift to
            # the window — alternation cancels it (digests compare
            # across the FIRST rep of each arm, which see identical
            # fresh sessions)
            for bsz in (1, 8, 32):
                for rep in range(2):
                    for window in (1, 4):
                        point, digs, compiles, waits = serve_async_run(
                            window, bsz
                        )
                        prev = window_ab[f"w{window}"].get(f"b{bsz}")
                        if prev is None or point["qps"] > prev["qps"]:
                            window_ab[f"w{window}"][f"b{bsz}"] = point
                        if rep == 0:
                            digests_ab[(window, bsz)] = digs
                        sa_compiles += compiles
                        if window == 4 and bsz == 8:
                            sa_waits = waits
                        print(
                            f"[bench] serve_async w{window} b{bsz} "
                            f"rep{rep}: {point['qps']} q/s "
                            f"p99={point['p99_ms']}ms "
                            f"{point['updates_per_s']} upd/s "
                            f"({point['ok']}/{point['n']} ok, "
                            f"{compiles} compiles)",
                            file=sys.stderr,
                        )
            identical = all(
                digests_ab[(1, bsz)] == digests_ab[(4, bsz)]
                for bsz in (1, 8, 32)
            )
            w1b8 = window_ab["w1"]["b8"]["qps"]
            w4b8 = window_ab["w4"]["b8"]["qps"]
            serve_async_block = {
                "scale": sa_scale, "app": "sssp", "queries": sa_q,
                "window_ab": window_ab,
                "identical": identical,
                "qps_win_b8": round(w4b8 / w1b8, 3) if w1b8 else 0.0,
                "updates_per_chunk": sa_chunk,
                "overlay_recompiles": sa_compiles,
                "admission_wait_ms": {
                    "p50": sa_waits["p50_ms"], "p99": sa_waits["p99_ms"],
                },
                "declines": PUMP_STATS.snapshot()["declines"],
            }
            record["serve_async"] = serve_async_block
            _emit_record(record)
            print(
                f"[bench] serve_async: b8 qps w4/w1 = "
                f"{serve_async_block['qps_win_b8']}x, identical="
                f"{identical}, overlay_recompiles={sa_compiles}",
                file=sys.stderr,
            )
            if not identical:
                serve_async_mismatch = (
                    "W=4 results diverged from W=1 — the dispatch "
                    "window changed answers"
                )
            elif sa_compiles:
                serve_async_mismatch = (
                    f"{sa_compiles} XLA compile(s) during measured "
                    "overlay-only ingests — the zero-recompile "
                    "contract broke under the pump"
                )
        except Exception as e:  # the lane must not cost the bench
            print(
                f"[bench] serve_async lane failed: "
                f"{type(e).__name__}: {e}",
                file=sys.stderr,
            )

    # dynamic-graph lane (r10, ROADMAP item 4): updates/sec ingested
    # while a query stream stays live, plus the incremental-vs-cold
    # comparison (dyn/, docs/DYNAMIC_GRAPHS.md).  A dyn-enabled
    # session pins a weighted RMAT twin; a reproducible additive
    # update stream (scripts/gen_rmat.py delta_edges — the SAME
    # distribution the --delta flag scripts) ingests in chunks between
    # 4-query groups, riding the overlay below the repack threshold so
    # the live queries recompile nothing.  The incremental point:
    # Worker.query_incremental seeded from the pre-delta fixed point
    # vs a cold recompute on the mutated view, wall and rounds.
    # GRAPE_BENCH_NO_DYN=1 skips; GRAPE_BENCH_DYN_SCALE /
    # GRAPE_BENCH_DYN_UPDATES size the lane.
    if not os.environ.get("GRAPE_BENCH_NO_DYN"):
        try:
            from libgrape_lite_tpu.dyn import DynGraph, RepackPolicy
            from libgrape_lite_tpu.models import SSSP
            from libgrape_lite_tpu.serve import (
                BatchPolicy,
                ServeSession,
            )

            sys.path.insert(
                0, os.path.join(os.path.dirname(os.path.abspath(
                    __file__)), "scripts"))
            from gen_rmat import delta_edges

            dyn_scale = int(os.environ.get(
                "GRAPE_BENCH_DYN_SCALE", min(SCALE, 12)))
            n_upd = int(os.environ.get(
                "GRAPE_BENCH_DYN_UPDATES", 1024))
            dn, dsrc, ddst, dcomm, dvm = build_bench_inputs(dyn_scale)
            dfrag = build_bench_weighted_fragment(
                dsrc, ddst, dcomm, dvm, retain_edge_list=True
            )
            u_src, u_dst = delta_edges(dyn_scale, n_upd, seed=29)
            rng_uw = np.random.default_rng(31)
            u_w = rng_uw.uniform(0.1, 10.0, n_upd)
            ops = [("a", int(s), int(d), float(x)) for s, d, x in
                   zip(u_src, u_dst, u_w)]
            # capacity sized to hold the full stream as an overlay;
            # the ratio threshold still fires if the stream is large
            # relative to the graph (a counted repack, reported below)
            sess = ServeSession(
                dfrag, policy=BatchPolicy(max_batch=8),
                dyn=RepackPolicy(capacity=max(4096, 2 * n_upd)),
            )
            rng_q = np.random.default_rng(17)
            warm_sources = [int(x) for x in rng_q.integers(0, dn, 8)]
            for s in warm_sources:
                sess.submit("sssp", {"source": s})
            sess.drain()  # warm the batched runner shapes

            chunk = max(1, n_upd // 8)
            q_ok = q_n = 0
            t0 = time.perf_counter()
            oi = 0
            while oi < len(ops):
                for s in rng_q.integers(0, dn, 4):
                    sess.submit("sssp", {"source": int(s)})
                res = sess.drain()
                q_n += len(res)
                q_ok += sum(1 for r in res if r.ok)
                sess.ingest(ops[oi:oi + chunk])
                oi += chunk
            wall = time.perf_counter() - t0
            dyn_block = {
                "updates_per_s": round(n_upd / wall, 1),
                "ingested": sess.stats["ingested_ops"],
                "repack_count": sess.stats["repacks"],
                "overlay_applies": sess.stats["overlay_applies"],
                "queries": q_n,
                "queries_ok": q_ok,
            }
            print(
                f"[bench] dyn: {dyn_block['updates_per_s']} upd/s "
                f"({n_upd} ingested, {q_n} queries live, "
                f"{dyn_block['repack_count']} repack(s))",
                file=sys.stderr,
            )

            # incremental-vs-cold: seed from the pre-delta fixed point
            from libgrape_lite_tpu.worker.worker import Worker

            base = build_bench_weighted_fragment(
                dsrc, ddst, dcomm, dvm, retain_edge_list=True
            )
            w_prev = Worker(SSSP(), base)
            prev = w_prev.query(source=0)
            dg = DynGraph(base, RepackPolicy(
                capacity=max(4096, 2 * n_upd)))
            small = ops[:max(1, n_upd // 16)]
            # the report's delta snapshot stays valid even if the
            # apply repacked (summary() would then be empty)
            inc_delta = dg.ingest(small)["delta"]
            w_cold = Worker(SSSP(), dg.fragment)
            w_cold.query(source=0)  # warm (compiles the overlay shape)
            tc = time.perf_counter()
            w_cold.query(source=0)
            t_cold = time.perf_counter() - tc
            # prev came from a DIFFERENT worker on the pre-ingest
            # fragment: name it, so a repacking ingest still migrates
            # the seeded rows by oid instead of trusting the layout
            w_inc = Worker(SSSP(), dg.fragment)
            w_inc.query_incremental(prev, inc_delta,
                                    prev_fragment=base, source=0)
            ti = time.perf_counter()
            w_inc.query_incremental(prev, inc_delta,
                                    prev_fragment=base, source=0)
            t_inc = time.perf_counter() - ti
            dyn_block["inc_cold_rounds"] = int(w_cold.rounds)
            dyn_block["inc_seeded_rounds"] = int(w_inc.rounds)
            dyn_block["inc_speedup"] = round(
                t_cold / t_inc, 3) if t_inc > 0 else 0.0
            print(
                f"[bench] dyn incremental: seeded {w_inc.rounds} "
                f"rounds / {t_inc:.4f}s vs cold {w_cold.rounds} "
                f"rounds / {t_cold:.4f}s "
                f"({dyn_block['inc_speedup']}x)",
                file=sys.stderr,
            )
            record["dyn"] = dyn_block
            _emit_record(record)
        except Exception as e:  # the dyn lane must not cost the bench
            print(f"[bench] dyn lane failed: {type(e).__name__}: {e}",
                  file=sys.stderr)

    # serving-fleet lane (r13, ROADMAP item 2b/2c): the drain drill —
    # R=2 replica sessions behind a version-fenced router serving a
    # mixed sssp+khop stream (khop = the sampling-shaped workload,
    # ROADMAP 5c one notch) with a concurrent barrier-ingested delta
    # stream, one replica drained mid-run for an offline forced
    # repack and rejoined through its catch-up log.  Gated exit-2 on:
    # per-query byte identity vs the undrained R=1 run, zero dropped
    # queries, and zero XLA compiles on an evict -> re-admit of a
    # replica session (the warm-host-artifact contract).  Reports
    # sustained qps@p99 PER REPLICA — the ROADMAP's stated target
    # bench.  GRAPE_BENCH_NO_FLEET=1 skips;
    # GRAPE_BENCH_FLEET_QUERIES / _UPDATES size the lane.
    fleet_mismatch = None
    if not os.environ.get("GRAPE_BENCH_NO_FLEET"):
        try:
            from libgrape_lite_tpu.analysis import compile_events
            from libgrape_lite_tpu.dyn import RepackPolicy
            from libgrape_lite_tpu.fleet import (
                FLEET_STATS,
                FleetRouter,
                run_fleet_script,
            )
            from libgrape_lite_tpu.fragment.mutation import (
                replicate_fragment,
            )
            from libgrape_lite_tpu.serve import (
                BatchPolicy,
                ServeSession,
            )

            sys.path.insert(
                0, os.path.join(os.path.dirname(os.path.abspath(
                    __file__)), "scripts"))
            from gen_rmat import delta_edges

            fl_scale = int(os.environ.get(
                "GRAPE_BENCH_SERVE_SCALE", min(SCALE, 12)))
            fl_q = int(os.environ.get(
                "GRAPE_BENCH_FLEET_QUERIES", 64))
            fl_upd = int(os.environ.get(
                "GRAPE_BENCH_FLEET_UPDATES", 128))
            fn_, fsrc, fdst, fcomm, fvm = build_bench_inputs(fl_scale)
            rng_q = np.random.default_rng(7)
            fl_srcs = [
                int(x) for x in rng_q.integers(0, fn_, size=fl_q)
            ]
            fl_queries = [
                ("sssp" if i % 2 == 0 else "khop", {"source": s})
                for i, s in enumerate(fl_srcs)
            ]
            u_src, u_dst = delta_edges(fl_scale, fl_upd, seed=43)
            rng_uw = np.random.default_rng(47)
            u_w = rng_uw.uniform(0.1, 10.0, fl_upd)
            fl_ops = [("a", int(s), int(d), float(x)) for s, d, x in
                      zip(u_src, u_dst, u_w)]
            fl_drain_at = fl_q // 2

            def fleet_run(R, drain):
                base = build_bench_weighted_fragment(
                    fsrc, fdst, fcomm, fvm, retain_edge_list=True
                )
                frags = [base] + [
                    replicate_fragment(base) for _ in range(R - 1)
                ]
                sessions = [
                    ServeSession(
                        f, policy=BatchPolicy(max_batch=8),
                        dyn=RepackPolicy(
                            capacity=max(4096, 4 * fl_upd)),
                    )
                    for f in frags
                ]
                router = FleetRouter(sessions)
                # warm every (app, batch-shape) runner the run touches
                for s in fl_srcs[:8]:
                    router.submit("sssp", {"source": s})
                    router.submit("khop", {"source": s})
                router.drain()
                for r in router.replicas:  # hist/latency = measured
                    r.latencies, r.served, r.ok = [], 0, 0
                t0 = time.perf_counter()
                reqs = run_fleet_script(
                    router, fl_queries, delta_ops=fl_ops,
                    ingest_every=16,
                    drain_at=fl_drain_at if drain else None,
                    drain_idx=0,
                    # the offline work: a forced empty-delta repack
                    # THROUGH the session (counted, adopts the rebuilt
                    # fragment into the resident workers)
                    offline=(lambda s: s.ingest([], force_repack=True))
                    if drain else None,
                )
                wall = time.perf_counter() - t0
                digs = [
                    q.result.values.tobytes()
                    if q.result is not None and q.result.ok else b""
                    for q in reqs
                ]
                dropped = sum(1 for q in reqs if q.result is None)
                return router, reqs, digs, dropped, wall

            FLEET_STATS.reset()
            _, _, base_digs, base_drop, _ = fleet_run(1, False)
            router, reqs, digs, dropped, wall = fleet_run(2, True)
            identical = digs == base_digs
            # evict -> re-admit drill on replica 0: warm the probe
            # shape once (the drain's offline repack re-keyed the
            # runners, an ordinary counted compile), then release the
            # device buffers and re-admit — the REPEAT of a warmed
            # query must compile NOTHING (the tenancy zero-replanning
            # contract: host plan caches and runner caches stay warm
            # across eviction)
            sess0 = router.replicas[0].session
            sess0.submit("sssp", {"source": fl_srcs[0]})
            sess0.drain()
            sess0.release_device()
            sess0.restore_device()
            with compile_events() as ev:
                sess0.submit("sssp", {"source": fl_srcs[0]})
                sess0.drain()
            readmit_compiles = ev.compiles
            drain_evs = [e for e in FLEET_STATS.events
                         if e.get("kind") == "drain"]
            rejoin_evs = [e for e in FLEET_STATS.events
                          if e.get("kind") == "rejoin"]
            per_replica = {}
            for rkey, s in router.summary(wall)["replicas"].items():
                per_replica[rkey] = {
                    "qps": s.get("qps", 0.0), "p50_ms": s["p50_ms"],
                    "p99_ms": s["p99_ms"], "served": s["served"],
                    "ok": s["ok"],
                }
            fleet_block = {
                "scale": fl_scale,
                "replicas": 2,
                "tenants": 0,
                "queries": fl_q,
                "ok": sum(
                    1 for q in reqs
                    if q.result is not None and q.result.ok
                ),
                "dropped": dropped + base_drop,
                "drain_at": fl_drain_at,
                "drained_replica": 0,
                "drain_wall_s": (
                    drain_evs[-1]["wall_s"] if drain_evs else 0.0
                ),
                "catchup_ops": (
                    rejoin_evs[-1]["catchup_ops"] if rejoin_evs
                    else 0
                ),
                "updates": fl_upd,
                "updates_per_s": (
                    round(fl_upd / wall, 1) if wall > 0 else 0.0
                ),
                "fence": router.fence,
                "byte_identical": identical,
                "per_replica": per_replica,
                "evictions": FLEET_STATS.evictions,
                "readmit_compiles": readmit_compiles,
            }
            record["fleet"] = fleet_block
            _emit_record(record)
            print(
                f"[bench] fleet: R=2 drain@{fl_drain_at} "
                f"identical={identical} dropped={fleet_block['dropped']} "
                + " ".join(
                    f"{k}={v['qps']}q/s@p99={v['p99_ms']}ms"
                    for k, v in per_replica.items()
                )
                + f" catchup={fleet_block['catchup_ops']}ops "
                f"readmit_compiles={readmit_compiles}",
                file=sys.stderr,
            )
            if not identical:
                fleet_mismatch = (
                    "drained R=2 results diverged from the undrained "
                    "R=1 run — the drain/fence changed answers"
                )
            elif fleet_block["dropped"]:
                fleet_mismatch = (
                    f"{fleet_block['dropped']} dropped quer(ies) — "
                    "the drain was not zero-downtime"
                )
            elif readmit_compiles:
                fleet_mismatch = (
                    f"{readmit_compiles} XLA compile(s) after "
                    "evict -> re-admit — the warm-host-artifact "
                    "contract broke"
                )
        except Exception as e:  # the lane must not cost the bench
            print(
                f"[bench] fleet lane failed: {type(e).__name__}: {e}",
                file=sys.stderr,
            )

    # autopilot lane (r16, ROADMAP item 2): the closed-loop drill —
    # one replica serving an sssp stream whose arrival rate (real
    # wall-clock feeder) is calibrated to 0.8x the measured service
    # rate and DOUBLED a third of the way in; the Autoscaler must
    # answer with >= 1 scale-up through the zero-drop machinery, with
    # zero dropped queries and per-query byte identity vs the static
    # R=1 scripted run.  Then the result-cache sub-drill: a repeated
    # source must hit with ZERO XLA compiles, one fence-bumping
    # ingest must reap the cached epoch, and the post-ingest answer
    # must byte-match a cache-less session on the same mutated graph.
    # All five verdicts gate exit-2.  GRAPE_BENCH_NO_AUTOPILOT=1
    # skips; GRAPE_BENCH_AUTOPILOT_QUERIES / _P99_MS size the lane.
    autopilot_mismatch = None
    if not os.environ.get("GRAPE_BENCH_NO_AUTOPILOT"):
        try:
            from collections import deque as _deque

            from libgrape_lite_tpu.analysis import compile_events
            from libgrape_lite_tpu.autopilot import (
                Autoscaler,
                ResultCache,
                ScalerConfig,
            )
            from libgrape_lite_tpu.autopilot.signals import (
                AUTOPILOT_STATS,
            )
            from libgrape_lite_tpu.dyn import RepackPolicy
            from libgrape_lite_tpu.fleet import FleetRouter
            from libgrape_lite_tpu.serve import (
                ArrivalFeeder,
                BatchPolicy,
                ServeSession,
            )

            sys.path.insert(
                0, os.path.join(os.path.dirname(os.path.abspath(
                    __file__)), "scripts"))
            from gen_rmat import delta_edges

            ap_scale = int(os.environ.get(
                "GRAPE_BENCH_SERVE_SCALE", min(SCALE, 12)))
            ap_q = int(os.environ.get(
                "GRAPE_BENCH_AUTOPILOT_QUERIES", 48))
            ap_p99_bound = float(os.environ.get(
                "GRAPE_BENCH_AUTOPILOT_P99_MS", 15000.0))
            an_, a_src, a_dst, a_comm, a_vm = build_bench_inputs(
                ap_scale)
            rng_a = np.random.default_rng(11)
            ap_srcs = [
                int(x) for x in rng_a.integers(0, an_, size=ap_q)
            ]

            def ap_fragment():
                return build_bench_weighted_fragment(
                    a_src, a_dst, a_comm, a_vm, retain_edge_list=True
                )

            def ap_session(f):
                return ServeSession(
                    f, policy=BatchPolicy(max_batch=8),
                    dyn=RepackPolicy(capacity=4096),
                )

            # static reference (R=1, scripted): the identity digests
            # AND the service rate the feeder calibrates from
            ref = ap_session(ap_fragment())
            for s in ap_srcs[:4]:
                ref.submit("sssp", {"source": s})
            ref.drain()
            t0 = time.perf_counter()
            ref_reqs = [
                ref.submit("sssp", {"source": s}) for s in ap_srcs
            ]
            ref.drain()
            ref_wall = time.perf_counter() - t0
            ref_digs = [
                q.result.values.tobytes()
                if q.result is not None and q.result.ok else b""
                for q in ref_reqs
            ]
            svc_qps = ap_q / max(ref_wall, 1e-6)

            # the load shift: 0.8x service rate, doubled at a third
            # of the stream — the queue MUST grow from there, so the
            # scale-up is deterministic, not a timing accident
            step_at = max(2, ap_q // 3)
            rate_spec = (
                f"{max(1.0, round(0.8 * svc_qps, 1))}:2x@{step_at}"
            )
            AUTOPILOT_STATS.reset()
            router = FleetRouter([ap_session(ap_fragment())])
            ap_cache = ResultCache(capacity=1024)
            router.attach_cache(ap_cache)

            def ap_factory(f):
                # a scale-up replica joins WARM (one throwaway query
                # compiles its runners before it becomes routable)
                s = ap_session(f)
                s.submit("sssp", {"source": ap_srcs[0]})
                s.drain()
                return s

            pilot = Autoscaler(
                router,
                ScalerConfig(min_replicas=1, max_replicas=2,
                             window=2, cooldown_ticks=2,
                             up_queue_depth=4),
                session_factory=ap_factory,
            )
            for s in ap_srcs[:4]:  # warm r0 before the clock starts
                router.submit("sssp", {"source": s})
            router.drain()
            inbox = _deque()
            feeder = ArrivalFeeder(
                lambda app, args, **kw: inbox.append((app, args)),
                [("sssp", {"source": s}) for s in ap_srcs],
                rate_spec,
            )
            ap_reqs = []
            feeder.start()
            while feeder.is_alive() or inbox or any(
                r.session.queue.pending() or r.pump.inflight()
                for r in router.replicas
            ):
                while inbox:
                    app_key, args = inbox.popleft()
                    ap_reqs.append(router.submit(app_key, dict(args)))
                router.pump()
                pilot.tick()
            feeder.join()
            router.drain()
            ap_digs = [
                q.result.values.tobytes()
                if q.result is not None and q.result.ok else b""
                for q in ap_reqs
            ]
            ap_drop = sum(1 for q in ap_reqs if q.result is None)
            identical = ap_digs == ref_digs
            from libgrape_lite_tpu.serve.queue import (
                latency_summary_ms,
            )

            ap_lat = latency_summary_ms([
                q.result.latency_s for q in ap_reqs
                if q.result is not None
            ])
            p99_ok = ap_lat["p99_ms"] <= ap_p99_bound

            # cache sub-drill: repeat of an answered source = a hit
            # with ZERO compiles
            hit_src = ap_srcs[0]
            router.submit("sssp", {"source": hit_src})
            router.drain()
            hits0 = ap_cache.hits
            with compile_events() as ev:
                router.submit("sssp", {"source": hit_src})
                router.drain()
            cache_hit_compiles = ev.compiles
            hit_seen = ap_cache.hits > hits0
            # fence invalidation: one barrier ingest bumps the fence
            # and reaps the epoch; the post-ingest answer must match
            # a CACHE-LESS session on the same mutated graph
            u2s, u2d = delta_edges(ap_scale, 32, seed=51)
            rng_w2 = np.random.default_rng(53)
            ap_ops = [
                ("a", int(s), int(d), float(x)) for s, d, x in
                zip(u2s, u2d, rng_w2.uniform(0.1, 10.0, 32))
            ]
            inv0 = ap_cache.invalidations
            router.ingest(ap_ops)
            invalidated = ap_cache.invalidations - inv0
            post_req = router.submit("sssp", {"source": hit_src})
            router.drain()
            cold = ap_session(ap_fragment())
            cold.ingest(ap_ops)
            cold_req = cold.submit("sssp", {"source": hit_src})
            cold.drain()
            post_identical = bool(
                post_req.result is not None and post_req.result.ok
                and cold_req.result is not None
                and cold_req.result.ok
                and post_req.result.values.tobytes()
                == cold_req.result.values.tobytes()
            )

            ap_stats = AUTOPILOT_STATS.snapshot()
            autopilot_block = {
                "scale": ap_scale,
                "queries": ap_q,
                "ok": sum(
                    1 for q in ap_reqs
                    if q.result is not None and q.result.ok
                ),
                "dropped": ap_drop,
                "rate_spec": rate_spec,
                "min_replicas": 1,
                "max_replicas": 2,
                "replicas_final": sum(
                    1 for r in router.replicas if r.routable
                ),
                "scale_ups": ap_stats["scale_ups"],
                "scale_downs": ap_stats["scale_downs"],
                "ticks": ap_stats["ticks"],
                "p99_ms": ap_lat["p99_ms"],
                "p99_bound_ms": ap_p99_bound,
                "p99_ok": p99_ok,
                "byte_identical": identical,
                "cache_hits": ap_cache.hits,
                "cache_misses": ap_cache.misses,
                "cache_hit_compiles": cache_hit_compiles,
                "cache_invalidations": ap_cache.invalidations,
                "post_ingest_identical": post_identical,
            }
            record["autopilot"] = autopilot_block
            _emit_record(record)
            print(
                f"[bench] autopilot: rate={rate_spec} "
                f"scale_ups={ap_stats['scale_ups']} "
                f"replicas={autopilot_block['replicas_final']} "
                f"identical={identical} dropped={ap_drop} "
                f"p99={ap_lat['p99_ms']}ms "
                f"cache_hits={ap_cache.hits} "
                f"hit_compiles={cache_hit_compiles} "
                f"invalidated={invalidated} "
                f"post_ingest_identical={post_identical}",
                file=sys.stderr,
            )
            if not identical:
                autopilot_mismatch = (
                    "autoscaled results diverged from the static R=1 "
                    "run — scaling changed answers"
                )
            elif ap_drop:
                autopilot_mismatch = (
                    f"{ap_drop} dropped quer(ies) — the scale moves "
                    "were not zero-drop"
                )
            elif ap_stats["scale_ups"] < 1:
                autopilot_mismatch = (
                    "no scale-up under a 2x mid-stream rate step — "
                    "the control loop never closed"
                )
            elif not p99_ok:
                autopilot_mismatch = (
                    f"p99 {ap_lat['p99_ms']}ms over the "
                    f"{ap_p99_bound}ms bound"
                )
            elif cache_hit_compiles or not hit_seen:
                autopilot_mismatch = (
                    f"repeated-source hit compiled "
                    f"{cache_hit_compiles} time(s) (hit_seen="
                    f"{hit_seen}) — the cache did not skip the device"
                )
            elif not invalidated:
                autopilot_mismatch = (
                    "the fence-bumping ingest invalidated nothing — "
                    "stale epoch entries survived"
                )
            elif not post_identical:
                autopilot_mismatch = (
                    "post-ingest answer diverged from a cache-less "
                    "run on the mutated graph — the cache served a "
                    "stale epoch"
                )
        except Exception as e:  # the lane must not cost the bench
            print(
                f"[bench] autopilot lane failed: "
                f"{type(e).__name__}: {e}",
                file=sys.stderr,
            )

    # 2-D vertex-cut partition lane (r10, ROADMAP item 2): the
    # hub-heavy RMAT A/B at fnum 4 (k=2) — max-tile vs the raw hub
    # fragment, modeled exchange bytes, serial-vs-2D wall, byte/eps
    # identity verdicts, the planner's recorded auto decision against
    # the measured winner, and the per-tile pack-plan recount (gated
    # at the shared 5% tolerance).  GRAPE_BENCH_NO_P2D=1 skips;
    # GRAPE_BENCH_P2D_SCALE sizes the twin.
    p2d_mismatch = None
    if not os.environ.get("GRAPE_BENCH_NO_P2D"):
        try:
            # default 12 REGARDLESS of GRAPE_BENCH_SCALE: the lane's
            # tile-vs-hub bound is a statement about RMAT hub
            # statistics, which under-develop below scale ~12 (at
            # scale 10 the raw hub fragment is only ~2x the mean and
            # the 0.5x bound sits on the noise floor)
            p2d_scale = int(os.environ.get(
                "GRAPE_BENCH_P2D_SCALE", 12))
            if jax.device_count() >= 4:
                p2d = partition2d_lane(p2d_scale)
            else:
                p2d = _partition2d_lane_subprocess(p2d_scale)
            record["partition2d"] = p2d
            _emit_record(record)
            print(
                f"[bench] partition2d: 1d={p2d['serial_1d_s']}s "
                f"2d={p2d['vc2d_s']}s byte_identical="
                f"{p2d['sssp_byte_identical']} max_tile="
                f"{p2d['max_tile_edges']} vs hub={p2d['hub_1d_edges']} "
                f"({p2d['tile_ratio_vs_hub']}x) planner="
                f"{p2d['planner_choice']} measured="
                f"{p2d['measured_winner']}",
                file=sys.stderr,
            )
            for bad, why in (
                (not p2d["sssp_byte_identical"],
                 "2-D SSSP diverged from the 1-D result"),
                (not p2d["pagerank_eps_identical"],
                 "2-D PageRank drifted past eps"),
                (not p2d["tile_bound_ok"],
                 "max tile exceeds 0.5x the 1-D hub fragment"),
                (not p2d["exchange_reduced"],
                 "modeled 2-D exchange bytes not below the 1-D "
                 "gather"),
                (not p2d["decision_matches"],
                 "planner decision contradicts the measured winner"),
            ):
                if bad:
                    p2d_mismatch = why
                    break
        except Exception as e:  # the lane must not cost the bench
            print(
                f"[bench] partition2d lane failed: "
                f"{type(e).__name__}: {e}",
                file=sys.stderr,
            )

    # masked-SpGEMM lane (r11, ROADMAP 5a): LCC intersect-vs-spgemm
    # wall A/B at GRAPE_BENCH_SPGEMM_SCALE (default min(SCALE, 10))
    # with the bit-exactness verdict + shipped-plan recount, and the
    # modeled ops/edge A/B at the full bench geometry.  Gated like the
    # ledger lane: recount drift > 5%, a non-identical result, or a
    # modeled LOSS against popcount fails the bench with exit 2.
    # GRAPE_BENCH_NO_SPGEMM=1 skips.
    spgemm_mismatch = None
    if not os.environ.get("GRAPE_BENCH_NO_SPGEMM"):
        try:
            sg_scale = int(os.environ.get(
                "GRAPE_BENCH_SPGEMM_SCALE", min(SCALE, 10)))
            sgb = spgemm_lane(sg_scale, SCALE, EDGE_FACTOR)
            record["spgemm"] = sgb
            _emit_record(record)
            print(
                f"[bench] spgemm: intersect={sgb['intersect_s']}s "
                f"spgemm={sgb['spgemm_s']}s byte_identical="
                f"{sgb['byte_identical']} modeled@{SCALE}: "
                f"mxu/edge={sgb['mxu_elems_per_edge']} vs popcount "
                f"word-ops/edge={sgb['intersect_word_ops_per_edge']} "
                f"win={sgb['modeled_win']} auto={sgb['auto_backend']}",
                file=sys.stderr,
            )
            scripts = os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "scripts")
            if scripts not in sys.path:
                sys.path.insert(0, scripts)
            from pack_cost_model import MISMATCH_TOLERANCE as _TOLS

            # the modeled-win verdict gates only at/above the
            # crossover scale (~2^13 vertices, docs/SPGEMM.md): below
            # it the packed-bitmap sweep SHOULD win and auto records
            # the intersect decline — a shrunken GRAPE_BENCH_SCALE
            # smoke (app_tests runs scale 10) must not read an
            # expected loss as drift.  Identity + recount gate always.
            for bad, why in (
                (not sgb["byte_identical"],
                 "spgemm LCC diverged from the intersect backend"),
                (sgb["ledger_recount_mismatch"] > _TOLS,
                 "spgemm ledger recount drifted"),
                (SCALE >= 14 and not sgb["modeled_win"],
                 "modeled spgemm cost does not beat popcount at bench "
                 "geometry"),
            ):
                if bad:
                    spgemm_mismatch = why
                    break
        except Exception as e:  # the lane must not cost the bench
            print(
                f"[bench] spgemm lane failed: {type(e).__name__}: {e}",
                file=sys.stderr,
            )

    # calibration lane (r17, ops/calibration.py, docs/CALIBRATION.md):
    # the drift gate — recompute the ACTIVE profile's modeled walls
    # over a measured sample set and fail the bench when an explicit
    # GRAPE_RATE_PROFILE has drifted >5% from measurement on any
    # priced surface.  Samples come from GRAPE_CALIBRATION_SAMPLES
    # (the recorded sweep a `calibrate` run persisted — deterministic
    # in CI) or a fresh small-geometry sweep.  The pinned default is
    # NOT gated off-hardware: CPU walls are not v5e walls by
    # construction, only a profile somebody explicitly installed
    # claims to model THIS backend.  A fresh fit is also reported
    # (rates + residual + fallback notes) so PERF_NOTES can table
    # pinned-vs-fitted.  GRAPE_BENCH_NO_CALIBRATION=1 skips.
    calibration_mismatch = None
    if not os.environ.get("GRAPE_BENCH_NO_CALIBRATION"):
        try:
            from libgrape_lite_tpu.ops import calibration as calib

            spath = os.environ.get("GRAPE_CALIBRATION_SAMPLES")
            if spath:
                samples = calib.load_samples(spath)
            else:
                samples = calib.microbench_samples(
                    scales=(8, 9, 10), repeats=2)
                floor = calib.default_min_wall_s()
                samples = [s for s in samples if s["wall_s"] >= floor]
            prof = calib.active_profile()
            rep = calib.drift_report(prof, samples)
            try:
                fit, notes = calib.fit_rates_auto(
                    samples, base=prof, name="bench-fit")
                fitted_prof = fit.profile
                residual_pct = round(fit.residual * 100.0, 3)
            except calib.CalibrationError as e:
                fitted_prof = prof
                notes = [f"fit failed: {e}"]
                residual_pct = -1.0
            record["calibration"] = {
                "profile": prof.label(),
                "fingerprint": calib.backend_fingerprint(),
                "source": prof.source,
                "fitted": bool(prof.fitted),
                "samples": len(samples),
                "residual_pct": residual_pct,
                "drift_pct": rep["drift_pct"],
                "max_sample_drift_pct": rep["max_sample_drift_pct"],
                "drift_ok": rep["drift_ok"],
                "rates": {
                    "clock_hz": fitted_prof.clock_hz,
                    "vpu_lanes_per_cycle":
                        fitted_prof.vpu_lanes_per_cycle,
                    "mxu_cyc_per_elem": fitted_prof.mxu_cyc_per_elem,
                    "hbm_bps": fitted_prof.hbm_bps,
                    "gather_rows_per_cycle":
                        fitted_prof.gather_rows_per_cycle,
                    "dispatch_overhead_s":
                        fitted_prof.dispatch_overhead_s,
                },
                "unfitted": sorted(fitted_prof.unfitted),
                "fallback_notes": notes,
                "surfaces": rep["surfaces"],
            }
            _emit_record(record)
            if os.environ.get(calib.PROFILE_ENV) and not rep["drift_ok"]:
                calibration_mismatch = rep["drift_pct"]
        except Exception as e:  # the lane must not cost the bench
            print(
                f"[bench] calibration lane failed: "
                f"{type(e).__name__}: {e}",
                file=sys.stderr,
            )

    if os.environ.get("GRAPE_BENCH_FULL"):
        # side metrics on stderr AFTER the primary line is out — a hang
        # or failure here must not cost the already-made measurement
        # (SSSP graduated to the primary record above)
        from libgrape_lite_tpu.models import BFS, CDLP, WCC

        print(f"[bench-extra] load: {t_load:.2f}s", file=sys.stderr)

        for nm, a, kw in (
            ("wcc", WCC(), {}),
            ("bfs", BFS(), {"source": 0}),
            ("cdlp", CDLP(), {"max_round": 10}),
        ):
            try:
                wk = Worker(a, frag)
                wk.query(**kw)  # compile
                t0 = time.perf_counter()
                wk.query(**kw)
                print(
                    f"[bench-extra] {nm}: {time.perf_counter() - t0:.4f}s "
                    f"rounds={wk.rounds}",
                    file=sys.stderr,
                )
            except Exception as e:  # side metrics are best-effort
                print(f"[bench-extra] {nm}: failed ({e})", file=sys.stderr)

    # obs rollup (r8): per-phase span aggregation over every query the
    # bench ran (warmups included — their compile-heavy first rounds
    # are why max_s >> mean_s on the query span).  The tracer was armed
    # in-memory at the top of main(), so this costs no file I/O unless
    # GRAPE_TRACE asked for it.
    try:
        record["obs"] = {
            "trace_id": obs.trace_id(),
            "spans": obs.rollup(obs.history()),
        }
        _emit_record(record)
        if os.environ.get(obs.TRACE_ENV) or os.environ.get(
            obs.METRICS_ENV
        ):
            out = obs.flush()
            print(f"[bench] obs: trace={out['trace']} "
                  f"metrics={out['metrics']}", file=sys.stderr)
    except Exception as e:  # the obs lane must not cost the bench
        print(f"[bench] obs lane failed: {type(e).__name__}: {e}",
              file=sys.stderr)

    # gang-telemetry lane (PR 20, obs/gang.py): the in-process
    # two-rank sidecar federation drill plus the armed-vs-disarmed
    # fused-HLO identity re-proof.  Runs AFTER the obs rollup: the
    # HLO leg has to fully disarm (obs.reset), which drops the span
    # history the rollup reads.  GRAPE_BENCH_NO_OBS_GANG=1 skips.
    obs_gang_mismatch = None
    if not os.environ.get("GRAPE_BENCH_NO_OBS_GANG"):
        try:
            og = obs_gang_lane()
            record["obs_gang"] = og
            _emit_record(record)
            print(
                f"[bench] obs_gang: ranks={og['ranks']} "
                f"events={og['events']} cross_rank_flows="
                f"{og['cross_rank_flows']} complete={og['complete']} "
                f"monotonic={og['monotonic']} "
                f"hlo_identical={og['hlo_identical']}",
                file=sys.stderr,
            )
            if not og["complete"]:
                obs_gang_mismatch = (
                    "the merged gang trace is incomplete (missing "
                    "rank, unaligned clocks, or a span-less rank)"
                )
            elif og["cross_rank_flows"] < 1:
                obs_gang_mismatch = (
                    "no flow arrow crossed the rank tracks — the "
                    "vote legs lost their shared (cat, id)"
                )
            elif not og["monotonic"]:
                obs_gang_mismatch = (
                    "post-alignment timestamps are not monotonic"
                )
            elif not og["hlo_identical"]:
                obs_gang_mismatch = (
                    "arming the tracer changed the fused runner's "
                    "lowered HLO — tracing leaked into the program"
                )
        except Exception as e:  # the lane must not cost the bench
            print(
                f"[bench] obs_gang lane failed: "
                f"{type(e).__name__}: {e}",
                file=sys.stderr,
            )

    if p2d_mismatch is not None:
        print(
            f"[bench] FATAL: partition2d lane verdict failed: "
            f"{p2d_mismatch} — see the partition2d block above",
            file=sys.stderr,
        )
        sys.exit(2)
    if spgemm_mismatch is not None:
        print(
            f"[bench] FATAL: spgemm lane verdict failed: "
            f"{spgemm_mismatch} — see the spgemm block above",
            file=sys.stderr,
        )
        sys.exit(2)
    if serve_async_mismatch is not None:
        print(
            f"[bench] FATAL: serve_async lane verdict failed: "
            f"{serve_async_mismatch} — see the serve_async block above",
            file=sys.stderr,
        )
        sys.exit(2)
    if fleet_mismatch is not None:
        print(
            f"[bench] FATAL: fleet lane verdict failed: "
            f"{fleet_mismatch} — see the fleet block above",
            file=sys.stderr,
        )
        sys.exit(2)
    if autopilot_mismatch is not None:
        print(
            f"[bench] FATAL: autopilot lane verdict failed: "
            f"{autopilot_mismatch} — see the autopilot block above",
            file=sys.stderr,
        )
        sys.exit(2)
    if calibration_mismatch is not None:
        print(
            f"[bench] FATAL: the installed GRAPE_RATE_PROFILE drifts "
            f"{calibration_mismatch:.1f}% (> 5%) from measured device "
            "walls — recalibrate (python -m libgrape_lite_tpu.cli "
            "calibrate) or unset the stale profile",
            file=sys.stderr,
        )
        sys.exit(2)
    if obs_gang_mismatch is not None:
        print(
            f"[bench] FATAL: obs_gang lane verdict failed: "
            f"{obs_gang_mismatch} — see the obs_gang block above",
            file=sys.stderr,
        )
        sys.exit(2)
    if _SCHEMA_ERRORS:
        print(
            f"[bench] FATAL: {len(_SCHEMA_ERRORS)} BENCH-record schema "
            "error(s) (see SCHEMA lines above) — the record drifted "
            "from scripts/check_bench_schema.py",
            file=sys.stderr,
        )
        sys.exit(3)


if __name__ == "__main__":
    if "--partition2d-lane" in sys.argv:
        # subprocess entrypoint for the 1-D vs 2-D partition A/B (the
        # parent's backend is frozen at 1 device); prints ONE json line
        _i = sys.argv.index("--partition2d-lane")
        print(json.dumps(partition2d_lane(int(sys.argv[_i + 1]))))
    else:
        main()
