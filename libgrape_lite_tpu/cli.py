"""Command-line driver: `python -m libgrape_lite_tpu.cli --application sssp ...`

Flag names mirror the reference gflags catalog
(`examples/analytical_apps/flags.cc:23-69`).

`python -m libgrape_lite_tpu.cli serve ...` drives the multi-query
serving runtime instead (serve/, docs/SERVING.md): load the graph
once, pump a scripted query stream through the admission queue with
vmapped multi-source batching, and print one JSON summary line
(queries, qps, p50/p99 latency globally and per app, batch-size
histogram).  `--replicas R / --tenants ... / --drain_at K` raise the
serving fleet instead (fleet/, docs/FLEET.md): replica routing
behind a graph-version fence, HBM-budget tenancy, and a
zero-downtime mid-stream drain; `--arrival_rate` feeds the stream
from a wall-clock feeder thread (serve/feeder.py).

`python -m libgrape_lite_tpu.cli lint ...` runs grape-lint
(analysis/, docs/STATIC_ANALYSIS.md): the AST contract rules R1-R8
over the library tree (or explicit paths), optionally the
compiled-artifact audits (--artifact), against the suppression
baseline — exits nonzero on any unsuppressed finding.

`python -m libgrape_lite_tpu.cli calibrate ...` runs the pricing-rate
calibration pass (ops/calibration.py, docs/CALIBRATION.md): a seeded
micro-bench sweep of the pack SpMV / masked-SpGEMM dispatches, a
least-squares rate fit over the measured walls, profile + sample
persistence, and the 5% modeled-vs-measured drift gate (`--check`
re-gates the active GRAPE_RATE_PROFILE without refitting; exit 2 on
drift).

`python -m libgrape_lite_tpu.cli postmortem <bundle.json>` renders a
flight-recorder bundle (obs/recorder.py; dumped into the
GRAPE_POSTMORTEM sink on a guard breach, fence violation or deadline
storm) and, with --trace, proves the bundle's serve_query span rows
byte-match the Chrome trace's rows for the same query ids.
"""

from __future__ import annotations

import argparse

from libgrape_lite_tpu.runner import QueryArgs, run_app
from libgrape_lite_tpu.utils.compile_cache import place_compile_cache


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="libgrape_lite_tpu")
    p.add_argument("--application", required=True)
    p.add_argument("--efile", required=True)
    p.add_argument("--vfile", default="")
    p.add_argument("--out_prefix", default="")
    p.add_argument("--directed", action="store_true")
    # source ids parse as text so --string_id graphs can name their
    # real ids; numeric strings coerce back to int in the runner
    p.add_argument("--sssp_source", default="0")
    p.add_argument("--bfs_source", default="0")
    p.add_argument("--bc_source", default="0")
    p.add_argument("--kcore_k", type=int, default=0)
    p.add_argument("--kclique_k", type=int, default=3)
    p.add_argument("--khop_k", type=int, default=2,
                   help="k-hop neighborhood hop bound (models/khop.py; "
                        "the source comes from --bfs_source)")
    p.add_argument("--cn_source", default="0",
                   help="common_neighbors 2-hop query source vertex")
    p.add_argument("--pr_d", type=float, default=0.85)
    p.add_argument("--pr_mr", type=int, default=10)
    p.add_argument("--cdlp_mr", type=int, default=10)
    p.add_argument("--degree_threshold", type=int, default=0,
                   help="LCC hub cap: skip neighbor lists of vertices "
                        "above this degree (flags.cc:39; 0 = disabled)")
    p.add_argument("--fnum", type=int, default=None,
                   help="fragment count (default: all local devices)")
    p.add_argument("--partitioner_type", default="map",
                   choices=["hash", "map", "segment"])
    p.add_argument("--idxer_type", default="hashmap",
                   choices=["hashmap", "sorted_array", "pthash", "local"])
    p.add_argument("--serialize", action="store_true")
    p.add_argument("--deserialize", action="store_true")
    p.add_argument("--serialization_prefix", default="")
    p.add_argument("--vc", action="store_true",
                   help="vertex-cut (2-D) storage; fnum must be k^2")
    p.add_argument("--delta_efile", default="")
    p.add_argument("--delta_vfile", default="")
    p.add_argument("--string_id", action="store_true",
                   help="treat vertex ids as strings (load_tests.cc:45)")
    p.add_argument("--rebalance", action="store_true")
    p.add_argument("--rebalance_vertex_factor", type=int, default=0)
    p.add_argument("--memory_stats", action="store_true")
    p.add_argument("--checkpoint_every", type=int, default=0,
                   help="snapshot the query carry every K supersteps "
                        "(ft/checkpoint.py; 0 = off; forces stepwise "
                        "execution, requires --checkpoint_dir)")
    p.add_argument("--checkpoint_dir", default="",
                   help="directory for superstep checkpoints")
    p.add_argument("--resume", action="store_true",
                   help="continue from the last complete checkpoint in "
                        "--checkpoint_dir (query args replay from the "
                        "checkpoint metadata; the config fingerprint "
                        "must match)")
    p.add_argument("--guard", default="",
                   choices=["", "off", "warn", "halt", "rollback"],
                   help="runtime invariant guard policy (guard/): warn "
                        "logs breaches, halt raises with a diagnostic "
                        "bundle, rollback self-heals from the last "
                        "checkpoint (needs --checkpoint_every); default "
                        "reads GRAPE_GUARD")
    p.add_argument("--profile", action="store_true",
                   help="stepwise rounds with per-round timing (PROFILING)")
    p.add_argument("--trace", default="",
                   help="arm obs/ tracing: write a Chrome trace_event "
                        "JSON (Perfetto-loadable) to this path plus a "
                        "JSONL twin next to it; equivalent to "
                        "GRAPE_TRACE=path (docs/OBSERVABILITY.md)")
    p.add_argument("--metrics", default="",
                   help="write the obs/ metrics snapshot to "
                        "<path>.json and <path>.prom at query end; "
                        "equivalent to GRAPE_METRICS=path")
    p.add_argument("--platform", default="",
                   help="jax platform override (e.g. cpu); default ambient")
    p.add_argument("--cpu_devices", type=int, default=0,
                   help="with --platform cpu: virtual device count")
    p.add_argument("--coordinator", default="",
                   help="jax.distributed coordinator address "
                        "(host:port); arms the multi-process runtime "
                        "together with --num_processes/--process_id")
    p.add_argument("--num_processes", type=int, default=0,
                   help="total process count for jax.distributed "
                        "(0 = single-process)")
    p.add_argument("--process_id", type=int, default=-1,
                   help="this process's rank in [0, num_processes)")
    return p


def make_serve_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="libgrape_lite_tpu serve")
    p.add_argument("--efile", required=True)
    p.add_argument("--vfile", default="")
    p.add_argument("--directed", action="store_true")
    p.add_argument("--application", default="sssp",
                   help="app for --sources/--num_queries streams "
                        "(--stream lines carry their own app)")
    p.add_argument("--sources", default="",
                   help="comma-separated source ids, one query each")
    p.add_argument("--num_queries", type=int, default=0,
                   help="generate N queries with sources 0..N-1 "
                        "(used when --sources/--stream are not given)")
    p.add_argument("--stream", default="",
                   help="scripted stream file: one 'app source' line "
                        "per query")
    p.add_argument("--max_batch", type=int, default=8,
                   help="lanes per vmapped dispatch (serve/policy.py)")
    p.add_argument("--max_wait_ms", type=float, default=0.0,
                   help="queue-head wait before a partial batch ships")
    p.add_argument("--inflight", type=int, default=1,
                   help="dispatch-window depth (serve/pipeline.py): "
                        ">1 arms the async pump — up to W coalesced "
                        "batches dispatched un-synced with lazy FIFO "
                        "harvest, ingest as a window barrier; 1 "
                        "(default) keeps the synchronous loop "
                        "bit-for-bit (GRAPE_SERVE_INFLIGHT overrides "
                        "a pump's depth, recorded in PUMP_STATS)")
    p.add_argument("--dump_results", default="",
                   help="write one line per query in submit order "
                        "(index, app, ok, rounds, sha256 of the "
                        "assembled values) — the identity surface the "
                        "async smoke cmp's between --inflight 1 and "
                        "--inflight 4 runs")
    p.add_argument("--max_rounds", type=int, default=0)
    p.add_argument("--guard", default="",
                   choices=["", "off", "warn", "halt", "rollback"],
                   help="per-lane guard policy (breach isolation: a "
                        "poisoned lane fails alone)")
    p.add_argument("--replicas", type=int, default=1,
                   help="fleet/: serve the graph from R replica "
                        "sessions behind a least-outstanding front "
                        "router with a graph-version fence "
                        "(docs/FLEET.md); 1 keeps the single-session "
                        "path bit-for-bit")
    p.add_argument("--drain_at", type=int, default=-1,
                   help="fleet/: begin draining replica 0 before the "
                        "K-th query (zero-downtime drain drill — it "
                        "rejoins after the next ingest barrier, or at "
                        "stream end); requires --replicas >= 2")
    p.add_argument("--tenants", default="",
                   help="fleet/: multi-tenant front — 'by_app' gives "
                        "each distinct app its own tenant, an integer "
                        "N round-robins queries over N tenants; "
                        "tenants share the HBM budget "
                        "(GRAPE_FLEET_HBM_BYTES) with weighted "
                        "round-robin fairness and never share a "
                        "batched dispatch")
    p.add_argument("--arrival_rate", default="",
                   help="threaded admission front (serve/feeder.py): "
                        "submit the stream at this rate from a feeder "
                        "thread with real wall-clock arrivals, so "
                        "--max_wait_ms and priority/deadline "
                        "scheduling are exercised under load; a plain "
                        "QPS float, or a step schedule like "
                        "'50:2x@100' (double the rate from query "
                        "index 100 — the autopilot load-shift drill); "
                        "0/empty keeps the deterministic scripted "
                        "mode")
    p.add_argument("--autopilot", action="store_true",
                   help="autopilot/: close the observe->decide->act "
                        "loop over a replica fleet — an Autoscaler "
                        "scales replicas between --min_replicas and "
                        "--max_replicas through the zero-drop "
                        "drain/rejoin/replicate machinery, and a "
                        "shared fence-epoch result cache "
                        "(--cache_entries) answers repeated point "
                        "queries without the device "
                        "(docs/AUTOPILOT.md)")
    p.add_argument("--min_replicas", type=int, default=1,
                   help="autopilot: replica floor (and the initial "
                        "replica count)")
    p.add_argument("--max_replicas", type=int, default=4,
                   help="autopilot: replica ceiling")
    p.add_argument("--cache_entries", type=int, default=1024,
                   help="autopilot: result-cache capacity in entries "
                        "(0 disables the cache)")
    p.add_argument("--delta_stream", default="",
                   help="dyn/ live ingest: a delta-op stream file "
                        "('a src dst [w]' / 'd src dst' / 'u src dst "
                        "w' lines, scripts/gen_rmat.py --delta emits "
                        "one); chunks are ingested between query "
                        "batches while the stream runs")
    p.add_argument("--ingest_every", type=int, default=8,
                   help="queries pumped between delta-chunk ingests")
    p.add_argument("--dyn_repack_ratio", type=float, default=None,
                   help="delta ratio past which staged ops fold into "
                        "a rebuilt CSR (default GRAPE_DYN_REPACK_RATIO "
                        "or 0.05); below it, ingest is zero-recompile")
    p.add_argument("--fnum", type=int, default=None)
    p.add_argument("--string_id", action="store_true")
    p.add_argument("--trace", default="",
                   help="obs/ Chrome-trace path (per-query lane rows)")
    p.add_argument("--metrics", default="")
    p.add_argument("--metrics_port", type=int, default=None,
                   help="obs/exporter.py: serve a live OpenMetrics "
                        "endpoint from a background thread for the "
                        "run's duration (/metrics, /federation, "
                        "/healthz); 0 binds an ephemeral port (the "
                        "URL prints to stderr); equivalent to "
                        "GRAPE_METRICS_PORT")
    p.add_argument("--slo", default="",
                   help="obs/slo.py latency objectives, e.g. "
                        "'sssp=5,tenant:t0=50,*=100' (ms per "
                        "app/tenant); a breach is a trace instant + "
                        "a federated error-budget burn counter, "
                        "never an exception; equivalent to GRAPE_SLO "
                        "(budget fraction: GRAPE_SLO_BUDGET)")
    p.add_argument("--platform", default="")
    p.add_argument("--cpu_devices", type=int, default=0)
    return p


def make_lint_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="libgrape_lite_tpu lint")
    p.add_argument("paths", nargs="*",
                   help="files/directories to lint (default: the "
                        "installed libgrape_lite_tpu tree)")
    p.add_argument("--json", action="store_true",
                   help="print the structured report (schema-checked "
                        "against analysis/report.py before printing, "
                        "check_bench_schema discipline)")
    p.add_argument("--baseline", default="",
                   help="suppression baseline path (default: "
                        "analysis/baseline.json)")
    p.add_argument("--artifact", action="store_true",
                   help="also run the compiled-artifact audits "
                        "(A1 constant bloat / A2 donation / A3 "
                        "zero-compile warm matrix) — compiles small "
                        "canonical runners, so it needs a working "
                        "jax backend")
    p.add_argument("--update-baseline", default=None, metavar="REASON",
                   help="suppress every CURRENT unsuppressed AST "
                        "finding into the baseline with this reason "
                        "string (exceptions are named, not invisible)")
    p.add_argument("--platform", default="",
                   help="jax platform override for --artifact")
    return p


def lint_main(argv=None) -> int:
    """The `lint` subcommand; returns the process exit code (nonzero
    on any unsuppressed finding — the CI gate app_tests.sh enforces)."""
    import json as _json
    import sys

    ns = make_lint_parser().parse_args(argv)
    _apply_platform(ns.platform, 0)

    from libgrape_lite_tpu import analysis

    if ns.update_baseline is not None:
        import os

        if not ns.update_baseline:
            # an empty reason (e.g. an unset shell variable) must not
            # silently degrade to a plain lint run — the mandatory-
            # reason contract Baseline.add enforces starts HERE
            print(
                "grape-lint: --update-baseline needs a non-empty "
                "REASON — exceptions are named, not invisible",
                file=sys.stderr,
            )
            return 2

        paths = ns.paths or [
            os.path.join(analysis.repo_root(), "libgrape_lite_tpu")
        ]
        try:
            findings = analysis.lint_paths(paths)
        except FileNotFoundError as e:
            print(f"grape-lint: {e}", file=sys.stderr)
            return 2
        baseline = analysis.Baseline.load(ns.baseline or None)
        live, _ = analysis.split_by_baseline(findings, baseline)
        for f in live:
            baseline.add(f, ns.update_baseline)
        path = baseline.save()
        print(f"baseline: {len(live)} suppression(s) added -> {path}")
        return 0

    try:
        report, rc = analysis.run_lint(
            ns.paths, baseline_path=ns.baseline or None,
            artifact=ns.artifact,
        )
    except FileNotFoundError as e:
        print(f"grape-lint: {e}", file=sys.stderr)
        return 2
    if ns.json:
        errors = analysis.validate_lint_report(report)
        if errors:
            # the report record is a pinned artifact like the BENCH
            # json: schema drift fails AFTER the findings are shown
            print(_json.dumps(report), flush=True)
            for e in errors:
                print(f"lint-report schema: {e}", file=sys.stderr)
            return 3
        print(_json.dumps(report), flush=True)
    else:
        live = [analysis.Finding(**{k: f[k] for k in (
            "rule", "path", "line", "symbol", "message")})
            for f in report["findings"] if not f["suppressed"]]
        quiet = [f for f in report["findings"] if f["suppressed"]]
        print(analysis.render_text(live, quiet, report.get("stale")))
    return rc


def make_calibrate_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="libgrape_lite_tpu calibrate")
    p.add_argument("--out", default="",
                   help="write the fitted RateProfile json here "
                        "(install it via GRAPE_RATE_PROFILE=<path>)")
    p.add_argument("--samples-out", default="",
                   help="persist the measured sweep json — the bench "
                        "calibration lane and --check replay it "
                        "deterministically (GRAPE_CALIBRATION_SAMPLES)")
    p.add_argument("--samples", default="",
                   help="fit/check from a RECORDED sample set instead "
                        "of re-measuring")
    p.add_argument("--check", action="store_true",
                   help="no fit: drift-gate the ACTIVE profile "
                        "(GRAPE_RATE_PROFILE, or --profile) against "
                        "the samples; exit 2 beyond the 5%% tolerance")
    p.add_argument("--profile", default="",
                   help="explicit profile json for --check (default: "
                        "the active profile)")
    p.add_argument("--scales", default="8,9,10",
                   help="comma-separated RMAT scales for the sweep")
    p.add_argument("--ef", type=int, default=8,
                   help="sweep edge factor")
    p.add_argument("--repeats", type=int, default=3,
                   help="best-of-N walls per dispatch")
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--min-wall-s", type=float, default=-1.0,
                   help="exclude sweep samples with walls under this "
                        "(default: backend-appropriate — 20ms on the "
                        "CPU backend where sub-noise-floor walls are "
                        "scheduler jitter, 0 on real accelerators)")
    p.add_argument("--json", action="store_true",
                   help="print one structured record instead of the "
                        "table")
    p.add_argument("--platform", default="",
                   help="jax platform override (e.g. cpu)")
    return p


def calibrate_main(argv=None) -> int:
    """The `calibrate` subcommand (ops/calibration.py,
    docs/CALIBRATION.md): measure device walls, fit the pricing-rate
    profile, persist it, and drift-gate modeled-vs-measured.  Exit 0 =
    fit ok / gate passed, 2 = infeasible fit or the drift gate
    tripped."""
    import json as _json
    import sys

    ns = make_calibrate_parser().parse_args(argv)
    _apply_platform(ns.platform, 0)

    from libgrape_lite_tpu.ops import calibration as calib

    try:
        if ns.samples:
            samples = calib.load_samples(ns.samples)
        else:
            scales = tuple(int(s) for s in ns.scales.split(",") if s)
            samples = calib.microbench_samples(
                scales=scales, ef=ns.ef, seed=ns.seed,
                repeats=ns.repeats,
            )
            floor = (ns.min_wall_s if ns.min_wall_s >= 0
                     else calib.default_min_wall_s())
            kept = [s for s in samples if s["wall_s"] >= floor]
            if len(kept) < len(samples):
                print(
                    f"calibrate: dropped {len(samples) - len(kept)} "
                    f"sample(s) under the {floor * 1e3:.0f}ms noise "
                    "floor",
                    file=sys.stderr,
                )
            samples = kept
        if not samples:
            print("calibrate: no usable samples measured — nothing "
                  "to fit", file=sys.stderr)
            return 2

        notes: list = []
        fit = None
        if ns.check:
            prof = (calib.load_profile(ns.profile) if ns.profile
                    else calib.active_profile())
        else:
            fit, notes = calib.fit_rates_auto(
                samples, base=calib.default_profile(),
                source="samples" if ns.samples else "microbench",
            )
            prof = fit.profile
        rep = calib.drift_report(prof, samples)
    except calib.CalibrationError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2

    out_path = samples_path = None
    if not ns.check and ns.out:
        out_path = calib.save_profile(prof, ns.out)
    if ns.samples_out:
        samples_path = calib.save_samples(samples, ns.samples_out)

    # the same shape as the bench record's `calibration` block, so one
    # schema (scripts/check_bench_schema.py _CALIBRATION) pins both
    block = {
        "profile": prof.label(),
        "fingerprint": calib.backend_fingerprint(),
        "source": prof.source,
        "fitted": bool(prof.fitted),
        "samples": len(samples),
        "residual_pct": (round(fit.residual * 100.0, 3)
                         if fit is not None else -1.0),
        "drift_pct": rep["drift_pct"],
        "max_sample_drift_pct": rep["max_sample_drift_pct"],
        "drift_ok": rep["drift_ok"],
        "rates": {
            "clock_hz": prof.clock_hz,
            "vpu_lanes_per_cycle": prof.vpu_lanes_per_cycle,
            "mxu_cyc_per_elem": prof.mxu_cyc_per_elem,
            "hbm_bps": prof.hbm_bps,
            "gather_rows_per_cycle": prof.gather_rows_per_cycle,
            "dispatch_overhead_s": prof.dispatch_overhead_s,
        },
        "unfitted": sorted(prof.unfitted),
        "fallback_notes": list(notes),
        "surfaces": rep["surfaces"],
    }
    if ns.json:
        print(_json.dumps({"calibration": block, "out": out_path,
                           "samples_out": samples_path}))
    else:
        print(f"profile:  {block['profile']} "
              f"(source={block['source']}, "
              f"fitted={block['fitted']})")
        for r, v in sorted(block["rates"].items()):
            print(f"  {r:<22} {v:g}")
        if block["unfitted"]:
            print(f"  unfitted (inherited): "
                  f"{', '.join(block['unfitted'])}")
        for n in notes:
            print(f"  [fallback] {n}")
        for surf, e in sorted(rep["surfaces"].items()):
            print(f"drift[{surf}]: modeled {e['modeled_s']:.4f}s vs "
                  f"measured {e['measured_s']:.4f}s over "
                  f"{e['samples']} sample(s) = {e['drift_pct']:g}%")
        verdict = "OK" if rep["drift_ok"] else "FAIL"
        print(f"{verdict}: drift {rep['drift_pct']:g}% "
              f"(tolerance {rep['tolerance_pct']:g}%), "
              f"residual {block['residual_pct']:g}%")
        if out_path:
            print(f"profile -> {out_path}")
        if samples_path:
            print(f"samples -> {samples_path}")
    return 0 if rep["drift_ok"] else 2


def _apply_platform(platform: str, cpu_devices: int) -> None:
    if cpu_devices:
        import os

        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={cpu_devices}"
        ).strip()
    if platform:
        import jax

        jax.config.update("jax_platforms", platform)


def serve_main(argv=None):
    """The `serve` subcommand: resident session + scripted stream."""
    import json
    import sys
    import time

    import numpy as np

    ns = make_serve_parser().parse_args(argv)
    _apply_platform(ns.platform, ns.cpu_devices)
    place_compile_cache()
    if ns.trace or ns.metrics:
        from libgrape_lite_tpu import obs

        obs.configure(trace_path=ns.trace or None,
                      metrics_path=ns.metrics or None)
    if ns.slo:
        from libgrape_lite_tpu.obs import slo

        slo.configure(ns.slo)
    if ns.metrics_port is not None:
        from libgrape_lite_tpu.obs import exporter

        exp = exporter.start_exporter(ns.metrics_port)
        print(f"[serve] metrics exporter: {exp.url}", file=sys.stderr)
    else:
        from libgrape_lite_tpu.obs import exporter

        exp = exporter.maybe_start_from_env()
        if exp is not None:
            print(f"[serve] metrics exporter: {exp.url}",
                  file=sys.stderr)

    from libgrape_lite_tpu.fragment.loader import LoadGraph, LoadGraphSpec
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession
    from libgrape_lite_tpu.utils import timer

    # the scripted stream: (app, source) per query
    def coerce(src):
        if ns.string_id:
            return src
        try:
            return int(src)
        except ValueError:
            return src

    queries = []
    if ns.stream:
        for line in open(ns.stream):
            line = line.strip()
            if not line or line.startswith("#"):
                continue
            app_key, src = line.split()
            queries.append((app_key, coerce(src)))
    elif ns.sources:
        queries = [(ns.application, coerce(s))
                   for s in ns.sources.split(",")]
    else:
        queries = [(ns.application, s)
                   for s in range(max(1, ns.num_queries))]
    if not queries:
        # an all-comment --stream / empty --sources: fail BEFORE the
        # (possibly minutes-long) graph load, not on an empty latency
        # percentile afterwards
        sys.exit("serve: the query stream is empty")
    for app_key, _ in queries:
        if app_key not in APP_REGISTRY:
            raise ValueError(f"unknown application {app_key!r}")

    # one load serves every query — the point of the session
    weighted = any(
        getattr(APP_REGISTRY[a], "needs_edata", False) for a, _ in queries
    )

    # dyn/ live ingest: parse the delta stream up front (reproducible
    # chunking, malformed lines fail BEFORE the load) with the SAME
    # weightedness as the graph — a weighted serve must not silently
    # ingest zero-cost edges from an unweighted stream
    delta_ops = []
    if ns.delta_stream:
        from libgrape_lite_tpu.dyn import parse_ops_file

        delta_ops = parse_ops_file(
            ns.delta_stream, weighted=weighted, string_id=ns.string_id
        )
    # --arrival_rate: a float or a step spec ("50:2x@100") — validate
    # BEFORE the load; "0" keeps the legacy disabled meaning
    if ns.arrival_rate:
        try:
            if float(ns.arrival_rate) == 0.0:
                ns.arrival_rate = ""
        except ValueError:
            pass
    if ns.arrival_rate:
        from libgrape_lite_tpu.serve.feeder import parse_rate_spec

        try:
            parse_rate_spec(ns.arrival_rate)
        except ValueError as e:
            sys.exit(f"serve: {e}")
    fleet_mode = ns.replicas > 1 or bool(ns.tenants)
    if ns.drain_at >= 0 and ns.replicas < 2:
        sys.exit("serve: --drain_at needs --replicas >= 2 (draining "
                 "the only replica would drop traffic)")
    if ns.autopilot:
        # the autopilot runs its OWN fleet loop — it owns replica
        # count (min/max), so the static fleet knobs don't compose
        for flag, bad in (("--tenants", bool(ns.tenants)),
                          ("--drain_at", ns.drain_at >= 0),
                          ("--delta_stream", bool(ns.delta_stream))):
            if bad:
                sys.exit(f"serve: --autopilot does not compose with "
                         f"{flag} yet")
        if ns.min_replicas < 1:
            sys.exit("serve: --min_replicas must be >= 1")
        if ns.max_replicas < ns.min_replicas:
            sys.exit("serve: --max_replicas must be >= --min_replicas")
    elif fleet_mode and ns.arrival_rate:
        sys.exit("serve: --arrival_rate does not compose with "
                 "--replicas/--tenants yet")
    spec = LoadGraphSpec(
        directed=ns.directed, weighted=weighted,
        string_id=ns.string_id, edata_dtype=np.float64,
        # autopilot scale-ups replicate fresh fragments from the
        # retained edge list, exactly like --replicas
        retain_edge_list=bool(ns.delta_stream) or ns.replicas > 1
        or ns.autopilot,
    )
    with timer.phase("load graph"):
        frag = LoadGraph(ns.efile, ns.vfile or None,
                         CommSpec(fnum=ns.fnum), spec)

    def dyn_policy():
        if not ns.delta_stream:
            return None
        from libgrape_lite_tpu.dyn import RepackPolicy

        return (
            RepackPolicy(threshold=ns.dyn_repack_ratio)
            if ns.dyn_repack_ratio is not None
            else RepackPolicy.from_env()
        )

    policy = BatchPolicy(max_batch=ns.max_batch,
                         max_wait_s=ns.max_wait_ms / 1e3)

    if ns.autopilot:
        return _serve_autopilot(ns, frag, queries, policy, dyn_policy)
    if fleet_mode:
        return _serve_fleet(ns, frag, queries, delta_ops, policy,
                            dyn_policy)

    sess = ServeSession(
        frag,
        policy=policy,
        guard=ns.guard or None,
        dyn=dyn_policy(),
    )
    # --inflight > 1 arms the async pump (serve/pipeline.py): up to W
    # coalesced batches dispatched un-synced, lazy FIFO harvest, and
    # every ingest an explicit window barrier.  --inflight 1 keeps the
    # synchronous loop below bit-for-bit.
    pump = sess.async_pump(window=ns.inflight) if ns.inflight > 1 else None
    t0 = time.perf_counter()
    if ns.arrival_rate:
        # threaded admission front (serve/feeder.py): a feeder thread
        # submits at the asked rate with REAL wall-clock arrival
        # timestamps while this thread pumps — max_wait_ms and
        # priority/deadline scheduling genuinely gate under load.
        # Does not compose with --delta_stream (the deterministic
        # ingest cadence is pinned by dispatch count, which a
        # wall-clock feeder cannot reproduce).
        if delta_ops:
            sys.exit("serve: --arrival_rate does not compose with "
                     "--delta_stream")
        from libgrape_lite_tpu.serve import ArrivalFeeder

        feeder = ArrivalFeeder(
            sess.submit,
            # dict form so --max_rounds reaches submit exactly as on
            # the scripted path
            [{"app": app_key, "args": {"source": src},
              "max_rounds": ns.max_rounds or None}
             for app_key, src in queries],
            ns.arrival_rate,
        )
        results = []
        feeder.start()
        while feeder.is_alive() or sess.queue.pending() or (
            pump is not None and pump.inflight()
        ):
            got = (pump.pump() if pump is not None
                   else sess.pump())
            results.extend(got)
            if not got:
                time.sleep(1e-4)
        feeder.join()
        results.extend(
            pump.drain() if pump is not None else sess.drain()
        )
        reqs = feeder.requests
        wall = time.perf_counter() - t0
        return _serve_summary(ns, sess, pump, reqs, results, wall,
                              delta_ops)
    reqs = [
        sess.submit(app_key, {"source": src},
                    max_rounds=ns.max_rounds or None)
        for app_key, src in queries
    ]
    if delta_ops:
        # streaming mode: ingest a delta chunk after every
        # --ingest_every dispatched queries, so updates land between
        # batches while the query stream stays live.  The sync loop
        # makes each ingest a superstep boundary by construction; the
        # async pump makes it an explicit window quiesce — and pins
        # the SAME ingest points by dispatch count (`max_dispatch`),
        # so the batch <-> graph-version interleave (and therefore
        # every result byte) is identical at any --inflight.
        ingest_every = max(1, ns.ingest_every)
        n_chunks = max(1, -(-len(queries) // ingest_every))
        chunk = -(-len(delta_ops) // n_chunks)
        oi = 0
        results = []
        if pump is not None:
            while (sess.queue.pending() or pump.inflight()
                   or oi < len(delta_ops)):
                target = pump.dispatched_queries + ingest_every
                while (sess.queue.pending()
                       and pump.dispatched_queries < target):
                    pump.pump(force=True, block=True,
                              max_dispatch=target)
                if oi < len(delta_ops):
                    pump.ingest(delta_ops[oi:oi + chunk])
                    oi += chunk
                else:
                    pump.drain()
            results = [q.result for q in reqs]
        else:
            while sess.queue.pending() or oi < len(delta_ops):
                pumped = 0
                while sess.queue.pending() and pumped < ingest_every:
                    got = sess.pump(force=True)
                    results.extend(got)
                    pumped += len(got)
                if oi < len(delta_ops):
                    sess.ingest(delta_ops[oi:oi + chunk])
                    oi += chunk
    else:
        results = pump.drain() if pump is not None else sess.drain()
    wall = time.perf_counter() - t0
    return _serve_summary(ns, sess, pump, reqs, results, wall,
                          delta_ops)


def _serve_fleet(ns, frag, queries, delta_ops, policy, dyn_policy):
    """The fleet serving path (fleet/, docs/FLEET.md): R replica
    sessions behind a version-fenced router and/or N tenants under
    one HBM budget, driven by the deterministic
    `run_fleet_script` — so a `--replicas 2 --drain_at K` run is
    byte-identical per query to the plain single-replica run (the
    smoke in scripts/app_tests.sh cmp's exactly that via
    --dump_results).  `dyn_policy` is serve_main's own repack-policy
    factory — ONE copy of that decision, so the fleet run can never
    quietly use a different policy than the plain run it must match
    byte-for-byte."""
    import sys
    import time

    from libgrape_lite_tpu.fleet import (
        FLEET_STATS,
        FleetBudget,
        FleetManager,
        FleetRouter,
        run_fleet_script,
    )
    from libgrape_lite_tpu.fragment.mutation import replicate_fragment
    from libgrape_lite_tpu.serve import ServeSession

    # the summary's fleet counters are a per-run record (the bench
    # PUMP_STATS discipline): reset the process-global stats first
    FLEET_STATS.reset()

    def make_session(f):
        return ServeSession(
            f, policy=policy, guard=ns.guard or None,
            dyn=dyn_policy(),
        )

    frags = [frag] + [
        replicate_fragment(frag) for _ in range(ns.replicas - 1)
    ]
    sessions = [make_session(f) for f in frags]
    router = (
        FleetRouter(sessions, window=max(1, ns.inflight))
        if ns.replicas > 1 else None
    )
    target = router if router is not None else sessions[0]

    manager = None
    tenant_of = None
    if ns.tenants:
        manager = FleetManager(FleetBudget())
        if ns.tenants == "by_app":
            names = sorted({app for app, _ in queries})
            tenant_of = lambda i, app: app  # noqa: E731
        else:
            try:
                n_t = max(1, int(ns.tenants))
            except ValueError:
                sys.exit(f"serve: --tenants must be 'by_app' or an "
                         f"integer, got {ns.tenants!r}")
            names = [f"t{j}" for j in range(n_t)]
            tenant_of = lambda i, app: f"t{i % n_t}"  # noqa: E731
        for name in names:
            manager.add_tenant(name, target)

    fleet_queries = [
        (app_key, {"source": src}) for app_key, src in queries
    ]
    t0 = time.perf_counter()
    reqs = run_fleet_script(
        target, fleet_queries, manager=manager, tenant_of=tenant_of,
        delta_ops=delta_ops, ingest_every=max(1, ns.ingest_every),
        drain_at=(ns.drain_at if ns.drain_at >= 0 else None),
        drain_idx=0,
        # stream-wide limits reach the queue exactly as on the plain
        # path (a dropped --max_rounds would silently change results)
        submit_kwargs={"max_rounds": ns.max_rounds or None},
    )
    wall = time.perf_counter() - t0
    results = [q.result for q in reqs if q.result is not None]

    fleet_block = {
        "replicas": ns.replicas,
        "tenants": len(manager.tenants) if manager is not None else 0,
        "fence": router.fence if router is not None else 0,
        "dropped": len(reqs) - len(results),
        **FLEET_STATS.snapshot(),
    }
    if router is not None:
        fleet_block["router"] = router.summary(wall)
    if manager is not None:
        snap = manager.snapshot()
        fleet_block["tenant_stats"] = snap["tenants"]
        fleet_block["budget"] = {
            "capacity": snap["budget"]["capacity"],
            "used_bytes": snap["budget"]["used_bytes"],
        }
    return _serve_summary(
        ns, sessions[0], None, reqs, results, wall, delta_ops,
        fleet_block=fleet_block, sessions=sessions,
    )


def _serve_autopilot(ns, frag, queries, policy, dyn_policy):
    """The closed-loop serving path (autopilot/, docs/AUTOPILOT.md):
    a replica fleet whose size the Autoscaler moves between
    --min_replicas and --max_replicas from live queue/burn signals,
    with a shared fence-epoch result cache in front of the device.
    With --arrival_rate the stream arrives on a feeder thread (the
    rate may STEP mid-stream: '50:2x@100') while this thread routes,
    pumps, and ticks the control loop; without it the scripted stream
    submits up front and the loop still ticks between pumps."""
    import sys  # noqa: F401  (parity with the sibling drivers)
    import time
    from collections import deque

    from libgrape_lite_tpu.autopilot import (
        Autoscaler,
        ResultCache,
        ScalerConfig,
    )
    from libgrape_lite_tpu.autopilot.signals import AUTOPILOT_STATS
    from libgrape_lite_tpu.fleet import (
        FLEET_STATS,
        FleetBudget,
        FleetRouter,
    )
    from libgrape_lite_tpu.fragment.mutation import replicate_fragment
    from libgrape_lite_tpu.serve import ServeSession

    # per-run record discipline (the _serve_fleet PUMP_STATS rule):
    # process-global stats reset first
    FLEET_STATS.reset()
    AUTOPILOT_STATS.reset()

    def make_session(f):
        return ServeSession(
            f, policy=policy, guard=ns.guard or None, dyn=dyn_policy(),
        )

    n0 = max(1, ns.min_replicas, ns.replicas)
    frags = [frag] + [replicate_fragment(frag) for _ in range(n0 - 1)]
    sessions = [make_session(f) for f in frags]
    router = FleetRouter(sessions, window=max(1, ns.inflight))
    cache = None
    if ns.cache_entries > 0:
        cache = ResultCache(capacity=ns.cache_entries)
        router.attach_cache(cache)
    cfg = ScalerConfig(
        min_replicas=n0, max_replicas=max(n0, ns.max_replicas),
    )
    autopilot = Autoscaler(
        router, cfg, session_factory=make_session, budget=FleetBudget(),
    )

    def busy():
        return any(
            r.session.queue.pending() or r.pump.inflight()
            for r in router.replicas
        )

    stream = [
        {"app": app_key, "args": {"source": src},
         "max_rounds": ns.max_rounds or None}
        for app_key, src in queries
    ]
    reqs = []
    t0 = time.perf_counter()
    if ns.arrival_rate:
        from libgrape_lite_tpu.serve import ArrivalFeeder

        # the feeder thread only APPENDS arrivals; this thread alone
        # touches the router (submit/pump/tick), so the fleet stays
        # single-threaded like every other driver
        inbox: deque = deque()

        def enqueue(app_key, args, **kw):
            inbox.append((app_key, args, kw))

        feeder = ArrivalFeeder(enqueue, stream, ns.arrival_rate)
        feeder.start()
        while feeder.is_alive() or inbox or busy():
            moved = 0
            while inbox:
                app_key, args, kw = inbox.popleft()
                reqs.append(router.submit(app_key, args, **kw))
                moved += 1
            got = router.pump()
            autopilot.tick()
            if not got and not moved:
                time.sleep(1e-4)
        feeder.join()
    else:
        for item in stream:
            reqs.append(router.submit(
                item["app"], item["args"],
                max_rounds=item["max_rounds"],
            ))
            router.pump()
            autopilot.tick()
        while busy():
            router.pump()
            autopilot.tick()
    router.drain()
    wall = time.perf_counter() - t0
    results = [q.result for q in reqs if q.result is not None]

    routable = [r for r in router.replicas if r.routable]
    ap = AUTOPILOT_STATS.snapshot()
    autopilot_block = {
        "min_replicas": cfg.min_replicas,
        "max_replicas": cfg.max_replicas,
        "replicas_final": len(routable),
        "replicas_peak": len(router.replicas),
        **{k: ap[k] for k in (
            "ticks", "scale_ups", "scale_downs", "holds", "shed",
            "deferred", "cache_hits", "cache_misses", "cache_stores",
        )},
    }
    if cache is not None:
        autopilot_block["cache"] = cache.snapshot()
    fleet_block = {
        "replicas": len(router.replicas),
        "tenants": 0,
        "fence": router.fence,
        "dropped": len(reqs) - len(results),
        **FLEET_STATS.snapshot(),
        "router": router.summary(wall),
    }
    return _serve_summary(
        ns, router.replicas[0].session, None, reqs, results, wall,
        [], fleet_block=fleet_block,
        sessions=[r.session for r in router.replicas],
        autopilot_block=autopilot_block,
    )


def _per_app_latency_ms(results) -> dict:
    """Per-app p50/p99 latency next to the global one — the fleet
    bench's per-workload view of a mixed stream."""
    from libgrape_lite_tpu.serve.queue import latency_summary_ms

    by_app: dict = {}
    for r in results:
        by_app.setdefault(r.app_key, []).append(r.latency_s)
    out = {}
    for app, lat in sorted(by_app.items()):
        s = latency_summary_ms(lat)
        out[app] = {"p50": s["p50_ms"], "p99": s["p99_ms"]}
    return out


def _serve_summary(ns, sess, pump, reqs, results, wall, delta_ops,
                   fleet_block=None, sessions=None,
                   autopilot_block=None):
    """Build + print the serve summary record (shared by the plain,
    feeder and fleet paths).  `sessions` (fleet) merges batch
    histograms and admission waits across replicas/tenant sessions;
    otherwise `sess` alone reports."""
    import json
    import sys

    from libgrape_lite_tpu.serve.queue import latency_summary_ms

    sessions = sessions or [sess]
    lat = latency_summary_ms([r.latency_s for r in results])
    ok = sum(1 for r in results if r.ok)
    per_app: dict = {}
    for r in results:
        per_app[r.app_key] = per_app.get(r.app_key, 0) + 1
    waits = latency_summary_ms(
        [w for s in sessions for w in s.queue.admission_waits]
    )
    batch_hist: dict = {}
    for s in sessions:
        for k, v in s.queue.batch_hist.items():
            batch_hist[k] = batch_hist.get(k, 0) + v
    cache = {"runner": {"hits": 0, "misses": 0}}
    for s in sessions:
        st = s.cache_stats()["runner"]
        cache["runner"]["hits"] += st["hits"]
        cache["runner"]["misses"] += st["misses"]
    record = {
        "queries": len(results),
        "ok": ok,
        "failed": len(results) - ok,
        "wall_s": round(wall, 4),
        "qps": round(len(results) / wall, 2) if wall > 0 else 0.0,
        "p50_ms": lat["p50_ms"],
        "p99_ms": lat["p99_ms"],
        "max_batch": ns.max_batch,
        "inflight": ns.inflight,
        "batch_hist": {
            str(k): v for k, v in sorted(batch_hist.items())
        },
        # per-request submit->dispatch wait (serve/queue.py): the
        # admission-latency half of the p99 story, next to batch_hist
        "admission_wait_ms": {
            "p50": waits["p50_ms"], "p99": waits["p99_ms"],
        },
        "apps": per_app,
        # per-app latency split next to the global p50/p99 (a mixed
        # stream's per-workload tails diverge — sssp vs khop)
        "per_app_ms": _per_app_latency_ms(results),
        "cache": cache,
    }
    # per-stage p50/p99 decomposition (queue_wait/window_wait/
    # dispatch/device/harvest µs, from ServeResult.stages): where the
    # global p99 actually went — shared by plain, pump and fleet paths
    stage_lists: dict = {}
    for r in results:
        for k, v in (r.stages or {}).items():
            stage_lists.setdefault(k, []).append(v / 1e6)
    if stage_lists:
        record["stages"] = {}
        for k, v in sorted(stage_lists.items()):
            s = latency_summary_ms(v)
            record["stages"][k] = {"p50": s["p50_ms"], "p99": s["p99_ms"]}
    from libgrape_lite_tpu.obs import slo as _slo

    if _slo.configured():
        record["slo"] = _slo.SLO_STATS.snapshot()
    if pump is not None:
        from libgrape_lite_tpu.serve import PUMP_STATS

        record["pump"] = {
            "window": pump.window,
            **pump.stats,
            **PUMP_STATS.snapshot(),
        }
    if delta_ops:
        # the same field names as bench.py's schema-checked dyn block
        # (scripts/check_bench_schema.py _DYN), so both surfaces
        # validate against one declaration
        ingested = sum(s.stats["ingested_ops"] for s in sessions)
        record["dyn"] = {
            "ingested": ingested,
            "overlay_applies": sum(
                s.stats["overlay_applies"] for s in sessions
            ),
            "repack_count": sum(s.stats["repacks"] for s in sessions),
            "queries": len(results),
            "queries_ok": ok,
            "updates_per_s": (
                round(ingested / wall, 2) if wall > 0 else 0.0
            ),
        }
    if fleet_block is not None:
        record["fleet"] = fleet_block
    if autopilot_block is not None:
        record["autopilot"] = autopilot_block
    if ns.dump_results:
        # submit-order identity surface: one line per query with a
        # digest of its assembled values — byte-comparable across
        # --inflight settings (the async smoke cmp's 4 against 1)
        import hashlib

        with open(ns.dump_results, "w") as fh:
            for i, req in enumerate(reqs):
                r = req.result
                digest = (
                    hashlib.sha256(r.values.tobytes()).hexdigest()
                    if r is not None and r.ok and r.values is not None
                    else "-"
                )
                ok_flag = int(bool(r is not None and r.ok))
                rounds = r.rounds if r is not None else -1
                fh.write(
                    f"{i} {req.app_key} {ok_flag} {rounds} {digest}\n"
                )
    print(json.dumps(record), flush=True)
    if record["failed"]:
        print(f"[serve] {record['failed']} of {len(results)} queries "
              "failed", file=sys.stderr)
        sys.exit(1)

    from libgrape_lite_tpu import obs

    if obs.armed():
        obs.flush()


def make_postmortem_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="libgrape_lite_tpu postmortem")
    p.add_argument("bundle",
                   help="flight-recorder bundle json (obs/recorder.py "
                        "writes one per trigger into the "
                        "GRAPE_POSTMORTEM sink directory)")
    p.add_argument("--trace", default="",
                   help="Chrome trace file from the same run: verify "
                        "every serve_query span row in the bundle "
                        "byte-matches the trace's row for the same "
                        "query id (exit 1 on any mismatch — the "
                        "postmortem and the timeline must join "
                        "row-for-row)")
    p.add_argument("--json", action="store_true",
                   help="print the raw bundle instead of the report")
    return p


def postmortem_main(argv=None) -> int:
    """The `postmortem` subcommand: render a flight-recorder bundle,
    and with --trace prove its span rows are the SAME rows as the
    Chrome trace's (byte-equality of the sort_keys serialization per
    query id — bundles copy tracer history verbatim, so any drift is
    a recorder bug, not formatting noise)."""
    import json
    import sys
    from collections import Counter

    from libgrape_lite_tpu.obs.recorder import BUNDLE_SCHEMA

    ns = make_postmortem_parser().parse_args(argv)
    try:
        with open(ns.bundle) as fh:
            bundle = json.load(fh)
    except (OSError, ValueError) as e:
        print(f"postmortem: {ns.bundle}: {e}", file=sys.stderr)
        return 2
    if bundle.get("schema") != BUNDLE_SCHEMA:
        print(f"postmortem: {ns.bundle}: schema "
              f"{bundle.get('schema')!r} != {BUNDLE_SCHEMA!r}",
              file=sys.stderr)
        return 2
    if ns.json:
        print(json.dumps(bundle, indent=1))
        return 0

    events = bundle.get("events") or []
    spans = bundle.get("spans") or []
    instants = bundle.get("instants") or []
    fed = bundle.get("federation") or {}
    lines = [
        f"postmortem: {bundle['reason']}",
        f"  trace_id:    {bundle.get('trace_id')}",
        f"  extra:       {json.dumps(bundle.get('extra') or {}, sort_keys=True)}",
        f"  ring events: {len(events)} "
        f"({dict(Counter(e.get('kind') for e in events))})",
        f"  spans:       {len(spans)} "
        f"({dict(Counter(s.get('name') for s in spans))})",
        f"  instants:    {len(instants)} "
        f"({dict(Counter(i.get('name') for i in instants))})",
        f"  federation:  {sorted(fed)}",
        f"  guard:       "
        f"{'yes (' + str((bundle['guard'].get('verdict') or {}).get('kind')) + ')' if bundle.get('guard') else 'no'}",
    ]
    slo_snap = fed.get("slo") or {}
    if slo_snap.get("objectives_ms"):
        lines.append(
            f"  slo:         {slo_snap.get('breaches', 0)} breach(es) "
            f"of {slo_snap.get('observed', 0)} observed, "
            f"max burn {slo_snap.get('max_burn', 0.0)}"
        )
    print("\n".join(lines))

    if not ns.trace:
        return 0
    try:
        with open(ns.trace) as fh:
            trace = json.load(fh)
    except (OSError, ValueError) as e:
        print(f"postmortem: {ns.trace}: {e}", file=sys.stderr)
        return 2
    by_qid: dict = {}
    for ev in trace.get("traceEvents", []):
        if ev.get("ph") != "X" or ev.get("name") != "serve_query":
            continue
        qid = (ev.get("args") or {}).get("query_id")
        if qid is not None:
            by_qid.setdefault(qid, []).append(
                json.dumps(ev, sort_keys=True)
            )
    matched = mismatched = missing = 0
    for row in spans:
        if row.get("name") != "serve_query":
            continue
        qid = (row.get("args") or {}).get("query_id")
        want = json.dumps(row, sort_keys=True)
        cands = by_qid.get(qid, [])
        if want in cands:
            matched += 1
        elif cands:
            mismatched += 1
        else:
            missing += 1
    print(f"trace cross-check: {matched} serve_query row(s) "
          f"byte-matched, {mismatched} mismatched, {missing} absent "
          f"from the trace")
    return 1 if (mismatched or missing) else 0


def main(argv=None):
    import sys

    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "serve":
        return serve_main(argv[1:])
    if argv and argv[0] == "postmortem":
        return postmortem_main(argv[1:])
    if argv and argv[0] == "lint":
        # returned (not sys.exit'd) so programmatic callers get the
        # code; the module tail exits with it
        return lint_main(argv[1:])
    if argv and argv[0] == "calibrate":
        return calibrate_main(argv[1:])
    ns = make_parser().parse_args(argv)
    _apply_platform(ns.platform, ns.cpu_devices)
    place_compile_cache()
    args = QueryArgs(
        **{k: v for k, v in vars(ns).items()
           if k not in ("platform", "cpu_devices")}
    )
    run_app(args)


if __name__ == "__main__":
    import sys

    sys.exit(main())
