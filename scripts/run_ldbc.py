#!/usr/bin/env python
"""LDBC Graphalytics benchmark driver.

Re-design of the reference's Java harness (`ldbc_driver/`, driven by
`run_ldbc.sh`): runs the six Graphalytics algorithms (BFS, PR, WCC,
CDLP, LCC, SSSP) on a dataset, times load/compile/run phases separately
(Graphalytics scores processing time only), optionally validates
against expected-output files, and writes a JSON report.

Usage:
  python scripts/run_ldbc.py --efile dataset/p2p-31.e \
      --vfile dataset/p2p-31.v --validation_dir dataset \
      --dataset_name p2p-31 --fnum 4 [--platform cpu --cpu_devices 8]
  python scripts/run_ldbc.py ci     # the run_ldbc.sh ci equivalent
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ALGOS = ["bfs", "pagerank", "wcc", "cdlp", "lcc", "sssp"]


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "ci":
        argv = [
            "--efile", os.path.join(REPO, "dataset", "p2p-31.e"),
            "--vfile", os.path.join(REPO, "dataset", "p2p-31.v"),
            "--validation_dir", os.path.join(REPO, "dataset"),
            "--dataset_name", "p2p-31",
            "--platform", "cpu", "--cpu_devices", "4", "--fnum", "4",
        ] + argv[1:]

    p = argparse.ArgumentParser()
    p.add_argument("--efile", required=True)
    p.add_argument("--vfile", required=True)
    p.add_argument("--dataset_name", default="dataset")
    p.add_argument("--validation_dir", default="")
    p.add_argument("--fnum", type=int, default=None)
    p.add_argument("--platform", default="")
    p.add_argument("--cpu_devices", type=int, default=0)
    p.add_argument("--algorithms", default=",".join(ALGOS))
    p.add_argument("--source", type=int, default=6)
    p.add_argument("--report", default="ldbc_report.json")
    p.add_argument("--runs", type=int, default=3)
    args = p.parse_args(argv)

    if args.cpu_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.cpu_devices}"
        ).strip()
    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    from libgrape_lite_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()

    import numpy as np

    from libgrape_lite_tpu.fragment.loader import LoadGraph, LoadGraphSpec
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec
    from libgrape_lite_tpu.worker.worker import Worker, format_result_lines

    comm = CommSpec(fnum=args.fnum)
    report = {
        "dataset": args.dataset_name,
        "fnum": comm.fnum,
        "platform": jax.devices()[0].platform,
        "device": str(jax.devices()[0]),
        "results": {},
    }

    t0 = time.perf_counter()
    frag_w = LoadGraph(
        args.efile, args.vfile, comm,
        LoadGraphSpec(weighted=True, edata_dtype=np.float64),
    )
    report["load_seconds"] = round(time.perf_counter() - t0, 4)

    def query_kwargs(name):
        if name in ("sssp", "bfs"):
            return {"source": args.source}
        if name == "pagerank":
            return {"delta": 0.85, "max_round": 10}
        if name == "cdlp":
            return {"max_round": 10}
        return {}

    for name in args.algorithms.split(","):
        app = APP_REGISTRY[name]()
        worker = Worker(app, frag_w)
        kw = query_kwargs(name)
        t0 = time.perf_counter()
        worker.query(**kw)  # includes compile
        cold = time.perf_counter() - t0
        # processing_s = best of `runs` warm runs (cold run excluded,
        # like Graphalytics' makespan vs processing split).  Every query
        # blocks on the result (Worker.query -> block_until_ready), so a
        # warm run exceeding the cold makespan can only be host-load
        # noise — the full warm list is recorded so a single noisy
        # sample is visible instead of silently reported as the metric.
        warm = []
        for _ in range(max(1, args.runs)):
            t0 = time.perf_counter()
            worker.query(**kw)
            warm.append(time.perf_counter() - t0)
        entry = {
            "makespan_cold_s": round(cold, 4),
            "processing_s": round(min(warm), 4),
            "warm_runs_s": [round(w, 4) for w in warm],
            "rounds": worker.rounds,
        }
        if min(warm) > cold:
            entry["timer_note"] = (
                "warm > cold despite blocked timing: host-load noise"
            )

        suffix_map = {
            "bfs": "BFS", "pagerank": "PR", "wcc": "WCC",
            "cdlp": "CDLP", "lcc": "LCC", "sssp": "SSSP",
        }
        base = name.split("_")[0]  # same-result variants share the golden
        # pagerank_local* are a genuinely different algorithm
        # (competitor-compatible convergence, Performance.md:61-67) and
        # can never match the standard PR golden
        if name.startswith("pagerank_local"):
            base = None
        if args.validation_dir and base in suffix_map:
            suffix = suffix_map[base]
            golden_path = os.path.join(
                args.validation_dir, f"{args.dataset_name}-{suffix}"
            )
            if os.path.exists(golden_path):
                entry["validated"] = _validate(
                    worker, frag_w, base, golden_path, format_result_lines
                )
        report["results"][name] = entry
        print(f"{name}: {entry}")

    with open(args.report, "w") as f:
        json.dump(report, f, indent=2)
    print(f"report -> {args.report}")
    failed = [
        k for k, v in report["results"].items() if v.get("validated") is False
    ]
    if failed:
        print(f"VALIDATION FAILED: {failed}")
        return 1
    return 0


def _validate(worker, frag, name, golden_path, fmt_lines) -> bool:
    from tests.verifiers import (
        eps_verify, exact_verify, load_golden, load_result_lines, wcc_verify,
    )

    values = worker.result_values()
    chunks = []
    for f in range(frag.fnum):
        n = frag.inner_vertices_num(f)
        if n:
            chunks.append(
                fmt_lines(frag.inner_oids(f), values[f, :n],
                          worker.app.result_format)
            )
    res = load_result_lines("".join(chunks))
    gold = load_golden(golden_path)
    try:
        if name == "wcc":
            wcc_verify(res, gold)
        elif name in ("pagerank", "lcc"):
            eps_verify(res, gold)
        elif name == "sssp":
            inf_r = {k for k, v in res.items() if v == "infinity"}
            inf_g = {k for k, v in gold.items() if v == "infinity"}
            if inf_r != inf_g:
                return False
            eps_verify(
                {k: v for k, v in res.items() if k not in inf_r},
                {k: v for k, v in gold.items() if k not in inf_g},
            )
        else:
            exact_verify(res, gold)
        return True
    except AssertionError:
        return False


if __name__ == "__main__":
    sys.exit(main())
