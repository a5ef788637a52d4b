#!/usr/bin/env python3
"""The benchmark's one command: one cell, one run, one result line.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which imports JAX itself.  It exits non-zero before any work
unless JAX's first device is a TPU whose `device_kind` is in `peaks.json`
and the cell's `chips` are present.  Then it loads the cell's graph through
`LoadGraph`, warms the cell's own shapes (all of that is set-up), measures
whole queries for `--seconds`, checks every answer against the plain
references outside the window, and prints the contract's JSON object as the
last line of its standard output.  With `--trace 1` it also profiles one
whole query per job kind and reports the per-layer metrics instead.

Everything that belongs to one cell is data found by the name in
`BENCHMARK.json`: `configs/<config>.json`, `traffic/<mix>.json` (which names
its `drivers/<driver>.py`), `references/<app>.py`, `graphs/<generator>.py`,
`layer_metrics/<metric>.json` (+ `.py`).  See README.md.

`--rehearse` permits a run without the chip at the configuration's
`rehearse_scale`: a rehearsal of the control flow, whose last line names no
device and is never a result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # process start, as near as Python allows

import argparse
import glob
import importlib
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(msg: str) -> None:
    """An earlier line of the run: never the last."""
    print(f"[bench] {msg}", flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmarks/run.py: no {what} named {name!r} in BENCHMARK.json")


def summary(items: list) -> str:
    """'reference x64, graph files' from a list of 'reference <name>' notes."""
    kinds: dict = {}
    for it in items:
        kind = it.split(" ")[0] if it.startswith("reference ") else it
        kinds[kind] = kinds.get(kind, 0) + 1
    return ", ".join(k if n == 1 else f"{k} x{n}" for k, n in kinds.items()) or "nothing"


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Run:
    """What one run knows: handed to the driver and to every metric reader."""

    def __init__(self, **kw):
        self.readings: dict = {}  # counts and host-clock readings, by name
        self.trace: dict | None = None  # reduce_xplane.reduce()'s result
        self.__dict__.update(kw)

    def span(self, name: str):
        """A `bench.*` span on the profiler's clock (free when not tracing)."""
        import jax

        return jax.profiler.TraceAnnotation(name)

    def wrong_vertices(self, app: str, params: dict, values) -> int:
        """Vertices on which the program's answer breaks the configuration's
        guarantee for `app` against the plain reference."""
        from benchmarks.compare import mismatches

        rule = self.config["guarantees"][app]
        mod = importlib.import_module(f"benchmarks.references.{app}")
        got = mod.to_reference_form(by_vertex(self.frag, values))
        return mismatches(rule["rule"], got, self.dataset.reference(app, params),
                          rule.get("eps"))


def load_fragment(run: Run):
    """`LoadGraph` through the program's own fragment cache (--serialize /
    --deserialize): the first load in a checkout parses the TSV and writes
    the cache, later ones deserialize."""
    import numpy as np

    from libgrape_lite_tpu.fragment.loader import LoadGraph, LoadGraphSpec
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec

    ds, fnum = run.dataset, int(run.config["fnum"])
    spec = dict(run.config["load_graph_spec"])
    spec["edata_dtype"] = np.dtype(spec["edata_dtype"]).type
    sigs = os.path.join(ds.fragment_prefix, "*", f"part_{fnum}", "sig")
    path = "deserialize" if glob.glob(sigs) else "parse"
    (ds.hits if path == "deserialize" else ds.misses).append(f"fragment fnum {fnum}")
    t0 = time.perf_counter()
    with run.span("bench.load"):
        frag = LoadGraph(
            ds.efile, ds.vfile, CommSpec(fnum=fnum),
            LoadGraphSpec(serialize=True, deserialize=True,
                          serialization_prefix=ds.fragment_prefix, **spec))
    run.readings["load_graph_s"] = time.perf_counter() - t0
    log(f"LoadGraph: {path}, {run.readings['load_graph_s']:.2f} s, fnum {fnum}")
    return frag


def by_vertex(frag, values):
    """[fnum, vp] result rows -> one value per original id 0..n-1."""
    import numpy as np

    out = np.empty(frag.dev.total_vnum, dtype=values.dtype)
    for f in range(frag.fnum):
        out[frag.inner_oids(f)] = values[f, :frag.inner_vertices_num(f)]
    return out


def trace_pass(run: Run, fn) -> str:
    """Runs `fn` under `jax.profiler` and returns the `.xplane.pb` written."""
    import jax

    out = os.path.join(HERE, "cache", "traces", f"{run.cell['name']}-seed{run.seed}")
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        with run.span("bench.trace"):
            fn()
    finally:
        jax.profiler.stop_trace()
    files = sorted(glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under {out}")
    log(f"trace: {files[-1]} ({os.path.getsize(files[-1])} bytes)")
    return files[-1]


def read_layer_metrics(run: Run, metrics: list) -> dict:
    """Each per-layer metric through its own `layer_metrics/<name>.json`
    (+ `<reader>.py`).  A reader that finds nothing returns None and the
    metric is left out of the line."""
    out = {}
    for m in metrics:
        spec = load_json(os.path.join(HERE, "layer_metrics", m["name"] + ".json"))
        if "reader" in spec:
            mod = importlib.import_module(f"benchmarks.layer_metrics.{spec['reader']}")
            value = mod.read(run, spec)
        else:
            value = run.readings.get(spec["reading"])
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def device_memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    return max(peaks) if all(p is not None for p in peaks) else None


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="permit a run off the chip at the configuration's "
                        "rehearse_scale: never a result")
    args = p.parse_args(argv)

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = by_name(bench["workloads"], args.workload, "workload")
    config_entry = by_name(bench["configs"], cell["config"], "configuration")
    config = load_json(os.path.join(ROOT, config_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    seconds = float(bench["run_seconds"] if args.seconds is None else args.seconds)
    chips = int(cell["chips"])

    import jax

    devices = jax.devices()
    dev0 = devices[0]
    if not args.rehearse and dev0.platform != "tpu":
        print(f"benchmarks/run.py: JAX found platform {dev0.platform!r}, not "
              "'tpu': nothing was run", file=sys.stderr)
        return 2
    if not args.rehearse and dev0.device_kind not in peaks["devices"]:
        print(f"benchmarks/run.py: device_kind {dev0.device_kind!r} is not in "
              "benchmarks/peaks.json: nothing was run", file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"benchmarks/run.py: cell {cell['name']} needs {chips} devices, "
              f"JAX found {len(devices)}: nothing was run", file=sys.stderr)
        return 2
    devices = devices[:chips]

    # the program under test: in a directory that holds the benchmark alone
    # these imports fail, before anything reaches stdout
    from libgrape_lite_tpu.analysis.artifact import compile_events
    from libgrape_lite_tpu.io import native
    from libgrape_lite_tpu.utils.compile_cache import place_compile_cache

    from benchmarks import reduce_xplane
    from benchmarks.datasets import Dataset

    import jaxlib
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    scale = int(config["rehearse_scale"] if args.rehearse else config["scale"])
    log(f"cell {cell['name']}: config {config['name']} (scale {scale}), traffic "
        f"{cell['traffic']}, seed {args.seed}, {seconds:g} s, trace {args.trace}"
        + (", REHEARSAL: no number below is a result" if args.rehearse else ""))
    log(f"platform {dev0.platform}, device_kind {dev0.device_kind}, "
        f"{len(jax.devices())} devices present, {chips} used; jax {jax.__version__}, "
        f"jaxlib {jaxlib.__version__}, libtpu {libtpu}")
    log(f"reduced: {json.dumps(config['reduced'])}")
    log(f"assumed: {json.dumps(config['assumed'])}")

    cache_dir = place_compile_cache()
    log(f"compile cache: {cache_dir}")
    run = Run(cell=cell, config=config, traffic=traffic, seed=args.seed,
              seconds=seconds, chips=chips, devices=devices, peaks=peaks,
              rehearse=args.rehearse, log=log)
    driver_mod = importlib.import_module(f"benchmarks.drivers.{traffic['driver']}")

    # ---- set-up: everything before the first measured query ----
    with compile_events() as setup_ev:
        if not native.available():
            raise RuntimeError("native/loader.cc did not build or load: the "
                               "benchmark does not time the Python parsers")
        run.dataset = Dataset(config, scale, log)
        run.dataset_info = run.dataset.ensure_files()
        log(f"graph: {json.dumps(run.dataset_info)}")
        if not args.rehearse:  # the sizes a configuration states are the graph's
            stated = {k: config[k] for k in ("vertices", "edges", "pull_entries")}
            if stated != {k: run.dataset_info[k] for k in stated}:
                raise RuntimeError(f"{config_entry['file']} states {stated}")
        run.frag = load_fragment(run)
        driver = driver_mod.Driver(run)
        driver.warm_up()
    names = [name for name, _ in setup_ev.events]
    run.readings["compile_s"] = setup_ev.compile_seconds()
    hits = names.count("/jax/compilation_cache/cache_hits")
    misses = names.count("/jax/compilation_cache/cache_misses")
    log(f"set-up compile: {setup_ev.compiles} requests, "
        f"{run.readings['compile_s']:.2f} s, executable cache {hits} hits / "
        f"{misses} misses")

    xplane = None
    if args.trace:
        xplane = trace_pass(run, driver.traced_pass)

    setup_s = time.perf_counter() - T_START
    with compile_events() as window_ev:
        driver.measure(seconds)
    memory_peak = device_memory_peak(devices)  # before the check allocates
    run.readings["compiles_in_window"] = window_ev.compiles
    log(f"window: {driver.describe_samples()}; compiles in window "
        f"{window_ev.compiles}")

    # ---- outside the window: answers, the trace ----
    attempted, failed = driver.check()
    end_to_end = driver.end_to_end()
    end_to_end["setup_s"] = setup_s
    if memory_peak is not None:
        end_to_end["hbm_peak_bytes"] = memory_peak
    log(f"end to end: {json.dumps(end_to_end)}")
    run.readings.update(driver.readings())
    run.readings.update(end_to_end)
    driver.close()
    log(f"dataset cache: took {summary(run.dataset.hits)}; made "
        f"{summary(run.dataset.misses)}")

    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": chips, "memory_peak_bytes": memory_peak}
    if args.rehearse:  # a rehearsal names no device
        device.update(platform="rehearsal", kind=None)
    result = {"correct": failed == 0 and attempted > 0 and window_ev.compiles == 0,
              "attempted": attempted, "failed": failed}
    cell_e2e = [m for m in bench["end_to_end"] if applies(m, cell["name"])]
    if args.trace:
        run.trace = reduce_xplane.reduce(
            xplane, n_devices=chips,
            device_plane_prefix="/device:TPU:" if dev0.platform == "tpu" else None)
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        moved = {m["name"] for m in cell_e2e}
        wanted = [m for m in bench["per_layer"]
                  if applies(m, cell["name"]) and m["moves"] in moved]
        result["metrics"] = read_layer_metrics(run, wanted)
        result["breakdown"] = {"device_ops": run.trace["device_ops"][:10],
                               "idle_gaps": run.trace["idle_gaps"][:10]}
    else:
        result["metrics"] = {
            m["name"]: {"value": float(end_to_end[m["name"]]), "unit": m["unit"]}
            for m in cell_e2e if m["name"] in end_to_end}
    result["device"] = device
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
