"""Shared reader of the seven set-up metrics: what a process pays once.

The program keeps a ledger of its set-up phases whether or not it is traced
(`libgrape_lite_tpu/obs/tracer.py`: `SETUP_PHASES`, the federated namespace
`setup`).  One record a closed phase: `name`, `parent` (the enclosing phase
or None), `t0_ns` and `dur_ns` on `time.perf_counter_ns` (the clock of
`run.py`'s `T_START` and `setup_s`), the span's `args`, and for a phase that
places arrays `bytes_in_use` `{"open", "close"}` of the fullest local device.

A metric file names its `quantity`.  Only phases closed before the window
count (the traced pass and the window may open none: `ledger()` logs how many
did).  A program from before the ledger has no `setup` namespace and every
metric is left out; a ledger without `bytes_in_use` (a backend without
allocator statistics) leaves the two `hbm_*` metrics out, never 0.
"""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
DERIVED = "derived."  # the per-fragment structures' phases
PLACE = {"hbm_graph_bytes": "load.place", "hbm_derived_bytes": "derived.place"}


def t_start_s(run):
    """Process start on the ledger's clock: `run.t_start` (a test's), else
    `T_START` of the `__main__` that is `benchmarks/run.py`; None elsewhere."""
    t = getattr(run, "t_start", None)
    if t is None:
        t = getattr(sys.modules.get("__main__"), "T_START", None)
    return t


def ledger(run):
    """The run's set-up view, made and logged once: the records closed before
    the window, the denominator of the share, the gaps no phase covers.  None
    from a program without the ledger."""
    if "setup_view" in run.__dict__:
        return run.setup_view
    run.setup_view = None
    snap = getattr(run, "setup_ledger", None)
    if snap is None:
        try:
            from libgrape_lite_tpu.obs import federation
        except ImportError:
            return None
        snap = federation.snapshot().get("setup")
    if not snap or "records" not in snap:
        return None
    records = sorted(snap["records"], key=lambda r: r["t0_ns"])
    setup_s, t0 = run.readings.get("setup_s"), t_start_s(run)
    late = []
    if t0 is not None and setup_s is not None:
        window_ns = (t0 + setup_s) * 1e9
        late = [r for r in records if r["t0_ns"] + r["dur_ns"] > window_ns]
        records = [r for r in records if r["t0_ns"] + r["dur_ns"] <= window_ns]
    top = [r for r in records if r["parent"] is None]
    span_s, basis, gaps = setup_s, "setup_s (no T_START in __main__)", []
    if t0 is not None and top:
        edge = t0 * 1e9  # what no phase covers, by where it lies
        for r in top:
            gaps.append((f"before {r['name']}", (r["t0_ns"] - edge) / 1e9))
            edge = max(edge, r["t0_ns"] + r["dur_ns"])
        span_s, basis = edge / 1e9 - t0, "T_START to the last phase's close"
    run.log(f"setup ledger: {len(records)} phases before the window, "
            f"{len(late)} after ({[r['name'] for r in late]}), "
            f"{snap.get('dropped', 0)} dropped; share over {basis}: "
            f"{span_s if span_s is None else round(span_s, 3)} s")
    run.log("setup outside every phase: " + (", ".join(
        f"{where} {s:.3f} s" for where, s in gaps if s >= 0.05) or "not known"))
    run.log("setup phases: " + ", ".join(
        f"{r['name']} {r['dur_ns'] / 1e9:.3f} s" for r in records))
    _keep(run, snap)
    run.setup_view = {"records": records, "top": top, "span_s": span_s,
                      "stamped": any("bytes_in_use" in r for r in records)}
    return run.setup_view


def _keep(run, snap):
    """The ledger as read, beside the run's trace: what the pinned testdata
    under `benchmarks/testdata/` was copied from."""
    cell = getattr(run, "cell", None)
    if not cell or not hasattr(run, "seed"):
        return
    out = os.path.join(os.path.dirname(HERE), "cache", "traces",
                       f"{cell['name']}-seed{run.seed}")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "setup_ledger.json"), "w") as f:
        json.dump({"cell": cell["name"], "t_start": t_start_s(run),
                   "readings": {k: run.readings.get(k) for k in
                                ("setup_s", "load_graph_s", "compile_s",
                                 "hbm_peak_bytes")},
                   "setup": snap}, f)


def seconds(records):
    return sum(r["dur_ns"] for r in records) / 1e9


def growth(view, phase):
    """Bytes the fullest device gained over the records of `phase`; None
    where no record of the ledger carries `bytes_in_use`."""
    if not view["stamped"]:
        return None
    return sum(r["bytes_in_use"]["close"] - r["bytes_in_use"]["open"]
               for r in view["records"]
               if r["name"] == phase and "bytes_in_use" in r)


def read(run, spec):
    view = ledger(run)
    if view is None:
        return None
    records, quantity = view["records"], spec["quantity"]
    place = [r for r in records if r["name"] == "load.place"]
    if quantity == "spanned_share":
        if not view["span_s"]:
            return None
        return 100.0 * seconds(view["top"]) / view["span_s"]
    if quantity == "load_host_s":
        stages = [r for r in records if r["parent"] == "load_graph"]
        return seconds(stages) - seconds([r for r in place if r["parent"]])
    if quantity == "load_place_s":
        return seconds(place)
    if quantity == "derived_build_s":
        built = [r for r in records if r["name"].startswith(DERIVED)
                 and not (r["parent"] or "").startswith(DERIVED)]
        if "derived_logged" not in run.__dict__:
            run.derived_logged = True
            run.log("derived structures: " + (", ".join(
                f"{r['name']} {r['dur_ns'] / 1e9:.3f} s {r['args']}"
                for r in built) or "none built"))
        return seconds(built)
    if quantity == "runner_trace_lower_s":
        return sum(r["args"].get("trace_s", 0.0) + r["args"].get("lower_s", 0.0)
                   for r in records if r["name"] == "runner.compile")
    if quantity in PLACE:
        return growth(view, PLACE[quantity])
    raise ValueError(f"setup_phase: unknown quantity {quantity!r}")
