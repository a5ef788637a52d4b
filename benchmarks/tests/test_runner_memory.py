"""The runner-memory reader on ledgers recorded on the v5e.

    python -m pytest benchmarks/tests

`runner_ledger_serve_v5e.json` is the program's set-up ledger as
`layer_metrics/setup_phase.py` kept it in a traced run of the one-chip cell
`serve-g500-s18.keys8` (two batched runners), `runner_ledger_lcc_x4_v5e.json`
the same of `g500-lcc-x4.lcc` on the four-chip v5e (PR 48, chip runs);
`runner_ledgers.expected.json` holds the three metrics' values for each.  The
ledger of a program from before the stamp is PR 34's recording,
`setup_ledger_pagerank_v5e.json`.  tests/test_benchmark_runner_memory.py runs
the same cases in tier-1; tests/test_setup_ledger.py holds the program to what
it stamps.
"""

import copy
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.layer_metrics import runner_memory  # noqa: E402

TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
METRICS = ["hbm_runner_code_bytes", "hbm_runner_state_bytes",
           "hbm_runner_temp_bytes"]
RECORDED = ["serve", "lcc_x4"]
RUNNERS = {"serve": 2, "lcc_x4": 1}


def metric_spec(name):
    return json.load(open(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".json")))


def recorded(name, prefix="runner_ledger"):
    path = os.path.join(TESTDATA, f"{prefix}_{name}_v5e.json")
    if not os.path.exists(path):
        pytest.skip(f"{path} is not recorded")
    return json.load(open(path))


def run_of(rec, **over):
    """What `run.py` hands the reader, from a recorded ledger."""
    lines = []
    run = types.SimpleNamespace(
        readings=dict(rec["readings"]), setup_ledger=copy.deepcopy(rec["setup"]),
        t_start=rec["t_start"], log=lines.append, lines=lines)
    run.__dict__.update(over)
    return run


def read_all(run):
    return {m: runner_memory.read(run, metric_spec(m)) for m in METRICS}


def stamped(rec):
    return [r for r in rec["setup"]["records"] if r["name"] == "runner.compile"]


@pytest.mark.parametrize("name", RECORDED)
def test_the_three_metrics_on_a_recorded_ledger(name):
    rec = recorded(name)
    want = json.load(open(os.path.join(TESTDATA, "runner_ledgers.expected.json")))[name]
    run = run_of(rec)
    got = read_all(run)
    assert got == want
    args = [r["args"] for r in stamped(rec)]
    assert len(args) == RUNNERS[name]
    assert got["hbm_runner_code_bytes"] == sum(a["code_bytes"] for a in args)
    assert got["hbm_runner_temp_bytes"] == max(a["temp_bytes"] for a in args)
    assert got["hbm_runner_state_bytes"] == max(
        a["state_bytes"] + a["output_bytes"] - a["alias_bytes"] for a in args)
    # code is HBM the peak counts; the state and the answer lie within it too
    assert 0 < got["hbm_runner_code_bytes"] < rec["readings"]["hbm_peak_bytes"]
    assert 0 < got["hbm_runner_state_bytes"] < rec["readings"]["hbm_peak_bytes"]
    # one reconciliation line a runner, once a run, with the allocator's growth
    lines = [ln for ln in run.lines if ln.startswith("runner memory: ")]
    assert len(lines) == RUNNERS[name]
    for ln, r in zip(lines, stamped(rec)):
        grew = r["bytes_in_use"]["close"] - r["bytes_in_use"]["open"]
        assert f"the allocator grew {grew} over the phase" in ln
        assert f"temp_bytes {r['args']['temp_bytes']}," in ln
    read_all(run)
    assert len([ln for ln in run.lines if ln.startswith("runner memory: ")]) \
        == RUNNERS[name]
    if name == "lcc_x4":
        # what the peak does not count: a chip's temporaries are several
        # times everything the metric reads there
        assert got["hbm_runner_temp_bytes"] > 2 * rec["readings"]["hbm_peak_bytes"]


def test_every_metric_file_names_the_phase_and_what_it_reads():
    for m in METRICS:
        spec = metric_spec(m)
        assert spec["reader"] == "runner_memory" and "quantity" in spec
        assert "runner.compile" in spec["what"] and "left out" in spec["what"]
        entry = next(e for e in BENCH["per_layer"] if e["name"] == m)
        assert "workloads" not in entry  # every cell compiles a runner
        assert entry["moves"] == spec["moves"] == "hbm_peak_bytes"
        assert entry["source"] == spec["source"] == "program_counter"
        assert entry["layer"] == spec["layer"] == (
            "kernels" if m == "hbm_runner_temp_bytes" else "worker")
        assert (entry["unit"], entry["better"]) == ("bytes", "lower")
    assert "what a step needs and hbm_peak_bytes does not count" \
        in metric_spec("hbm_runner_temp_bytes")["what"]
    with pytest.raises(ValueError):
        runner_memory.read(run_of(recorded("serve")), {"quantity": "share"})


def test_a_program_from_before_the_stamp_reports_none_of_the_three():
    """PR 34's recording: `runner.compile` with its seconds and its
    `bytes_in_use`, no analysis.  Nothing is read, and nothing is 0."""
    rec = recorded("pagerank", prefix="setup_ledger")
    assert stamped(rec) and "code_bytes" not in stamped(rec)[0]["args"]
    run = run_of(rec)
    assert read_all(run) == dict.fromkeys(METRICS)
    assert "runner memory: no runner.compile phase carries an analysis" in run.lines
    # and the line `run.py` prints leaves them out
    from benchmarks.run import read_layer_metrics

    wanted = [e for e in BENCH["per_layer"] if e["moves"] == "hbm_peak_bytes"]
    line = read_layer_metrics(run, wanted)
    assert set(line) == {"hbm_graph_bytes", "hbm_derived_bytes"}


def test_an_executable_without_an_analysis_leaves_the_metrics_out():
    rec = recorded("serve")
    for r in stamped(rec):
        for k in runner_memory.STAMPED[:5]:
            del r["args"][k]  # `state_bytes` stays: it comes from shapes
    assert read_all(run_of(rec)) == dict.fromkeys(METRICS)


def test_a_program_without_the_ledger_reports_nothing():
    run = run_of(recorded("serve"), setup_ledger={})
    assert read_all(run) == dict.fromkeys(METRICS)
    assert not [ln for ln in run.lines if ln.startswith("runner memory")]


def test_a_ledger_without_bytes_in_use_keeps_the_metrics():
    """The analysis is the executable's own: a backend without allocator
    statistics still says what a runner holds, and the line says that the
    growth is not known."""
    rec = recorded("serve")
    clean = read_all(run_of(rec))
    for r in rec["setup"]["records"]:
        r.pop("bytes_in_use", None)
    run = run_of(rec)
    assert read_all(run) == clean
    assert all("the allocator grew not known" in ln
               for ln in run.lines if ln.startswith("runner memory: "))


def test_a_runner_compiled_in_the_window_does_not_count():
    rec = recorded("serve")
    clean = read_all(run_of(rec))
    late = copy.deepcopy(stamped(rec)[0])
    late["t0_ns"] = int((rec["t_start"] + rec["readings"]["setup_s"] + 1) * 1e9)
    late["args"].update(code_bytes=10 ** 9, temp_bytes=10 ** 12)
    rec["setup"]["records"].append(late)
    run = run_of(rec)
    assert read_all(run) == clean
    assert ", 1 after (['runner.compile'])" in "\n".join(run.lines)
