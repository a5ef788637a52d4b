"""The set-up reader on ledgers recorded on the v5e.

    python -m pytest benchmarks/tests

`setup_ledger_lcc_x4_v5e.json` is the program's set-up ledger as
`layer_metrics/setup_phase.py` read it in a traced run of the cell
`g500-lcc-x4.lcc` on the four-chip v5e, `setup_ledger_pagerank_v5e.json` the
same of `g500-s21.pagerank` on one chip (PR 34, chip runs; `_keep` wrote both
beside the runs' traces); `setup_ledgers.expected.json` holds the seven
metrics' values for each.  tests/test_benchmark_setup.py runs the same cases
in tier-1; tests/test_setup_ledger.py holds the program to the ledger's rules.
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

from benchmarks.layer_metrics import setup_phase  # noqa: E402

TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
METRICS = ["setup_spanned_share", "load_host_s", "load_place_s", "derived_build_s",
           "runner_trace_lower_s", "hbm_graph_bytes", "hbm_derived_bytes"]
RECORDED = ["lcc_x4", "pagerank"]


def metric_spec(name):
    return json.load(open(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".json")))


def recorded(name):
    path = os.path.join(TESTDATA, f"setup_ledger_{name}_v5e.json")
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
    return {m: setup_phase.read(run, metric_spec(m)) for m in METRICS}


@pytest.mark.parametrize("name", RECORDED)
def test_the_seven_metrics_on_a_recorded_ledger(name):
    rec = recorded(name)
    want = json.load(open(os.path.join(TESTDATA, "setup_ledgers.expected.json")))[name]
    run = run_of(rec)
    got = read_all(run)
    assert set(got) == set(want["metrics"])
    for m in METRICS:
        assert got[m] == pytest.approx(want["metrics"][m], rel=1e-9), m
    # inside against outside: the ledger's clock is the benchmark's
    inside = got["load_host_s"] + got["load_place_s"]
    assert inside == pytest.approx(rec["readings"]["load_graph_s"], rel=0.05)
    assert got["hbm_graph_bytes"] + got["hbm_derived_bytes"] \
        <= rec["readings"]["hbm_peak_bytes"]
    assert 0 < got["setup_spanned_share"] < 100
    # nothing was opened after set-up, and the log says what lies outside
    log = "\n".join(run.lines)
    assert f"{want['phases']} phases before the window, 0 after" in log
    assert "T_START to the last phase's close" in log
    assert "setup outside every phase: before " in log
    assert "derived structures: " in log
    if name == "lcc_x4":
        assert got["derived_build_s"] > 1.0 and "derived.lcc_adjacency" in log
        assert got["hbm_derived_bytes"] > 2.7e8


def test_every_metric_file_names_the_phases_it_reads():
    vocabulary = ("load_graph", "load.place", "derived.", "runner.compile")
    for m in METRICS:
        spec = metric_spec(m)
        assert spec["reader"] == "setup_phase" and "quantity" in spec
        assert any(v in spec["what"] for v in vocabulary), m
        entry = next(e for e in BENCH["per_layer"] if e["name"] == m)
        assert "workloads" not in entry  # every cell reports set-up
        assert entry["moves"] == ("hbm_peak_bytes" if m.startswith("hbm_")
                                  else "setup_s")


def test_a_ledger_without_bytes_leaves_the_hbm_metrics_out():
    rec = recorded("lcc_x4")
    for r in rec["setup"]["records"]:
        r.pop("bytes_in_use", None)
    got = read_all(run_of(rec))
    assert got["hbm_graph_bytes"] is None and got["hbm_derived_bytes"] is None
    assert all(got[m] is not None for m in METRICS if not m.startswith("hbm_"))


def test_a_cell_that_places_nothing_derived_reads_zero_not_absent():
    rec = recorded("pagerank")
    got = read_all(run_of(rec))
    assert got["hbm_derived_bytes"] == 0 and got["derived_build_s"] == 0
    assert got["hbm_graph_bytes"] > 1e9


def test_a_program_without_the_ledger_reports_nothing():
    rec = recorded("pagerank")
    run = run_of(rec, setup_ledger={})  # no `records`: not this namespace
    assert all(v is None for v in read_all(run).values())


def test_phases_opened_in_the_window_do_not_count_and_are_logged():
    rec = recorded("pagerank")
    late = {"name": "derived.boundary_split", "parent": None, "args": {},
            "t0_ns": int((rec["t_start"] + rec["readings"]["setup_s"] + 1) * 1e9),
            "dur_ns": 2_000_000_000}
    clean = read_all(run_of(rec))
    rec["setup"]["records"].append(late)
    run = run_of(rec)
    assert read_all(run) == clean
    assert ", 1 after (['derived.boundary_split'])" in "\n".join(run.lines)


def test_without_t_start_the_share_is_over_setup_s():
    rec = recorded("pagerank")
    run = run_of(rec, t_start=None)
    share = setup_phase.read(run, metric_spec("setup_spanned_share"))
    top = sum(r["dur_ns"] for r in rec["setup"]["records"] if r["parent"] is None)
    assert share == pytest.approx(100 * top / 1e9 / rec["readings"]["setup_s"])
    assert "setup_s (no T_START in __main__)" in "\n".join(run.lines)
