"""The LCC metrics' readers on a trace recorded on the chip, the bytes and
wedges of `lcc_list_bytes` against a hand count, and the cell's rehearsal.

    python -m pytest benchmarks/tests

`tiny_lcc_v5e_scoped.xplane.pb` is one traced query of the cell
`g500-lcc.lcc` at `rehearse_scale` on the v5e (PR 30, chip run); its
`.expected.json` holds the lanes the program counted for it and what the
reduction gives.  tests/test_benchmark_lcc.py runs the same cases in tier-1;
tests/test_lcc_kronecker.py holds the reference to the dense definition and
the program to the reference.
"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import reduce_scopes as rs  # noqa: E402
from benchmarks import reduce_xplane as rx  # noqa: E402
from benchmarks.graphs import kronecker_simple  # noqa: E402
from benchmarks.layer_metrics import lcc_list_bytes, lcc_roofline, lcc_scope  # noqa: E402

TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")
CONFIG = json.load(open(os.path.join(ROOT, "benchmarks", "configs", "g500-lcc.json")))
LANE_METRICS = ["lcc_intersect_ns_lane", "lcc_rows_ns_lane", "lcc_credit_ns_lane"]


def metric_spec(name):
    return json.load(open(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".json")))


def recorded_run(monkeypatch, name="tiny_lcc_v5e_scoped", lanes=True):
    """What `run.py` hands a reader, from the recorded trace and its numbers;
    the program's counter reads what it read in the recorded run."""
    path = os.path.join(TESTDATA, name + ".xplane.pb")
    expected = os.path.join(TESTDATA, "tiny_lcc_v5e_scoped.expected.json")
    if not (os.path.exists(path) and os.path.exists(expected)):
        pytest.skip(f"{name} is not recorded")
    want = json.load(open(expected))
    from libgrape_lite_tpu.models.lcc_beta import LCC_STATS

    monkeypatch.setitem(LCC_STATS, "query_lanes", want["query_lanes"] if lanes else 0)
    scale = int(CONFIG["rehearse_scale"])
    dataset = types.SimpleNamespace(
        n=1 << scale, edges=kronecker_simple.edges(CONFIG["generator"], scale))
    run = types.SimpleNamespace(
        trace=rx.reduce(path, n_devices=1), scopes=rs.reduce(path), chips=1,
        traffic=json.load(open(os.path.join(ROOT, "benchmarks", "traffic", "lcc.json"))),
        dataset=dataset, log=lambda msg: None,
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")],
        peaks=json.load(open(os.path.join(ROOT, "benchmarks", "peaks.json"))))
    return run, want


# ---- counts from the graph alone -----------------------------------------


def test_list_bytes_and_wedges_by_hand():
    # a triangle 0-1-2, a tail 2-3, an isolated 4; 1-0 drawn twice, a loop at 4.
    # Degrees 2, 2, 3, 1, 0, so by (degree, id): 0 -> 1, 0 -> 2, 1 -> 2, 3 -> 2
    src = np.array([0, 0, 1, 2, 1, 4])
    dst = np.array([1, 2, 2, 3, 0, 4])
    v, u, out = lcc_list_bytes.oriented_lists(5, src, dst)
    assert sorted(zip(v.tolist(), u.tolist())) == [(0, 1), (0, 2), (1, 2), (3, 2)]
    assert out.tolist() == [2, 1, 0, 1, 0]
    # one lane per member of N+(v) per oriented edge (v, u): 2 + 2 + 1 + 1
    assert lcc_list_bytes.wedges(v, out) == 6
    # both lists per oriented edge: (2+1) + (2+0) + (1+0) + (1+0) ids of 4 B, 8 B a vertex
    assert lcc_list_bytes.lcc_list_bytes(5, v, u, out) == 4 * 7 + 8 * 5


def test_counts_on_the_rehearsal_graph(monkeypatch):
    run, want = recorded_run(monkeypatch)
    assert lcc_list_bytes.for_run(run) == want["graph"]
    assert lcc_list_bytes.for_run(run) is run.lcc_counts  # counted once a run


# ---- the readers, on the recorded trace ----------------------------------


@pytest.mark.parametrize("name", LANE_METRICS + ["lcc_orient_ms"])
def test_scope_metric_on_the_recorded_trace(monkeypatch, name):
    run, want = recorded_run(monkeypatch)
    got = lcc_scope.read(run, metric_spec(name))
    assert got == pytest.approx(want["metrics"][name], rel=1e-9) and got > 0


def test_the_scopes_account_for_the_busy_time(monkeypatch):
    run, want = recorded_run(monkeypatch)
    assert lcc_scope.traced_queries(run) == 1
    ns = sum(lcc_scope.read(run, metric_spec(m)) for m in LANE_METRICS)
    ms = lcc_scope.read(run, metric_spec("lcc_orient_ms"))
    named = ns * 1e-9 * want["query_lanes"] + ms * 1e-3
    assert named <= run.trace["busy_s"] * 1.001
    assert named >= run.trace["busy_s"] * want["named_share_at_least"]
    assert run.scopes["scoped_share"] == pytest.approx(want["scoped_share"], rel=1e-9)


def test_lane_pad_ratio_and_roofline_on_the_recorded_trace(monkeypatch):
    run, want = recorded_run(monkeypatch)
    ratio = lcc_list_bytes.read(run, metric_spec("lcc_lane_pad_ratio"))
    assert ratio == pytest.approx(want["query_lanes"] / want["graph"]["wedges"], rel=1e-12)
    assert ratio == pytest.approx(want["metrics"]["lcc_lane_pad_ratio"], rel=1e-9) and ratio > 1
    share = lcc_roofline.read(run, metric_spec("lcc_roofline"))
    assert share == pytest.approx(want["metrics"]["lcc_roofline"], rel=1e-9)
    assert 0 < share <= 100


@pytest.mark.parametrize("name", LANE_METRICS + ["lcc_lane_pad_ratio"])
def test_a_program_that_counts_no_lanes_reads_nothing(monkeypatch, name):
    run, _ = recorded_run(monkeypatch, lanes=False)
    mod = lcc_list_bytes if name == "lcc_lane_pad_ratio" else lcc_scope
    assert mod.read(run, metric_spec(name)) is None


@pytest.mark.parametrize("name", LANE_METRICS + ["lcc_orient_ms"])
def test_a_trace_without_the_scopes_reads_nothing(monkeypatch, name):
    # CDLP's recorded trace: scoped, and not one grape.lcc.* in it
    run, _ = recorded_run(monkeypatch, name="tiny_cdlp_v5e_scoped")
    assert lcc_scope.read(run, metric_spec(name)) is None


def test_a_program_without_lcc_stats_reads_nothing(monkeypatch):
    """The parent's program, with these files laid over it."""
    run, _ = recorded_run(monkeypatch)
    import libgrape_lite_tpu.models.lcc_beta as program

    monkeypatch.delattr(program, "LCC_STATS")
    assert lcc_scope.lcc_stats(run) is None
    for name in LANE_METRICS:
        assert lcc_scope.read(run, metric_spec(name)) is None
    assert lcc_list_bytes.read(run, metric_spec("lcc_lane_pad_ratio")) is None
    assert lcc_roofline.read(run, metric_spec("lcc_roofline")) is None
    assert lcc_scope.read(run, metric_spec("lcc_orient_ms")) > 0  # needs no counter


# ---- the cell, rehearsed ---------------------------------------------------


def test_the_cell_rehearses():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload",
         "g500-lcc.lcc", "--seed", "3000000007", "--seconds", "1", "--trace", "1",
         "--rehearse"], cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 2
    assert last["rehearsal"] is True and last["device"]["platform"] == "rehearsal"
    assert last["metrics"]["rounds"]["value"] == 0  # PEval is the whole algorithm
    assert last["metrics"]["compiles_in_window"]["value"] == 0
    assert last["metrics"]["lcc_lane_pad_ratio"]["value"] > 1
    # one build for the whole run: warm-up, traced pass and window found it resident
    stats = next(l for l in lines if "LCC_STATS" in l)
    assert "'builds': 1," in stats and f"'cache_hits': {last['attempted']}," in stats
