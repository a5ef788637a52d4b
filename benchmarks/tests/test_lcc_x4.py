"""The ring reader on a trace recorded on the four-chip v5e, its bytes against
a hand count, the LCC readers across four devices, and the cell's rehearsal.

    python -m pytest benchmarks/tests

`tiny_lcc_x4_v5e_scoped.xplane.pb` is one traced query of the cell
`g500-lcc-x4.lcc` at `rehearse_scale` on the four-chip v5e (PR 32, chip
run); its `.expected.json` holds what the program counted for it
(`LCC_STATS`, the fragment's `vp`) and what the reduction gives.
tests/test_benchmark_lcc_x4.py runs the same cases in tier-1;
tests/test_lcc_kronecker.py holds the program to the reference on four
fragments and pins the ring's scope and counters.
"""

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import reduce_scopes as rs  # noqa: E402
from benchmarks import reduce_xplane as rx  # noqa: E402
from benchmarks.graphs import kronecker_simple  # noqa: E402
from benchmarks.layer_metrics import (  # noqa: E402
    collective_exposed_share, lcc_list_bytes, lcc_ring, lcc_roofline, lcc_scope)

TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")
CELL = "g500-lcc-x4.lcc"
CONFIG = json.load(open(os.path.join(ROOT, "benchmarks", "configs", "g500-lcc-x4.json")))
RING_METRICS = ["lcc_ring_ms", "lcc_ring_roofline"]
SCOPE_METRICS = ["lcc_intersect_ns_lane", "lcc_rows_ns_lane", "lcc_credit_ns_lane",
                 "lcc_orient_ms"]
RING_KEYS = ["ring_passes", "ring_bytes", "shard_lanes", "shard_kept_max", "shard_kept_min"]


def metric_spec(name):
    return json.load(open(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".json")))


def recorded_run(monkeypatch, name="tiny_lcc_x4_v5e_scoped", chips=4, stats=None):
    """What `run.py` hands a reader, from the recorded trace and its numbers;
    the program's counter reads what it read in the recorded run, or `stats`."""
    path = os.path.join(TESTDATA, name + ".xplane.pb")
    expected = os.path.join(TESTDATA, "tiny_lcc_x4_v5e_scoped.expected.json")
    if not (os.path.exists(path) and os.path.exists(expected)):
        pytest.skip(f"{name} is not recorded")
    want = json.load(open(expected))
    from libgrape_lite_tpu.models.lcc_beta import LCC_STATS

    for key, value in (want["lcc_stats"] if stats is None else stats).items():
        monkeypatch.setitem(LCC_STATS, key, value)
    scale = int(CONFIG["rehearse_scale"])
    dataset = types.SimpleNamespace(
        n=1 << scale, edges=kronecker_simple.edges(CONFIG["generator"], scale))
    run = types.SimpleNamespace(
        trace=rx.reduce(path, n_devices=chips), scopes=rs.reduce(path), chips=chips,
        traffic=json.load(open(os.path.join(ROOT, "benchmarks", "traffic", "lcc.json"))),
        dataset=dataset, frag=types.SimpleNamespace(vp=want["vp"]), log=lambda msg: None,
        xplane=path,
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")],
        peaks=json.load(open(os.path.join(ROOT, "benchmarks", "peaks.json"))))
    return run, want


# ---- the bytes, by hand ----------------------------------------------------


def test_ring_bytes_by_hand():
    # a device's block of 5 rows of 3 ids is 60 B; it leaves once a pass
    assert lcc_ring.ring_bytes(1, 5, 3) == 60
    assert lcc_ring.ring_bytes(4, 5, 3) == 240
    assert lcc_ring.ring_bytes(0, 5, 3) == 0  # one fragment: no ring
    # the cell's rehearsal: 1,024 ids over four fragments, lists of up to 43
    assert lcc_ring.ring_bytes(4, 256, 43) == 176128


def test_the_programs_count_is_the_benchmarks(monkeypatch):
    run, want = recorded_run(monkeypatch)
    stats = want["lcc_stats"]
    assert stats["ring_passes"] == run.chips == CONFIG["fnum"]
    assert lcc_ring.sent_bytes(run) == stats["ring_bytes"] == stats["ell_bytes"]
    assert stats["query_lanes"] == stats["ring_passes"] * stats["shard_lanes"]
    assert stats["shard_kept_max"] >= stats["shard_kept_min"] > 0


def test_a_count_that_disagrees_is_an_error(monkeypatch):
    run, want = recorded_run(monkeypatch)
    stats = dict(want["lcc_stats"], ring_bytes=want["lcc_stats"]["ring_bytes"] - 4)
    run, _ = recorded_run(monkeypatch, stats=stats)
    with pytest.raises(RuntimeError, match="ring_bytes"):
        lcc_ring.read(run, metric_spec("lcc_ring_roofline"))
    assert lcc_ring.read(run, metric_spec("lcc_ring_ms")) > 0  # reads no counter


# ---- the readers, on the recorded trace ------------------------------------


@pytest.mark.parametrize("name", RING_METRICS)
def test_ring_metric_on_the_recorded_trace(monkeypatch, name):
    run, want = recorded_run(monkeypatch)
    got = lcc_ring.read(run, metric_spec(name))
    assert got == pytest.approx(want["metrics"][name], rel=1e-9) and got > 0


def test_ring_roofline_is_bytes_over_the_time_in_flight(monkeypatch):
    run, want = recorded_run(monkeypatch)
    ms = lcc_ring.read(run, metric_spec("lcc_ring_ms"))
    share = lcc_ring.read(run, metric_spec("lcc_ring_roofline"))
    assert ms == pytest.approx(1e3 * want["in_flight_s"], rel=1e-9)
    assert share == pytest.approx(
        100 * want["lcc_stats"]["ring_bytes"] / 200e9 / (ms * 1e-3), rel=1e-12)
    assert 0 < share <= 100
    # the journey is longer than its two ends and no longer than the query
    assert run.scopes["scope_s"]["grape.lcc.ring"] < ms * 1e-3 <= run.trace["window_s"]
    exposed = collective_exposed_share.read(run, None)
    assert exposed == pytest.approx(want["metrics"]["collective_exposed_share"], rel=1e-9)
    assert 0 <= exposed <= 100


def test_in_flight_is_read_where_the_trace_records_it(monkeypatch):
    """The chip's trace has the start-to-done spans on the first device's plane
    only: the mean is over that plane, not over all four."""
    run, want = recorded_run(monkeypatch)
    shown = lcc_ring.async_in_flight(run.xplane)
    assert list(shown) == want["async_devices"] == ["/device:TPU:0"]
    assert lcc_ring.in_flight_s(run) == shown["/device:TPU:0"] == want["in_flight_s"]
    assert len(run.trace["devices"]) == 4
    # the mean over all four planes reads about a quarter of it
    assert run.trace["collective_s"] == pytest.approx(want["collective_s"], rel=1e-9)
    assert 3 < want["in_flight_s"] / run.trace["collective_s"] <= 4


def test_collectives_that_are_not_asynchronous_read_collective_s(monkeypatch):
    # PageRank's recorded four-chip trace: an all-gather a round, no such span
    run, _ = recorded_run(monkeypatch, name="tiny_pagerank_x4_v5e_scoped")
    assert lcc_ring.async_in_flight(run.xplane) == {}
    assert lcc_ring.in_flight_s(run) == run.trace["collective_s"] > 0


@pytest.mark.parametrize("name", SCOPE_METRICS)
def test_scope_metric_across_four_devices(monkeypatch, name):
    run, want = recorded_run(monkeypatch)
    got = lcc_scope.read(run, metric_spec(name))
    assert got == pytest.approx(want["metrics"][name], rel=1e-9) and got > 0


def test_pad_ratio_and_roofline_count_all_four_devices(monkeypatch):
    run, want = recorded_run(monkeypatch)
    assert lcc_list_bytes.for_run(run) == want["graph"]
    ratio = lcc_list_bytes.read(run, metric_spec("lcc_lane_pad_ratio"))
    lanes = want["lcc_stats"]["query_lanes"]
    assert ratio == pytest.approx(4 * lanes / want["graph"]["wedges"], rel=1e-12)
    assert ratio == pytest.approx(want["metrics"]["lcc_lane_pad_ratio"], rel=1e-9)
    share = lcc_roofline.read(run, metric_spec("lcc_roofline"))
    assert share == pytest.approx(want["metrics"]["lcc_roofline"], rel=1e-9)
    assert 0 < share <= 100


def test_the_scopes_account_for_the_busy_time(monkeypatch):
    run, want = recorded_run(monkeypatch)
    scope_s = run.scopes["scope_s"]
    assert scope_s["grape.lcc.ring"] > 0
    for scope, seconds in want["scope_s"].items():
        assert scope_s[scope] == pytest.approx(seconds, rel=1e-9)
    named = sum(scope_s[s] for s in ("grape.lcc.rows", "grape.lcc.intersect",
                                     "grape.lcc.credit", "grape.lcc.ring"))
    assert named <= run.trace["busy_s"] * 1.001
    assert named >= run.trace["busy_s"] * want["named_share_at_least"]
    assert run.scopes["scoped_share"] == pytest.approx(want["scoped_share"], rel=1e-9)


@pytest.mark.parametrize("name", RING_METRICS)
def test_one_fragment_has_no_ring_to_read(monkeypatch, name):
    # the one-chip cell's recorded trace: not one collective in it
    one = json.load(open(os.path.join(TESTDATA, "tiny_lcc_v5e_scoped.expected.json")))
    stats = dict(one["lcc_stats"], **{key: 0 for key in RING_KEYS})
    run, _ = recorded_run(monkeypatch, name="tiny_lcc_v5e_scoped", chips=1, stats=stats)
    assert run.trace["collective_s"] == 0
    assert lcc_ring.read(run, metric_spec(name)) is None


def test_a_program_that_counts_no_ring_reads_no_roofline(monkeypatch):
    """The parent's program, with these files laid over it: `LCC_STATS` has
    no ring keys, and without `LCC_STATS` there is no LCC at all."""
    run, want = recorded_run(monkeypatch)
    from libgrape_lite_tpu.models.lcc_beta import LCC_STATS

    for key in RING_KEYS:
        monkeypatch.delitem(LCC_STATS, key)
    assert lcc_ring.sent_bytes(run) is None
    assert lcc_ring.read(run, metric_spec("lcc_ring_roofline")) is None
    assert lcc_ring.read(run, metric_spec("lcc_ring_ms")) == pytest.approx(
        want["metrics"]["lcc_ring_ms"], rel=1e-9)  # the trace alone
    import libgrape_lite_tpu.models.lcc_beta as program

    monkeypatch.delattr(program, "LCC_STATS")
    assert lcc_ring.read(run, metric_spec("lcc_ring_roofline")) is None


# ---- the cell, rehearsed ---------------------------------------------------


def test_the_cell_rehearses_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL,
         "--seed", "3200000007", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 2
    assert last["rehearsal"] is True and last["device"]["platform"] == "rehearsal"
    assert last["device"]["count"] == 4
    assert last["metrics"]["rounds"]["value"] == 0  # PEval is the whole algorithm
    assert last["metrics"]["compiles_in_window"]["value"] == 0
    assert last["metrics"]["lcc_lane_pad_ratio"]["value"] > 4  # four walks of it
    assert "lcc_ring_roofline" not in last["metrics"]  # no peaks off the chip
    # one build for the whole run, the ring counted
    stats = next(l for l in lines if "LCC_STATS" in l)
    assert "'builds': 1," in stats and f"'cache_hits': {last['attempted']}," in stats
    assert "'ring_passes': 4," in stats and "fnum 4" in out.stdout
