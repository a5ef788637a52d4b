"""The road-like surrogate: its generator, its configuration, the readers of
the round record's metrics, and the cell rehearsed.

    python -m pytest benchmarks/tests

`tiny_road_v5e_scoped.xplane.pb` is one traced query of the cell
`road-like.bfs-key1` at `rehearse_scale` on the v5e (PR 39, chip run).
tests/test_benchmark_road.py runs the same cases in tier-1;
tests/test_road_bfs.py holds the program to the reference on this graph.
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
from benchmarks.graphs import road_like  # noqa: E402
from benchmarks.graphs.csr import degrees, symmetric_csr  # noqa: E402
from benchmarks.layer_metrics import (  # noqa: E402
    pull_roofline, round_device_us, round_record, scope_per_round, scope_us_per_round,
    scoped_share)
from benchmarks.references import bfs as bfs_reference  # noqa: E402

CELL = "road-like.bfs-key1"
TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")
CONFIG = json.load(open(os.path.join(ROOT, "benchmarks", "configs", "road-like.json")))
GEN = CONFIG["generator"]
SMALL = 11  # 2,048 ids: a hundredth of a second a draw
NEW_METRICS = ["round_device_us", "bfs_update_us_round", "bfs_frontier_max",
               "bfs_live_row_share"]


def metric_spec(name):
    return json.load(open(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".json")))


def drawn_key(n, src, dst):
    """The one key `Dataset.key_pool(1)` draws: uniform over the vertices with
    an edge, from the configuration's seed."""
    return int(np.random.default_rng(int(GEN["generator_seed"])).choice(
        np.flatnonzero(degrees(n, src, dst) > 0), size=1, replace=False)[0])


def levels_from(n, edges, key):
    """The plain reference's hop depths from `key` on the drawn graph."""
    minw, _ = symmetric_csr(n, *edges)
    return bfs_reference.reference(types.SimpleNamespace(minw=minw), {"source": key})


# ---- the generator -------------------------------------------------------------


@pytest.mark.parametrize("scale", [10, 11, 12, 13])
def test_the_surrogate_is_of_the_kind(scale):
    from scipy.sparse.csgraph import connected_components

    n = 1 << scale
    src, dst, w = road_like.edges(GEN, scale)
    # undirected and simple: no self-loop, each pair once
    assert (src < dst).all() and 0 <= src.min() and dst.max() < n
    assert len(np.unique(src.astype(np.int64) << scale | dst)) == len(src)
    degree = degrees(n, src, dst)
    assert degree.min() >= 1 and degree.max() <= 8
    assert abs(2 * len(src) / n - GEN["mean_degree"]) <= 0.05
    shares = np.bincount(degree, minlength=5) / n
    assert shares[2] + shares[3] > 0.6 and shares[2] > shares[1] and shares[3] > shares[4]
    minw, _ = symmetric_csr(n, src, dst, w)
    assert connected_components(minw, directed=False)[0] == 1
    lo, hi = GEN["weights"]
    assert w.dtype == np.dtype(GEN["weight_dtype"]) and lo <= w.min() and w.max() <= hi
    # a hop eccentricity of the order of sqrt(vertices), from any vertex
    depth = levels_from(n, (src, dst, w), int(src[0]))
    assert depth.min() >= 0 and n ** 0.5 <= depth.max() <= 3 * n ** 0.5


def test_the_configuration_states_what_the_generator_draws():
    scale = int(CONFIG["scale"])
    n = 1 << scale
    src, dst, w = road_like.edges(GEN, scale)
    assert CONFIG["vertices"] == n
    assert CONFIG["edges"] == len(src) and CONFIG["pull_entries"] == 2 * len(src)
    found = CONFIG["found"]
    degree = degrees(n, src, dst)
    assert found["mean_degree"] == pytest.approx(2 * len(src) / n, abs=1e-4)
    assert abs(found["mean_degree"] - 2.4) <= 0.05 and found["largest_degree"] == degree.max() <= 8
    assert found["components"] == 1
    assert found["degree_shares"] == pytest.approx(
        (np.bincount(degree, minlength=5) / n)[1:5].tolist(), abs=1e-3)
    # the cell's key is the one the harness draws, and its levels are the reference's
    key = drawn_key(n, src, dst)
    depth = levels_from(n, (src, dst, w), key)
    assert found["search_key"] == key and found["eccentricity"] == depth.max()
    assert found["rounds"] == depth.max() + 1 and (depth >= 0).all()
    assert found["widest_level"] == np.bincount(depth).max()
    assert n ** 0.5 <= found["eccentricity"] <= 3 * n ** 0.5
    assert CONFIG["reduced"].keys() == {"scale"}
    assert set(GEN) - {"name"} <= set(CONFIG["assumed"]), "a parameter nobody owned up to"
    assert {"source_vertices", "source_edges"} <= set(CONFIG["assumed"])


def test_files_hold_the_edges_and_every_id(tmp_path):
    efile, vfile = str(tmp_path / "g.e"), str(tmp_path / "g.v")
    info = road_like.write_files(GEN, SMALL, efile, vfile)
    src, dst, w = road_like.edges(GEN, SMALL)
    assert info == {"vertices": 1 << SMALL, "edges": len(src),
                    "pull_entries": 2 * len(src), "efile_bytes": os.path.getsize(efile)}
    rows = np.loadtxt(efile, dtype=np.int64)
    assert (rows[:, 0] == src).all() and (rows[:, 1] == dst).all() and (rows[:, 2] == w).all()
    assert open(vfile).read().split() == [str(i) for i in range(1 << SMALL)]


def test_the_graph_belongs_to_the_seed():
    a, b = road_like.edges(GEN, SMALL), road_like.edges(GEN, SMALL)
    assert all((x == y).all() for x, y in zip(a, b))
    other = road_like.edges(dict(GEN, generator_seed=GEN["generator_seed"] + 1), SMALL)
    assert len(other[0]) != len(a[0]) or (other[0] != a[0]).any()


@pytest.mark.parametrize("key", sorted(set(GEN) - {"name"}))
def test_every_parameter_is_read_from_the_block(key):
    with pytest.raises(KeyError, match=key):
        road_like.edges({k: v for k, v in GEN.items() if k != key}, SMALL)


@pytest.mark.parametrize("key,value", [
    ("mean_degree", 3.0), ("weights", [3, 3]), ("weight_dtype", "uint16")])
def test_a_changed_parameter_changes_the_draw(key, value):
    base = road_like.edges(GEN, SMALL)
    got = road_like.edges(dict(GEN, **{key: value}), SMALL)
    assert any(len(x) != len(y) or x.dtype != y.dtype or (x != y).any()
               for x, y in zip(base, got))


def test_ids_carry_no_position():
    """Neighbours on the lattice are `1` or `cols` apart by position; by id
    they are as far apart as two ids drawn at random."""
    src, dst, _ = road_like.edges(GEN, 14)
    gap = np.abs(dst.astype(np.int64) - src)
    assert np.median(gap) > (1 << 14) / 8 and (gap <= 128).mean() < 0.05


# ---- the readers, on a stub ----------------------------------------------------


def stub_run(vertices=1000, traced_rounds=40, busy_s=0.2):
    logged = []
    run = types.SimpleNamespace(
        log=logged.append, logged=logged, dataset_info={"vertices": vertices},
        readings={"traced_rounds": traced_rounds}, trace={"busy_s": busy_s},
        scopes={"scope_s": {"grape.app.update": 0.002, "grape.pull.fold": 0.1}})
    return run


def set_stats(monkeypatch, **stats):
    from libgrape_lite_tpu.worker.worker import ROUND_STATS

    for k, v in stats.items():
        monkeypatch.setitem(ROUND_STATS, k, v)


def test_readers_read_the_record(monkeypatch):
    set_stats(monkeypatch, app="BFS", rounds=40, active_max=57, active_sum=999,
              active_bits=[1] + [0] * 32)
    run = stub_run()
    assert round_record.read(run, metric_spec("bfs_frontier_max")) == 57
    # 999 live rows among 40 rounds x 1,000 rows folded
    assert round_record.read(run, metric_spec("bfs_live_row_share")) == pytest.approx(2.4975)
    assert len(run.logged) == 1 and "ROUND_STATS" in run.logged[0] and "'rounds': 40" in run.logged[0]
    # 0.2 s busy over 40 rounds; 2 ms under the update's scope over 40 rounds
    assert round_device_us.read(run, metric_spec("round_device_us")) == pytest.approx(5000.0)
    assert scope_us_per_round.read(run, metric_spec("bfs_update_us_round")) == pytest.approx(50.0)
    assert scope_us_per_round.read(run, metric_spec("bfs_update_us_round")) == pytest.approx(
        1e3 * scope_per_round.read(run, metric_spec("bfs_update_us_round")))


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_to_read(monkeypatch, name):
    """A program without the record (the parent's), a run without a trace, a
    trace without scopes: None, never a raise."""
    spec = metric_spec(name)
    reader = {"round_device_us": round_device_us, "bfs_update_us_round": scope_us_per_round}.get(
        name, round_record)
    if reader is round_record:
        set_stats(monkeypatch, rounds=0)  # no answer of the fused loop was extracted
        assert reader.read(stub_run(), spec) is None
        monkeypatch.delattr("libgrape_lite_tpu.worker.worker.ROUND_STATS")
        assert reader.read(stub_run(), spec) is None
        return
    run = stub_run(traced_rounds=0)
    assert reader.read(run, spec) is None
    run = stub_run()
    run.trace, run.scopes = None, None
    assert reader.read(run, spec) is None
    run = stub_run()
    run.scopes = {"scope_s": None}  # an executable cached without the scopes
    if name == "bfs_update_us_round":
        assert reader.read(run, spec) is None


def test_the_benchmark_lists_the_cell_where_the_issue_names_it():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, "road-like", "bfs-key1", 1)
    entry = bench["configs"][-1]
    assert entry["name"] == "road-like" and entry["source"] == CONFIG["source"]
    assert len(entry["source"]) <= 200 and len(cell["why"]) <= 200
    assert entry["reduced"] == ["scale"] and entry["file"].endswith("road-like.json")
    lists = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
             if CELL in m.get("workloads", [])}
    assert lists == {
        "proc_time_s", "dispatch_ms", "rounds", "device_idle_share", "scoped_share",
        "worker_state_ms", "pull_gather_ns_entry", "pull_fold_ns_entry", "pull_roofline",
        *NEW_METRICS}
    assert [m["name"] for m in bench["per_layer"][-4:]] == NEW_METRICS
    for name in NEW_METRICS:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        spec = metric_spec(name)
        assert m["workloads"] == [CELL] and m["moves"] == "proc_time_s"
        assert {k: spec[k] for k in ("layer", "unit", "better", "source", "moves")} == {
            k: m[k] for k in ("layer", "unit", "better", "source", "moves")}
    traffic = json.load(open(os.path.join(ROOT, "benchmarks", "traffic", "bfs-key1.json")))
    assert traffic["jobs"] == [{"app": "bfs", "params": {},
                                "keys": {"param": "source", "pool": 1}}]
    assert (traffic["driver"], traffic["callers"], traffic["think_s"]) == (
        "analytics_closed", 1, 0)


# ---- the readers, on the recorded trace ----------------------------------------


def recorded_run():
    """What `run.py` hands a reader, from the recorded trace and its numbers."""
    path = os.path.join(TESTDATA, "tiny_road_v5e_scoped.xplane.pb")
    if not os.path.exists(path):
        pytest.skip("tiny_road_v5e_scoped is not recorded")
    want = json.load(open(os.path.join(TESTDATA, "tiny_road_v5e_scoped.expected.json")))
    ie = types.SimpleNamespace(edge_src=np.empty((1, want["padded_entries"]), np.int32))
    run = types.SimpleNamespace(
        trace=rx.reduce(path, n_devices=1), scopes=rs.reduce(path), chips=1,
        log=lambda msg: None, readings={"traced_rounds": want["traced_rounds"]},
        traffic=json.load(open(os.path.join(ROOT, "benchmarks", "traffic", "bfs-key1.json"))),
        frag=types.SimpleNamespace(dev=types.SimpleNamespace(ie=ie)),
        dataset_info={"pull_entries": want["pull_entries"], "vertices": want["vertices"]},
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")],
        peaks=json.load(open(os.path.join(ROOT, "benchmarks", "peaks.json"))))
    return run, want


READERS = {"round_device_us": round_device_us, "bfs_update_us_round": scope_us_per_round,
           "pull_gather_ns_entry": scope_per_round, "pull_fold_ns_entry": scope_per_round,
           "pull_roofline": pull_roofline, "scoped_share": scoped_share}


@pytest.mark.parametrize("name", sorted(READERS))
def test_metric_on_the_recorded_trace(name):
    run, want = recorded_run()
    assert READERS[name].read(run, metric_spec(name)) == pytest.approx(
        want["metrics"][name], rel=1e-9)


def test_the_recorded_trace_names_the_round():
    run, want = recorded_run()
    assert run.trace["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    scope_s = run.scopes["scope_s"]
    assert scope_s == pytest.approx(want["scope_s"], rel=1e-6)
    # the vote's record is scalar arithmetic, as the loop's condition is: the
    # `while`'s own time, no device operation, so a trace shows neither name
    assert not {"grape.worker.record", "grape.worker.terminate"} & set(scope_s)
    assert run.scopes["scoped_share"] >= want["named_share_at_least"]
    assert 0 < want["metrics"]["pull_roofline"] < 105


# ---- the cell, rehearsed -------------------------------------------------------


def test_the_cell_rehearses():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL,
         "--seed", "3600000007", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 2
    assert last["rehearsal"] is True and last["device"]["platform"] == "rehearsal"
    # the rehearsal's graph, 1,024 ids: the key's levels by the plain reference
    edges = road_like.edges(GEN, int(CONFIG["rehearse_scale"]))
    n = 1 << int(CONFIG["rehearse_scale"])
    key = drawn_key(n, *edges[:2])
    depth = levels_from(n, edges, key)
    metrics = last["metrics"]
    assert metrics["rounds"]["value"] == depth.max() + 1
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["bfs_frontier_max"]["value"] == np.bincount(depth).max()
    assert metrics["bfs_live_row_share"]["value"] == pytest.approx(
        100.0 * (n - 1) / ((depth.max() + 1) * n))
    assert metrics["round_device_us"]["value"] > 0
    stats = next(l for l in lines if "ROUND_STATS" in l)
    assert "'app': 'BFS'" in stats and f"'active_sum': {n - 1}" in stats
