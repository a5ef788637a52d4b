"""The road deployment's weighted kernel: the configuration `road-like-sssp`,
the readers of the metrics its cell brings, and the cell rehearsed.

    python -m pytest benchmarks/tests

tests/test_benchmark_road_sssp.py runs the same cases in tier-1;
tests/test_sssp_frontier.py holds the program to the reference and to the
plain relaxations of tests/sssp_oracles.py on this graph.
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

from benchmarks.graphs import road_like  # noqa: E402
from benchmarks.graphs.csr import degrees, symmetric_csr  # noqa: E402
from benchmarks.layer_metrics import (  # noqa: E402
    round_count, round_record, scope_us_per_round, sssp_query_roofline)
from benchmarks.references import sssp as sssp_reference  # noqa: E402
from tests.sssp_oracles import bellman_ford, near_far  # noqa: E402

CELL, BFS_CELL = "road-like-sssp.sssp-key1", "road-like.bfs-key1"


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


CONFIG = load("benchmarks", "configs", "road-like-sssp.json")
ROAD = load("benchmarks", "configs", "road-like.json")
GEN = CONFIG["generator"]
NEW_METRICS = ["sssp_frontier_round_share", "sssp_bucket_advances", "sssp_pushes_per_vertex",
               "sssp_frontier_max", "frontier_compact_us_round", "frontier_advance_us_round",
               "sssp_query_roofline"]
READERS = {"sssp_frontier_round_share": round_count, "sssp_bucket_advances": round_count,
           "sssp_pushes_per_vertex": round_count, "sssp_frontier_max": round_record,
           "frontier_compact_us_round": scope_us_per_round,
           "frontier_advance_us_round": scope_us_per_round,
           "sssp_query_roofline": sssp_query_roofline}


def metric_spec(name):
    return load("benchmarks", "layer_metrics", name + ".json")


def drawn(scale):
    """The graph at `scale`, the one key `Dataset.key_pool(1)` draws on it, and
    the references' matrix."""
    n = 1 << scale
    src, dst, w = road_like.edges(GEN, scale)
    key = int(np.random.default_rng(int(GEN["generator_seed"])).choice(
        np.flatnonzero(degrees(n, src, dst) > 0), size=1, replace=False)[0])
    return n, key, symmetric_csr(n, src, dst, w)[0].tocsr()


def csr_of(minw):
    return minw.indptr.astype(np.int64), minw.indices.astype(np.int64), minw.data.astype(np.float32)


# ---- the configuration -----------------------------------------------------


def test_the_graph_is_the_road_cells():
    for key in ("generator", "load_graph_spec", "scale", "rehearse_scale", "fnum", "chips",
                "vertices", "edges", "pull_entries", "source_vertices", "source_edges"):
        assert CONFIG[key] == ROAD[key], key
    assert CONFIG["reduced"].keys() == {"scale"}
    assert set(GEN) - {"name"} <= set(CONFIG["assumed"]), "a parameter nobody owned up to"
    assert "read now" in CONFIG["assumed"]["weights"]
    rule = CONFIG["guarantees"]["sssp"]
    assert rule["rule"] == "exact" and "eps" not in rule and "references/sssp.py" in rule["against"]
    assert set(CONFIG["guarantees"]) == {"statement", "sssp"}


def test_the_configuration_states_what_the_key_finds():
    """At the configuration's scale, against the generator and SciPy's
    Dijkstra: the key, its weighted eccentricity, the hops of its longest
    shortest path (so the dense loop's rounds), and that float32 holds every
    distance."""
    from scipy.sparse.csgraph import dijkstra

    n, key, minw = drawn(int(CONFIG["scale"]))
    found = CONFIG["found"]
    assert found["search_key"] == key == ROAD["found"]["search_key"]
    want = sssp_reference.reference(types.SimpleNamespace(minw=minw), {"source": key})
    assert np.isfinite(want).all() and found["weighted_eccentricity"] == want.max() < 1 << 24
    assert (want == np.round(want)).all() and (want.astype(np.float32) == want).all()
    assert found["largest_weight"] == minw.data.max() == GEN["weights"][1]
    # the hops of a lightest path, the fewest among equals: Dijkstra over
    # (weight, hops) pairs packed into one float64, exactly
    hops = dijkstra(minw * float(1 << 24) + minw.astype(bool), directed=True, indices=key)
    assert (hops // (1 << 24) == want).all()
    assert found["longest_shortest_path_hops"] == (hops % (1 << 24)).max()
    assert found["dense_rounds"] == found["longest_shortest_path_hops"] + 1
    near = found["near_far"]
    assert near["bucket"] == 16 * found["largest_weight"]
    # every push on the frontier arm; the steps and the last look are rounds too
    assert near["rounds"] == near["pushing_rounds"] + near["advances"] + 1
    assert near["pushes_a_vertex"] == pytest.approx(near["rows_pushed"] / n, abs=1e-4)
    hop = found["hop_synchronous"]
    assert hop["pushes_a_vertex"] == pytest.approx(hop["rows_pushed"] / n, abs=1e-3)


@pytest.mark.parametrize("scale,pushes,widest_rows", [
    (10, 1.8340, 55), (11, 1.4502, 92), (12, 2.2061, 202), (13, 4.4028, 407)])
def test_the_band_behind_the_wavefront_is_the_draws(scale, pushes, widest_rows):
    """What makes the deployment what it is, at sizes a test can afford: a
    hop-synchronous relaxation improves a vertex more than once, more often
    the larger the graph, and its list widens with the band; under a
    threshold a vertex pushes under twice and the list stays narrower.  (At
    2^20: 38.7 times and 48,036 rows against 1.60 and 2,011;
    found.hop_synchronous, found.near_far.)"""
    n, key, minw = drawn(scale)
    indptr, nbr, w = csr_of(minw)
    want = sssp_reference.reference(types.SimpleNamespace(minw=minw), {"source": key})
    dist, rounds, pushed, widest = bellman_ford(indptr, nbr, w, key)
    assert (dist == want).all()
    assert pushed / n == pytest.approx(pushes, abs=1e-4) and widest[0] == widest_rows
    start = np.full(n, np.inf, np.float32)
    start[key] = 0
    near, told = near_far(indptr, nbr, w, start, 2048, 8192, 16.0 * w.max())
    assert (near == want).all()
    assert told["frontier_rounds"] == told["rounds"] - told["advances"] - 1  # no push fell back
    assert told["pushed_sum"] / n < 2 and told["pushed_sum"] <= pushed
    assert max(told["active"]) <= widest[0] < 2048


# ---- the readers, on a stub ------------------------------------------------


def stub_run(vertices=1000, traced_rounds=40, busy_s=0.2):
    logged = []
    return types.SimpleNamespace(
        log=logged.append, logged=logged, chips=1,
        dataset_info={"vertices": vertices, "pull_entries": 2400},
        readings={"traced_rounds": traced_rounds}, trace={"busy_s": busy_s},
        traffic=load("benchmarks", "traffic", "sssp-key1.json"),
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")],
        peaks=load("benchmarks", "peaks.json"),
        scopes={"scope_s": {"grape.frontier.compact": 0.002, "grape.frontier.advance": 0.0004,
                            "grape.pull.fold": 0.1}})


def set_stats(monkeypatch, **stats):
    from libgrape_lite_tpu.worker.worker import ROUND_STATS

    for k, v in stats.items():
        monkeypatch.setitem(ROUND_STATS, k, v)


def test_readers_read_the_record(monkeypatch):
    set_stats(monkeypatch, app="SSSP", rounds=40, active_max=57, active_sum=1599,
              frontier_rounds=38, advances=3, pushed_sum=1600, active_bits=[1] + [0] * 32)
    run = stub_run()
    read = {name: READERS[name].read(run, metric_spec(name)) for name in NEW_METRICS}
    assert read["sssp_frontier_round_share"] == pytest.approx(95.0)
    assert read["sssp_bucket_advances"] == 3 and read["sssp_frontier_max"] == 57
    assert read["sssp_pushes_per_vertex"] == pytest.approx(1.6)
    # 2 ms and 0.4 ms under the scopes over 40 traced rounds
    assert read["frontier_compact_us_round"] == pytest.approx(50.0)
    assert read["frontier_advance_us_round"] == pytest.approx(10.0)
    # 12 B x 2,400 entries + 12 B x 1,000 vertices at 819 GB/s, over 0.2 s busy
    assert read["sssp_query_roofline"] == pytest.approx(100 * (40800 / 819e9) / 0.2)
    assert 0 < read["sssp_query_roofline"] < 100
    assert sum("ROUND_STATS" in line for line in run.logged) == 1


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_to_read(monkeypatch, name):
    """A program whose record lacks the count (the parent's), one without the
    record, a run without a trace, a trace without the scope: None or 0, never
    a raise."""
    from libgrape_lite_tpu.worker import worker

    spec, reader = metric_spec(name), READERS[name]
    if reader in (round_count, round_record):
        set_stats(monkeypatch, rounds=40, active_max=5, frontier_rounds=0)
        for gone in ("advances", "pushed_sum"):
            monkeypatch.delitem(worker.ROUND_STATS, gone, raising=False)
        want = {"sssp_frontier_round_share": 0.0, "sssp_frontier_max": 5}.get(name)
        assert reader.read(stub_run(), spec) == want  # the parent's record
        set_stats(monkeypatch, rounds=0)  # no answer of the fused loop was extracted
        assert reader.read(stub_run(), spec) is None
        monkeypatch.delattr(worker, "ROUND_STATS")
        assert reader.read(stub_run(), spec) is None
        return
    run = stub_run()
    run.trace, run.scopes = None, None
    assert reader.read(run, spec) is None
    if reader is scope_us_per_round:
        assert reader.read(stub_run(traced_rounds=0), spec) is None
        run = stub_run()
        run.scopes = {"scope_s": None}  # an executable cached without the scopes
        assert reader.read(run, spec) is None
        run.scopes = {"scope_s": {"grape.pull.fold": 0.1}}  # the parent: no such scope
        assert reader.read(run, spec) == 0.0
    else:
        run = stub_run()
        run.devices[0].device_kind = "a chip without a table of peaks"
        assert reader.read(run, spec) is None
        assert reader.read(stub_run(busy_s=0), spec) is None


def test_the_benchmark_lists_the_cell_where_the_issue_names_it():
    # by name, never by place: later cells and metrics come after these
    bench = load("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("road-like-sssp", "sssp-key1", 1)
    entry = next(c for c in bench["configs"] if c["name"] == "road-like-sssp")
    assert entry["name"] == CONFIG["name"]
    assert entry["source"] == CONFIG["source"] != ROAD["source"]
    assert len(entry["source"]) <= 200 and len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == ["scale"] and entry["file"].endswith("road-like-sssp.json")
    assert BFS_CELL in cell["why"] and "serve-g500-s18.keys8" in cell["why"]
    lists = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
             if CELL in m.get("workloads", [])}
    assert lists == {
        "proc_time_s", "dispatch_ms", "worker_state_ms", "rounds", "round_device_us",
        "scoped_share", "device_idle_share", *NEW_METRICS}
    # wrong for a frontier round (PERF.md section 7): not here
    assert not lists & {"pull_roofline", "pull_gather_ns_entry", "pull_fold_ns_entry",
                        "bfs_update_us_round", "bfs_frontier_max", "bfs_live_row_share"}
    names = [m["name"] for m in bench["per_layer"]]
    assert names[names.index(NEW_METRICS[0]):][:7] == NEW_METRICS
    for name in NEW_METRICS:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        spec = metric_spec(name)
        both = name == "frontier_compact_us_round"  # the one ISSUE 40 asked for: BFS's too
        assert m["workloads"] == ([BFS_CELL, CELL] if both else [CELL])
        assert {k: spec[k] for k in ("layer", "unit", "better", "source", "moves")} == {
            k: m[k] for k in ("layer", "unit", "better", "source", "moves")}
        assert m["moves"] == "proc_time_s" and set(m) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
    traffic = load("benchmarks", "traffic", "sssp-key1.json")
    assert traffic["jobs"] == [{"app": "sssp", "params": {},
                                "keys": {"param": "source", "pool": 1}}]
    assert (traffic["driver"], traffic["callers"], traffic["think_s"]) == (
        "analytics_closed", 1, 0)
    # the nine cells and eight configurations the benchmark had come first, as they were
    assert [w["name"] for w in bench["workloads"]].index(CELL) == 9
    assert [c["name"] for c in bench["configs"]].index("road-like-sssp") == 8


# ---- the cell, rehearsed ---------------------------------------------------


def test_the_cell_rehearses():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL,
         "--seed", "3600000043", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 2
    assert last["rehearsal"] is True and last["device"]["platform"] == "rehearsal"
    # the rehearsal's graph, 1,024 ids, lies under the dense floor: the dense
    # loop, which is the hop-synchronous relaxation
    n, key, minw = drawn(int(CONFIG["rehearse_scale"]))
    _, rounds, pushed, widest = bellman_ford(*csr_of(minw), key)
    metrics = last["metrics"]
    assert metrics["rounds"]["value"] == rounds
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["sssp_frontier_max"]["value"] == widest[0]
    assert metrics["sssp_frontier_round_share"]["value"] == 0
    assert metrics["sssp_bucket_advances"]["value"] == 0
    assert metrics["round_device_us"]["value"] > 0
    assert not {"pull_roofline", "bfs_frontier_max"} & set(metrics)
    stats = next(line for line in lines if "ROUND_STATS" in line)
    assert "'app': 'SSSP'" in stats and f"'active_sum': {pushed - 1}" in stats
