"""The Kronecker deployment's betweenness kernel: the configuration `g500-bc`,
the readers of the metrics its cell brings, and the cell rehearsed.

    python -m pytest benchmarks/tests

tests/test_benchmark_bc.py runs the same cases in tier-1; tests/test_bc_pull.py
holds the program to the reference, in float64 and in float32, and the
reference to plain loops.
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

from benchmarks.compare import mismatches  # noqa: E402
from benchmarks.graphs import kronecker_simple  # noqa: E402
from benchmarks.graphs.csr import degrees, symmetric_csr  # noqa: E402
from benchmarks.layer_metrics import bc_query_roofline, bc_scope_per_pull  # noqa: E402
from benchmarks.references import bc as bc_reference  # noqa: E402
from tests.bc_oracles import brandes_rounded  # noqa: E402

CELL, PAGERANK_CELL = "g500-bc.bc-key1", "g500-s21.pagerank"


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


CONFIG = load("benchmarks", "configs", "g500-bc.json")
LCC = load("benchmarks", "configs", "g500-lcc.json")
GEN = CONFIG["generator"]
RULE = CONFIG["guarantees"]["bc"]
NEW_METRICS = ["bc_levels", "bc_gather_ns_entry", "bc_fold_ns_entry", "bc_update_us_pull",
               "bc_query_roofline"]
READERS = {name: bc_scope_per_pull for name in NEW_METRICS[:4]}
READERS["bc_query_roofline"] = bc_query_roofline
# the lists ISSUE 50 names for the cell, beside the five it brings
LISTED = {"proc_time_s", "rounds", "dispatch_ms", "worker_state_ms", "device_idle_share",
          "scoped_share"}


def metric_spec(name):
    return load("benchmarks", "layer_metrics", name + ".json")


def drawn(scale):
    """The graph at `scale` as the references see it, and its one search key
    by `datasets.key_pool(1)`'s rule."""
    n = 1 << scale
    src, dst, w = kronecker_simple.edges(GEN, scale)
    minw, mult = symmetric_csr(n, src, dst, w)
    has_edge = np.flatnonzero(degrees(n, src, dst) > 0)
    key = int(np.random.default_rng(int(GEN["generator_seed"])).choice(
        has_edge, size=1, replace=False)[0])
    return types.SimpleNamespace(n=n, minw=minw, mult=mult, edges=len(src)), key


# ---- the configuration -----------------------------------------------------


def test_the_graph_is_the_lcc_cells_and_only_the_scale_is_cut():
    for key in ("generator", "load_graph_spec", "rehearse_scale", "fnum", "chips"):
        assert CONFIG[key] == LCC[key], key
    assert CONFIG["architecture"] is None and CONFIG["source_scale"] == 27
    assert CONFIG["scale"] in (19, 20, 21) and CONFIG["vertices"] == 1 << CONFIG["scale"]
    assert CONFIG["pull_entries"] == 2 * CONFIG["edges"]  # simple: no loop, no twin
    assert CONFIG["reduced"].keys() == {"scale"}
    for said in ("79cc001", "25 s", "peak_bytes_in_use"):  # the rule, and its sweep or why none
        assert said in CONFIG["reduced"]["scale"], said
    assert {"sources", "precision", "one root a query", "simple graph", "app",
            "root's own dependency"} <= set(CONFIG["assumed"])
    assert (RULE["rule"], RULE["eps"]) == ("eps", 1e-3)
    assert "references/bc.py" in RULE["against"] and "root's own included" in RULE["against"]
    assert set(CONFIG["guarantees"]) == {"statement", "bc"}
    assert "stricter" in RULE["against GAP's own check"]
    readings = RULE["the two readings"]
    # the program in float32, with room; the precision below, refused.  The
    # chip's own reading where a chip run was made, else said to be missing
    chip = readings["float32_on_the_chip"]
    ours = chip if chip["largest_relative_error"] is not None else (
        readings["float32_program_on_the_cpu"])
    assert chip is ours or "not measured" in chip["from"]
    assert 0 < ours["largest_relative_error"] < RULE["eps"] / 10
    assert ours["vertices_off"] == 0
    assert readings["bfloat16_recurrences"]["vertices_off"] > 0


def test_the_configuration_states_what_the_sweep_finds():
    """At the rehearse scale (the cell's own scale takes minutes and
    gigabytes here): the generator's counts, the key and what the reference's
    sweep finds from it, as `found.at_rehearse_scale` states them; the same
    keys at the cell's scale are the chip run's, from the same code."""
    found, small = CONFIG["found"], CONFIG["found"]["at_rehearse_scale"]
    graph, key = drawn(int(CONFIG["rehearse_scale"]))
    delta, sigma, depth, levels = bc_reference.brandes(graph, key)
    assert (graph.n, graph.edges, graph.mult.nnz) == (
        small["vertices"], small["edges"], small["pull_entries"])
    assert graph.mult.data.max() == 1  # simple
    assert key == small["search_key"]
    assert int(graph.mult[key].sum()) == small["key_degree"] == len(levels[1])
    assert [len(level) for level in levels] == small["level_sizes"]
    assert len(levels) - 1 == small["levels"] and small["pulls"] == 2 * small["levels"] + 1
    assert int((depth >= 0).sum()) == small["reached"] == sum(small["level_sizes"])
    assert sigma.max() == small["largest_path_count"]
    assert delta.max() == pytest.approx(small["largest_dependency"], rel=1e-12)
    assert delta[key] == pytest.approx(small["reached"] - 1, rel=1e-12)
    for stated in (found, small):  # the same shape at both scales
        assert len(stated["level_sizes"]) == stated["levels"] + 1
        assert stated["level_sizes"][:2] == [1, stated["key_degree"]]
        assert sum(stated["level_sizes"]) == stated["reached"]
        assert stated["pulls"] == 2 * stated["levels"] + 1
        assert stated["largest_path_count"] < 2 ** 24  # float32 counts them exactly
    assert found["largest_dependency"] == pytest.approx(found["reached"] - 1, rel=1e-6)


def test_the_precision_below_fails_the_rule():
    """The second reading of the limit, held at the rehearse scale: the
    reference's recurrences kept in bfloat16 are off on vertices by the
    hundred, kept in float32 on none with thirty times of room."""
    import ml_dtypes

    graph, key = drawn(int(CONFIG["rehearse_scale"]))
    delta = bc_reference.reference(graph, {"source": key})
    off = mismatches(RULE["rule"], brandes_rounded(graph, key, ml_dtypes.bfloat16), delta,
                     RULE["eps"])
    assert off == CONFIG["found"]["at_rehearse_scale"]["bfloat16_vertices_off"] > 100
    assert mismatches(RULE["rule"], brandes_rounded(graph, key, np.float32), delta,
                      RULE["eps"] / 30) == 0


def test_the_rule_on_an_answer_with_zeros():
    """`eps` on a BC answer: relative where the reference scores, and a
    vertex the reference scores 0 (unreached, a leaf) must read 0."""
    want = np.array([900.0, 0.0, 12.5, 0.0, 1.0, 3.0e5])
    assert mismatches("eps", want.astype(np.float32), want, 1e-3) == 0
    assert mismatches("eps", want * (1 + 9e-4), want, 1e-3) == 0
    assert mismatches("eps", want * (1 + 2e-3), want, 1e-3) == 4
    leaked = want.copy()
    leaked[1] = 1e-6  # a leaf handed a crumb
    assert mismatches("eps", leaked, want, 1e-3) == 1
    lost = want.copy()
    lost[4] = 0.0  # a vertex on a path, scored nothing
    assert mismatches("eps", lost, want, 1e-3) == 1
    assert mismatches("eps", np.where(want > 0, np.nan, 0.0), want, 1e-3) == 4
    assert mismatches("eps", want[:-1], want, 1e-3) == len(want)


# ---- the readers, on a stub ------------------------------------------------


def stub_run(busy_s=0.5, entries=2560):
    logged = []
    edge_src = types.SimpleNamespace(shape=(1, entries))
    return types.SimpleNamespace(
        log=logged.append, logged=logged, chips=1,
        dataset_info={"vertices": 1000, "pull_entries": 2400},
        readings={"traced_rounds": 0}, trace={"busy_s": busy_s},
        traffic=load("benchmarks", "traffic", "bc-key1.json"),
        frag=types.SimpleNamespace(dev=types.SimpleNamespace(
            ie=types.SimpleNamespace(edge_src=edge_src))),
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")],
        peaks=load("benchmarks", "peaks.json"),
        scopes={"scope_s": {"grape.pull.gather": 0.026, "grape.pull.fold": 0.039,
                            "grape.bc.forward": 0.004, "grape.bc.backward": 0.0025,
                            "": 0.001}})


def set_stats(monkeypatch, **stats):
    from libgrape_lite_tpu.models.bc import BC_STATS

    for k, v in stats.items():
        monkeypatch.setitem(BC_STATS, k, v)


def test_readers_read_the_record_and_the_scopes(monkeypatch):
    set_stats(monkeypatch, levels=6, reached=1243, pulls=13)
    run = stub_run()
    read = {name: READERS[name].read(run, metric_spec(name)) for name in NEW_METRICS}
    assert read["bc_levels"] == 6
    # 26 ms and 39 ms under the pull's scopes over 13 pulls of 2,560 padded entries
    assert read["bc_gather_ns_entry"] == pytest.approx(0.026 / 13 / 2560 * 1e9)
    assert read["bc_fold_ns_entry"] == pytest.approx(0.039 / 13 / 2560 * 1e9)
    assert read["bc_update_us_pull"] == pytest.approx(500.0)
    # 8 B x 2,400 entries + 20 B x 1,000 vertices at 819 GB/s, over 0.5 s busy
    assert read["bc_query_roofline"] == pytest.approx(100 * (39200 / 819e9) / 0.5)
    assert 0 < read["bc_query_roofline"] < 100
    assert bc_query_roofline.bc_query_bytes(
        CONFIG["pull_entries"], CONFIG["vertices"]) == 8 * CONFIG["pull_entries"] + 20 * CONFIG["vertices"]
    assert sum("BC_STATS" in line for line in run.logged) == 1


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_reader_finds_nothing_to_read(monkeypatch, name):
    """A program without the record (the parent commit), a run without a
    trace, a trace without the scopes: None or 0, never a raise."""
    from libgrape_lite_tpu.models import bc

    spec, reader = metric_spec(name), READERS[name]
    if reader is bc_query_roofline:  # from shapes and the trace alone
        run = stub_run()
        run.trace = None
        assert reader.read(run, spec) is None
        assert reader.read(stub_run(busy_s=0), spec) is None
        run = stub_run()
        run.devices[0].device_kind = "a chip without a table of peaks"
        assert reader.read(run, spec) is None
        run = stub_run()
        run.traffic = load("benchmarks", "traffic", "wcc.json")  # no bc job
        assert reader.read(run, spec) is None
        return
    set_stats(monkeypatch, levels=0, reached=0, pulls=0)  # no BC answer was extracted
    assert reader.read(stub_run(), spec) is None
    set_stats(monkeypatch, levels=6, reached=1243, pulls=13)
    if "scopes" in spec:
        run = stub_run()
        run.trace, run.scopes = None, None
        assert reader.read(run, spec) is None
        run = stub_run()
        run.scopes = {"scope_s": None}  # an executable cached without the scopes
        assert reader.read(run, spec) is None
        run.scopes = {"scope_s": {"grape.app.update": 0.1}}  # none of this metric's
        assert reader.read(run, spec) == 0.0
        run = stub_run()
        run.traffic = load("benchmarks", "traffic", "wcc.json")
        assert reader.read(run, spec) is None
    monkeypatch.delattr(bc, "BC_STATS")  # the parent commit's module
    assert reader.read(stub_run(), spec) is None


def test_the_benchmark_lists_the_cell_where_the_issue_names_it():
    # by name, never by place: later cells and metrics come after these
    bench = load("BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("g500-bc", "bc-key1", 1)
    entry = next(c for c in bench["configs"] if c["name"] == "g500-bc")
    assert entry["name"] == CONFIG["name"]
    assert entry["source"] == CONFIG["source"] and "kernel BC on graph Kron" in entry["source"]
    assert all(entry["source"] != c["source"] for c in bench["configs"] if c is not entry)
    assert len(entry["source"]) <= 200 and len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == ["scale"] and entry["file"].endswith("g500-bc.json")
    for shared in (PAGERANK_CELL, "serve/", "float32"):
        assert shared in cell["why"], shared
    lists = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
             if CELL in m.get("workloads", [])}
    assert lists == LISTED | set(NEW_METRICS)
    # what divides by traced rounds reads nothing where the levels are inside PEval
    assert not lists & {"pull_gather_ns_entry", "pull_fold_ns_entry", "pull_roofline",
                        "entry_round_ns", "round_device_us", "gather_reduce_share"}
    names = [m["name"] for m in bench["per_layer"]]
    assert names[names.index(NEW_METRICS[0]):][:5] == NEW_METRICS
    for name in NEW_METRICS:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        spec = metric_spec(name)
        assert m["workloads"] == [CELL]
        assert {k: spec[k] for k in ("layer", "unit", "better", "source", "moves")} == {
            k: m[k] for k in ("layer", "unit", "better", "source", "moves")}
        assert m["moves"] == "proc_time_s" and set(m) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
    for name in LISTED:
        m = next(m for m in bench["per_layer"] + bench["end_to_end"] if m["name"] == name)
        assert m["workloads"].index(CELL) > m["workloads"].index("road-like-cc.wcc")
    traffic = load("benchmarks", "traffic", "bc-key1.json")
    assert traffic["jobs"] == [
        {"app": "bc", "params": {}, "keys": {"param": "source", "pool": 1}}]
    assert (traffic["driver"], traffic["loop"], traffic["callers"], traffic["think_s"]) == (
        "analytics_closed", "closed", 1, 0)
    # the eleven cells and ten configurations the benchmark had come first, as they were
    assert [w["name"] for w in bench["workloads"]].index(CELL) == 11
    assert [c["name"] for c in bench["configs"]].index("g500-bc") == 10
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2


# ---- the cell, rehearsed ---------------------------------------------------


def test_the_cell_rehearses():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL,
         "--seed", "3600000050", "--seconds", "1", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] >= 2
    assert last["rehearsal"] is True and last["device"]["platform"] == "rehearsal"
    small = CONFIG["found"]["at_rehearse_scale"]
    metrics = last["metrics"]
    assert metrics["bc_levels"] == {"value": small["levels"], "unit": "levels"}
    assert metrics["rounds"]["value"] == 0  # both sweeps are loops inside PEval
    assert metrics["compiles_in_window"]["value"] == 0
    # off the chip the trace's operations carry no scope, and no peak is tabled
    assert not set(NEW_METRICS[1:]) & set(metrics)
    assert not {"pull_gather_ns_entry", "pull_roofline", "entry_round_ns"} & set(metrics)
    stats = next(line for line in lines if "BC_STATS" in line)
    assert (f"'levels': {small['levels']}" in stats and f"'reached': {small['reached']}" in stats
            and f"'pulls': {small['pulls']}" in stats)
    assert any(f"'source': {small['search_key']}" in line or "warm-up bc" in line
               for line in lines)
