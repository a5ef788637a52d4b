"""The Datagen-like surrogate: its generator, its configuration, the reader of
the packed arm's metrics, and the cell rehearsed.

    python -m pytest benchmarks/tests

`graphs/datagen_like.py` is the benchmark's copy of
`scripts/gen_datagen_like.py`'s construction; the script calls it, and the
last generator case pins the published ratios the script hands it.
tests/test_benchmark_cdlp_datagen.py runs the same cases in tier-1;
tests/test_cdlp_datagen.py holds the program to the reference on this graph.
"""

import importlib.util
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

from benchmarks.graphs import datagen_like  # noqa: E402
from benchmarks.layer_metrics import cdlp_packed  # noqa: E402

CELL = "datagen-like.cdlp-10r"
CONFIG = json.load(open(os.path.join(ROOT, "benchmarks", "configs", "datagen-like.json")))
GEN = CONFIG["generator"]
SMALL = 11  # 2,048 ids: one community, a sixteenth of a second a draw


def test_the_configuration_states_what_the_generator_draws():
    scale = int(CONFIG["scale"])
    src, dst, w = datagen_like.edges(GEN, scale)
    assert CONFIG["vertices"] == 1 << scale
    assert CONFIG["edges"] == len(src) and CONFIG["pull_entries"] == 2 * len(src)
    # simple, as the published file: no self-loop, each pair once
    assert (src < dst).all()
    assert len(np.unique(src.astype(np.int64) << scale | dst)) == len(src)
    lo, hi = GEN["weights"]
    assert w.dtype == np.dtype(GEN["weight_dtype"]) and lo <= w.min() and w.max() <= hi
    degree = np.bincount(src, minlength=1 << scale) + np.bincount(dst, minlength=1 << scale)
    assert degree.max() <= GEN["degree_clip"][1] + 1  # one stub may be added for parity
    # the cut is of scale alone: the source's ratio of edges to vertices is the block's
    assert GEN["mean_degree"] == pytest.approx(
        2 * CONFIG["source_edges"] / CONFIG["source_vertices"], rel=1e-4)
    assert CONFIG["reduced"].keys() == {"scale"}
    assert set(GEN) - {"name"} <= set(CONFIG["assumed"]), "a parameter nobody owned up to"


def test_files_hold_the_edges_and_every_id(tmp_path):
    efile, vfile = str(tmp_path / "g.e"), str(tmp_path / "g.v")
    info = datagen_like.write_files(GEN, SMALL, efile, vfile)
    src, dst, w = datagen_like.edges(GEN, SMALL)
    assert info == {"vertices": 1 << SMALL, "edges": len(src),
                    "pull_entries": 2 * len(src), "efile_bytes": os.path.getsize(efile)}
    rows = np.loadtxt(efile, dtype=np.int64)
    assert (rows[:, 0] == src).all() and (rows[:, 1] == dst).all() and (rows[:, 2] == w).all()
    assert open(vfile).read().split() == [str(i) for i in range(1 << SMALL)]


def test_the_graph_belongs_to_the_seed():
    a, b = datagen_like.draw(GEN, 1 << SMALL), datagen_like.draw(GEN, 1 << SMALL)
    assert all((x == y).all() for x, y in zip(a, b))
    other = datagen_like.draw(dict(GEN, generator_seed=GEN["generator_seed"] + 1), 1 << SMALL)
    assert len(other[0]) != len(a[0]) or (other[0] != a[0]).any()


@pytest.mark.parametrize("key", sorted(set(GEN) - {"name"}))
def test_every_parameter_is_read_from_the_block(key):
    with pytest.raises(KeyError, match=key):
        datagen_like.draw({k: v for k, v in GEN.items() if k != key}, 1 << SMALL)


@pytest.mark.parametrize("key,value", [
    ("mean_degree", 40.0), ("degree_sigma", 0.5), ("degree_clip", [1, 100]),
    ("vertices_per_community", 500), ("community_zipf", 2.5),
    ("community_size_unit", 900), ("community_clip", [400, 600]),
    ("intra_share", 0.2), ("weights", [3, 3])])
def test_a_changed_parameter_changes_the_draw(key, value):
    n = 1 << 13  # five communities: their parameters show
    base = datagen_like.draw(GEN, n)
    got = datagen_like.draw(dict(GEN, **{key: value}), n)
    assert any(len(x) != len(y) or (x != y).any() for x, y in zip(base, got))


def test_the_script_draws_through_the_benchmarks_generator():
    path = os.path.join(ROOT, "scripts", "gen_datagen_like.py")
    spec = importlib.util.spec_from_file_location("gen_datagen_like_script", path)
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    gen = script.published_block(seed=5)
    # the published file's ratio, and the construction's shape as the cell has it
    assert gen["mean_degree"] == 2 * script.FULL_E / script.FULL_V
    shape = set(GEN) - {"name", "mean_degree", "generator_seed", "weights", "weight_dtype"}
    assert {k: gen[k] for k in shape} == {k: GEN[k] for k in shape}
    n, src, dst, w, comm, deg = script.generate(6400, 5)
    want = datagen_like.draw(gen, script.FULL_V // 6400)
    assert n == script.FULL_V // 6400 and w.dtype == np.float64 and 0 < w.min() <= w.max() <= 1
    assert all((x == y).all() for x, y in zip((src, dst, w, comm, deg), want))


# ---- the reader, on a stub ---------------------------------------------------


def metric_spec(name):
    return json.load(open(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".json")))


def stub_run(scope_s):
    oe = types.SimpleNamespace(edge_src=np.empty((1, 4000), np.int32))
    return types.SimpleNamespace(
        scopes={"scope_s": scope_s}, log=lambda msg: None,
        traffic=json.load(open(os.path.join(ROOT, "benchmarks", "traffic", "cdlp-10r.json"))),
        frag=types.SimpleNamespace(dev=types.SimpleNamespace(oe=oe)))


def set_stats(monkeypatch, **stats):
    from libgrape_lite_tpu.models.cdlp import CDLP_STATS

    for k, v in stats.items():
        monkeypatch.setitem(CDLP_STATS, k, v)


def test_reader_divides_by_the_packed_passes(monkeypatch):
    set_stats(monkeypatch, passes=10, packed_passes=8, branch="dynamic")
    run = stub_run({"grape.cdlp.live": 0.016, "grape.cdlp.rank": 0.64,
                    "grape.cdlp.sort": 1.0})
    assert cdlp_packed.read(run, metric_spec("cdlp_packed_passes")) == 8
    assert cdlp_packed.read(run, metric_spec("cdlp_live_ms_pass")) == pytest.approx(2.0)
    # 0.64 s over 8 passes of 4,000 padded entries
    assert cdlp_packed.read(run, metric_spec("cdlp_rank_ns_entry")) == pytest.approx(2e4)


def test_reader_finds_nothing_to_read(monkeypatch):
    live = metric_spec("cdlp_live_ms_pass")
    set_stats(monkeypatch, passes=10, packed_passes=0, branch="dynamic")
    run = stub_run({"grape.cdlp.sort": 1.0})  # g500-cdlp's program: no pass packs
    assert cdlp_packed.read(run, metric_spec("cdlp_packed_passes")) == 0
    assert cdlp_packed.read(run, live) is None
    assert cdlp_packed.read(run, metric_spec("cdlp_rank_ns_entry")) is None
    set_stats(monkeypatch, packed_passes=8)
    assert cdlp_packed.read(stub_run(None), live) is None  # a trace without scopes
    assert cdlp_packed.read(stub_run({"grape.cdlp.sort": 1.0}), live) is None
    set_stats(monkeypatch, passes=0)  # no CDLP answer was extracted
    assert cdlp_packed.read(run, metric_spec("cdlp_packed_passes")) is None
    # a program from before CDLP_STATS: the import finds no such name
    monkeypatch.delattr("libgrape_lite_tpu.models.cdlp.CDLP_STATS")
    assert cdlp_packed.read(run, metric_spec("cdlp_packed_passes")) is None


def test_the_benchmark_lists_the_cell_where_the_issue_names_it():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("datagen-like", "cdlp-10r", 1)
    entry = next(c for c in bench["configs"] if c["name"] == "datagen-like")
    assert entry["source"] == CONFIG["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == ["scale"] and entry["file"].endswith("datagen-like.json")
    lists = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
             if cell["name"] in m.get("workloads", [])}
    assert lists == {
        "proc_time_s", "dispatch_ms", "rounds", "device_idle_share", "scoped_share",
        "worker_state_ms", "cdlp_gather_ns_entry", "cdlp_fold_ns_entry",
        "cdlp_sort_ns_entry", "cdlp_count_ns_entry", "cdlp_universe_ms_pass",
        "cdlp_round_roofline", "cdlp_packed_passes", "cdlp_live_ms_pass",
        "cdlp_rank_ns_entry"}
    for name in ("cdlp_packed_passes", "cdlp_live_ms_pass", "cdlp_rank_ns_entry"):
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        spec = metric_spec(name)
        assert m["workloads"] == [CELL]
        assert {k: spec[k] for k in ("layer", "unit", "better", "source", "moves")} == {
            k: m[k] for k in ("layer", "unit", "better", "source", "moves")}


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
    assert last["metrics"]["rounds"]["value"] == 9  # ten passes: PEval is the first
    assert last["metrics"]["compiles_in_window"]["value"] == 0
    # 1,024 ids pack against the initial universe: no predicate, no packed arm
    assert last["metrics"]["cdlp_packed_passes"]["value"] == 0
    stats = next(l for l in lines if "CDLP_STATS" in l)
    assert "'branch': 'static'" in stats and "'passes': 10" in stats
