"""The vertex-cut deployment's cell, `g500-s21-vc2x2.pagerank`: its entries
in `BENCHMARK.json` found by name, its configuration against the 1-D cut's
(one graph, three cells), the readers of the metrics it brings on a stub and
on nothing to read, and the cell rehearsed on four virtual devices.
"""

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import roofline  # noqa: E402
from benchmarks.layer_metrics import (  # noqa: E402
    pull_roofline, scope_us_per_round, vc_pull_ns_entry, vc_tile_pad_ratio)

CELL, CONFIG_NAME, TRAFFIC = ("g500-s21-vc2x2.pagerank", "g500-s21-vc2x2",
                              "pagerank-vc-10r")
NEW_METRICS = {"vc_gather_master_us_round": scope_us_per_round,
               "vc_scatter_us_round": scope_us_per_round,
               "vc_tile_pad_ratio": vc_tile_pad_ratio,
               "vc_pull_ns_entry": vc_pull_ns_entry}
APPENDED = ["dispatch_ms", "rounds", "device_idle_share", "scoped_share",
            "worker_state_ms", "collective_ms_round",
            "collective_exposed_share", "pull_roofline"]


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


BENCH = load("BENCHMARK.json")
CONFIG = load("benchmarks", "configs", CONFIG_NAME + ".json")
X4 = load("benchmarks", "configs", "g500-s21-x4.json")


def named(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def metric_spec(name):
    return load("benchmarks", "layer_metrics", name + ".json")


# ---- the entries, by name ----


def test_the_cell_and_its_configuration_are_declared():
    cell = named(BENCH["workloads"], CELL)
    assert cell == {"name": CELL, "config": CONFIG_NAME, "traffic": TRAFFIC,
                    "chips": 4, "why": cell["why"]}
    assert len(cell["why"]) <= 200
    for other in ("g500-s21.pagerank", "g500-s21-x4.pagerank"):
        assert other in cell["why"]  # the three cells that read one graph
    entry = named(BENCH["configs"], CONFIG_NAME)
    assert entry["file"] == f"benchmarks/configs/{CONFIG_NAME}.json"
    assert entry["reduced"] == ["scale"] == list(CONFIG["reduced"])
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert "--vc" in entry["source"] and "graph500-26" in entry["source"]
    # by name, never by place: of the cells up to this one, the
    # thirteenth, three are on four chips; later cells come after
    cells = BENCH["workloads"]
    upto = cells[:[c["name"] for c in cells].index(CELL) + 1]
    assert len(upto) == 13 and sum(c["chips"] == 4 for c in upto) == 3
    assert [c["name"] for c in cells if c["config"] == CONFIG_NAME] == [CELL]


def test_the_cell_reports_what_the_issue_lists():
    assert CELL in named(BENCH["end_to_end"], "proc_time_s")["workloads"]
    for name in APPENDED + list(NEW_METRICS):
        metric = named(BENCH["per_layer"], name)
        assert CELL in metric["workloads"], name
        assert metric["moves"] == "proc_time_s"
    for name in NEW_METRICS:
        metric, spec = named(BENCH["per_layer"], name), metric_spec(name)
        assert metric["workloads"] == [CELL]
        for key in ("name", "unit", "better", "source", "layer", "moves"):
            assert metric[key] == spec[key], (name, key)
    assert named(BENCH["per_layer"], "vc_pull_ns_entry")["layer"] == "kernels"
    # the 1-D cut's twins read `frag.dev.ie` and stay off the cell
    for name in ("shard_pad_ratio", "pull_gather_ns_entry",
                 "pull_fold_ns_entry", "exchange_pack_ms_round"):
        assert CELL not in named(BENCH["per_layer"], name)["workloads"]


def test_the_configuration_is_the_1d_cuts_graph_cut_2d():
    for key in ("generator", "scale", "source_scale", "rehearse_scale",
                "vertices", "edges", "pull_entries", "fnum", "chips"):
        assert CONFIG[key] == X4[key], key
    assert CONFIG["generator"]["generator_seed"] == 7
    assert (CONFIG["scale"], CONFIG["vertices"], CONFIG["edges"],
            CONFIG["pull_entries"]) == (21, 2097152, 33554432, 67108864)
    spec = dict(CONFIG["load_graph_spec"])
    assert spec.pop("vertex_cut") is True and spec == X4["load_graph_spec"]
    for key, text in X4["assumed"].items():  # to the letter: shared files
        assert CONFIG["assumed"][key] == text, key
    assert set(CONFIG["assumed"]) - set(X4["assumed"]) == {
        "chunks", "state", "storage", "weights (vertex cut)"}
    assert list(CONFIG["guarantees"]) == ["statement", "pagerank_vc"]
    assert CONFIG["guarantees"]["statement"] == X4["guarantees"]["statement"]
    assert CONFIG["guarantees"]["pagerank_vc"] == X4["guarantees"]["pagerank"]
    assert "--vc" in CONFIG["deployment"] and "diagonal" in CONFIG["deployment"]
    # both cuts' files are one file: the dataset's key is the generator block
    from benchmarks.datasets import Dataset

    assert Dataset(CONFIG, 21).dir == Dataset(X4, 21).dir


def test_the_traffic_is_one_caller_asking_pagerank_vc():
    traffic = load("benchmarks", "traffic", TRAFFIC + ".json")
    assert (traffic["driver"], traffic["loop"], traffic["callers"],
            traffic["think_s"]) == ("analytics_closed", "closed", 1, 0)
    assert traffic["jobs"] == [{"app": "pagerank_vc", "params": {
        "delta": 0.85, "max_round": 10}}]
    from libgrape_lite_tpu.models import APP_REGISTRY

    assert "pagerank_vc" in APP_REGISTRY
    import benchmarks.references.pagerank_vc as reference

    assert callable(reference.reference) and callable(reference.to_reference_form)


# ---- the readers ----


def stub_run(width=2560, tiles=4, rounds=10):
    pull = types.SimpleNamespace(
        edge_src=types.SimpleNamespace(shape=(tiles, width)))
    return types.SimpleNamespace(
        log=lambda line: None, chips=4,
        dataset_info={"vertices": 1000, "edges": 4800, "pull_entries": 9600},
        readings={"traced_rounds": rounds}, trace={"busy_s": 0.5},
        traffic=load("benchmarks", "traffic", TRAFFIC + ".json"),
        frag=types.SimpleNamespace(dev=types.SimpleNamespace(pull=pull)),
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite",
                                       platform="tpu")],
        peaks=load("benchmarks", "peaks.json"),
        scopes={"scope_s": {"grape.pull.gather": 0.02, "grape.pull.fold": 0.005,
                            "grape.vc.gather_master": 0.003,
                            "grape.vc.scatter": 0.0015, "": 0.001}})


def test_the_readers_read_the_tiles_and_the_scopes():
    run = stub_run()
    read = {name: reader.read(run, metric_spec(name))
            for name, reader in NEW_METRICS.items()}
    # 4 tiles of 2,560 padded entries over 4,800 edges once a direction
    assert read["vc_tile_pad_ratio"] == pytest.approx(4 * 2560 / 9600)
    # 25 ms under the pull's two scopes over 10 rounds of 2,560 entries
    assert read["vc_pull_ns_entry"] == pytest.approx(0.025 / 10 / 2560 * 1e9)
    assert read["vc_gather_master_us_round"] == pytest.approx(300.0)
    assert read["vc_scatter_us_round"] == pytest.approx(150.0)
    # the roofline that is there: 12 B an entry + 8 B a vertex over four
    # chips, the 1-D cut's reading of the same graph
    floor = roofline.pull_round_floor_s(9600, 1000, False, 4, 819e9)
    assert pull_roofline.read(run, metric_spec("pull_roofline")) == (
        pytest.approx(100 * floor / (0.5 / 10)))


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_reader_finds_nothing_to_read(name):
    """An edge-cut fragment (any other cell), a run without a trace, a
    trace without the scopes, a program from before the tiles' CSRs: None,
    never a raise."""
    spec, reader = metric_spec(name), NEW_METRICS[name]
    run = stub_run()
    run.frag = types.SimpleNamespace(dev=types.SimpleNamespace(
        ie=types.SimpleNamespace(edge_src=types.SimpleNamespace(
            shape=(4, 2560)))))
    if name in ("vc_tile_pad_ratio", "vc_pull_ns_entry"):
        assert reader.read(run, spec) is None
    if name != "vc_tile_pad_ratio":
        run = stub_run()
        run.scopes = None  # no traced pass on the chip
        assert reader.read(run, spec) is None
        run = stub_run()
        run.scopes = {"scope_s": None}  # operations that carry no scope
        assert reader.read(run, spec) is None
        assert reader.read(stub_run(rounds=0), spec) is None
    else:
        run = stub_run()
        run.dataset_info = {"vertices": 1000}
        assert reader.read(run, spec) is None


# ---- the cell, rehearsed ----


def test_the_cell_rehearses_on_four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "5300000007", "--seconds", "1",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 2 and last["rehearsal"] is True
    assert last["device"]["platform"] == "rehearsal"
    assert last["device"]["count"] == 4
    metrics = last["metrics"]
    assert metrics["rounds"]["value"] == 10
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["vc_tile_pad_ratio"]["value"] >= 1.0
    assert metrics["collective_ms_round"]["value"] > 0  # psums, transposes
    assert "setup_spanned_share" in metrics and "load_place_s" in metrics
    for name in ("pull_roofline", "vc_pull_ns_entry", "shard_pad_ratio"):
        assert name not in metrics  # no peaks, no scopes off the chip
    assert any("setup phases:" in l and "load_graph" in l and "load.place" in l
               for l in lines)
    assert "fnum 4" in out.stdout
