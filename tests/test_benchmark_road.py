"""The cases of benchmarks/tests/test_road.py, counted in tier-1.

The road-like surrogate's generator against its configuration, the readers
of the round record's metrics on a stub and on the trace recorded on the
v5e, and the cell `road-like.bfs-key1` rehearsed.  The cases live with the
benchmark and are loaded from there, by path, so that both suites run the
same code: but for one.  The benchmark's
`test_the_benchmark_lists_the_cell_where_the_issue_names_it` finds the cell,
its configuration and its four metrics by their place at the end of
`BENCHMARK.json`'s lists, and pins the set of metrics that list the cell; a
later cell (PR 43's `road-like-sssp.sssp-key1`) comes after them, and
`frontier_compact_us_round` lists this cell too.  No PR but a `benchmark` PR
may edit the benchmark's file, so the case below holds the same things by
name and tier-1 runs it in the other's place (PERF.md section 7).
"""

import json

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks", "tests", "test_road.py")
_spec = importlib.util.spec_from_file_location("benchmarks_test_road", _PATH)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

globals().update({name: case for name, case in vars(_cases).items()
                  if name.startswith("test_")})


def test_the_benchmark_lists_the_cell_where_the_issue_names_it():
    with open(os.path.join(os.path.dirname(_PATH), "..", "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == _cases.CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("road-like", "bfs-key1", 1)
    entry = next(c for c in bench["configs"] if c["name"] == "road-like")
    assert entry["source"] == _cases.CONFIG["source"]
    assert len(entry["source"]) <= 200 and len(cell["why"]) <= 200
    assert entry["reduced"] == ["scale"] and entry["file"].endswith("road-like.json")
    lists = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
             if _cases.CELL in m.get("workloads", [])}
    assert lists == {
        "proc_time_s", "dispatch_ms", "rounds", "device_idle_share", "scoped_share",
        "worker_state_ms", "pull_gather_ns_entry", "pull_fold_ns_entry", "pull_roofline",
        *_cases.NEW_METRICS, "frontier_compact_us_round"}
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(_cases.NEW_METRICS[0])
    assert names[at:at + 4] == _cases.NEW_METRICS  # together, in the order they came
    for name in _cases.NEW_METRICS:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        spec = _cases.metric_spec(name)
        assert m["workloads"][0] == _cases.CELL and m["moves"] == "proc_time_s"
        assert {k: spec[k] for k in ("layer", "unit", "better", "source", "moves")} == {
            k: m[k] for k in ("layer", "unit", "better", "source", "moves")}
    with open(os.path.join(os.path.dirname(_PATH), "..", "traffic", "bfs-key1.json")) as f:
        traffic = json.load(f)
    assert traffic["jobs"] == [{"app": "bfs", "params": {},
                                "keys": {"param": "source", "pool": 1}}]
    assert (traffic["driver"], traffic["callers"], traffic["think_s"]) == (
        "analytics_closed", 1, 0)
