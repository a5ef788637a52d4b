"""The CDLP reference against two oracles built otherwise, and the CDLP
metrics' readers on a trace recorded on the chip.

    python -m pytest benchmarks/tests

`references/cdlp.py` counts labels by a sparse matrix product.
`tests/test_cdlp_scale.py::np_cdlp` sorts (row, label) pairs and takes run
lengths; the `collections.Counter` case counts one vertex at a time.
`tiny_cdlp_v5e_scoped.xplane.pb` is one traced query of the cell
`g500-cdlp.cdlp-10r` at `rehearse_scale` on the v5e (PR 26, chip run).
tests/test_benchmark_cdlp.py runs the same cases in tier-1.
"""

import importlib.util
import json
import os
import sys
import types
from collections import Counter

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import reduce_scopes as rs  # noqa: E402
from benchmarks import reduce_xplane as rx  # noqa: E402
from benchmarks.graphs.csr import symmetric_csr  # noqa: E402
from benchmarks.layer_metrics import (  # noqa: E402
    cdlp_pass_bytes, cdlp_round_roofline, cdlp_scope_per_pass)
from benchmarks.references import cdlp  # noqa: E402

TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")
N = 200
# vertices the seeded edges leave alone, for the three forced cases
TIE, TIE_A, TIE_B = 190, 191, 192  # one edge to each: both labels count 1
DOUBLED, TWICE, ONCE = 193, 195, 194  # 195 twice, 194 once: the larger label wins
LOOP, LOOP_NBR = 197, 196  # a self-loop and one edge to a smaller id


@pytest.fixture(scope="module")
def graph():
    rng = np.random.default_rng(26)
    src = rng.integers(0, 180, 600)
    dst = rng.integers(0, 180, 600)  # seeded: multi-edges and loops of its own
    forced = [(TIE, TIE_A), (TIE, TIE_B), (DOUBLED, TWICE), (TWICE, DOUBLED),
              (DOUBLED, ONCE), (LOOP, LOOP), (LOOP, LOOP_NBR)]
    src = np.concatenate([src, [a for a, _ in forced]])
    dst = np.concatenate([dst, [b for _, b in forced]])
    minw, mult = symmetric_csr(N, src, dst, np.ones(len(src)))
    return types.SimpleNamespace(n=N, minw=minw, mult=mult, src=src, dst=dst)


def test_the_forced_cases_after_one_pass(graph):
    got = cdlp.reference(graph, {"max_round": 1})
    assert got[TIE] == TIE_A  # a tie goes to the smallest label
    assert got[DOUBLED] == TWICE  # a doubled edge counts twice: 195 beats 194
    assert got[LOOP] == LOOP  # a self-loop counts twice: 197 beats 196
    assert got[198] == 198 and got[199] == 199  # no neighbour: the label stays


@pytest.mark.parametrize("rounds", [1, 2, 10])
def test_reference_agrees_with_the_sorting_oracle(graph, rounds):
    path = os.path.join(ROOT, "tests", "test_cdlp_scale.py")
    spec = importlib.util.spec_from_file_location("cdlp_scale_cases", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    want = mod.np_cdlp(N, graph.src, graph.dst, rounds)
    assert (cdlp.reference(graph, {"max_round": rounds}) != want).sum() == 0


def test_reference_agrees_with_a_counter_per_vertex(graph):
    adj = {}
    for u, v in zip(np.concatenate([graph.src, graph.dst]).tolist(),
                    np.concatenate([graph.dst, graph.src]).tolist()):
        adj.setdefault(u, []).append(v)
    labels = list(range(N))
    for rounds in range(1, 6):
        new = list(labels)
        for u, nbrs in adj.items():
            counts = Counter(labels[v] for v in nbrs)
            top = max(counts.values())
            new[u] = min(l for l, c in counts.items() if c == top)
        labels = new
        got = cdlp.reference(graph, {"max_round": rounds})
        assert got.tolist() == labels, f"after {rounds} passes"


def test_the_pad_sentinel_is_no_label():
    big = np.iinfo(np.int32).max
    got = cdlp.to_reference_form(np.array([2, 0, big, 1], dtype=np.int32))
    assert got.tolist() == [2, 0, -1, 1] and got.dtype == np.int64


def test_pass_bytes():
    # per entry 12 + 16 + 8, per vertex 8
    assert cdlp_pass_bytes.cdlp_pass_bytes(100, 10) == 100 * 36 + 80
    assert cdlp_pass_bytes.cdlp_pass_floor_s(819, 0, 1, 819e9) == pytest.approx(36e-9)
    assert cdlp_pass_bytes.cdlp_pass_floor_s(819, 0, 4, 819e9) == pytest.approx(9e-9)


# ---- the readers, on the recorded trace ----------------------------------


def recorded_run(scoped: bool = True):
    """What `run.py` hands a reader, from the recorded trace and its numbers."""
    name = "tiny_cdlp_v5e_scoped" if scoped else "tiny_pagerank_v5e"
    path = os.path.join(TESTDATA, name + ".xplane.pb")
    if not os.path.exists(path):
        pytest.skip(f"{name} is not recorded")
    want = json.load(open(os.path.join(TESTDATA, name + ".expected.json")))
    entries = want.get("padded_entries", 32768)
    oe = types.SimpleNamespace(edge_src=np.empty((1, entries), np.int32))
    run = types.SimpleNamespace(
        trace=rx.reduce(path, n_devices=1), scopes=rs.reduce(path), chips=1,
        traffic=json.load(open(os.path.join(ROOT, "benchmarks", "traffic", "cdlp-10r.json"))),
        frag=types.SimpleNamespace(dev=types.SimpleNamespace(oe=oe)),
        dataset_info={"pull_entries": 32768, "vertices": 1024},
        devices=[types.SimpleNamespace(device_kind="TPU v5 lite", platform="tpu")],
        peaks=json.load(open(os.path.join(ROOT, "benchmarks", "peaks.json"))))
    return run, want


def metric_spec(name):
    return json.load(open(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".json")))


SCOPE_METRICS = ["cdlp_gather_ns_entry", "cdlp_fold_ns_entry", "cdlp_sort_ns_entry",
                 "cdlp_count_ns_entry", "cdlp_universe_ms_pass"]


@pytest.mark.parametrize("name", SCOPE_METRICS)
def test_scope_metric_on_the_recorded_trace(name):
    run, want = recorded_run()
    got = cdlp_scope_per_pass.read(run, metric_spec(name))
    if name not in want["metrics"]:  # scale 10 packs its keys: no universe
        assert got is None
        return
    assert got == pytest.approx(want["metrics"][name], rel=1e-9) and got > 0


def test_the_scopes_account_for_the_busy_time_over_ten_passes():
    run, want = recorded_run()
    assert cdlp_scope_per_pass.passes(run) == 10
    entries = run.frag.dev.oe.edge_src.shape[1]
    ns = sum(cdlp_scope_per_pass.read(run, metric_spec(m)) or 0.0
             for m in SCOPE_METRICS if m.endswith("_ns_entry"))
    ms = cdlp_scope_per_pass.read(run, metric_spec("cdlp_universe_ms_pass")) or 0.0
    named = ns * 1e-9 * entries * 10 + ms * 1e-3 * 10
    assert named <= run.trace["busy_s"] * 1.001
    assert named >= run.trace["busy_s"] * want["named_share_at_least"]


def test_round_roofline_on_the_recorded_trace():
    run, want = recorded_run()
    got = cdlp_round_roofline.read(run, metric_spec("cdlp_round_roofline"))
    assert got == pytest.approx(want["metrics"]["cdlp_round_roofline"], rel=1e-9)
    assert 0 < got <= 100


@pytest.mark.parametrize("name", SCOPE_METRICS)
def test_a_trace_without_the_scopes_reads_nothing(name):
    run, _ = recorded_run(scoped=False)  # PR 23's trace: no scope at all
    assert cdlp_scope_per_pass.read(run, metric_spec(name)) is None
