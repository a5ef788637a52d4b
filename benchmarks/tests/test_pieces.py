"""The generator, the roofline arithmetic and the trace reduction's pieces.

    python -m pytest benchmarks/tests
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import reduce_xplane as rx  # noqa: E402
from benchmarks import roofline  # noqa: E402
from benchmarks.graphs import kronecker  # noqa: E402

TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")


GEN = {"name": "kronecker", "a": 0.57, "b": 0.19, "c": 0.19, "d": 0.05,
       "edge_factor": 16, "generator_seed": 5, "weights": [1, 10]}


def test_generator_file_and_memory_agree(tmp_path, monkeypatch):
    monkeypatch.setattr(kronecker, "CHUNK", 1 << 10)  # several chunks, several processes
    info = kronecker.write_files(GEN, 8, str(tmp_path / "g.e"), str(tmp_path / "g.v"))
    src, dst, w = kronecker.edges(GEN, 8)
    rows = np.loadtxt(tmp_path / "g.e", dtype=np.int64)
    assert info == {"vertices": 256, "edges": 4096, "pull_entries": 8192,
                    "efile_bytes": os.path.getsize(tmp_path / "g.e")}
    assert (rows[:, 0] == src).all() and (rows[:, 1] == dst).all() and (rows[:, 2] == w).all()
    assert 1 <= w.min() and w.max() <= 10 and src.max() < 256
    assert (np.loadtxt(tmp_path / "g.v", dtype=np.int64) == np.arange(256)).all()
    again = kronecker.edges(GEN, 8)
    assert all((a == b).all() for a, b in zip((src, dst, w), again))
    assert not (kronecker.edges(dict(GEN, generator_seed=6), 8)[0] == src).all()


def test_generator_skew_matches_the_quadrant_probabilities():
    gen = dict(GEN, generator_seed=1)
    src, dst, _ = kronecker.edges(gen, 12)
    perm = kronecker.label_permutation(gen, 12)
    assert sorted(perm.tolist()) == list(range(1 << 12))
    drawn = np.argsort(perm)  # written id -> the label the quadrants drew
    # the top bit of src is set with probability c + d = 0.24, of dst with b + d
    assert abs((drawn[src] >> 11).mean() - 0.24) < 0.01
    assert abs((drawn[dst] >> 11).mean() - 0.24) < 0.01


def test_written_ids_carry_no_degree_order():
    """Graph500 scrambles the labels: a block of the id range holds its
    share of the entries, not (a+b)^2 of them as the drawn labels would."""
    gen = dict(GEN, generator_seed=1)
    src, dst, _ = kronecker.edges(gen, 14)
    ends = np.concatenate([src, dst])
    share = np.bincount(ends >> 12, minlength=4) / len(ends)
    assert share.max() < 0.30 and share.min() > 0.20
    drawn = np.argsort(kronecker.label_permutation(gen, 14))
    assert (np.bincount(drawn[ends] >> 12, minlength=4) / len(ends))[0] > 0.55


def test_generator_refuses_probabilities_that_do_not_sum_to_one():
    with pytest.raises(ValueError):
        kronecker.edges(dict(GEN, d=0.1), 4)


def test_pull_round_bytes():
    assert roofline.pull_round_bytes(100, 10, weighted=False) == 100 * 12 + 10 * 8
    assert roofline.pull_round_bytes(100, 10, weighted=True) == 100 * 16 + 10 * 8
    # scale 21 on one v5e: about 1 ms
    s = roofline.pull_round_floor_s(67108864, 2097152, False, 1, 819e9)
    assert 0.9e-3 < s < 1.1e-3
    assert roofline.pull_round_floor_s(67108864, 2097152, False, 4, 819e9) == pytest.approx(s / 4)


def test_interval_arithmetic():
    u = rx.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert u == [[0, 3], [5, 8]] and rx.length(u) == 6
    assert rx.subtract([[0, 10]], u) == [[3, 5], [8, 10]]
    assert rx.subtract([[0, 3], [5, 8]], [[1, 6]]) == [[0, 1], [6, 8]]
    assert rx.subtract([[0, 3]], []) == [[0, 3]]


def test_self_times_take_nested_operations_out():
    ops = [(0, 100, "while"), (10, 30, "gather"), (30, 60, "fusion"),
           (35, 40, "inner"), (120, 130, "copy")]
    got = dict(rx.self_times(ops))
    assert got == {"while": 50, "gather": 20, "fusion": 25, "inner": 5, "copy": 10}


def recorded(name):
    path = os.path.join(TESTDATA, name)
    if not os.path.exists(path):
        pytest.skip(f"{name} is not recorded")
    return path


def test_reduction_of_the_recorded_chip_trace():
    """A PageRank query at scale 10 recorded on the v5e (PR 23, chip run):
    the reduction must keep giving the numbers in the file beside it."""
    want = json.load(open(recorded("tiny_pagerank_v5e.expected.json")))
    got = rx.reduce(recorded("tiny_pagerank_v5e.xplane.pb"), "/device:TPU:", 1)
    assert got["devices"] == want["devices"]
    for key in ("window_s", "busy_s", "idle_share", "collective_s", "collective_exposed_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-9, abs=1e-12), key
    assert 0 < got["busy_s"] <= got["window_s"] and 0 <= got["idle_share"] < 1
    assert [n for n, _ in got["device_ops"][:5]] == [n for n, _ in want["device_ops"][:5]]
    assert sum(s for _, s in got["device_ops"]) == pytest.approx(got["busy_s"], rel=1e-6)
    assert sum(s for _, s in got["idle_gaps"]) == pytest.approx(
        got["window_s"] - got["busy_s"], rel=1e-6)
    assert got["spans"]["bench.query"][0] == 1
