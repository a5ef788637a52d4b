"""`reduce_scopes` on traces recorded on the chip, and its pieces.

    python -m pytest benchmarks/tests

`tiny_*_scoped.xplane.pb` were recorded on the v5e at `rehearse_scale` with
the library's named scopes and mirrored spans in place (PR 24, chip run);
`tiny_pagerank_v5e.xplane.pb` is PR 23's, from before either existed.
tests/test_reduce_scopes.py runs the same cases in tier-1.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import reduce_scopes as rs  # noqa: E402

TESTDATA = os.path.join(ROOT, "benchmarks", "testdata")
SCOPED = ["tiny_pagerank_v5e_scoped", "tiny_pagerank_x4_v5e_scoped", "tiny_serve_v5e_scoped"]


def recorded(name):
    path = os.path.join(TESTDATA, name)
    if not os.path.exists(path):
        pytest.skip(f"{name} is not recorded")
    return path


def test_scope_of_takes_the_innermost_grape_component():
    assert rs.scope_of("jit(stepper)/while/body/grape.pull.gather/gather:") == "grape.pull.gather"
    assert rs.scope_of("jit(stepper)/while/body/grape.app.update/grape.exchange.collective/psum:") \
        == "grape.exchange.collective"
    assert rs.scope_of("jit(stepper)/while/body/vmap(grape.pull.fold)/scatter-min:") == "grape.pull.fold"
    assert rs.scope_of("jit(stepper)/while/body/gather:") == "" and rs.scope_of(None) == ""


def test_wire_reader_reads_maps_strings_and_references():
    def varint(n):
        out = b""
        while n > 0x7F:
            out, n = out + bytes([n & 0x7F | 0x80]), n >> 7
        return out + bytes([n])

    def field(no, payload):
        if isinstance(payload, int):
            return varint(no << 3) + varint(payload)
        return varint(no << 3 | 2) + varint(len(payload)) + payload

    def entry(key, value):
        return field(1, key) + field(2, value)

    stat_meta = [field(5, entry(i, field(1, i) + field(2, name.encode())))
                 for i, name in ((1, "tf_op"), (2, "hlo_category"), (300, "custom fusion"))]
    event = (field(1, 7) + field(2, b"%fusion.1 = f32[8]{0} fusion(...), kind=kCustom")
             + field(4, b"fusion.1")
             + field(5, field(1, 1) + field(5, b"jit(f)/grape.pull.fold/scatter-add:"))
             + field(5, field(1, 2) + field(7, 300))
             + varint(9 << 3 | 1) + b"\0" * 8)  # a fixed64 field is skipped
    plane = field(2, b"/device:TPU:0") + field(4, entry(7, event)) + b"".join(stat_meta)
    path = os.path.join(os.environ.get("TMPDIR", "/tmp"), "wire_reader_test.pb")
    with open(path, "wb") as f:
        f.write(field(1, plane))
    try:
        got = rs.event_metadata(path)
    finally:
        os.remove(path)
    assert got == {"/device:TPU:0": {"%fusion.1 = f32[8]{0} fusion(...), kind=kCustom": {
        "display_name": "fusion.1", "tf_op": "jit(f)/grape.pull.fold/scatter-add:",
        "hlo_category": "custom fusion"}}}


def test_idle_is_cut_at_span_boundaries_and_goes_to_the_innermost_span():
    spans = [(0, 100, "bench.serve.pump", {}), (10, 60, "grape.serve.harvest", {}),
             (20, 30, "grape.worker.extract", {"lane": "0"}), (70, 90, "grape.serve.pop", {})]
    got = rs.idle_by_span([[5, 80], [95, 120]], spans)
    want = {"bench.serve.pump": (5 + 10 + 5) / 1e9, "grape.serve.harvest": (10 + 30) / 1e9,
            "grape.worker.extract": 10 / 1e9, "grape.serve.pop": 10 / 1e9, "bench.trace": 20 / 1e9}
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx((75 + 25) / 1e9)


def test_the_unscoped_trace_has_categories_and_name_stacks_but_no_scope():
    """PR 23's trace does carry `hlo_category` and `tf_op`, in the event
    metadata; with no `grape.*` scope every scope reading is None, never 0."""
    path = recorded("tiny_pagerank_v5e.xplane.pb")
    by_display = {e.get("display_name"): e
                  for e in rs.event_metadata(path)["/device:TPU:0"].values()}
    assert by_display["fusion.15"]["hlo_category"] == "custom fusion"
    assert by_display["fusion.15"]["tf_op"].endswith("/while/body/gather:")
    assert by_display["fusion.16"]["hlo_category"] == "custom fusion"
    assert by_display["fusion.16"]["tf_op"].endswith("/while/body/scatter-add:")
    red = rs.reduce(path)
    assert red["scope_s"] is None and red["scoped_share"] is None
    assert red["spans"] == [] and red["batches"] == []
    assert {k for k, _ in red["idle_by_span"]} == {"bench.query", "bench.extract", "bench.trace"}


@pytest.mark.parametrize("name", SCOPED)
def test_reduction_of_the_recorded_scoped_traces(name):
    want = json.load(open(recorded(name + ".expected.json")))
    red = rs.reduce(recorded(name + ".xplane.pb"))
    assert red["scoped_share"] == pytest.approx(want["scoped_share"], rel=1e-9)
    assert red["scope_s"] == pytest.approx(want["scope_s"], rel=1e-9, abs=1e-15)
    assert red["idle_s"] == pytest.approx(want["idle_s"], rel=1e-9)
    assert dict(red["idle_by_span"]) == pytest.approx(dict(want["idle_by_span"]), rel=1e-9, abs=1e-15)
    assert sum(v for _, v in red["idle_by_span"]) == pytest.approx(red["idle_s"], rel=1e-6)
    assert [s[0] for s in red["spans"]] == want["span_names"]
    assert red["batches"] == pytest.approx(want["batches"])
    assert red["scoped_share"] > 0.9
    for scope in want["must_have_scopes"]:
        assert red["scope_s"].get(scope, 0) > 0, scope


def test_the_exchange_pack_is_its_own_scope_on_four_chips():
    red = rs.reduce(recorded("tiny_pagerank_x4_v5e_scoped.xplane.pb"))
    s = red["scope_s"]
    assert s["grape.exchange.pack"] > 0 and s["grape.exchange.collective"] > 0
    assert s["grape.pull.gather"] > s["grape.exchange.pack"]


def test_a_served_batch_has_its_lanes_and_its_device_time():
    red = rs.reduce(recorded("tiny_serve_v5e_scoped.xplane.pb"))
    assert red["batches"] and all(b["lanes"] >= 1 for b in red["batches"])
    assert all(0 < b["busy_s"] <= b["span_s"] for b in red["batches"])
    names = [s[0] for s in red["spans"]]
    assert "grape.serve.harvest" in names and "grape.worker.extract" in names
    owners = {k for k, _ in red["idle_by_span"]}
    assert owners & {"grape.serve.harvest", "grape.worker.extract"}
