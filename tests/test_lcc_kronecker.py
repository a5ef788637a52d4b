"""LCC on the benchmark's own graph, against the benchmark's plain reference.

The simple Graph500 Kronecker graph of `benchmarks/configs/g500-lcc.json`
(`benchmarks/graphs/kronecker_simple.py`) has what p2p-31 lacks: hubs, so
that the tiered schedule runs unforced from scale 12 up, isolated vertices
and ids permuted at random.  The registry's `lcc` is held to the
configuration's own rule on every vertex; the plain reference is held to
the dense definition; the generator to what it says of itself.

The residency cases pin that the oriented adjacency is built once per
fragment and rides as read-only ephemeral leaves; the lowered-text cases
that the `grape.lcc.*` scopes are there, are metadata only, hold no search
under `.intersect`, and left the other runners alone.  On several fragments
the target blocks ride a ring: `grape.lcc.ring` names its `ppermute`, and
`LCC_STATS` counts its passes and bytes and the shards' schedules
(`benchmarks/configs/g500-lcc-x4.json` is that deployment).

The far-end credits go by adjacency slot (PR 33): a row of the `[vp, D]`
table an edge inside the chunk loops, flushed by id in a walk of its own;
the lowered text holds no element scatter of C x W updates under
`grape.lcc.credit`, and `LCC_STATS` counts the rows and the flush.

On several fragments the tier schedule is cut by ring step (PR 35): a pass
walks the segments of its own step, an entry sits in the one segment of the
step at which its neighbour's block is on the device, the fold's bound is
counted from the schedule, and the answer is the one fragment's bit for bit.
"""

import contextlib
import json
import os
import re
import types

import jax
import numpy as np
import pytest

from benchmarks.compare import mismatches
from benchmarks.graphs import kronecker, kronecker_simple
from benchmarks.graphs.csr import symmetric_csr
from benchmarks.references import lcc as lcc_reference
from libgrape_lite_tpu.fragment.loader import LoadGraph, LoadGraphSpec
from libgrape_lite_tpu.models import APP_REGISTRY
from libgrape_lite_tpu.models.lcc_beta import LCC_STATS, ApexTriangleCount
from libgrape_lite_tpu.parallel.comm_spec import CommSpec
from libgrape_lite_tpu.worker.worker import Worker

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmarks", "configs", "g500-lcc.json")) as f:
    CONFIG = json.load(f)
with open(os.path.join(ROOT, "benchmarks", "configs", "g500-lcc-x4.json")) as f:
    CONFIG_X4 = json.load(f)
GEN = CONFIG["generator"]
RULE = CONFIG_X4["guarantees"]["lcc"]
SCOPES = ("grape.lcc.orient", "grape.lcc.rows", "grape.lcc.intersect",
          "grape.lcc.credit", "grape.app.update")
RING = "grape.lcc.ring"


@pytest.fixture(scope="module")
def kron(tmp_path_factory):
    """(scale, fnum) -> a fragment through LoadGraph; scale -> the graph."""
    files, graphs = {}, {}

    def graph(scale: int):
        if scale not in graphs:
            d = tmp_path_factory.mktemp(f"kron_simple{scale}")
            files[scale] = str(d / "graph.e"), str(d / "graph.v")
            kronecker_simple.write_files(GEN, scale, *files[scale])
            n = 1 << scale
            _, mult = symmetric_csr(n, *kronecker_simple.edges(GEN, scale))
            graphs[scale] = types.SimpleNamespace(n=n, mult=mult)
        return graphs[scale]

    def load(scale: int, fnum: int):
        graph(scale)
        spec = dict(CONFIG["load_graph_spec"])
        spec["edata_dtype"] = np.dtype(spec["edata_dtype"]).type
        return LoadGraph(*files[scale], CommSpec(fnum=fnum), LoadGraphSpec(**spec))

    return types.SimpleNamespace(graph=graph, load=load)


def by_id(frag, values) -> np.ndarray:
    values = np.asarray(values)
    out = np.empty(frag.dev.total_vnum, dtype=values.dtype)
    for f in range(frag.fnum):
        out[frag.inner_oids(f)] = values[f, :frag.inner_vertices_num(f)]
    return out


# ---- the answer ------------------------------------------------------------


@pytest.mark.parametrize("key", ["generator", "load_graph_spec", "guarantees"])
def test_the_four_chip_deployment_loads_the_one_chip_graph(key):
    """At an equal scale the two configurations share graph files and the
    reference's cache, and hold the answer to one rule."""
    assert CONFIG_X4[key] == CONFIG[key]
    assert (CONFIG_X4["fnum"], CONFIG_X4["chips"]) == (4, 4)


@pytest.mark.parametrize("scale,fnum", [(10, 1), (10, 2), (12, 1), (12, 2), (10, 4),
                                        (12, 4)])
def test_registry_lcc_holds_the_configurations_rule(kron, scale, fnum):
    frag = kron.load(scale, fnum)
    app = APP_REGISTRY["lcc"]()
    w = Worker(app, frag)
    w.query()
    assert w.rounds == 0  # PEval is the whole algorithm
    assert (app._tier_info is not None) == (scale >= 12)  # hubs: tiers unforced
    got = lcc_reference.to_reference_form(by_id(frag, w.result_values()))
    want = lcc_reference.reference(kron.graph(scale), {})
    assert want.max() == 1.0 and 0.05 < want.mean() < 0.9  # triangles are there
    assert mismatches(RULE["rule"], got, want, RULE["eps"]) == 0
    # one lost triangle at any vertex that has one breaks the rule
    d = np.diff(lcc_reference.simple_adjacency(kron.graph(scale).mult).indptr)
    one_less = np.where(want > 0, want - 2.0 / np.maximum(d * (d - 1.0), 1.0), want)
    assert mismatches(RULE["rule"], one_less, want, RULE["eps"]) == (want > 0).sum()


def test_reference_is_the_dense_definition(kron):
    graph = kron.graph(8)
    a = lcc_reference.simple_adjacency(graph.mult).toarray()
    assert (a == a.T).all() and not a.diagonal().any() and a.max() == 1
    d = a.sum(axis=1)
    tri = np.diag(a @ a @ a) // 2
    assert (lcc_reference.triangles(lcc_reference.simple_adjacency(graph.mult))
            == tri).all() and tri.sum() > 0
    want = np.where(d >= 2, 2.0 * tri / np.maximum(d * (d - 1.0), 1.0), 0.0)
    assert np.array_equal(lcc_reference.reference(graph, {}), want)


def test_reference_in_row_blocks_is_the_reference_whole(kron, monkeypatch):
    graph = kron.graph(10)
    whole = lcc_reference.reference(graph, {})
    monkeypatch.setattr(lcc_reference, "BLOCK_PRODUCTS", 500)  # hundreds of blocks
    assert np.array_equal(lcc_reference.reference(graph, {}), whole)


def test_reference_ignores_doubled_edges_and_self_loops():
    src = np.array([0, 1, 2, 0, 0, 3, 3, 1])
    dst = np.array([1, 2, 0, 3, 0, 3, 0, 0])  # a triangle, a tail, loops, repeats
    _, mult = symmetric_csr(5, src, dst, np.ones(len(src)))
    got = lcc_reference.reference(types.SimpleNamespace(mult=mult), {})
    assert got.tolist() == [1 / 3, 1.0, 1.0, 0.0, 0.0]


def test_kronecker_simple_is_kroneckers_draws_made_simple():
    scale = 10
    n = 1 << scale
    src, dst, w = kronecker_simple.edges(GEN, scale)
    assert (src != dst).all()
    pairs = np.minimum(src, dst).astype(np.int64) * n + np.maximum(src, dst)
    assert len(np.unique(pairs)) == len(pairs)
    ds, dd, dw = kronecker.edges(GEN, scale)
    drawn = {}
    for s, d, x in zip(ds.tolist(), dd.tolist(), dw.tolist()):
        if s != d:
            drawn.setdefault((min(s, d), max(s, d)), []).append((s, d, x))
    assert len(drawn) == len(pairs) < len(ds)  # every pair once, some were repeats
    for s, d, x in zip(src.tolist(), dst.tolist(), w.tolist()):
        tuples = drawn[min(s, d), max(s, d)]
        assert (s, d, x) in tuples and x == min(t[2] for t in tuples)
    # in drawn order
    order = {t: i for i, t in reversed(list(enumerate(zip(ds.tolist(), dd.tolist(),
                                                           dw.tolist()))))}
    at = [order[t] for t in zip(src.tolist(), dst.tolist(), w.tolist())]
    assert at == sorted(at)


def test_kronecker_simple_files(kron, tmp_path):
    efile, vfile = str(tmp_path / "g.e"), str(tmp_path / "g.v")
    info = kronecker_simple.write_files(GEN, 8, efile, vfile)
    src, dst, w = kronecker_simple.edges(GEN, 8)
    rows = np.loadtxt(efile, dtype=np.int64)
    assert (rows == np.stack([src, dst, w], 1)).all()
    assert open(vfile).read().split() == [str(i) for i in range(256)]
    assert info["vertices"] == 256 and info["edges"] == len(src) < info["drawn"] == 4096
    assert info["pull_entries"] == 2 * len(src)


# ---- the resident adjacency ------------------------------------------------


def test_second_query_builds_nothing_and_a_rebuilt_fragment_builds_again(kron):
    frag = kron.load(10, 1)
    before = LCC_STATS.snapshot()
    w = Worker(APP_REGISTRY["lcc"](), frag)
    w.query()
    first = np.asarray(w.result_values()).copy()
    state = w.app.init_state(frag)
    placed = w._place_state(state)
    for k in ("ell", "cnt"):
        assert isinstance(state[k], jax.Array), k  # on the device already
        assert placed[k] is state[k], f"{k} was copied again"
    w.query()
    after = LCC_STATS.snapshot()
    assert after["builds"] - before["builds"] == 1
    assert after["cache_hits"] - before["cache_hits"] == 2
    assert np.array_equal(np.asarray(w.result_values()), first)
    assert after["ell_bytes"] == state["ell"].nbytes == frag.vp * after["d_max"] * 4
    assert after["oriented_edges"] == frag.host_oe[0].num_edges // 2
    # another worker, another app of the family: the fragment's adjacency
    Worker(ApexTriangleCount(), frag).query()
    assert LCC_STATS["builds"] == after["builds"]
    # another threshold is another adjacency
    Worker(APP_REGISTRY["lcc"](), frag).query(degree_threshold=5)
    assert LCC_STATS["builds"] == after["builds"] + 1
    # a rebuilt fragment is another key
    again = kron.load(10, 1)
    Worker(APP_REGISTRY["lcc"](), again).query()
    assert LCC_STATS["builds"] == after["builds"] + 2


def test_the_adjacency_goes_with_its_fragment(kron):
    import gc

    from libgrape_lite_tpu.models import lcc_beta

    frag = kron.load(10, 1)
    Worker(APP_REGISTRY["lcc"](), frag).query()
    # earlier cases' fragments may still wait for the collector: count
    # after it has run, so that only this one goes between the counts
    gc.collect()
    held = len(lcc_beta._ADJACENCY_CACHE)
    del frag
    gc.collect()
    assert len(lcc_beta._ADJACENCY_CACHE) == held - 1


@pytest.mark.parametrize("app", ["lcc", "apex"])
def test_the_adjacency_is_in_no_carry_and_no_result(kron, app):
    frag = kron.load(12, 1)
    made = APP_REGISTRY["lcc"]() if app == "lcc" else ApexTriangleCount()
    w = Worker(made, frag)
    out = w.query()
    keep = {"lcc"} | ({"tri"} if app == "apex" else set())
    assert set(out) == keep == set(w._result_state)
    assert made.ephemeral_keys == {"ell", "cnt", "eperm"}
    _, carry, eph_part, _ = w._staged(
        lambda: made.init_state(frag), w._place_state, lambda st: w._runner_for(0, st))
    assert set(carry) == keep and set(eph_part) == {"ell", "cnt", "eperm"}


def test_query_lanes_counts_the_schedule(kron):
    frag = kron.load(12, 2)
    app = APP_REGISTRY["lcc"]()
    state = app.init_state(frag)
    lanes = sum(n * c * w for _, n, c, w, _ in app._tier_info)  # a pass: one step
    assert LCC_STATS["tiers"] == len(app._tier_info) >= 2
    assert LCC_STATS["shard_lanes"] == lanes
    assert LCC_STATS["query_lanes"] == 2 * lanes  # two passes, each its own segments
    # a tier holds a segment a step, side by side
    assert state["eperm"].shape == (2, 2 * sum(n * c for _, n, c, _, _ in app._tier_info))
    assert [off for off, *_ in app._tier_info] == list(np.cumsum(
        [0] + [2 * n * c for _, n, c, _, _ in app._tier_info[:-1]]))
    assert LCC_STATS["d_max"] == state["ell"].shape[-1] == app._tier_info[-1][3]
    assert LCC_STATS["fold_runs_max"] == app._tier_info[-1][4]


@pytest.mark.parametrize("scale", [10, 12])
@pytest.mark.parametrize("fnum", [1, 2, 4])
def test_ring_counters(kron, scale, fnum):
    """What the ring sends and what a shard walks a pass, from the geometry
    of the adjacency the query read; nothing of the ring on one fragment."""
    frag = kron.load(scale, fnum)
    app = APP_REGISTRY["lcc"]()
    state = app.init_state(frag)
    stats = LCC_STATS.snapshot()
    passes = fnum if fnum > 1 else 0
    assert stats["ring_passes"] == passes
    assert stats["ring_bytes"] == passes * frag.vp * stats["d_max"] * 4
    assert stats["ring_bytes"] * fnum == passes * state["ell"].nbytes
    assert stats["query_lanes"] == max(passes, 1) * stats["shard_lanes"] > 0
    if app._tier_info is not None:  # a pass walks a step's segment of each tier
        assert stats["shard_lanes"] == sum(n * c * w for _, n, c, w, _ in app._tier_info)
        assert state["eperm"].shape[1] == fnum * sum(n * c for _, n, c, _, _ in app._tier_info)
        assert stats["flush_updates"] == state["eperm"].shape[1]
        assert stats["fold_runs_max"] == app._tier_info[-1][4]
    # the orientation keeps each undirected edge at one of its ends
    assert stats["shard_kept_max"] >= stats["shard_kept_min"] > 0
    # a shard's kept entries fall over its fnum steps
    assert stats["step_kept_max"] >= stats["step_kept_min"] > 0
    assert stats["step_kept_min"] * fnum <= stats["shard_kept_min"]
    assert stats["step_kept_max"] * fnum >= stats["shard_kept_max"]
    if fnum == 1:
        assert stats["step_kept_max"] == stats["step_kept_min"] == stats["shard_kept_max"]
    assert (stats["shard_kept_min"] * fnum <= stats["oriented_edges"]
            <= stats["shard_kept_max"] * fnum)
    assert stats["oriented_edges"] == kron.graph(scale).mult.nnz // 2


def test_apex_counts_are_the_references_triangles(kron):
    frag = kron.load(10, 2)
    w = Worker(ApexTriangleCount(), frag)
    w.query()
    apex = by_id(frag, w.result_values())
    tri = lcc_reference.triangles(lcc_reference.simple_adjacency(kron.graph(10).mult))
    assert apex.sum() * 3 == tri.sum() > 0


def test_every_counter_of_the_namespace_is_in_the_inventory():
    """docs/OBSERVABILITY.md's row of the `lcc` namespace names each field."""
    with open(os.path.join(ROOT, "docs", "OBSERVABILITY.md")) as f:
        row, = [ln for ln in f if ln.startswith("| federated counter | `lcc` namespace")]
    for field in LCC_STATS.snapshot():
        assert f"`{field}`" in row, field


# ---- the scopes, and what they left alone ----------------------------------


def lowered(app, frag, debug_info: bool, **params) -> str:
    w = Worker(app, frag)
    state = w._place_state(app.init_state(frag, **params))
    eph = frozenset(getattr(app, "ephemeral_keys", ()) or ())
    carry = {k: v for k, v in state.items() if k not in eph}
    eph_part = {k: v for k, v in state.items() if k in eph}
    return w._runner_for(0, state).lower(frag.dev, carry, eph_part).as_text(
        debug_info=debug_info)


@pytest.mark.parametrize("tiers", ["2,8", "0"])
@pytest.mark.parametrize("fnum", [1, 4])
def test_lcc_names_its_step(graph_cache, monkeypatch, fnum, tiers):
    monkeypatch.setenv("GRAPE_LCC_TIERS", tiers)  # tiered and untiered walks
    text = lowered(APP_REGISTRY["lcc"](), graph_cache(fnum), True)
    for scope in SCOPES:
        assert scope in text, f"no {scope} in LCC's lowered runner"


def locs_under(text: str, scope: str) -> dict:
    """`#loc7` -> what its name stack holds behind `scope`."""
    # `#loc7 = loc("grape.lcc.intersect/eq"(#loc3))`: the name stack, with
    # the enclosing loops and the primitive as components behind the scope
    return {ref: name.split(scope + "/", 1)[1] for ref, name in
            re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M)
            if scope + "/" in name}


def functions(text: str) -> dict:
    """Name -> text of each function of the lowered module but the first."""
    return {chunk.split("(", 1)[0].split("@")[-1]: chunk
            for chunk in text.split("\n  func.func ")[1:]}


def steps_under(text: str, scope: str):
    """(JAX's names, StableHLO operations) of what the lowered `text`
    holds under `scope`, the functions called from there included."""
    locs = locs_under(text, scope)
    names = {part for name in locs.values() for part in name.split("/")}
    ops, calls = set(), set()
    for line in text.splitlines():
        at = re.search(r"loc\((#loc\d+)\)$", line)
        if at and at.group(1) in locs:
            ops.update(re.findall(r"\bstablehlo\.\w+", line)[:1])
            calls.update(re.findall(r"\bcall @([\w.]+)", line))
    bodies = functions(text)
    seen = set()
    while calls - seen:
        callee = (calls - seen).pop()
        seen.add(callee)
        ops.update(re.findall(r"\bstablehlo\.\w+", bodies[callee]))
        calls.update(re.findall(r"\bcall @([\w.]+)", bodies[callee]))
    return names, ops


@pytest.mark.parametrize("tiers", ["2,8", "0"])
@pytest.mark.parametrize("fnum", [1, 4])
def test_lcc_intersects_without_a_search(graph_cache, monkeypatch, fnum, tiers):
    """Nothing under `grape.lcc.intersect` addresses by data or loops: no
    gather, no sort, no while (a `searchsorted` is a while of gathers, a
    `take_along_axis` a gather), so the search cannot come back unnoticed."""
    monkeypatch.setenv("GRAPE_LCC_TIERS", tiers)
    text = lowered(APP_REGISTRY["lcc"](), graph_cache(fnum), True)
    names, ops = steps_under(text, "grape.lcc.intersect")
    assert {"transpose", "eq", "reduce_or", "reduce_sum"} <= names
    assert {"stablehlo.compare", "stablehlo.transpose", "stablehlo.reduce"} <= ops
    for word in ("while", "gather", "sort", "scan", "search", "take_along",
                 "dynamic_slice"):
        assert not [n for n in names | ops if word in n], (word, names, ops)


def ops_at(part: str, locs: dict) -> set:
    """StableHLO operations of the lines of `part` located in `locs`."""
    ops = set()
    for line in part.splitlines():
        at = re.search(r"loc\((#loc\d+)\)$", line)
        if at and at.group(1) in locs:
            ops.update(re.findall(r"\bstablehlo\.\w+", line)[:1])
    return ops


def loops(text: str):
    """What each `stablehlo.while` of the lowered `text` runs: its regions
    and every function called from them."""
    bodies, lines = functions(text), text.splitlines()
    for i, line in enumerate(lines):
        if "stablehlo.while(" not in line:
            continue
        pad = line[:len(line) - len(line.lstrip())]
        end = next(j for j in range(i + 1, len(lines))
                   if lines[j].startswith(pad + "} loc("))
        part, seen = "\n".join(lines[i:end]), set()
        while (calls := set(re.findall(r"\bcall @([\w.]+)", part)) - seen):
            seen |= calls
            part += "".join(bodies[callee] for callee in calls)
        yield part


def scatters_under(text: str, scope: str):
    """(shape of the updates, whether an update is a window and not a
    scalar) of every `stablehlo.scatter` of the lowered `text` under `scope`."""
    locs, lines, found = locs_under(text, scope), text.splitlines(), []
    for i, line in enumerate(lines):
        if '"stablehlo.scatter"' not in line:
            continue
        pad = line[:len(line) - len(line.lstrip())]
        # `}) : (tensor<65536x14xi32>, tensor<4096x2xi32>, tensor<4096x8xi32>) -> ...`
        end = next(ln for ln in lines[i + 1:] if ln.startswith(pad + "}) : ("))
        if re.search(r"loc\((#loc\d+)\)$", end).group(1) in locs:
            updates = re.findall(r"tensor<([\dx]+)xi\d+>", end.split("->")[0])[2]
            window = re.search(r"update_window_dims = \[\d", line)
            found.append((tuple(map(int, updates.split("x"))), bool(window)))
    return found


@pytest.mark.parametrize("tiers", ["2,8", "0"])
@pytest.mark.parametrize("fnum", [1, 4])
def test_lcc_credits_the_far_end_by_slot(graph_cache, monkeypatch, fnum, tiers):
    """Under `grape.lcc.credit` a chunk writes C rows of width W into the
    slot table and C elements into the credit table, never C x W elements
    (8 ns each on the chip), so the element scatter cannot come back
    unnoticed; the flush by id reads the table in a walk of its own, after
    the chunk loops and after the ring."""
    monkeypatch.setenv("GRAPE_LCC_TIERS", tiers)
    app, frag = APP_REGISTRY["lcc"](), graph_cache(fnum)
    text = lowered(app, frag, True)
    d = LCC_STATS["d_max"]
    walks = ({(c, w) for _, _, c, w, _ in app._tier_info} if tiers != "0"
             else {(min(4096, len(frag.host_oe[0].edge_src)), d)})
    assert (len(walks) == 3) == (tiers != "0")
    # a chunk of a tier's entries holds as many source rows as the host
    # counted at most, summed before they are written where that is fewer
    # (at most half the chunk: above that the sum costs more than it spares)
    folds = ({(r if 2 * r <= c else c, w) for _, _, c, w, r in app._tier_info}
             if tiers != "0" else walks)
    assert (folds != walks) == (tiers != "0")
    scatters = scatters_under(text, "grape.lcc.credit")
    assert {shape for shape, window in scatters if window} == folds  # the row folds
    for shape, window in scatters:
        if not window:  # into the credit table: the edge's two ends, the flush
            assert shape in {(c,) for c, _ in walks}, shape
    assert not [s for s in scatters_under(text, "grape.lcc.rows")
                + scatters_under(text, "grape.lcc.intersect")]
    # the main walk scatters and gathers nothing under the scope; the flush
    # gathers the table's rows, in loops that hold no intersection
    credit = locs_under(text, "grape.lcc.credit")
    meet = locs_under(text, "grape.lcc.intersect")
    flushing = 0
    for part in loops(text):
        reads = "stablehlo.gather" in ops_at(part, credit)
        assert not (reads and ops_at(part, meet)), "the flush is inside a chunk loop"
        flushing += reads
    assert flushing == len(walks)


@pytest.mark.parametrize("fnum", [1, 4])
def test_apex_mode_has_no_far_end_and_no_table(graph_cache, fnum):
    text = lowered(ApexTriangleCount(), graph_cache(fnum), True)
    scatters = scatters_under(text, "grape.lcc.credit")
    assert scatters and not [s for s, window in scatters if window]
    assert "stablehlo.gather" not in steps_under(text, "grape.lcc.credit")[1]


@pytest.mark.parametrize("runs", [200, 56])
@pytest.mark.parametrize("w", [1, 7, 64, 130])
def test_slot_fold_and_flush_are_the_credits_by_id(w, runs):
    """`_fold_rows` over two chunks (row by row, and with a chunk's runs
    summed first where their number is bounded under the chunk), then
    `_slot_of` at every real slot's edge: NumPy's `add.at` of the far-end
    credits by id, as the parent scattered them."""
    from libgrape_lite_tpu.models.lcc_beta import _fold_rows, _slot_of

    rng = np.random.default_rng(w)
    vp, c, n_pad = 50, 200, 1000
    cnt = rng.integers(0, w + 1, size=vp)
    cnt[:2] = 0, w
    ids = np.sort(rng.random((vp, n_pad)).argsort(axis=1)[:, :w], axis=1)
    ell = np.where(np.arange(w)[None, :] < cnt[:, None], ids, n_pad).astype(np.int32)
    want = np.zeros(n_pad + 1, dtype=np.int64)
    slot = jax.numpy.zeros((vp, w), dtype=np.int32)
    for _ in range(2):
        sl = np.sort(rng.integers(0, vp, size=c)).astype(np.int32)  # repeated rows
        sl[-5:] = vp - 1  # as a schedule's padding
        assert len(np.unique(sl)) <= min(runs, vp) < c
        q = ell[sl]
        hit = (rng.random((c, w)) < 0.3) & (q != n_pad)
        np.add.at(want, np.where(hit, q, n_pad).reshape(-1), hit.reshape(-1))
        slot = _fold_rows(slot, sl, hit.T, runs)
    slot = np.asarray(slot)
    assert slot.dtype == np.int32 and slot.sum() == want[:n_pad].sum() > 0
    assert slot.max() > 1
    # one edge (v, u) a real slot, in any order
    ev, ej = np.nonzero(ell != n_pad)
    order = rng.permutation(len(ev))
    ev, eu = ev[order], ell[ev, ej][order]
    far = np.asarray(_slot_of(slot[ev], ell[ev], eu))
    got = np.zeros(n_pad + 1, dtype=np.int64)
    np.add.at(got, eu, far)
    assert np.array_equal(got, want) and want[n_pad] == 0


@pytest.mark.parametrize("fnum,rows,flush", [(1, 49152, 49152), (2, 40960, 40960),
                                             (4, 32768, 32768)])
def test_credit_counters(kron, fnum, rows, flush):
    """Row updates folded into the slot table a query a device (a ring pass
    folds the padded segments of its step) and element updates of the flush
    (the whole schedule once: `fnum` segments a tier), from the geometry, at
    scale 12."""
    frag = kron.load(12, fnum)
    app = APP_REGISTRY["lcc"]()
    app.init_state(frag)
    stats = LCC_STATS.snapshot()
    entries = sum(n * c for _, n, c, _, _ in app._tier_info)  # a pass
    assert stats["flush_updates"] == fnum * entries == flush
    assert stats["credit_rows"] == max(stats["ring_passes"], 1) * entries == rows
    assert stats["flush_updates"] >= stats["shard_kept_max"]
    assert entries >= stats["step_kept_max"]
    assert stats["credit_rows"] * app._tier_info[0][3] <= stats["query_lanes"]


# ---- the schedule cut by ring step ------------------------------------------


def schedule(frag):
    """(tier_info, eperm [fnum, L], cnt [fnum, vp]) of the fragment's adjacency."""
    app = APP_REGISTRY["lcc"]()
    state = app.init_state(frag)
    return app._tier_info, np.asarray(state["eperm"]), np.asarray(state["cnt"])


def kept_by_rule(frag) -> list:
    """Per shard the `oe` entries the "lo" orientation keeps, from the host
    CSRs alone (the graph is simple: no entry repeats)."""
    fnum, vp = frag.fnum, frag.vp
    deg = np.concatenate([np.diff(frag.host_oe[f].indptr) for f in range(fnum)])
    kept = []
    for f in range(fnum):
        oe = frag.host_oe[f]
        v = f * vp + oe.edge_src[:oe.num_edges].astype(np.int64)
        u = oe.edge_nbr[:oe.num_edges].astype(np.int64)
        kept.append(np.flatnonzero(
            ((deg[u] > deg[v]) | ((deg[u] == deg[v]) & (u > v))) & (u != v)))
    return kept


def source_rows(frag, f, idx):
    """The local source rows of schedule entries `idx` as the step reads them
    (a pad reads the shard's last entry)."""
    ep = len(frag.host_oe[f].edge_src)
    return np.minimum(frag.host_oe[f].edge_src[np.minimum(idx, ep - 1)], frag.vp - 1)


@pytest.mark.parametrize("fnum", [2, 4])
def test_an_entry_sits_in_the_segment_of_its_ring_step(kron, fnum):
    """Every kept entry of shard f is scheduled once, in its source row's
    tier and in the segment of step (nbr_fid - f) % fnum, the one pass at
    which its neighbour's block is on device f; a segment is in `oe` order
    with its padding behind, so a chunk's source rows ascend."""
    frag = kron.load(12, fnum)
    info, eperm, cnt = schedule(frag)
    vp, ep = frag.vp, len(frag.host_oe[0].edge_src)
    assert eperm.shape[1] == info[-1][0] + fnum * info[-1][1] * info[-1][2]
    lows = [0] + [w for _, _, _, w, _ in info[:-1]]
    for f, kept in enumerate(kept_by_rule(frag)):
        oe = frag.host_oe[f]
        assert np.array_equal(np.sort(eperm[f][eperm[f] < ep]), kept)
        for (off, n, c, w, _), low in zip(info, lows):
            tier = eperm[f, off:off + fnum * n * c].reshape(fnum, n * c)
            for s in range(fnum):
                idx = tier[s][tier[s] < ep]
                assert ((oe.edge_nbr[idx] // vp - f) % fnum == s).all()
                width = cnt[f][oe.edge_src[idx]]
                assert ((low < width) & (width <= w)).all()
                assert (np.diff(idx) > 0).all() and (tier[s][len(idx):] == ep).all()
                rows = source_rows(frag, f, tier[s]).reshape(n, c)
                assert (np.diff(rows, axis=1) >= 0).all()
    # the fullest segment decides every segment's chunks
    for t, (off, n, c, _, _) in enumerate(info):
        fullest = max(((eperm[f, off:off + fnum * n * c] < ep).reshape(fnum, -1)
                       .sum(axis=1).max()) for f in range(fnum))
        assert n == max(1, -(-fullest // c))


@pytest.mark.parametrize("fnum", [2, 4])
def test_the_fold_bound_is_counted_from_the_schedule(kron, fnum):
    """A tier's `runs` is the most distinct source rows (the pad run among
    them) any chunk of any shard holds: `_fold_rows` with it is the credit
    element by element on the chunk that attains it, and one less loses that
    chunk's last run without a word."""
    from libgrape_lite_tpu.models.lcc_beta import _fold_rows

    frag = kron.load(12, fnum)
    info, eperm, _ = schedule(frag)
    vp = frag.vp
    rng = np.random.default_rng(fnum)
    for off, n, c, w, runs in info:
        rows = np.concatenate([
            source_rows(frag, f, eperm[f, off:off + fnum * n * c]).reshape(-1, c)
            for f in range(fnum)])
        distinct = np.array([len(np.unique(r)) for r in rows])
        assert distinct.max() == runs and 2 * (runs - 1) <= c  # the runs are summed
        sl = rows[distinct.argmax()]
        hit = rng.random((w, c)) < 0.3
        want = np.zeros((vp, w), dtype=np.int64)
        np.add.at(want, (sl[:, None], np.arange(w)[None, :]), hit.T)
        empty = jax.numpy.zeros((vp, w), dtype=np.int32)
        assert np.array_equal(_fold_rows(empty, sl, hit, runs), want)
        assert np.array_equal(_fold_rows(empty, sl, hit, c), want)  # row by row
        short = np.asarray(_fold_rows(empty, sl, hit, runs - 1))
        assert short.sum() < want.sum()


def test_one_fragment_keeps_the_uncut_layout(kron):
    """One fragment has one step: a tier is one segment of the kept entries of
    its rows in `oe` order, padded to whole chunks, as before the cut."""
    frag = kron.load(12, 1)
    info, eperm, cnt = schedule(frag)
    vp, ep = frag.vp, len(frag.host_oe[0].edge_src)
    kept, src = kept_by_rule(frag)[0], frag.host_oe[0].edge_src
    tier = np.searchsorted([w for _, _, _, w, _ in info], cnt[0][src[kept]])
    want = []
    for t, (off, n, c, _, _) in enumerate(info):
        idx = kept[tier == t]
        assert off == sum(map(len, want)) and n == max(1, -(-len(idx) // c))
        want.append(np.concatenate([idx, np.full(n * c - len(idx), ep)]))
    assert np.array_equal(eperm[0], np.concatenate(want))
    assert eperm.dtype == np.int32 and eperm.shape == (1, sum(map(len, want)))


@pytest.fixture(scope="module")
def answers(kron):
    """(scale, fnum, app) -> the answer by vertex id."""
    got = {}

    def answer(scale: int, fnum: int, app: str):
        if (scale, fnum, app) not in got:
            frag = kron.load(scale, fnum)
            w = Worker(APP_REGISTRY["lcc"]() if app == "lcc" else ApexTriangleCount(), frag)
            w.query()
            got[scale, fnum, app] = by_id(frag, w.result_values())
        return got[scale, fnum, app]

    return answer


@pytest.mark.parametrize("app", ["lcc", "apex"])
@pytest.mark.parametrize("fnum", [2, 4])
@pytest.mark.parametrize("scale", [10, 12])
def test_several_fragments_answer_as_one_bit_for_bit(answers, scale, fnum, app):
    """Every oriented edge meets its target block once, at the step the host
    computed: the same integer credits, so the same bytes."""
    one, several = answers(scale, 1, app), answers(scale, fnum, app)
    assert several.dtype == one.dtype and several.tobytes() == one.tobytes()
    assert one.sum() > 0


def trip_count(loop: str):
    """The static trip count of a lowered `fori_loop`: the constant its
    condition compares with (None where it compares with no constant)."""
    cond = loop.split("cond {", 1)[1].split("} do {", 1)[0]
    found = re.search(r"stablehlo\.constant dense<(\d+)>", cond)
    return int(found.group(1)) if found else None


@pytest.mark.parametrize("fnum", [1, 4])
def test_a_ring_pass_walks_its_own_steps_chunks(graph_cache, monkeypatch, fnum):
    """In the lowered runner the chunk loops that intersect run a step's
    chunks of each tier, not the tier's `fnum` segments; the flush's loops run
    them all; the ring runs `fnum` passes."""
    monkeypatch.setenv("GRAPE_LCC_TIERS", "2,8")
    app, frag = APP_REGISTRY["lcc"](), graph_cache(fnum)
    text = lowered(app, frag, True)
    meet = locs_under(text, "grape.lcc.intersect")
    credit = locs_under(text, "grape.lcc.credit")
    ring, passes, flushes = [], [], []
    for part in loops(text):
        if "stablehlo.collective_permute" in part:
            ring.append(trip_count(part))
        elif ops_at(part, meet):
            passes.append(trip_count(part))
        elif "stablehlo.gather" in ops_at(part, credit):
            flushes.append(trip_count(part))
    a_step = sorted(n for _, n, _, _, _ in app._tier_info)
    assert len(a_step) == 3 and ring == [fnum] * (fnum > 1)
    assert sorted(passes) == a_step
    assert sorted(flushes) == [fnum * n for n in a_step]


@pytest.mark.parametrize("tiers", ["2,8", "0"])
@pytest.mark.parametrize("fnum", [1, 2, 4])
def test_the_ring_is_named_where_there_is_one(graph_cache, monkeypatch, fnum, tiers):
    monkeypatch.setenv("GRAPE_LCC_TIERS", tiers)
    text = lowered(APP_REGISTRY["lcc"](), graph_cache(fnum), True)
    assert (RING in text) == (fnum > 1)
    if fnum > 1:  # the name is on the permute and on nothing else
        names, ops = steps_under(text, RING)
        assert names == {"ppermute"} and ops == {"stablehlo.collective_permute"}
        assert text.count("stablehlo.collective_permute") == 1


@pytest.mark.parametrize("fnum", [1, 4])
def test_scopes_leave_lccs_lowered_program_alone(graph_cache, monkeypatch, fnum):
    frag = graph_cache(fnum)
    scoped = lowered(APP_REGISTRY["lcc"](), frag, False)
    assert "grape." not in scoped
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    assert "grape." not in lowered(APP_REGISTRY["lcc"](), frag, True)
    assert lowered(APP_REGISTRY["lcc"](), frag, False) == scoped


@pytest.mark.parametrize("app", ["pagerank", "bfs", "sssp", "wcc", "cdlp"])
def test_the_other_runners_hold_nothing_of_lcc(app, graph_cache):
    """A query of LCC on the fragment, its resident adjacency included,
    leaves the program another app lowers on it as it was."""
    frag = graph_cache(1)
    params = {"bfs": {"source": 6}, "sssp": {"source": 6}}.get(app, {})
    before = lowered(APP_REGISTRY[app](), frag, False, **params)
    Worker(APP_REGISTRY["lcc"](), frag).query()
    assert "grape.lcc" not in lowered(APP_REGISTRY[app](), frag, True, **params)
    assert lowered(APP_REGISTRY[app](), frag, False, **params) == before
