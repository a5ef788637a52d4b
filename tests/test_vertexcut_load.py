"""The vertex cut on the normal path: `LoadGraph(vertex_cut=True)` ->
`LoadVertexcutGraph` -> `Worker(pagerank_vc).query`.

A scale-10 Kronecker draw of the benchmark's own generator block
(`benchmarks/configs/g500-s21-vc2x2.json`), cut 2 x 2 over four of the
virtual devices: the answer against the cell's plain reference under the
cell's rule and against the 1-D `pagerank`, the tiles' pull CSRs, the
round's lowered text, the serialization cache, the set-up phases, the
runner's two vertex-cut branches, and where `mesh2d` puts the devices.
"""

import json
import os
import types

import jax
import numpy as np
import pytest

from benchmarks.compare import mismatches
from benchmarks.graphs import kronecker
from benchmarks.references import pagerank as pagerank_reference
from benchmarks.references import pagerank_vc as pagerank_vc_reference
from libgrape_lite_tpu.fragment.loader import (
    LoadGraph, LoadGraphSpec, LoadVertexcutGraph, _cache_dir,
)
from libgrape_lite_tpu.fragment.vertexcut import (
    VC_TILE_STATS, ImmutableVertexcutFragment, VCDeviceFragment,
    VCPullFragment,
)
from libgrape_lite_tpu.models import APP_REGISTRY
from libgrape_lite_tpu.parallel.comm_spec import CommSpec, _by_coords
from libgrape_lite_tpu.worker.worker import Worker
from tests.conftest import dataset_path

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "g500-s21-vc2x2.json")) as _f:
    CONFIG = json.load(_f)
SCALE, PARAMS = 10, {"delta": 0.85, "max_round": 10}


@pytest.fixture(scope="module")
def drawn(tmp_path_factory):
    """The graph's files, its edge list and a stand-in for the
    benchmark's `Dataset` (what a reference asks of one)."""
    from benchmarks.graphs.csr import symmetric_csr

    d = tmp_path_factory.mktemp("kron10")
    efile, vfile = str(d / "graph.e"), str(d / "graph.v")
    kronecker.write_files(CONFIG["generator"], SCALE, efile, vfile)
    src, dst, w = kronecker.edges(CONFIG["generator"], SCALE)
    graph = types.SimpleNamespace(
        n=1 << SCALE, edges=(src, dst, w),
        mult=symmetric_csr(1 << SCALE, src, dst, w)[1])
    return types.SimpleNamespace(efile=efile, vfile=vfile, graph=graph,
                                 prefix=str(d / "fragments"))


def spec_of(**more):
    spec = dict(CONFIG["load_graph_spec"], **more)
    spec["edata_dtype"] = np.dtype(spec["edata_dtype"]).type
    return LoadGraphSpec(**spec)


@pytest.fixture(scope="module")
def loaded(drawn):
    return LoadGraph(drawn.efile, drawn.vfile, CommSpec(fnum=4), spec_of())


def by_vertex(frag, values):
    out = np.empty(frag.total_vnum, dtype=values.dtype)
    for f in range(frag.fnum):
        out[frag.inner_oids(f)] = values[f, :frag.inner_vertices_num(f)]
    return out


def answer(app, frag):
    w = Worker(APP_REGISTRY[app](), frag)
    w.query(**PARAMS)
    assert w.rounds == PARAMS["max_round"]
    return by_vertex(frag, w.result_values())


# ---- the answer ----


def test_the_configuration_asks_for_the_cut():
    assert CONFIG["load_graph_spec"]["vertex_cut"] is True
    assert CONFIG["fnum"] == CONFIG["chips"] == 4


def test_pagerank_vc_through_loadgraph_holds_the_cells_rule(drawn, loaded):
    assert isinstance(loaded, ImmutableVertexcutFragment)
    assert loaded.layout == "pull" and isinstance(loaded.dev, VCPullFragment)
    got = answer("pagerank_vc", loaded)
    want = pagerank_vc_reference.reference(drawn.graph, PARAMS)
    rule = CONFIG["guarantees"]["pagerank_vc"]
    assert rule == {"rule": "eps", "eps": 0.001,
                    "against": "f64 power iteration, every vertex"}
    assert mismatches(rule["rule"], got, want, rule["eps"]) == 0
    np.testing.assert_allclose(got, want, rtol=1e-9)  # this lane is f64
    assert abs(got.sum() - 1.0) < 1e-9


def test_pagerank_vc_agrees_with_the_1d_pagerank(drawn, loaded):
    one_d = LoadGraph(drawn.efile, drawn.vfile, CommSpec(fnum=4),
                      spec_of(vertex_cut=False))
    want = np.empty(1 << SCALE)
    w = Worker(APP_REGISTRY["pagerank"](), one_d)
    w.query(**PARAMS)
    values = w.result_values()
    for f in range(one_d.fnum):
        want[one_d.inner_oids(f)] = values[f, :one_d.inner_vertices_num(f)]
    np.testing.assert_allclose(answer("pagerank_vc", loaded), want,
                               rtol=2e-6)


def test_the_two_references_agree_on_an_undirected_graph(drawn):
    """The vertex cut changes the layout and not the answer: libgrape-lite's
    `pagerank_vc.h` on the stored edge list is Graphalytics' PageRank on
    the undirected multigraph."""
    a = pagerank_vc_reference.reference(drawn.graph, PARAMS)
    b = pagerank_reference.reference(drawn.graph, PARAMS)
    np.testing.assert_allclose(a, b, rtol=1e-12, atol=0)
    assert (pagerank_vc_reference.to_reference_form(a) == a).all()
    src, dst, _ = drawn.graph.edges
    assert (src == dst).any()  # a self-loop counts twice in both


# ---- the tiles ----


def test_the_tiles_pull_csrs_hold_every_edge_once_in_each_order(drawn,
                                                                loaded):
    frag, (src, dst, _) = loaded, drawn.graph.edges
    k, vc, chunk = frag.k, frag.vc, frag.chunk
    assert (k, chunk, vc) == (2, 512, 512)
    indptr, rows, nbr, mask = frag._host_pull
    width = rows.shape[1]
    assert width % 128 == 0 and indptr.shape == (4, 2 * vc + 1)
    s_arr, d_arr, _, m_arr = frag._host_tiles
    for f in range(4):
        i, j = divmod(f, k)
        mine = (src // chunk == i) & (dst // chunk == j)
        n = int(mine.sum())
        assert int(m_arr[f].sum()) == n
        # the COO tile holds the list's edges in the list's order
        assert (s_arr[f, :n] == i * vc + src[mine] % chunk).all()
        assert (d_arr[f, :n] == j * vc + dst[mine] % chunk).all()
        # offsets monotone, rows sorted, pads behind the last row, masked
        assert indptr[f, 0] == 0 and indptr[f, -1] == 2 * n
        assert (np.diff(indptr[f]) >= 0).all()
        assert (np.diff(rows[f]) >= 0).all()
        assert mask[f, :2 * n].all() and not mask[f, 2 * n:].any()
        assert (rows[f, 2 * n:] == 2 * vc).all()
        assert (rows[f, :2 * n] == np.repeat(
            np.arange(2 * vc), np.diff(indptr[f]))).all()
        # by destination (rows below vc read the row copy) then by
        # source (rows from vc read the column copy, behind it)
        so, do = src[mine] % chunk, dst[mine] % chunk
        by_dst = indptr[f, vc]
        assert by_dst == n
        want = sorted(zip(do.tolist(), so.tolist()))
        assert list(zip(rows[f, :n].tolist(), nbr[f, :n].tolist())) == want
        want = sorted(zip((vc + so).tolist(), (vc + do).tolist()))
        assert list(zip(rows[f, n:2 * n].tolist(),
                        nbr[f, n:2 * n].tolist())) == want
    # what is placed is what the host holds, and nothing of the COO form
    leaves = jax.tree_util.tree_leaves(frag.dev)
    assert len(leaves) == 4 and frag.dev.pull.edge_w is None
    for host, dev in zip((indptr, rows, nbr, mask),
                         (frag.dev.pull.indptr, frag.dev.pull.edge_src,
                          frag.dev.pull.edge_nbr, frag.dev.pull.edge_mask)):
        assert (np.asarray(dev) == host).all()


def test_masters_sit_on_the_diagonal(loaded):
    owned = [loaded.inner_vertices_num(f) for f in range(4)]
    assert owned == [512, 0, 0, 512]
    assert (loaded.inner_oids(0) == np.arange(512)).all()
    assert (loaded.inner_oids(3) == np.arange(512, 1024)).all()


def test_the_tile_profile_is_published_with_the_load(drawn):
    from libgrape_lite_tpu.obs import federation

    VC_TILE_STATS["scans"] = 0
    frag = LoadGraph(drawn.efile, drawn.vfile, CommSpec(fnum=4), spec_of())
    snap = federation.snapshot()["vc_tiles"]
    assert snap["scans"] >= 1 and snap["tiles"] == 4
    assert snap["edges"] == 16384 == frag.total_enum
    assert snap["pad_slots"] == 4 * snap["edge_slots"] - 16384
    assert snap["tile_skew"] == frag.tile_stats()["tile_skew"] >= 1.0


def test_symmetrised_storage_keeps_the_coo_tiles(drawn):
    frag = LoadVertexcutGraph(drawn.efile, drawn.vfile, CommSpec(fnum=4),
                              spec_of(), symmetrize=True)
    assert frag.layout == "coo" and isinstance(frag.dev, VCDeviceFragment)
    assert frag._host_pull is None and frag.symmetrized
    with pytest.raises(ValueError, match="'pull' form"):
        APP_REGISTRY["pagerank_vc"]().init_state(frag)


def test_a_min_fold_app_refuses_the_pull_form(loaded):
    with pytest.raises(ValueError, match="'coo' form"):
        APP_REGISTRY["wcc_vc"]().init_state(loaded)


# ---- the round ----


def test_the_lowered_round_holds_no_scatter(loaded):
    """A round is the pull's gather and scan fold, the two axis sums and
    the transposes; the COO form's two E-wide scatters are gone (on the
    chip's backend the gather is the kernel's:
    tests/test_lanes_compile_v5e.py)."""
    from libgrape_lite_tpu.ops.segment import FOLD_STATS, GATHER_STATS

    w = Worker(APP_REGISTRY["pagerank_vc"](), loaded)
    state = w._place_state(w.app.init_state(loaded, **PARAMS))
    before = FOLD_STATS.snapshot(), GATHER_STATS.snapshot()
    text = w._make_runner(w.app.max_rounds)(state).lower(
        loaded.dev, state, {}).as_text(debug_info=True)
    assert "stablehlo.scatter" not in text
    assert FOLD_STATS.snapshot()["scatter"] == before[0]["scatter"]
    assert FOLD_STATS.snapshot()["scan"] == before[0]["scan"] + 1
    assert sum(GATHER_STATS.snapshot().values()) == sum(
        before[1].values()) + 1
    for scope in ("grape.vc.gather_master", "grape.vc.scatter",
                  "grape.pull.gather", "grape.pull.fold",
                  "grape.app.update"):
        assert scope in text, scope
    assert text.count("stablehlo.all_reduce") >= 2
    assert "stablehlo.collective_permute" in text


def test_the_state_is_sharded_two_ways(loaded):
    app = APP_REGISTRY["pagerank_vc"]()
    state = app.init_state(loaded, **PARAMS)
    wide = {k for k, v in state.items() if np.ndim(v)}
    assert wide == {"rank_col", "rank_row", "deg_col", "vmask_col"}
    assert set(app.custom_specs()) == wide
    assert all(state[k].shape == (loaded.k * loaded.vc,) for k in wide)


# ---- the cache ----


def test_deserialize_gives_a_byte_identical_fragment(drawn):
    spec = spec_of(serialize=True, deserialize=True,
                   serialization_prefix=drawn.prefix)
    first = LoadGraph(drawn.efile, drawn.vfile, CommSpec(fnum=4), spec)
    cache, sig = _cache_dir(drawn.efile, drawn.vfile, spec, 4,
                            cut={"symmetrize": False, "layout": "pull"})
    assert os.path.exists(os.path.join(cache, "sig"))
    assert json.loads(sig)["type"] == "ImmutableVertexcutFragment"
    again = LoadGraph(drawn.efile, drawn.vfile, CommSpec(fnum=4), spec)
    assert again.layout == first.layout == "pull"
    for a, b in zip(first._host_tiles + first._host_pull,
                    again._host_tiles + again._host_pull):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()
    for a, b in zip(jax.tree_util.tree_leaves(first.dev),
                    jax.tree_util.tree_leaves(again.dev)):
        assert a.dtype == b.dtype
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    for name in ("k", "vc", "chunk", "total_enum", "total_vnum", "directed",
                 "weighted", "symmetrized"):
        assert getattr(first, name) == getattr(again, name), name
    assert (first._oids == again._oids).all()
    # the edge cut's cache of the same files and fnum is another entry
    edge = _cache_dir(drawn.efile, drawn.vfile, spec, 4)
    assert edge[0] != cache
    assert json.loads(edge[1])["type"] == "ShardedEdgecutFragment"
    assert "vertex_cut" not in json.loads(edge[1])


def test_a_coo_cache_comes_back_as_coo(drawn):
    spec = spec_of(serialize=True, deserialize=True,
                   serialization_prefix=drawn.prefix)
    kw = dict(symmetrize=True)
    first = LoadVertexcutGraph(drawn.efile, drawn.vfile, CommSpec(fnum=4),
                               spec, **kw)
    again = LoadVertexcutGraph(drawn.efile, drawn.vfile, CommSpec(fnum=4),
                               spec, **kw)
    assert again.layout == "coo" and again._host_pull is None
    for a, b in zip(first._host_tiles, again._host_tiles):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


def test_an_unknown_field_of_the_spec_raises():
    """How a program from before the field fails on the cell: at once,
    with a TypeError, before anything is loaded."""
    assert LoadGraphSpec().vertex_cut is False
    with pytest.raises(TypeError):
        LoadGraphSpec(no_such_field=True)


# ---- set-up ----


def test_the_load_opens_the_set_up_phases(drawn):
    from libgrape_lite_tpu.obs import federation
    from libgrape_lite_tpu.obs.tracer import SETUP_LEDGER

    def phases(spec):
        SETUP_LEDGER.reset()  # the ledger keeps its first 256 records
        LoadGraph(drawn.efile, drawn.vfile, CommSpec(fnum=4), spec)
        return [(r["name"], r["parent"]) for r in
                federation.snapshot()["setup"]["records"]]

    prefix = os.path.join(drawn.prefix, "phases")
    spec = spec_of(serialize=True, deserialize=True,
                   serialization_prefix=prefix)
    assert phases(spec) == [
        ("read_edges", "load_graph"), ("partition", "load_graph"),
        ("load.place", "build_fragment"), ("build_fragment", "load_graph"),
        ("serialize", "load_graph"), ("load_graph", None)]
    assert phases(spec) == [
        ("load.place", "deserialize"), ("deserialize", "load_graph"),
        ("load_graph", None)]


# ---- the runner's branches ----


@pytest.mark.parametrize("how", ["vc", "partition2d"])
def test_the_runner_builds_through_the_loader(how, tmp_path, monkeypatch):
    """`--vc` and `GRAPE_PARTITION=2d` reach `LoadVertexcutGraph`, cache
    and all: a second run deserializes and answers the same."""
    from libgrape_lite_tpu import runner
    from tests.verifiers import eps_verify, load_golden, load_result_lines

    if how == "partition2d":
        monkeypatch.setenv("GRAPE_PARTITION", "2d")
    from libgrape_lite_tpu.fragment import loader

    calls = []
    real = loader.LoadVertexcutGraph

    def counted(*a, **kw):
        calls.append(kw)
        return real(*a, **kw)

    monkeypatch.setattr(loader, "LoadVertexcutGraph", counted)
    outs = []
    for turn in range(2):
        out = tmp_path / f"out{turn}"
        args = runner.QueryArgs(
            application="pagerank", efile=dataset_path("p2p-31.e"),
            vfile=dataset_path("p2p-31.v"), out_prefix=str(out),
            vc=how == "vc", fnum=4, serialize=True, deserialize=True,
            serialization_prefix=str(tmp_path / "cache"))
        worker = runner.run_app(args)
        assert type(worker.app).__name__ == "PageRankVC"
        assert worker.fragment.layout == "pull"
        text = "".join(open(out / f).read() for f in sorted(os.listdir(out)))
        outs.append(text)
    assert len(calls) == 2 and outs[0] == outs[1]
    assert calls[0]["layout"] == "pull" and calls[0]["symmetrize"] is False
    eps_verify(load_result_lines(outs[0]),
               load_golden(dataset_path("p2p-31-PR")))


# ---- the mesh ----


def test_mesh2d_follows_the_chips_coordinates():
    """A row and a column of the mesh are a row and a column of the
    host's 2 x 2 block of chips, whatever order the devices are listed
    in; devices without coordinates keep the list's order."""
    def chip(name, x, y):
        return types.SimpleNamespace(name=name, coords=(x, y, 0),
                                     core_on_chip=0)

    ring = [chip("a", 0, 0), chip("b", 1, 0), chip("c", 1, 1),
            chip("d", 0, 1)]
    assert [d.name for d in _by_coords(ring, 2)] == ["a", "b", "d", "c"]
    rows = [chip("a", 0, 0), chip("b", 1, 0), chip("c", 0, 1),
            chip("d", 1, 1)]
    assert _by_coords(rows, 2) == rows
    line = [chip(str(i), i, 0) for i in range(4)]  # no 2 x 2 block
    assert _by_coords(line, 2) == line
    plain = [types.SimpleNamespace(name=str(i)) for i in range(4)]
    assert _by_coords(plain, 2) == plain
    comm = CommSpec(fnum=4)
    assert list(comm.mesh2d().devices.reshape(-1)) == comm.devices
    assert comm.mesh2d() is comm.mesh2d()
    assert comm.sharded2d().mesh is comm.mesh2d()
