"""The default `bfs` on the road-like surrogate, against the benchmark's plain
reference, and the record of a query's rounds.

The graph of `benchmarks/configs/road-like.json` has what Kronecker lacks:
bounded degree and a hop diameter of the order of sqrt(vertices), so a search
takes one round a hop, dozens here and a thousand and more in the cell, and
a round's frontier is a few rows.  The fused serial runner carries a record
of the `active` each IncEval voted (`ROUND_STATS`, docs/OBSERVABILITY.md);
`Worker.result_values` reads it out with the answer.
"""

import json
import os
import types

import numpy as np
import pytest

from benchmarks.graphs import road_like
from benchmarks.graphs.csr import degrees, symmetric_csr
from benchmarks.references import bfs as bfs_reference
from libgrape_lite_tpu.fragment.loader import LoadGraph, LoadGraphSpec
from libgrape_lite_tpu.models import APP_REGISTRY
from libgrape_lite_tpu.obs import federation
from libgrape_lite_tpu.parallel.comm_spec import CommSpec
from libgrape_lite_tpu.worker.worker import ROUND_STATS, Worker

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmarks", "configs", "road-like.json")) as f:
    CONFIG = json.load(f)
GEN = CONFIG["generator"]
SCALES = [10, 12]


@pytest.fixture(scope="module")
def surrogate(tmp_path_factory):
    """(scale, fnum) -> (fragment through LoadGraph, the references' graph,
    the key the harness would draw)."""
    made = {}

    def get(scale: int, fnum: int = 1):
        if (scale, fnum) not in made:
            d = tmp_path_factory.mktemp(f"road{scale}x{fnum}")
            efile, vfile = str(d / "graph.e"), str(d / "graph.v")
            road_like.write_files(GEN, scale, efile, vfile)
            spec = dict(CONFIG["load_graph_spec"])
            spec["edata_dtype"] = np.dtype(spec["edata_dtype"]).type
            frag = LoadGraph(efile, vfile, CommSpec(fnum=fnum), LoadGraphSpec(**spec))
            n = 1 << scale
            src, dst, w = road_like.edges(GEN, scale)
            minw, mult = symmetric_csr(n, src, dst, w)
            key = int(np.random.default_rng(int(GEN["generator_seed"])).choice(
                np.flatnonzero(degrees(n, src, dst) > 0), size=1, replace=False)[0])
            made[scale, fnum] = frag, types.SimpleNamespace(n=n, minw=minw, mult=mult), key
        return made[scale, fnum]

    return get


def by_id(frag, values):
    out = np.empty(frag.dev.total_vnum, dtype=values.dtype)
    for f in range(frag.fnum):
        out[frag.inner_oids(f)] = values[f, :frag.inner_vertices_num(f)]
    return out


def search(frag, key, name="bfs"):
    """(depths by id in the reference's form, the worker, ROUND_STATS after
    the answer was extracted)."""
    w = Worker(APP_REGISTRY[name](), frag)
    w.query(source=key)
    got = bfs_reference.to_reference_form(by_id(frag, w.result_values()))
    return got, w, ROUND_STATS.snapshot()


@pytest.mark.parametrize("fnum", [1, 4])
@pytest.mark.parametrize("scale", SCALES)
def test_bfs_is_exact_on_every_vertex(surrogate, scale, fnum):
    frag, graph, key = surrogate(scale, fnum)
    want = bfs_reference.reference(graph, {"source": key})
    got, w, stats = search(frag, key)
    assert (got != want).sum() == 0 and (want >= 0).all()  # one component
    # a round a hop, and the last one finds nothing left
    assert w.rounds == want.max() + 1 >= graph.n ** 0.5
    levels = np.bincount(want)
    assert stats["app"] == "BFS" and stats["rounds"] == w.rounds
    assert sum(stats["active_bits"]) == w.rounds and len(stats["active_bits"]) == 33
    # a round's vote is the level it reached; the source is nobody's vote
    assert stats["active_sum"] == graph.n - 1 == levels[1:].sum()
    assert stats["active_max"] == levels[1:].max()
    by_bits = np.bincount([int(v).bit_length() for v in levels[1:]] + [0], minlength=33)
    assert stats["active_bits"] == by_bits.tolist()
    # the largest bucket in use bounds the widest level
    top = max(b for b, c in enumerate(stats["active_bits"]) if c)
    assert 1 << (top - 1) <= levels[1:].max() < 1 << top


def test_the_record_is_federated_and_the_last_extracted_querys(surrogate):
    frag, graph, key = surrogate(10)
    _, w, stats = search(frag, key)
    assert federation.EXPECTED["rounds"] == "libgrape_lite_tpu.worker.worker"
    assert federation.snapshot("rounds") == stats and not federation.self_check()
    json.dumps(stats)
    # the next extracted answer replaces the record: another key, other levels
    other = int(np.argmax(bfs_reference.reference(graph, {"source": key})))
    want = bfs_reference.reference(graph, {"source": other})
    got, w2, stats2 = search(frag, other)
    assert (got != want).sum() == 0 and stats2["rounds"] == want.max() + 1
    assert stats2 != stats and federation.snapshot("rounds") == stats2
    # the first worker's answer, extracted again, brings its own record back
    w.result_values()
    assert ROUND_STATS.snapshot() == stats


def test_a_round_limit_cuts_the_record_too(surrogate):
    frag, graph, key = surrogate(10)
    want = bfs_reference.reference(graph, {"source": key})
    w = Worker(APP_REGISTRY["bfs"](), frag)
    w.query(max_rounds=5, source=key)
    w.result_values()
    assert w.rounds == 5 and ROUND_STATS["rounds"] == 5
    assert sum(ROUND_STATS["active_bits"]) == 5
    assert ROUND_STATS["active_sum"] == np.bincount(want)[1:6].sum()


@pytest.mark.parametrize("app,args,votes", [
    ("pagerank", {}, lambda rounds: [1] * (rounds - 1) + [0]),
    ("wcc", {}, None), ("sssp", {"source": None}, None)])
def test_any_apps_votes_are_recorded(surrogate, app, args, votes):
    frag, graph, key = surrogate(10)
    w = Worker(APP_REGISTRY[app](), frag)
    w.query(**{k: key if v is None else v for k, v in args.items()})
    w.result_values()
    stats = ROUND_STATS.snapshot()
    assert stats["app"] == type(w.app).__name__ and stats["rounds"] == w.rounds > 0
    assert sum(stats["active_bits"]) == w.rounds and stats["active_bits"][0] == 1
    if votes:
        assert stats["active_sum"] == sum(votes(w.rounds)) and stats["active_max"] == 1


@pytest.mark.parametrize("path", ["stepwise", "batched", "host"])
def test_other_runners_leave_no_record(surrogate, path):
    """The batched, stepwise and host-driven paths carry no record: after
    their answer is extracted the registry holds its initial values, never
    an earlier query's."""
    frag, graph, key = surrogate(10)
    search(frag, key)
    assert ROUND_STATS["rounds"] > 0
    want = bfs_reference.reference(graph, {"source": key})
    w = Worker(APP_REGISTRY["bfs_opt" if path == "host" else "bfs"](), frag)
    if path == "batched":
        w.query_batch([{"source": key}, {"source": 0}])
        got = w.batch_result_values(0)
        assert ROUND_STATS["rounds"] > 0  # a lane's extraction is not a query's
        w.query_stepwise(source=key)
    elif path == "stepwise":
        w.query_stepwise(source=key)
    else:
        w.query(source=key)
    got = bfs_reference.to_reference_form(by_id(frag, w.result_values()))
    assert (got != want).sum() == 0
    initial = {"app": "", "rounds": 0, "active_bits": [], "active_max": 0, "active_sum": 0,
               "frontier_rounds": 0, "advances": 0, "pushed_sum": 0}
    assert ROUND_STATS.snapshot() == initial


def test_the_record_is_named_and_is_metadata_only(surrogate):
    frag, _, key = surrogate(10)
    w = Worker(APP_REGISTRY["bfs"](), frag)
    state = w._place_state(w.app.init_state(frag, source=key))
    eph = frozenset(getattr(w.app, "ephemeral_keys", ()) or ())
    carry = {k: v for k, v in state.items() if k not in eph}
    eph_part = {k: v for k, v in state.items() if k in eph}
    lowered = w._runner_for(0, state).lower(frag.dev, carry, eph_part)
    assert "grape.worker.record" in lowered.as_text(debug_info=True)
    plain = lowered.as_text(debug_info=False)
    assert "grape." not in plain and "tensor<36xui32>" in plain
    # a round's step is scalar arithmetic on 36 scalars: no array, so nothing
    # for the loop to fetch into VMEM; nothing scattered, nothing gathered
    import jax
    import jax.numpy as jnp

    from libgrape_lite_tpu.worker.worker import _note_round

    step = jax.jit(_note_round).lower((jnp.uint32(0),) * 36, jnp.int32(5)).as_text()
    assert "scatter" not in step and "gather" not in step and "while" not in step
    assert "tensor<ui32>" in step and "xui32>" not in step
