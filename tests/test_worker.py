"""Worker-level regression tests."""

import numpy as np
import pytest


def build_fragment(src, dst, w, n, fnum, directed=False):
    from libgrape_lite_tpu.fragment.edgecut import ShardedEdgecutFragment
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec
    from libgrape_lite_tpu.utils.id_parser import IdParser
    from libgrape_lite_tpu.utils.types import LoadStrategy
    from libgrape_lite_tpu.vertex_map.idxer import HashMapIdxer
    from libgrape_lite_tpu.vertex_map.partitioner import MapPartitioner
    from libgrape_lite_tpu.vertex_map.vertex_map import VertexMap

    comm_spec = CommSpec(fnum=fnum)
    oids = np.arange(n, dtype=np.int64)
    part = MapPartitioner(fnum, oids)
    fids = part.get_partition_id(oids)
    idxers = [HashMapIdxer(oids[fids == f]) for f in range(fnum)]
    max_iv = max(ix.size() for ix in idxers)
    vm = VertexMap(part, idxers, IdParser(fnum, max(2 * max_iv, 2)))
    return ShardedEdgecutFragment.build(
        comm_spec, vm, np.asarray(src), np.asarray(dst),
        None if w is None else np.asarray(w, np.float64),
        directed=directed, load_strategy=LoadStrategy.kBothOutIn,
    )


def test_runner_cache_respects_query_params():
    """Changed query hyperparameters must retrace, not reuse a stale
    compiled loop (regression: cache keyed only on state shapes)."""
    from libgrape_lite_tpu.models import PageRank
    from libgrape_lite_tpu.worker.worker import Worker

    rng = np.random.default_rng(0)
    src, dst = rng.integers(0, 64, 256), rng.integers(0, 64, 256)
    frag = build_fragment(src, dst, None, 64, 2)
    w = Worker(PageRank(), frag)
    w.query(delta=0.85, max_round=3)
    assert w.rounds == 3
    w.query(delta=0.85, max_round=7)
    assert w.rounds == 7


def test_runner_cache_keys_max_rounds():
    """A second query with a different `max_rounds` on the SAME worker
    must compile its own runner, not silently reuse the first one: the
    round limit is baked into the while_loop cond (ISSUE 6 satellite;
    the serve compatibility key pins the same contract in
    tests/test_serve.py)."""
    from libgrape_lite_tpu.models import SSSP
    from libgrape_lite_tpu.worker.worker import Worker

    # a 32-vertex path: convergence takes 31 relaxation rounds, so a
    # stale 2-round compile would be unmissable
    n = 32
    src = np.arange(n - 1)
    dst = np.arange(1, n)
    w_edge = np.ones(n - 1)
    frag = build_fragment(src, dst, w_edge, n, 2)

    w = Worker(SSSP(), frag)
    w.query(max_rounds=2, source=0)
    assert w.rounds == 2
    capped = w.result_values()
    stats_after_first = dict(w.runner_cache_stats)

    w.query(max_rounds=0, source=0)  # 0 = run to convergence
    # n-1 improving rounds + the final no-change round that votes stop
    assert w.rounds == n
    full = w.result_values()
    assert np.isinf(capped).sum() > np.isinf(full).sum()
    # the second limit was a genuine second compile, not a cache hit
    assert (
        w.runner_cache_stats["misses"]
        == stats_after_first["misses"] + 1
    )

    # and repeating either limit hits its own cached runner
    w.query(max_rounds=2, source=0)
    assert w.rounds == 2
    assert (
        w.runner_cache_stats["misses"]
        == stats_after_first["misses"] + 1
    )


def test_lcc_tiny_graph():
    """n_pad < 32 exercises the ceil in the bitmap word count
    (regression: words = n_pad // 32 zeroed the bitmaps)."""
    from libgrape_lite_tpu.models import LCC
    from libgrape_lite_tpu.worker.worker import Worker

    # triangle 0-1-2 plus pendant 3: lcc = 1,1,1,0
    src = [0, 1, 0, 2]
    dst = [1, 2, 2, 3]
    frag = build_fragment(src, dst, None, 4, 1)
    w = Worker(LCC(), frag)
    w.query()
    vals = w.result_values()[0, :4]
    # vertex 2 has degree 3 (1,0,3): one triangle -> 2*1/(3*2) = 1/3
    np.testing.assert_allclose(vals, [1.0, 1.0, 1 / 3, 0.0], atol=1e-12)


def test_lcc_tiny_graph_sharded():
    from libgrape_lite_tpu.models import LCC
    from libgrape_lite_tpu.worker.worker import Worker

    src = [0, 1, 0, 2]
    dst = [1, 2, 2, 3]
    frag = build_fragment(src, dst, None, 4, 4)
    w = Worker(LCC(), frag)
    w.query()
    vals = np.concatenate(
        [w.result_values()[f, : frag.inner_vertices_num(f)] for f in range(4)]
    )
    np.testing.assert_allclose(vals, [1.0, 1.0, 1 / 3, 0.0], atol=1e-12)


def test_force_terminate():
    """Cooperative abort (reference ForceTerminate + TerminateInfo):
    a negative active vote stops the loop on every shard and surfaces
    failure info."""
    import jax.numpy as jnp

    from libgrape_lite_tpu.models import SSSP
    from libgrape_lite_tpu.worker.worker import Worker

    class AbortingSSSP(SSSP):
        def inceval(self, ctx, frag, state):
            state, active = super().inceval(ctx, frag, state)
            # abort once more than 3 vertices have settled
            settled = ctx.sum(
                jnp.logical_and(
                    jnp.isfinite(state["dist"]), frag.inner_mask
                ).sum().astype(jnp.int32)
            )
            return state, jnp.where(settled > 3, jnp.int32(-7), active)

    rng = np.random.default_rng(0)
    src, dst = rng.integers(0, 32, 128), rng.integers(0, 32, 128)
    w = rng.random(128)
    frag = build_fragment(src, dst, w, 32, 2)
    worker = Worker(AbortingSSSP(), frag)
    worker.query(source=0)
    ok, info = worker.get_terminate_info()
    assert not ok
    assert "code -7" in info

    # a clean run reports success
    from libgrape_lite_tpu.models import SSSP as CleanSSSP

    w2 = Worker(CleanSSSP(), frag)
    w2.query(source=0)
    assert w2.get_terminate_info() == (True, "")


def test_put_global_matches_device_put():
    """Both branches of put_global (the multi-process placement helper)
    must agree with plain device_put: the fully-addressable fast path
    AND the make_array_from_callback path a jax.distributed run takes
    (exercised here by calling it directly on the same sharding —
    callback assembly works on addressable meshes too)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from libgrape_lite_tpu.parallel.comm_spec import (
        FRAG_AXIS, CommSpec, put_global,
    )

    comm = CommSpec(fnum=4)
    sh = NamedSharding(comm.mesh, P(FRAG_AXIS))
    x = np.arange(4 * 8, dtype=np.int64).reshape(4, 8)
    b = jax.device_put(jnp.asarray(x), sh)

    a = put_global(x, sh)  # fully-addressable branch
    assert a.dtype == b.dtype and a.shape == b.shape
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert a.sharding.is_equivalent_to(b.sharding, a.ndim)
    # the HOST array goes straight to its shards — one row per device —
    # and dtypes canonicalise as jnp.asarray's detour did, under x64
    # (this lane) and under x32 (a chip run: int64 oids land as int32)
    assert len({s.device for s in a.addressable_shards}) == 4
    assert all(s.data.shape == (1, 8) for s in a.addressable_shards)
    for dt in (np.int64, np.float64, np.float32, np.int32, np.bool_, np.uint8):
        y = x.astype(dt)
        assert put_global(y, sh).dtype == jnp.asarray(y).dtype
        with jax.enable_x64(False):
            assert put_global(y, sh).dtype == jnp.asarray(y).dtype
            assert put_global(y, sh).dtype.itemsize <= 4

    # the multi-process branch, forced on the same mesh: idx slicing
    # and values must match device_put exactly
    arr = np.asarray(x)
    c = jax.make_array_from_callback(arr.shape, sh, lambda idx: arr[idx])
    assert c.shape == b.shape
    np.testing.assert_array_equal(np.asarray(c), np.asarray(b))
    for shard in c.addressable_shards:
        np.testing.assert_array_equal(
            np.asarray(shard.data), arr[shard.index]
        )

    # replicated scalars too
    r = put_global(np.float32(3.5), NamedSharding(comm.mesh, P()))
    assert float(r) == 3.5
    assert put_global(None, sh) is None


@pytest.mark.parametrize("fails_in", ["init_state", "runner"])
def test_a_failed_query_and_the_last_answer(fails_in, monkeypatch):
    """A worker lets go of the last query's result once the next query has
    a state to place (two states in HBM at every query's peak otherwise):
    a query refused before that, in `init_state`, leaves the last answer
    standing; one that fails later leaves none, and says so."""
    from libgrape_lite_tpu.models import BFS
    from libgrape_lite_tpu.worker.worker import ROUND_STATS, Worker

    rng = np.random.default_rng(0)
    src, dst = rng.integers(0, 64, 256), rng.integers(0, 64, 256)
    w = Worker(BFS(), build_fragment(src, dst, None, 64, 1))
    w.query(source=3)
    last, rounds = w.result_values().copy(), ROUND_STATS["rounds"]
    if fails_in == "init_state":
        with pytest.raises(TypeError):
            w.query(sauce=3)
        assert (w.result_values() == last).all()
        assert ROUND_STATS["rounds"] == rounds == w.rounds
    else:
        def refuse(*_):
            raise MemoryError("no room for the runner")

        monkeypatch.setattr(w, "_runner_for", refuse)
        with pytest.raises(MemoryError):
            w.query(source=5)
        with pytest.raises(RuntimeError, match="query"):
            w.result_values()
        monkeypatch.undo()
    w.query(source=5)
    assert (w.result_values() != last).any()


@pytest.mark.parametrize("fails_in", ["init_state", "runner"])
def test_a_failed_batch_and_the_last_batch(fails_in, monkeypatch):
    """`query_batch` lets go of the last batch's result as `query` does of
    the last query's: once the next batch has a state to place, not
    before."""
    from libgrape_lite_tpu.models import BFS
    from libgrape_lite_tpu.worker.worker import Worker

    rng = np.random.default_rng(0)
    src, dst = rng.integers(0, 64, 256), rng.integers(0, 64, 256)
    w = Worker(BFS(), build_fragment(src, dst, None, 64, 1))
    w.query_batch([{"source": 3}, {"source": 4}])
    last = w.batch_result_values(1).copy()
    if fails_in == "init_state":
        with pytest.raises(TypeError):
            w.query_batch([{"sauce": 3}, {"sauce": 4}])
        assert (w.batch_result_values(1) == last).all()
    else:
        def refuse(*_):
            raise MemoryError("no room for the runner")

        monkeypatch.setattr(w, "_batched_runner_for", refuse)
        with pytest.raises(MemoryError):
            w.query_batch([{"source": 5}, {"source": 6}])
        with pytest.raises(RuntimeError, match="query_batch"):
            w.batch_result_values(1)
        monkeypatch.undo()
    w.query_batch([{"source": 5}, {"source": 6}])
    assert (w.batch_result_values(1) != last).any()


# ---- one round, three loops: fused, chunked, stepwise ------------------------

# the five pull apps, their query, and the cut-independent graph they run on
# (tests/conftest.rand_frag: 900 ids, 7,000 edges, f32 weights)
PULL_APPS = {
    "sssp": {"source": 0},
    "bfs": {"source": 0},
    "wcc": {},
    "pagerank": {"delta": 0.85, "max_round": 10},
    "cdlp": {"max_round": 10},
}


@pytest.fixture(scope="module")
def fused_run():
    """(fragment, values' bytes, rounds, terminate info) of the fused
    query, once per app and cut."""
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.worker.worker import Worker
    from tests.conftest import rand_frag

    frags, made = {}, {}

    def get(app, fnum):
        if (app, fnum) not in made:
            if fnum not in frags:
                frags[fnum] = rand_frag(fnum)
            w = Worker(APP_REGISTRY[app](), frags[fnum])
            w.query(**PULL_APPS[app])
            made[app, fnum] = (
                frags[fnum], np.asarray(w.result_values()).tobytes(),
                w.rounds, w.get_terminate_info())
        return made[app, fnum]

    return get


@pytest.mark.parametrize("chunk", [1, 3])
@pytest.mark.parametrize("fnum", [1, 2, 4])
@pytest.mark.parametrize("app", sorted(PULL_APPS))
def test_chunked_segments_compose_to_the_fused_run(app, fnum, chunk,
                                                   fused_run):
    """`_make_chunk_runner`'s contract: segments of `chunk` rounds, each
    entered at the (active, round) the last one left, as `_query_guarded`
    drives them, end where the fused loop ends, with its bytes."""
    from libgrape_lite_tpu.guard import GuardConfig
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.worker.worker import Worker

    frag, want, rounds, terminate = fused_run(app, fnum)
    w = Worker(APP_REGISTRY[app](), frag)
    w.query(guard=GuardConfig(policy="halt", every=chunk), **PULL_APPS[app])
    # through the chunk runner of this cadence, and no fused runner
    kinds = {k[0] if isinstance(k[0], str) else "fused"
             for k in w._runner_cache}
    assert "chunk" in kinds and "fused" not in kinds
    assert {k[1] for k in w._runner_cache if k[0] == "chunk"} == {chunk}
    assert np.asarray(w.result_values()).tobytes() == want
    assert (w.rounds, w.get_terminate_info()) == (rounds, terminate)
    assert w.guard_report["probes"] >= -(-rounds // chunk)
    assert not w.guard_report["breaches"]


@pytest.mark.parametrize("fnum", [1, 4])
@pytest.mark.parametrize("app", sorted(PULL_APPS))
def test_stepwise_equals_fused(app, fnum, fused_run):
    """One jitted round a dispatch (`_compile_single_step`) is the fused
    loop's round: the same bytes after the same number of rounds."""
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.worker.worker import Worker

    frag, want, rounds, terminate = fused_run(app, fnum)
    w = Worker(APP_REGISTRY[app](), frag)
    w.query_stepwise(**PULL_APPS[app])
    assert np.asarray(w.result_values()).tobytes() == want
    assert (w.rounds, w.get_terminate_info()) == (rounds, terminate)
