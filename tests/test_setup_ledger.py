"""The set-up ledger: what a process pays once, kept armed or not.

A span whose name is in `obs/tracer.SETUP_PHASES` leaves one record in
`SETUP_LEDGER` (federated namespace `setup`) when it closes.  The rule
that keeps the ledger off the hot path is pinned by its length: a
second `Worker.query`, a second `ServeSession` pump and a second LCC
query append nothing; a phase opens only in `LoadGraph`, on a cache
miss of a per-fragment structure, on a runner miss, in the native
loader's build and where the compile cache is placed.
"""

import contextlib
import json
import os

import numpy as np
import pytest

from libgrape_lite_tpu import obs
from libgrape_lite_tpu.obs import federation
from libgrape_lite_tpu.obs.tracer import (
    SETUP_LEDGER,
    SETUP_PHASES,
    SETUP_PLACING,
    SetupLedger,
)
from tests.conftest import dataset_path, rand_frag


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    """Disarmed, an empty ledger, and no state left behind."""
    monkeypatch.delenv(obs.TRACE_ENV, raising=False)
    monkeypatch.delenv(obs.METRICS_ENV, raising=False)
    obs.reset()
    SETUP_LEDGER.reset()
    yield
    obs.reset()
    SETUP_LEDGER.reset()


def records():
    return federation.snapshot("setup")["records"]


def names():
    return [r["name"] for r in records()]


def load_p2p(prefix, fnum=2):
    from libgrape_lite_tpu.fragment.loader import LoadGraph, LoadGraphSpec
    from libgrape_lite_tpu.parallel.comm_spec import CommSpec

    return LoadGraph(
        dataset_path("p2p-31.e"), dataset_path("p2p-31.v"),
        CommSpec(fnum=fnum),
        LoadGraphSpec(serialize=True, deserialize=True,
                      serialization_prefix=str(prefix)),
    )


# ---- LoadGraph's tree ------------------------------------------------------

TREES = {
    # closing order: a child before its parent
    "parse": [("read_edges", "load_graph"), ("partition", "load_graph"),
              ("load.place", "build_fragment"),
              ("build_fragment", "load_graph"), ("serialize", "load_graph"),
              ("load_graph", None)],
    "deserialize": [("load.place", "deserialize"),
                    ("deserialize", "load_graph"), ("load_graph", None)],
}


@pytest.mark.parametrize("path", ["parse", "deserialize"])
def test_disarmed_load_records_the_tree(tmp_path, path):
    """A disarmed process records `load_graph` with its stages and the
    placement under them: parents, order, children within the parent."""
    assert not obs.armed()
    load_p2p(tmp_path)  # parse, and write the cache
    if path == "deserialize":
        SETUP_LEDGER.reset()
        load_p2p(tmp_path)
    recs = records()
    assert [(r["name"], r["parent"]) for r in recs] == TREES[path]
    by_name = {r["name"]: r for r in recs}
    for r in recs:
        assert r["dur_ns"] > 0
        if r["parent"] is None:
            continue
        p = by_name[r["parent"]]
        assert p["t0_ns"] <= r["t0_ns"]
        assert r["t0_ns"] + r["dur_ns"] <= p["t0_ns"] + p["dur_ns"]
    top = by_name["load_graph"]
    assert top["args"]["fnum"] == 2
    if path == "parse":
        assert top["args"]["edges"] == 147892
        assert top["args"]["vertices"] == 62586
        # the stages are the whole of the load but for a few lines
        stages = sum(r["dur_ns"] for r in recs if r["parent"] == "load_graph")
        assert stages <= top["dur_ns"] and stages >= 0.9 * top["dur_ns"]
    else:
        assert top["args"]["path"] == "deserialize"
    assert federation.snapshot("setup")["seconds"]["load_graph"] == \
        pytest.approx(top["dur_ns"] / 1e9)


def test_bytes_in_use_absent_not_zero_on_the_cpu(tmp_path):
    """The CPU backend has no allocator statistics: a placing phase's
    record carries no `bytes_in_use`, never a 0."""
    from libgrape_lite_tpu.utils.memory import fullest_bytes_in_use

    assert fullest_bytes_in_use() is None
    load_p2p(tmp_path)
    placed = [r for r in records() if r["name"] in SETUP_PLACING]
    assert placed and all("bytes_in_use" not in r for r in placed)


def test_fullest_device_decides_the_stamp(monkeypatch):
    """Where the backend counts, the record carries the fullest local
    device's reading at open and at close; a device without statistics
    makes the reading unknown."""
    import jax

    from libgrape_lite_tpu.utils import memory

    class Dev:
        def __init__(self, b):
            self.b = b

        def memory_stats(self):
            return None if self.b is None else {"bytes_in_use": self.b}

    devs = [Dev(5), Dev(9), Dev(7)]
    monkeypatch.setattr(jax, "local_devices", lambda: devs)
    assert memory.fullest_bytes_in_use() == 9
    with obs.tracer().span("load.place"):
        devs[0].b = 40
    with obs.tracer().span("partition"):  # a phase that places nothing
        pass
    place, host = records()
    assert place["bytes_in_use"] == {"open": 9, "close": 40}
    assert "bytes_in_use" not in host
    devs.append(Dev(None))
    assert memory.fullest_bytes_in_use() is None


# ---- the hot-path rule -----------------------------------------------------


def _query_twice(kind):
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.worker.worker import Worker

    if kind == "serve_pump":
        from libgrape_lite_tpu.serve import BatchPolicy, ServeSession

        sess = ServeSession(rand_frag(1), policy=BatchPolicy(max_batch=2))

        def pump(sources):
            out = sess.serve([("sssp", {"source": s}) for s in sources])
            assert all(r.ok for r in out)

        return lambda: pump([0, 5]), lambda: pump([17, 33])
    if kind == "guarded_batch":
        w = Worker(APP_REGISTRY["sssp"](), rand_frag(1))
        return tuple(
            (lambda s=s: w.query_batch(
                [{"source": s}, {"source": s + 1}], guard="halt"))
            for s in (0, 5))
    app, frag, kw = {
        "query": ("sssp", rand_frag(2), {"source": 0}),
        "lcc": ("lcc", rand_frag(2, weighted=False), {}),
        "guarded": ("sssp", rand_frag(2), {"source": 0, "guard": "halt"}),
        "stepwise": ("sssp", rand_frag(2), {"source": 0}),
    }[kind]
    w = Worker(APP_REGISTRY[app](), frag)
    ask = w.query_stepwise if kind == "stepwise" else w.query
    return (lambda: ask(**kw)), (lambda: ask(**kw))


@pytest.mark.parametrize("kind", ["query", "serve_pump", "lcc", "guarded",
                                  "stepwise", "guarded_batch"])
def test_a_warm_query_appends_nothing(kind):
    """The first query pays the runner (and LCC its adjacency); a second
    `Worker.query`, a second `ServeSession` pump and a second LCC query
    open no phase: the ledger's length stands.  The same of the guarded,
    the stepwise and the guarded batched query, whose PEval's first call
    goes through `Worker._enqueue` too."""
    first, second = _query_twice(kind)
    SETUP_LEDGER.reset()  # the fragment's own build is not under test
    first()
    paid = names()
    assert "runner.compile" in paid
    if kind == "lcc":
        assert paid[:2] == ["derived.place", "derived.lcc_adjacency"]
        adj = records()[1]
        assert adj["args"]["fnum"] == 2 and adj["args"]["d_max"] >= 1
        assert adj["args"]["ell_bytes"] > 0
        assert records()[0]["parent"] == "derived.lcc_adjacency"
    second()
    second()
    assert names() == paid
    assert federation.snapshot("setup")["dropped"] == 0


@pytest.mark.parametrize("mode", ["fused", "batched"])
def test_runner_miss_records_one_compile_and_a_hit_none(mode):
    """The enqueue of a fresh runner is `runner.compile`, with what
    JAX's monitoring events gave for it; the listener is gone after."""
    from jax._src import monitoring

    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.worker.worker import Worker

    w = Worker(APP_REGISTRY["bfs"](), rand_frag(1))
    listeners = len(monitoring.get_event_duration_listeners())

    def ask(s):
        if mode == "fused":
            w.query(source=s)
        else:
            w.query_batch([{"source": s}, {"source": s + 1}])

    SETUP_LEDGER.reset()
    ask(0)
    (rec,) = [r for r in records() if r["name"] == "runner.compile"]
    args = rec["args"]
    assert args["app"] == "BFS" and args["mode"] == mode
    assert args["batch"] == (1 if mode == "fused" else 2)
    assert args["trace_s"] > 0 and args["lower_s"] > 0
    assert args["backend_s"] > 0  # the suite runs without a disk cache
    assert args["cache_hits"] == 0
    assert args["trace_s"] + args["lower_s"] + args["backend_s"] \
        <= rec["dur_ns"] / 1e9
    assert w.runner_cache_stats["misses"] == 1
    ask(3)
    assert w.runner_cache_stats["hits"] == 1
    assert names().count("runner.compile") == 1
    assert len(monitoring.get_event_duration_listeners()) == listeners


# ---- what a runner holds on the chip ----------------------------------------

STAMPED = ("code_bytes", "temp_bytes", "argument_bytes", "output_bytes",
           "alias_bytes", "state_bytes")


def _ask(w, mode, s=0):
    """One query of `mode` from source `s`: the five dispatches whose
    first call goes through `Worker._enqueue`."""
    pair = [{"source": s}, {"source": s + 1}]
    if mode == "fused":
        w.query(source=s)
    elif mode == "batched":
        w.query_batch(pair)
    elif mode == "guarded-fused":
        w.query(source=s, guard="halt")
    elif mode == "stepwise":
        w.query_stepwise(source=s)
    else:
        w.query_batch(pair, guard="halt")


def _bfs_worker(fnum=2):
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.worker.worker import Worker

    return Worker(APP_REGISTRY["bfs"](), rand_frag(fnum))


def _compile_records():
    return [r for r in records() if r["name"] == "runner.compile"]


@pytest.mark.parametrize("mode", ["fused", "batched"])
def test_a_miss_stamps_what_the_executable_holds(mode):
    """The six fields of a `runner.compile` record are the compiled
    runner's own `memory_analysis()` and the state operands' shards,
    all per device; the record stays JSON and the federation clean."""
    from libgrape_lite_tpu.utils.memory import EXECUTABLE_BYTES

    w = _bfs_worker()
    frag, app = w.fragment, w.app
    SETUP_LEDGER.reset()
    _ask(w, mode)
    (rec,) = _compile_records()
    args = rec["args"]
    assert set(STAMPED) <= set(args)
    # the same runner, lowered again for a state of the same structure:
    # the executable the dispatch ran
    if mode == "fused":
        state = w._place_state(w._seeded(app.init_state(frag, source=0)))
        runner = w._runner_for(app.max_rounds, state)
    else:
        state = w._place_state_batch(
            app.init_state_batch(frag, [{"source": 0}, {"source": 1}]))
        runner = w._batched_runner_for(app.max_rounds, 2, state)
    assert not w._last_runner_miss
    eph = frozenset(app.ephemeral_keys or ())
    carry = {k: v for k, v in state.items() if k not in eph}
    eph_part = {k: v for k, v in state.items() if k in eph}
    analysis = runner.lower(frag.dev, carry, eph_part).compile() \
        .memory_analysis()
    for name, field in EXECUTABLE_BYTES.items():
        assert args[name] == getattr(analysis, field), name
    assert args["temp_bytes"] > 0 and args["argument_bytes"] > 0
    # a donated carry comes back in place
    assert 0 < args["alias_bytes"] <= args["output_bytes"]
    # one device's shard of every state leaf
    shards = sum(x.addressable_shards[0].data.nbytes for x in state.values())
    assert args["state_bytes"] == shards > 0
    assert args["state_bytes"] <= args["argument_bytes"]
    json.dumps(federation.snapshot("setup"))
    assert federation.self_check() == []


@pytest.mark.parametrize("mode", ["fused", "batched", "guarded-fused",
                                  "stepwise", "guarded-batched"])
def test_a_miss_compiles_once_as_the_parents_did(mode, monkeypatch):
    """Compiling ahead of the call adds no compile: the phase holds as
    many lowerings, backend compiles and cache events as the parent's
    `_enqueue` (a plain first call under the listener) for the same
    query."""
    from libgrape_lite_tpu.analysis import artifact
    from libgrape_lite_tpu.worker.worker import Worker

    def parents(self, runner, mode, batch, *operands):
        if not self._last_runner_miss:
            return runner(*operands)
        with obs.tracer().span(
            "runner.compile", app=type(self.app).__name__, mode=mode,
            batch=batch,
        ) as sp, artifact.compile_events() as ev:
            out = runner(*operands)
            sp.set(**ev.phase_seconds())
        return out

    seen = []  # every listener a phase opened, in order
    listen = artifact.compile_events

    @contextlib.contextmanager
    def keeping():
        with listen() as ev:
            seen.append(ev)
            yield ev

    monkeypatch.setattr(artifact, "compile_events", keeping)

    def counts(enqueue):
        if enqueue is not None:
            monkeypatch.setattr(Worker, "_enqueue", enqueue)
        del seen[:]
        SETUP_LEDGER.reset()
        _ask(_bfs_worker(), mode)
        (rec,) = [r for r in _compile_records() if r["args"]["mode"] == mode]
        (ev,) = seen
        names = [name.rsplit("/", 1)[-1] for name, _ in ev.events]
        return rec["args"], {
            k: names.count(k) for k in (
                "jaxpr_to_mlir_module_duration", "backend_compile_duration",
                "cache_hits", "cache_misses")}

    _ask(_bfs_worker(), mode)  # the helpers' own jits, once a process
    args, change = counts(None)
    parent_args, parent = counts(parents)
    assert change == parent
    assert change["backend_compile_duration"] == 1
    assert change["jaxpr_to_mlir_module_duration"] == 1
    assert set(STAMPED) <= set(args) and not set(STAMPED) & set(parent_args)
    assert set(args) - set(STAMPED) == set(parent_args)


def test_an_executable_without_an_analysis_leaves_the_fields_out(
        monkeypatch):
    """`memory_analysis()` is None on a backend that gives none: the
    five fields it would fill are absent, never 0; the state's bytes
    come from shapes and stay."""
    import jax

    monkeypatch.setattr(jax.stages.Compiled, "memory_analysis",
                        lambda self: None)
    _ask(_bfs_worker(1), "fused")
    (rec,) = _compile_records()
    assert not set(STAMPED[:5]) & set(rec["args"])
    assert rec["args"]["state_bytes"] > 0 and rec["args"]["backend_s"] > 0


def test_nested_trace_durations_are_merged_not_summed():
    """A jit traced inside another reports its trace inside the outer
    one's: the covered seconds are the union of the intervals."""
    from libgrape_lite_tpu.analysis.artifact import (
        _LOWER_EVENT, _TRACE_EVENT, CompileEvents,
    )

    ev = CompileEvents()
    for name, dur, end in [(_TRACE_EVENT, 1.0, 11.0),   # inner
                           (_TRACE_EVENT, 3.0, 12.0),   # outer, covers it
                           (_LOWER_EVENT, 0.5, 13.0),
                           (_TRACE_EVENT, 2.0, 20.0)]:  # a second runner
        ev.events.append((name, dur))
        ev.arrived.append(end)
    got = ev.phase_seconds()
    assert got["trace_s"] == pytest.approx(5.0)
    assert got["lower_s"] == pytest.approx(0.5)
    assert got["backend_s"] == 0 and got["cache_hits"] == 0


# ---- per-fragment structures: the miss branch only -------------------------


@pytest.mark.parametrize("what", ["mirror_plan"])
def test_derived_structure_records_its_miss_alone(what):
    from libgrape_lite_tpu.parallel.mirror import build_mirror_plan

    frag = rand_frag(4)
    SETUP_LEDGER.reset()
    first = build_mirror_plan(frag, "ie")
    (rec,) = records()
    assert rec["name"] == "derived." + what and rec["parent"] is None
    assert rec["args"]["fnum"] == 4
    assert rec["args"]["m"] == first.m
    assert build_mirror_plan(frag, "ie") is first
    assert len(SETUP_LEDGER) == 1


@pytest.mark.parametrize("placed_by", ["env", "helper"])
def test_compile_cache_record_says_who_set_the_floor(monkeypatch, placed_by):
    """One record with the directory and whether the helper set the
    floor (it does not when `JAX_COMPILATION_CACHE_DIR` places it)."""
    import jax

    from libgrape_lite_tpu.utils import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append(k))
    if placed_by == "env":
        monkeypatch.setenv(compile_cache.CACHE_ENV, "/somewhere/cache")
    else:
        monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    where = compile_cache.place_compile_cache()
    (rec,) = records()
    assert rec["name"] == "compile_cache" and rec["parent"] is None
    assert rec["args"] == {"dir": where,
                           "floor_set": placed_by == "helper"}
    assert bool(updates) == (placed_by == "helper")


# ---- the ledger itself -----------------------------------------------------


def test_ledger_is_bounded_and_counts_what_it_drops():
    small = SetupLedger(cap=3)
    for i in range(5):
        small.append({"name": "partition", "parent": None, "t0_ns": i,
                      "dur_ns": 2_000_000_000, "args": {}})
    snap = small.snapshot()
    assert snap["count"] == 3 and snap["dropped"] == 2 and snap["cap"] == 3
    assert [r["t0_ns"] for r in snap["records"]] == [0, 1, 2]
    assert snap["seconds"] == {"partition": 6.0}
    small.reset()
    assert small.snapshot()["count"] == 0 and small.dropped == 0
    # the process's own is bounded the same way
    tr = obs.tracer()
    for _ in range(SETUP_LEDGER.cap + 7):
        tr.span("partition").close()
    snap = federation.snapshot("setup")
    assert snap["count"] == SETUP_LEDGER.cap and snap["dropped"] == 7


def test_only_the_vocabulary_is_kept_and_args_stay_json():
    tr = obs.tracer()
    assert not tr.enabled
    for name in ("query", "worker.enqueue", "serve.pop", "superstep"):
        assert name not in SETUP_PHASES
        with tr.span(name, round=1):
            pass
    assert len(SETUP_LEDGER) == 0
    with tr.span("partition", kind=np.dtype("int32"), n=3) as sp:
        sp.set(extra=[1, 2])
    (rec,) = records()
    assert rec["args"] == {"kind": "int32", "n": 3, "extra": "[1, 2]"}
    json.dumps(federation.snapshot("setup"))
    assert tr.events() == []  # disarmed: nothing reaches the other sinks


def test_nesting_is_per_thread():
    import threading

    tr = obs.tracer()
    with tr.span("load_graph"):
        t = threading.Thread(target=lambda: tr.span("native.build").close())
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    by_name = {r["name"]: r for r in records()}
    assert by_name["native.build"]["parent"] is None


@pytest.mark.parametrize("armed", [False, True])
def test_ledger_agrees_with_the_jsonl_sink(tmp_path, armed):
    """Armed, every phase is also a span of the JSONL sink, name for
    name and on the same interval; disarmed the ledger holds the same
    names and the sink nothing.  The two gauges the record's args made
    a duplicate are gone, and `timer.phase` opens no span."""
    from libgrape_lite_tpu.models import APP_REGISTRY
    from libgrape_lite_tpu.utils import timer
    from libgrape_lite_tpu.worker.worker import Worker

    trace = str(tmp_path / "t.json")
    if armed:
        obs.configure(trace_path=trace, metrics_path=str(tmp_path / "m"))
    with timer.phase("load graph"):
        frag = load_p2p(tmp_path / "cache", fnum=1)
    Worker(APP_REGISTRY["lcc"](), frag).query()
    want = TREES["parse"] + [("derived.place", "derived.lcc_adjacency"),
                             ("derived.lcc_adjacency", None),
                             ("runner.compile", None)]
    assert [(r["name"], r["parent"]) for r in records()] == want
    flushed = obs.flush()
    if not armed:
        assert flushed["events"] == 0 and not os.path.exists(trace)
        return
    with open(flushed["jsonl"]) as f:
        spans = [json.loads(ln) for ln in f]
    spans = [e for e in spans if e["ph"] == "X"]
    sink = [e for e in spans if e["name"] in SETUP_PHASES]
    assert [e["name"] for e in sink] == [n for n, _ in want]
    for e, r in zip(sink, records()):
        assert e["ts"] == r["t0_ns"] / 1000.0
        assert e["dur"] == r["dur_ns"] / 1000.0
    assert "load graph" not in {e["name"] for e in spans}
    gauges = obs.metrics().snapshot()
    assert not [k for k in gauges if k.startswith("grape_graph_")]


def test_federation_is_clean_with_the_setup_namespace():
    assert federation.EXPECTED["setup"] == "libgrape_lite_tpu.obs.tracer"
    assert federation.self_check() == []
    assert "setup" in federation.registered()
    obs.tracer().span("load_graph", efile="g.e").close()
    from libgrape_lite_tpu.obs.exporter import federation_text

    text = federation_text()
    assert 'grape_stats_registry{namespace="setup"} 1' in text
    assert "grape_stats_setup_count 1" in text
    assert 'grape_stats_setup_seconds{key="load_graph"}' in text
    # the postmortem bundle's snapshot is the same one
    from libgrape_lite_tpu.obs.recorder import RECORDER

    bundle = RECORDER.build_bundle("test")
    assert bundle["federation"]["setup"]["count"] == 1
    json.dumps(bundle["federation"]["setup"])
