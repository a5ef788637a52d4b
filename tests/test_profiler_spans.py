"""One span system on the profiler's clock.

Device side: the fused runners carry `grape.*` named scopes (metadata
only: the lowered program is the same without them).  Host side: every
`obs` span is also a `jax.profiler.TraceAnnotation` named
`grape.<name>`, armed or not, so a profiler session holds the worker's
and the pump's stages beside the device's operations.

The CPU's trace names its operations differently from the chip's (no
`tf_op` in the event metadata), so the join of operations to scopes is
pinned on recorded chip traces (tests/test_reduce_scopes.py), not here.
No profiler call happens while this module is imported.
"""

import contextlib
import glob
import time

import jax
import pytest

from libgrape_lite_tpu import obs
from libgrape_lite_tpu.models import APP_REGISTRY
from libgrape_lite_tpu.worker.worker import Worker

APPS = {"pagerank": {}, "sssp": {"source": 6}, "bfs": {"source": 6},
        "wcc": {}}


@pytest.fixture(autouse=True)
def _obs_disarmed(monkeypatch):
    """Every test starts disarmed and leaves no global state behind."""
    monkeypatch.delenv(obs.TRACE_ENV, raising=False)
    monkeypatch.delenv(obs.METRICS_ENV, raising=False)
    obs.reset()
    yield
    obs.reset()


def _lowered(app: str, frag, debug_info: bool) -> str:
    w = Worker(APP_REGISTRY[app](), frag)
    state = w._place_state(w.app.init_state(frag, **APPS[app]))
    eph = frozenset(getattr(w.app, "ephemeral_keys", ()) or ())
    carry = {k: v for k, v in state.items() if k not in eph}
    eph_part = {k: v for k, v in state.items() if k in eph}
    lowered = w._runner_for(0, state).lower(frag.dev, carry, eph_part)
    return lowered.as_text(debug_info=debug_info)


@pytest.mark.parametrize("app", sorted(APPS))
def test_fused_runner_names_the_pull(app, graph_cache):
    text = _lowered(app, graph_cache(1), True)
    for scope in ("grape.pull.gather", "grape.pull.fold",
                  "grape.app.update", "grape.worker.terminate"):
        assert scope in text, f"{app}: no {scope} in the lowered runner"


@pytest.mark.parametrize("app", sorted(APPS))
def test_sharded_runner_names_the_exchange(app, graph_cache, monkeypatch):
    # p2p-31 is too small for the bytes model to pick the mirror
    # exchange by itself; on the chip's graph it does
    monkeypatch.setenv("GRAPE_EXCHANGE", "mirror")
    text = _lowered(app, graph_cache(4), True)
    for scope in ("grape.exchange.pack", "grape.exchange.collective",
                  "grape.exchange.unpack"):
        assert scope in text, f"{app}: no {scope} in the lowered runner"


@pytest.mark.parametrize("app", ["pagerank", "bfs"])
def test_scopes_are_metadata_only(app, graph_cache, monkeypatch):
    """The program the compiler sees is byte-identical with the scopes
    in place and with `jax.named_scope` turned into a null context."""
    frag = graph_cache(4)
    scoped = _lowered(app, frag, False)
    assert "grape." not in scoped
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert "grape." not in _lowered(app, frag, True)
    assert _lowered(app, frag, False) == scoped


# ---- host spans under a profiler session ---------------------------------


def _profiled(tmp_path, fn) -> list:
    """[(name, start_ns, end_ns, stats)] of the `grape.*` host events
    of one profiler session around `fn`."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    assert files, "the profiler wrote no .xplane.pb"
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("grape."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _inside(events, outer, name):
    return [e for e in events
            if e[0] == name and outer[1] <= e[1] and e[2] <= outer[2]]


def test_query_stages_reach_the_profiler_disarmed(tmp_path, graph_cache):
    assert not obs.tracer().enabled
    w = Worker(APP_REGISTRY["sssp"](), graph_cache(1))
    w.query(source=6)  # compile outside the session

    def work():
        from jax.profiler import TraceAnnotation

        assert isinstance(obs.tracer().span("x"), TraceAnnotation)
        w.query(source=6)
        w.result_values()

    events = _profiled(tmp_path, work)
    (query,) = [e for e in events if e[0] == "grape.query"]
    assert query[3]["mode"] == "fused" and query[3]["app"] == "SSSP"
    stages = [_inside(events, query, "grape.worker." + s)
              for s in ("init_state", "place_state", "runner", "enqueue",
                        "wait", "readback")]
    assert all(len(s) == 1 for s in stages), stages
    starts = [s[0][1] for s in stages]
    assert starts == sorted(starts)
    assert "miss" not in stages[2][0][3]  # the runner was cached
    (extract,) = [e for e in events if e[0] == "grape.worker.extract"]
    assert extract[1] >= query[2]


def test_served_batch_stages_reach_the_profiler_disarmed(tmp_path,
                                                         graph_cache):
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession

    assert not obs.tracer().enabled
    session = ServeSession(
        graph_cache(1),
        policy=BatchPolicy(max_batch=4, max_wait_s=0, inflight=1))
    # not closed: that would release the suite's shared fragment

    def work():
        for s in (6, 7, 8):
            session.submit("sssp", {"source": s})
        assert all(r.ok for r in session.drain())

    events = _profiled(tmp_path, work)
    (batch,) = [e for e in events if e[0] == "grape.serve_batch"]
    # keyword arguments of a TraceAnnotation arrive as the event's stats
    assert int(batch[3]["batch"]) == 3 and batch[3]["app"] == "sssp"
    (harvest,) = _inside(events, batch, "grape.serve.harvest")
    assert int(harvest[3]["batch"]) == 3
    assert len(_inside(events, harvest, "grape.worker.extract")) == 3
    (runner,) = _inside(events, batch, "grape.worker.runner")
    assert int(runner[3]["miss"]) == 1  # which step compiled
    names = [e[0] for e in events]
    assert names.index("grape.serve.pop") < names.index("grape.serve_batch")
    assert "grape.serve.deliver" in names


def test_armed_span_and_its_mirror_agree(tmp_path, graph_cache):
    """Armed, one interval lands in both sinks: the JSONL span and the
    profiler span of one name agree to 1 ms, in length and in where
    they start inside the query."""
    w = Worker(APP_REGISTRY["bfs"](), graph_cache(1))
    w.query(source=6)
    obs.configure(in_memory=True)
    events = _profiled(tmp_path, lambda: w.query(source=6))
    jsonl = {e["name"]: e for e in obs.history() if e.get("ph") == "X"}
    prof = {e[0]: e for e in events}
    q_json, q_prof = jsonl["query"], prof["grape.query"]
    for name in ("query", "worker.init_state", "worker.enqueue",
                 "worker.wait"):
        j, p = jsonl[name], prof["grape." + name]
        assert abs(j["dur"] - (p[2] - p[1]) / 1e3) < 1e3, name
        assert abs((j["ts"] - q_json["ts"])
                   - (p[1] - q_prof[1]) / 1e3) < 1e3, name


class _Bare:
    """A context manager that does nothing: what `with ... as sp:
    sp.mark(...)` costs by itself on this machine, now."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def mark(self, name):
        pass


def test_disarmed_span_stays_within_budget_with_the_mirror_in_place():
    """The existing 1 µs budget (tests/test_obs.py) holds for a span with
    a keyword argument and a mark: with no profiler session the disarmed
    tracer asks the profiler and makes no annotation.  The budget is held
    over what a bare context manager costs in the same loop, the two
    timed turn by turn and each by its best of twenty short turns: an
    absolute wall-clock line failed on a machine whose other five
    workers were compiling (the driver's run of PR 51's tree)."""
    tr = obs.tracer()
    assert not tr.enabled
    assert tr.span("superstep") is tr.span("worker.runner", lane=1)
    bare = _Bare()
    n = 10_000
    best = {"span": float("inf"), "bare": float("inf")}
    for _ in range(20):
        for what in ("span", "bare"):
            t0 = time.perf_counter()
            if what == "span":
                for _ in range(n):
                    with tr.span("worker.runner", lane=1) as sp:
                        sp.mark("dispatched")
            else:
                for _ in range(n):
                    with bare as sp:
                        sp.mark("dispatched")
            best[what] = min(best[what], (time.perf_counter() - t0) / n)
    over = best["span"] - best["bare"]
    assert over < 1e-6, (
        f"disarmed span costs {over * 1e9:.0f} ns > 1 µs over a bare "
        f"context manager's {best['bare'] * 1e9:.0f} ns")
