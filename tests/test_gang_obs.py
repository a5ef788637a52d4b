"""Gang-wide telemetry (PR 20): the clock handshake, per-rank
sidecars + the rank-0 assembler, breach-vote flow riders + the shared
incident id, the distributed flight recorder's byte-verified gang
bundle, and the single-process byte-identity guarantees (solo events
carry no rank stamp; the first fused dispatch marks `compiled`)."""

import json
import os
import sys
import time

import numpy as np
import pytest

from libgrape_lite_tpu import obs
from libgrape_lite_tpu.obs import gang
from libgrape_lite_tpu.obs.tracer import Tracer

_SCRIPTS = os.path.join(os.path.dirname(__file__), "..", "scripts")


@pytest.fixture(autouse=True)
def _obs_reset(monkeypatch):
    """Every test starts disarmed with no env arming and leaves no
    global state behind (obs.reset also forgets the handshake)."""
    monkeypatch.delenv(obs.TRACE_ENV, raising=False)
    monkeypatch.delenv(obs.METRICS_ENV, raising=False)
    monkeypatch.delenv("GRAPE_POSTMORTEM", raising=False)
    obs.reset()
    yield
    obs.reset()


def _scripts_path():
    if _SCRIPTS not in sys.path:
        sys.path.insert(0, _SCRIPTS)


# ---- clock handshake ------------------------------------------------------


def test_handshake_offsets_align_on_rank0():
    peer_perf = time.perf_counter_ns() + 5_000_000
    peer_vec = np.asarray(
        gang._split_ns(peer_perf) + gang._split_ns(time.time_ns()),
        np.int32,
    )

    def allgather(v):
        return np.stack([np.asarray(v), peer_vec])

    hs = gang.ensure_handshake(rank=0, nprocs=2, allgather=allgather)
    assert hs["nprocs"] == 2
    offs = hs["offsets_ns"]
    assert offs["0"] == 0
    # rank 1's clock reads ahead; shifting by the offset lands it on
    # rank 0's clock exactly
    assert offs["1"] == hs["anchors"][0]["perf_ns"] - peer_perf
    # cached: the second call must not allgather again
    assert gang.ensure_handshake(allgather=None) is hs
    gang.reset()
    assert gang._state["handshake"] is None


def test_handshake_noop_single_process():
    assert gang.ensure_handshake(rank=0, nprocs=1) is None


# ---- sidecars + assembler -------------------------------------------------


def _two_rank_sidecars(tmp_path, skew_ns=2_500_000):
    """Two fake rank tracers, each with one superstep span and one leg
    of a shared breach-vote flow, written as real sidecars with an
    injected handshake (rank 1's clock skewed ahead)."""
    tracers = [Tracer(enabled=True, rank=r, nprocs=2) for r in (0, 1)]
    hs = {"nprocs": 2, "offsets_ns": {"0": 0, "1": -skew_ns},
          "allgather_wall_ns": 0}
    gdir = str(tmp_path / "trace.gang")
    for r, t in enumerate(tracers):
        with t.span("superstep", round=1):
            pass
        t.flow("breach_vote", flow_id=3, cat="gang-vote",
               phase="s" if r == 0 else "f", round=2)
        p = gang.write_sidecar(
            tracer=t, handshake=dict(hs, rank=r),
            path=os.path.join(gdir, f"rank_{r}.json"),
            events=t.events(),
        )
        assert p is not None
        doc = json.load(open(p))
        assert doc["schema"] == gang.GANG_TRACE_SCHEMA
        assert doc["rank"] == r and doc["nprocs"] == 2
    return gdir


def test_assemble_merges_aligns_and_counts_flows(tmp_path):
    gdir = _two_rank_sidecars(tmp_path)
    out = str(tmp_path / "merged.json")
    s = gang.assemble(gdir, out_path=out)
    assert s["ranks"] == [0, 1]
    assert s["complete"] and s["aligned"] and s["monotonic"]
    assert s["cross_rank_flows"] == 1
    assert s["flow_events"] == 2
    assert s["supersteps_by_rank"] == {"0": 1, "1": 1}
    doc = json.load(open(out))
    evs = doc["traceEvents"]
    assert {e["pid"] for e in evs} == {0, 1}
    # the vote legs keep their shared (cat, id) across rank tracks
    legs = [e for e in evs if e.get("ph") in ("s", "t", "f")]
    assert {(e["cat"], e["id"]) for e in legs} == {("gang-vote", 3)}
    assert {e["pid"] for e in legs} == {0, 1}
    # the merge records the offsets it aligned with
    assert doc["metadata"]["gang"]["offsets_ns"]["1"] == -2_500_000
    # post-alignment, non-metadata timestamps are non-decreasing
    ts = [e["ts"] for e in evs if e.get("ph") != "M"]
    assert ts == sorted(ts)


def test_assemble_incomplete_when_rank_missing(tmp_path):
    gdir = _two_rank_sidecars(tmp_path)
    os.remove(os.path.join(gdir, "rank_1.json"))
    s = gang.assemble(gdir)
    assert s["missing"] == [1]
    assert not s["complete"]


def test_assemble_unaligned_without_handshake(tmp_path):
    t = Tracer(enabled=True, rank=0, nprocs=2)
    with t.span("superstep"):
        pass
    gdir = str(tmp_path / "t.gang")
    gang.write_sidecar(tracer=t, handshake=None,
                       path=os.path.join(gdir, "rank_0.json"),
                       events=t.events())
    s = gang.assemble(gdir)
    assert not s["aligned"] and not s["complete"]


def test_trace_report_gang_cli(tmp_path, capsys):
    _scripts_path()
    import trace_report

    gdir = _two_rank_sidecars(tmp_path)
    # the CLI derives `<base>.gang` from the trace path it is given
    rc = trace_report.main(["--gang", str(tmp_path / "trace.json")])
    assert rc == 0
    assert os.path.exists(os.path.join(gdir, "merged.json"))
    out = capsys.readouterr().out
    assert "gang trace federation" in out
    assert "complete" in out


# ---- rank stamping / solo byte-identity -----------------------------------


def test_gang_events_stamp_rank_and_solo_stays_bare():
    solo = Tracer(enabled=True)
    with solo.span("superstep"):
        pass
    ev = [e for e in solo.events() if e["ph"] == "X"][0]
    # single-process output schema is untouched (byte-identity pin)
    assert "rank" not in ev and "nprocs" not in ev
    t1 = Tracer(enabled=True, rank=1, nprocs=2)
    with t1.span("superstep"):
        pass
    ev = [e for e in t1.events() if e["ph"] == "X"][0]
    assert ev["pid"] == 1 and ev["rank"] == 1 and ev["nprocs"] == 2
    meta = [e for e in t1.metadata() if e["name"] == "process_name"]
    assert meta[0]["rank"] == 1


# ---- breach-vote riders ---------------------------------------------------


def test_vote_halt_attaches_shared_incident_and_flow_legs():
    from libgrape_lite_tpu.guard.vote import (
        BreachVote,
        RemoteBreachError,
    )

    tr = obs.configure(in_memory=True)
    votes = np.asarray([[0, 3, 0], [4, 3, 0]], np.int32)
    incidents = []
    for rank in (0, 1):
        v = BreachVote(rank=rank, nprocs=2,
                       allgather=lambda vec: votes)
        with pytest.raises(RemoteBreachError) as ei:
            v.round_vote(3)
        assert ei.value.gang_incident
        incidents.append(ei.value.gang_incident)
    # the id is a digest of the allgathered matrix: identical on
    # every rank with no extra message
    assert incidents[0] == incidents[1]
    legs = [e for e in tr.events() if e.get("ph") in ("s", "t", "f")]
    assert len(legs) == 2
    assert {(e["cat"], e["id"]) for e in legs} == {("gang-vote", 4)}
    assert {e["ph"] for e in legs} == {"s", "f"}


def test_healthy_vote_emits_flow_but_no_incident():
    from libgrape_lite_tpu.guard.vote import BreachVote

    tr = obs.configure(in_memory=True)
    votes = np.asarray([[0, 5, 0], [0, 5, 0]], np.int32)
    v = BreachVote(rank=0, nprocs=2, allgather=lambda vec: votes)
    v.round_vote(5)  # unanimous healthy: returns
    legs = [e for e in tr.events() if e.get("ph") in ("s", "t", "f")]
    assert len(legs) == 1 and legs[0]["args"]["halted"] is False


# ---- distributed flight recorder ------------------------------------------


def test_gang_postmortem_byte_verified_manifest(tmp_path, monkeypatch):
    monkeypatch.setenv("GRAPE_POSTMORTEM", str(tmp_path))
    obs.configure(in_memory=True)
    incident = gang.incident_id({"kind": "test", "n": 1})
    captured = {}

    def ag1(vec):
        captured["r1"] = np.asarray(vec).copy()
        return np.stack([np.zeros(3, np.int32), np.asarray(vec)])

    out1 = gang.gang_postmortem(incident, "drill", rank=1, nprocs=2,
                                allgather=ag1)
    # rank 1 dumps its shard but never writes the manifest
    assert out1["manifest"] is None
    idir = os.path.join(str(tmp_path), f"incident_{incident}")
    assert os.path.exists(os.path.join(idir, "rank_1.json"))

    def ag0(vec):
        return np.stack([np.asarray(vec), captured["r1"]])

    out0 = gang.gang_postmortem(incident, "drill", rank=0, nprocs=2,
                                allgather=ag0)
    assert out0["complete"] is True
    man = json.load(open(out0["manifest"]))
    assert man["schema"] == gang.GANG_BUNDLE_SCHEMA
    assert man["incident"] == incident and man["nprocs"] == 2
    assert man["complete"] is True
    for r in ("0", "1"):
        assert man["shards"][r]["present"]
        assert man["shards"][r]["verified"]

    # tamper with rank 1's shard: byte-verification must catch it
    with open(os.path.join(idir, "rank_1.json"), "a") as fh:
        fh.write("\n")
    out_t = gang.gang_postmortem(incident, "drill", rank=0, nprocs=2,
                                 allgather=ag0)
    assert out_t["complete"] is False
    assert json.load(open(out_t["manifest"]))["complete"] is False


def test_gang_postmortem_counts_only_without_sink():
    obs.configure(in_memory=True)
    before = gang.GANG_STATS["postmortems"]
    out = gang.gang_postmortem("deadbeefdeadbeef", "drill",
                               rank=0, nprocs=2,
                               allgather=lambda v: (_ for _ in ()).throw(
                                   AssertionError("allgather reached")))
    # no sink: no shard, no collective — but the moment is counted
    assert out is None
    assert gang.GANG_STATS["postmortems"] == before + 1


def test_incident_id_deterministic():
    a = gang.incident_id({"votes": [[4, 3, 0]], "rounds": 3})
    b = gang.incident_id({"rounds": 3, "votes": [[4, 3, 0]]})
    assert a == b and len(a) == 16
    assert a != gang.incident_id({"votes": [[4, 4, 0]], "rounds": 3})


# ---- worker compile marks --------------------------------------------------


def test_fused_first_query_marks_compiled():
    from libgrape_lite_tpu.models import SSSP
    from libgrape_lite_tpu.worker.worker import Worker
    from tests.test_obs import _chain_fragment

    obs.configure(in_memory=True)
    w = Worker(SSSP(), _chain_fragment(n=8, fnum=2))
    w.query(source=0)
    w.query(source=0)
    qs = [e for e in obs.history()
          if e["ph"] == "X" and e["name"] == "query"]
    assert len(qs) == 2
    # the first dispatch carried trace+compile: stamped, so a reader
    # can keep it out of a round's wall
    assert "compiled_us" in qs[0]["args"]
    assert "compiled_us" not in qs[1]["args"]
    assert "device_wait_us" in qs[1]["args"]


def test_stepwise_first_superstep_marks_compiled():
    from libgrape_lite_tpu.models import SSSP
    from libgrape_lite_tpu.worker.worker import Worker
    from tests.test_obs import _chain_fragment

    obs.configure(in_memory=True)
    w = Worker(SSSP(), _chain_fragment(n=8, fnum=2))
    w.query_stepwise(source=0)
    steps = [e for e in obs.history()
             if e["ph"] == "X" and e["name"] == "superstep"
             and "device_wait_us" in (e.get("args") or {})]
    marked = [e for e in steps if "compiled_us" in e["args"]]
    assert len(steps) == w.rounds
    assert len(marked) == 1  # only the fresh-compile round


# ---- federation / schema wiring -------------------------------------------


def test_gang_stats_federated():
    from libgrape_lite_tpu.obs import federation

    snap = federation.snapshot()
    assert "gang" in snap
    for k in ("handshakes", "sidecar_writes", "assemblies",
              "postmortems", "halts"):
        assert k in snap["gang"]


def test_bench_schema_declares_gang_blocks():
    _scripts_path()
    import check_bench_schema as cbs

    assert cbs.self_check() == []
    assert "obs_gang" in cbs._BLOCKS
    rec = {
        "metric": "m", "value": 1.0, "unit": "s", "vs_baseline": 1.0,
        "obs_gang": {"ranks": 2, "events": 8, "flow_events": 2,
                     "cross_rank_flows": 1, "aligned": True,
                     "monotonic": True, "complete": True,
                     "hlo_identical": True},
    }
    assert cbs.validate_record(rec) == []
    bad = dict(rec, obs_gang=dict(rec["obs_gang"], complete=1))
    assert any("obs_gang.complete" in e
               for e in cbs.validate_record(bad))
