"""The 2-D (vertex-cut) fragment as a fleet citizen (PR 19).

* release/restore_device round-trips the tile buffers byte for byte,
  host reads survive an eviction, re-admission compiles nothing,
  `fragment_bytes` prices the host tile blocks, and `mesh_kind` keys
  session compatibility so a 2-D app can never coalesce with a 1-D one;
* batched vc2d dispatch (the `vc_source_carry` batch_query_key path)
  stays lane-identical to sequential queries, and a dyn session on a
  vertex-cut fragment is refused at construction;
* `tile_stats` publishes the fill / pad-waste profile into the
  "vc_tiles" federation namespace.

Moved unchanged from tests/test_vc2d_pipeline.py when the superstep
pipeline went (PR 42): none of these cases touched it.
"""

import numpy as np
import pytest

from tests.test_partition2d import (
    _assert_byte_identical,
    _result_dict,
    _vc_frag,
)


# ---- vertexcut residency + device reads (satellite a) ---------------------


def test_vc2d_host_reads_survive_release(monkeypatch):
    """The PR 18 bug class, audited for the 2-D fragment: tile_stats,
    inner_vertices_num/inner_oids and the per-tile CSR views read HOST
    arrays only — all must keep working with the device tiles deleted
    (under jax.distributed they span non-addressable devices and any
    device fetch would throw; eviction makes that loud on one
    process)."""
    frag = _vc_frag(4, weighted=True)
    want_stats = frag.tile_stats()
    want_ie = [c.edge_mask.sum() for c in frag.host_ie]
    assert frag.release_device() is True
    assert frag.dev is None
    stats = frag.tile_stats()
    assert stats == want_stats
    assert [c.edge_mask.sum() for c in frag.host_ie] == want_ie
    total = sum(frag.inner_vertices_num(f) for f in range(frag.fnum))
    oids = np.concatenate(
        [frag.inner_oids(f) for f in range(frag.fnum)]
    )
    assert total == len(oids) == frag.total_vnum
    assert frag.restore_device() is True


def test_vc2d_release_restore_byte_identical_tiles():
    """restore_device re-places byte-identical tile content (the
    deterministic `_place_tiles` shared by build and restore)."""
    frag = _vc_frag(4, weighted=True)
    before = {
        k: np.asarray(getattr(frag.dev, k)).tobytes()
        for k in ("src", "dst", "w", "mask")
    }
    assert frag.release_device() is True
    assert frag.release_device() is False  # idempotent
    assert frag.restore_device() is True
    assert frag.restore_device() is False
    for k, want in before.items():
        assert np.asarray(getattr(frag.dev, k)).tobytes() == want, k


def test_vc2d_placement_matches_callback_branch():
    """_place_tiles goes through put_global, whose multi-process branch
    assembles via make_array_from_callback: forced on the same mesh,
    that branch must agree with the fast path for every tile buffer
    (the regression idiom of test_worker's put_global pin)."""
    import jax

    frag = _vc_frag(4, weighted=True)
    sh = frag.comm_spec.sharded()
    s_arr, d_arr, w_arr, m_arr = frag._host_tiles
    for name, host, dev in (
        ("src", s_arr, frag.dev.src),
        ("dst", d_arr, frag.dev.dst),
        ("w", w_arr, frag.dev.w),
        ("mask", m_arr, frag.dev.mask),
    ):
        arr = np.asarray(host)
        cb = jax.make_array_from_callback(
            arr.shape, sh, lambda idx: arr[idx]
        )
        np.testing.assert_array_equal(np.asarray(cb), np.asarray(dev),
                                      err_msg=name)
        for shard in cb.addressable_shards:
            np.testing.assert_array_equal(
                np.asarray(shard.data), arr[shard.index]
            )


# ---- tile fill / pad-waste ledger (satellite b) ---------------------------


def test_tile_stats_fill_counters_federated():
    """tile_stats publishes the fill / pad-waste profile into the
    "vc_tiles" federation namespace; the counters partition the slot
    budget exactly and the namespace passes the wiring self-check."""
    from libgrape_lite_tpu.fragment.vertexcut import VC_TILE_STATS
    from libgrape_lite_tpu.obs import federation

    frag = _vc_frag(4, weighted=True)
    local = frag.tile_stats()
    snap = VC_TILE_STATS.snapshot()
    assert snap["scans"] >= 1
    assert snap["tiles"] == frag.fnum
    assert snap["edges"] + snap["pad_slots"] == (
        frag.fnum * snap["edge_slots"]
    )
    assert 0.0 <= snap["pad_waste_frac"] <= 1.0
    assert (0.0 <= snap["min_fill_frac"] <= snap["mean_fill_frac"]
            <= snap["max_fill_frac"] <= 1.0)
    assert snap["tile_skew"] == local["tile_skew"]
    assert snap["pad_slots"] == local["pad_slots"]
    assert not federation.self_check()
    fed = federation.snapshot()["vc_tiles"]
    assert fed["pad_waste_frac"] == snap["pad_waste_frac"]


# ---- serve / fleet integration (tentpole part 2) --------------------------


def test_mesh_kind_keys_session_compat():
    """`mesh_kind` is part of the coalescing compat key: two otherwise
    identical requests on different mesh kinds can never share a
    batched dispatch (a vc2d lane inside a 1-D vmap would read the
    wrong sharding)."""
    from libgrape_lite_tpu.serve.policy import compat_key

    a = compat_key("sssp", {"source": 0}, 100, "off", "source", "frag")
    b = compat_key("sssp", {"source": 0}, 100, "off", "source", "vc2d")
    assert a != b


def test_vc2d_session_batched_byte_identical(monkeypatch):
    """ServeSession over a vc2d fragment: batched dispatch of k
    sources (the vc_source_carry batch_query_key path) answers every
    lane byte-identically to standalone sequential queries."""
    from libgrape_lite_tpu.models import SSSPVC2D
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession

    frag = _vc_frag(4, weighted=True)
    sources = [0, 6, 31]
    want = {}
    for s in sources:
        out, _ = _result_dict(SSSPVC2D(), frag, source=s)
        want[s] = out
    sess = ServeSession(frag, policy=BatchPolicy(max_batch=4))
    res = sess.serve([("sssp_vc", {"source": s}) for s in sources])
    assert all(r.ok for r in res)
    for r, s in zip(res, sources):
        got, n = {}, 0
        for f in range(frag.fnum):
            k = frag.inner_vertices_num(f)
            for o, v in zip(frag.inner_oids(f), r.values[f, :k]):
                got[int(o)] = v
            n += k
        _assert_byte_identical(got, want[s])


def test_vc2d_dyn_session_refused_loudly():
    """The vc2d tile pulls never read the delta overlay, so a dyn
    vertex-cut session would serve stale results silently — the
    session must refuse at construction instead."""
    from libgrape_lite_tpu.serve import ServeSession

    with pytest.raises(ValueError, match="vertex-cut"):
        ServeSession(_vc_frag(4, weighted=True), dyn=True)


def test_vc2d_evict_readmit_zero_compiles():
    """The fleet acceptance pin on the 2-D path: release_device drops
    the tile buffers; the next query after restore hits the warm
    runner cache — zero XLA compiles — and answers byte-identically."""
    from libgrape_lite_tpu.analysis import compile_events
    from libgrape_lite_tpu.serve import BatchPolicy, ServeSession

    frag = _vc_frag(4, weighted=True)
    sess = ServeSession(frag, policy=BatchPolicy(max_batch=1))
    r1 = sess.serve([("sssp_vc", {"source": 0})])
    assert r1[0].ok
    want = r1[0].values.tobytes()
    rel = sess.release_device()
    assert rel["fragment_released"] and not sess.resident
    assert sess.fragment.dev is None
    assert sess.restore_device() and sess.resident
    with compile_events() as ev:
        r2 = sess.serve([("sssp_vc", {"source": 0})])
    assert r2[0].ok and r2[0].values.tobytes() == want
    assert ev.compiles == 0, ("2-D re-admission recompiled", ev.events)


def test_vc2d_fragment_bytes_and_fleet_admission():
    """fragment_bytes prices the host tile blocks (>= their nbytes —
    the footprint a restore will re-place), session_footprint works on
    a vc2d session, and a vc2d tenant admits to the fleet under an
    HBM budget sized from that price and answers correctly."""
    from libgrape_lite_tpu.fleet import (
        FleetBudget,
        FleetManager,
        fragment_bytes,
        session_footprint,
    )
    from libgrape_lite_tpu.serve import ServeSession

    frag = _vc_frag(4, weighted=True)
    fb = fragment_bytes(frag)
    s_arr, d_arr, w_arr, m_arr = frag._host_tiles
    tile_nbytes = (s_arr.nbytes + d_arr.nbytes + m_arr.nbytes
                   + w_arr.nbytes)
    assert fb >= tile_nbytes

    want, _ = _result_dict(
        __import__("libgrape_lite_tpu.models", fromlist=["SSSPVC2D"]
                   ).SSSPVC2D(), frag, source=0,
    )
    sess = ServeSession(frag)
    fp = session_footprint(sess)
    assert fp.frag_bytes == fb
    mgr = FleetManager(FleetBudget(capacity_bytes=int(fb * 4)))
    mgr.add_tenant("vc", sess)
    t = mgr.submit("vc", "sssp_vc", {"source": 0})
    mgr.drain()
    assert t.done and t.result.ok
    got = {}
    for f in range(frag.fnum):
        n = frag.inner_vertices_num(f)
        for o, v in zip(frag.inner_oids(f), t.result.values[f, :n]):
            got[int(o)] = v
    _assert_byte_identical(got, want)
